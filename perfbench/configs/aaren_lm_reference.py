"""Plain float32 reference of the Aaren decoder LM (arXiv:2405.13956).

A decoder-only language model whose every attention layer is Aaren: a
learned query token per layer, projected to per-head queries, reads keys
and values of the tokens up to and including the current one, and the
output at position i is the softmax-weighted mean of the values of
positions 1..i of its own document.  Pre-norm residual blocks with RMSNorm
and a SwiGLU MLP, untied unembedding, next-token cross entropy over
same-document targets.  Written from the paper and the configuration file,
in ``jax.numpy`` at ``Precision.HIGHEST``; it imports nothing of the
program.

Weights come from ``lib/weights.py``, drawn from the seed again layer by
layer, so the whole model never sits on the device in float32.

``precision="fp8"`` is the control: every matrix product takes its operands
rounded to float8 e4m3, and passes their gradients back rounded to e5m2,
each with one scale per tensor: the step below the bfloat16 that the
configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from lib import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30                 # finite "minus infinity": keeps gradients finite
SCAN_BLOCK = 128            # positions per block of the prefix softmax
LOSS_BLOCK = 2048           # tokens per block of the unembedding and loss
FP8_MAX = 448.0             # largest float8 e4m3fn value
FP8_E5M2_MAX = 57344.0      # largest float8 e5m2 value


def _quantize(x, dtype, largest: float):
    scale = jnp.max(jnp.abs(x)) / largest
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    """x rounded to float8 e4m3 with one scale per tensor; its gradient is
    rounded to e5m2 with a scale of its own, as fp8 training does."""
    return _quantize(x, jnp.float8_e4m3fn, FP8_MAX)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_quantize(g, jnp.float8_e5m2, FP8_E5M2_MAX),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def mm(eq: str, a, b, precision: str):
    """A matrix product at the given precision (float32 or the control)."""
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def rmsnorm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def prefix_softmax(s, v, seg):
    """o[b,h,i] = sum_j softmax(s[b,h,j]) v[b,h,j] over j <= i in i's document.

    s: (B, H, N) scores; v: (B, H, N, d); seg: (B, N) document ids, 0 for
    padding, each document one contiguous run.  Exact, in blocks of
    ``SCAN_BLOCK`` positions: dense within a block, and the running
    (max, denominator, numerator) of the current document carried from
    block to block.  Padding reads 0.
    """
    b, h, n = s.shape
    d = v.shape[-1]
    t = SCAN_BLOCK
    pad = (-n) % t
    if pad:
        s = jnp.pad(s, ((0, 0), (0, 0), (0, pad)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        seg = jnp.pad(seg, ((0, 0), (0, pad)))
    nb = (n + pad) // t
    s_b = s.reshape(b, h, nb, t).transpose(2, 0, 1, 3)
    v_b = v.reshape(b, h, nb, t, d).transpose(2, 0, 1, 3, 4)
    g_b = seg.reshape(b, nb, t).transpose(1, 0, 2)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def block(carry, xs):
        m, u, w, cseg = carry
        sb, vb, gb = xs
        same = ((gb[:, :, None] == gb[:, None, :]) & causal
                & (gb[:, :, None] != 0))[:, None]          # (B, 1, T, T)
        use_c = ((gb == cseg[:, None]) & (gb != 0))[:, None]  # (B, 1, T)
        sij = jnp.broadcast_to(sb[:, :, None, :], same.shape[:1] + (h, t, t))
        mx = jnp.max(jnp.where(same, sij, NEG), axis=-1)
        mx = jnp.maximum(mx, jnp.where(use_c, m[..., None], NEG))
        p = jnp.where(same, jnp.exp(jnp.where(same, sij - mx[..., None], 0.0)),
                      0.0)
        pc = jnp.where(use_c, jnp.exp(jnp.where(use_c, m[..., None] - mx,
                                                0.0)), 0.0)
        den = jnp.sum(p, axis=-1) + pc * u[..., None]
        num = (jnp.einsum("bhij,bhjd->bhid", p, vb, precision=HIGHEST)
               + pc[..., None] * w[:, :, None, :])
        o = num / jnp.where(den > 0, den, 1.0)[..., None]
        new = (mx[..., -1], den[..., -1], num[..., -1, :], gb[:, -1])
        return new, o

    init = (jnp.full((b, h), NEG), jnp.zeros((b, h)), jnp.zeros((b, h, d)),
            jnp.zeros((b,), seg.dtype))
    _, o = jax.lax.scan(block, init, (s_b, v_b, g_b))
    o = o.transpose(1, 2, 0, 3, 4).reshape(b, h, nb * t, d)
    return o[:, :, :n]


def aaren_mixer(w, x, seg, cfg, precision):
    nh, ng, dk = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = mm("d,dhk->hk", w["mixer.query"], w["mixer.wq"], precision)
    k = mm("bnd,dgk->bngk", x, w["mixer.wk"], precision)
    v = mm("bnd,dgk->bngk", x, w["mixer.wv"], precision)
    k = jnp.repeat(k, nh // ng, axis=2)   # query head h reads kv head h // (H/G)
    v = jnp.repeat(v, nh // ng, axis=2)
    s = mm("bnhk,hk->bhn", k, q, precision) / np.sqrt(dk)
    o = prefix_softmax(s, v.transpose(0, 2, 1, 3), seg)
    return mm("bhnk,hkd->bnd", o, w["mixer.wo"], precision)


def layer(w, x, seg, cfg, precision):
    eps = cfg["rms_norm_eps"]
    x = x + aaren_mixer(w, rmsnorm(x, w["norm1.scale"], eps), seg, cfg,
                        precision)
    h = rmsnorm(x, w["norm2.scale"], eps)
    gate = mm("bnd,df->bnf", h, w["mlp.wi_gate"], precision)
    up = mm("bnd,df->bnf", h, w["mlp.wi_up"], precision)
    return x + mm("bnf,fd->bnd", jax.nn.silu(gate) * up, w["mlp.wo"],
                  precision)


# ----------------------------------------------------------------- serving


def served_logits(cfg: dict, seed: int, tokens: np.ndarray, seg: np.ndarray,
                  rows: np.ndarray, cols: np.ndarray,
                  precision: str = "f32") -> np.ndarray:
    """Logits (len(rows), V) at positions ``(rows, cols)`` of ``tokens``.

    tokens, seg: (B, N); the model runs layer by layer, each layer's
    weights drawn again from the seed inside its own call.  Every array is
    an argument, so one compiled program serves every seed.
    """
    key = W.base_key(seed)
    tokens = jnp.asarray(tokens, jnp.int32)
    seg = jnp.asarray(seg, jnp.int32)
    top = jax.jit(lambda k: W.top_weights(cfg, k))(key)
    x = _embed(top["embed.table"], tokens)
    for l in range(cfg["num_hidden_layers"]):
        x = _served_layer(x, seg, key, l, cfg=_Frozen(cfg), precision=precision)
    return np.asarray(_served_head(x, top["final_norm.scale"],
                                   top["unembed.kernel"], jnp.asarray(rows),
                                   jnp.asarray(cols), cfg=_Frozen(cfg),
                                   precision=precision))


class _Frozen(dict):
    """A configuration usable as a static argument of ``jax.jit``."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


@jax.jit
def _embed(table, tokens):
    return table[tokens]


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def _served_layer(x, seg, key, l, *, cfg, precision):
    return layer(W.layer_weights(cfg, key, l), x, seg, cfg, precision)


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def _served_head(x, scale, unembed, rows, cols, *, cfg, precision):
    h = rmsnorm(x[rows, cols], scale, cfg["rms_norm_eps"])
    return mm("nd,dv->nv", h, unembed, precision)


# ---------------------------------------------------------------- training


def init_params(cfg: dict, key) -> dict:
    """Parameters as stored: the configuration's dtype."""
    dt = jnp.dtype(cfg["dtype"])
    return {
        "top": {n: v.astype(dt) for n, v in W.top_weights(cfg, key).items()},
        "layers": [{n: v.astype(dt) for n, v in
                    W.layer_weights(cfg, key, l).items()}
                   for l in range(cfg["num_hidden_layers"])],
    }


def loss(params: dict, batch: dict, cfg: dict, precision: str):
    """Mean next-token cross entropy over targets in the same document."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    tokens, seg = batch["tokens"], batch["segment_ids"]
    top = f32(params["top"])
    x = top["embed.table"][tokens]

    @jax.checkpoint
    def step(w, x):          # weights as stored; float32 inside, recomputed
        return layer(f32(w), x, seg, cfg, precision)

    for w in params["layers"]:
        x = step(w, x)
    h = rmsnorm(x, top["final_norm.scale"], cfg["rms_norm_eps"])[:, :-1]
    tgt = tokens[:, 1:]
    valid = ((seg[:, 1:] == seg[:, :-1]) & (seg[:, 1:] != 0)
             & (batch["loss_mask"][:, 1:] > 0))
    d = h.shape[-1]
    h, tgt, valid = h.reshape(-1, d), tgt.reshape(-1), valid.reshape(-1)
    pad = (-h.shape[0]) % LOSS_BLOCK
    h = jnp.pad(h, ((0, pad), (0, 0)))
    tgt = jnp.pad(tgt, (0, pad))
    valid = jnp.pad(valid, (0, pad))

    @jax.checkpoint
    def block_nll(args):
        hb, tb, vb = args
        logits = mm("nd,dv->nv", hb, top["unembed.kernel"], precision)
        lse = jax.nn.logsumexp(logits, axis=-1)
        nll = lse - jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(vb, nll, 0.0))

    nb = h.shape[0] // LOSS_BLOCK
    sums = jax.lax.map(block_nll, (h.reshape(nb, LOSS_BLOCK, d),
                                   tgt.reshape(nb, LOSS_BLOCK),
                                   valid.reshape(nb, LOSS_BLOCK)))
    return jnp.sum(sums) / jnp.maximum(jnp.sum(valid), 1)


def leaf_norms(tree: dict) -> dict:
    """{(name, layer): L2 norm} of a reference parameter-shaped tree."""
    out = {(n, 0): jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
           for n, v in tree["top"].items()}
    for l, w in enumerate(tree["layers"]):
        out.update({(n, l): jnp.sqrt(jnp.sum(jnp.square(
            v.astype(jnp.float32)))) for n, v in w.items()})
    return out


def host_leaves(tree: dict) -> dict:
    """{(name, layer): float32 array on the host} of a reference tree."""
    tree = jax.device_get(tree)
    out = {(n, 0): np.asarray(v, np.float32) for n, v in tree["top"].items()}
    for l, w in enumerate(tree["layers"]):
        out.update({(n, l): np.asarray(v, np.float32) for n, v in w.items()})
    return out


def train(cfg: dict, seed: int, batches: list, precision: str = "f32"):
    """AdamW over ``batches`` from the seed's weights, as the configuration
    states it.  Returns (losses, the first clipped gradient's leaf norms,
    leaf norms of the parameters' change after the last step, the first
    clipped gradient's leaves on the host)."""
    opt = cfg["optimizer"]
    b1, b2, eps, wd, lr = (opt["b1"], opt["b2"], opt["eps"],
                           opt["weight_decay"], opt["lr"])
    key = W.base_key(seed)
    dt = jnp.dtype(cfg["dtype"])
    params = jax.jit(lambda k: init_params(cfg, k))(key)
    batches = [{k: jnp.asarray(a) for k, a in b.items()} for b in batches]

    def clipped_grad(params, batch):
        val, g = jax.value_and_grad(loss)(params, batch, cfg, precision)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                             for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, opt["max_grad_norm"] / jnp.maximum(gnorm,
                                                                     1e-12))
        return val, jax.tree.map(lambda x: x * scale, g)

    # The first gradient alone, before the optimizer's state takes memory.
    grad1 = host_leaves(jax.jit(clipped_grad)(params, batches[0])[1])
    zeros = lambda: jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params)
    m, v = zeros(), zeros()

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, batch, t):
        val, g = clipped_grad(params, batch)
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t

        def upd(p, a, s):
            p32 = p.astype(jnp.float32)
            delta = (a / bc1) / (jnp.sqrt(s / bc2) + eps) + wd * p32
            return (p32 - lr * delta).astype(dt)

        return jax.tree.map(upd, params, m, v), m, v, val

    losses = []
    for t, batch in enumerate(batches, start=1):
        params, m, v, val = step(params, m, v, batch, float(t))
        losses.append(float(val))
    del m, v

    @jax.jit
    def change(params, k):
        p0 = init_params(cfg, k)
        return leaf_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            params, p0))

    delta = {k: float(x) for k, x in change(params, key).items()}
    norms = {k: float(np.linalg.norm(x)) for k, x in grad1.items()}
    return losses, norms, delta, grad1
