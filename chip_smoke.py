#!/usr/bin/env python3
"""Smoke run of the Aaren language model's main path on one TPU.

Drives the entry points a user calls (``factory.build``,
``StreamingEngine``, ``build_train_state`` + ``make_train_step`` +
``run_train_loop``) at the full width of phi3-mini-3.8b with random weights
drawn from ``--seed``, and checks what comes out:

* **serve** — all 32 layers, bf16, ``attn_mode="aaren"``.  An 8-slot
  ``StreamingEngine`` with its default chunk answers 16 requests (prompts of
  64-512 tokens, 64 new tokens each).  Every request must finish whole, with
  nothing quarantined, errored or shed.  Before that, the Pallas chunk step's
  last-token logits are compared with the jnp step's on the same chunks and
  carries.
* **train** — the same widths cut to 4 layers; batch 4 x 2048 tokens, 5
  steps, guard off, every loss finite.  One step's loss and gradient norm are
  compared with the jnp path.  Then the same cut with ``attn_mode="softmax"``
  takes 2 steps through the flash kernels.

Each phase asserts that the kernel mode resolved to ``pallas`` and that its
program holds a ``tpu_custom_call``.  Compile seconds are printed as set-up
time, with ``peak_bytes_in_use`` per device.  No speed is measured.

``--four-chips`` runs only the sharded training path: one step on a
``data=4`` mesh and one on a ``seq=4`` mesh, each loss compared with the
one-chip loss of the same params and batch.

Without a TPU the script exits non-zero before doing any work.  The last
line of a passing run is one JSON object naming the device.

Usage::

    python chip_smoke.py [--seed 0] [--four-chips]
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import math
import os
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

MODEL = "phi3-mini-3.8b"
SERVE_SLOTS = 8
SERVE_REQUESTS = 16
PROMPT_LENS = (64, 512)
MAX_NEW = 64
TRAIN_LAYERS = 4
TRAIN_BATCH, TRAIN_SEQ = 4, 2048
TRAIN_STEPS, SOFTMAX_STEPS = 5, 2

# Pallas vs jnp on the chip.  Both paths scan in f32 but round the mixer
# output to bf16; a rounding flip moves a bf16 activation by 2^-8 relative
# and 32 random layers carry it on, so the logits are held to a relative L2
# error, the loss (a mean over 8188 tokens) tighter, and the gradient norm
# in between.
LOGITS_REL_L2 = 5e-2
LOSS_REL = 1e-3
GNORM_REL = 2e-2
# One chip vs a data=4 or seq=4 mesh: the same kernels, other reduction
# orders.
MESH_LOSS_REL = 1e-3


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


@contextlib.contextmanager
def kernel_mode_env(mode: str):
    """Trace under ``REPRO_KERNEL_MODE=mode``.

    The mode is read at trace time and is in no cache key, so JAX's trace
    caches are cleared on the way in and out: a function traced under one
    mode is never reused under the other.
    """
    import jax

    prev = os.environ.get("REPRO_KERNEL_MODE")
    os.environ["REPRO_KERNEL_MODE"] = mode
    jax.clear_caches()
    try:
        yield
    finally:
        if prev is None:
            del os.environ["REPRO_KERNEL_MODE"]
        else:
            os.environ["REPRO_KERNEL_MODE"] = prev
        jax.clear_caches()


def compile_both(make_fn, *args, what: str):
    """AOT-compile ``make_fn()`` on the kernel path and on the jnp path.

    The kernel program must hold a ``tpu_custom_call`` and the reference
    must not, or the comparison would hold the kernel against itself.
    """
    import jax

    t0 = time.perf_counter()
    kernel = jax.jit(make_fn()).lower(*args).compile()
    require_kernel(kernel.as_text(), what)
    with kernel_mode_env("jnp"):
        ref = jax.jit(make_fn()).lower(*args).compile()
    check("tpu_custom_call" not in ref.as_text(),
          f"{what}: the jnp reference still calls a kernel")
    print(f"[{what}] compile kernel + jnp programs "
          f"{time.perf_counter() - t0:.1f}s (set-up)", flush=True)
    return kernel, ref


def rel_err(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def require_pallas() -> None:
    from repro.kernels.ops import kernel_mode

    check(kernel_mode() == "pallas",
          f"kernel mode resolved to {kernel_mode()!r}, not 'pallas'")


def require_kernel(program_text: str, what: str) -> None:
    check("tpu_custom_call" in program_text,
          f"{what}: no tpu_custom_call in the program")


def report_memory(label: str) -> None:
    import jax

    for d in jax.devices():
        stats = d.memory_stats() or {}
        print(f"[{label}] {d} peak_bytes_in_use="
              f"{stats.get('peak_bytes_in_use', 'not reported')} "
              f"bytes_in_use={stats.get('bytes_in_use', 'not reported')}",
              flush=True)


def model_config(attn_mode: str, n_layers: int | None = None):
    from repro.configs import get_config

    cfg = get_config(MODEL).replace(attn_mode=attn_mode)
    if n_layers is not None:
        cfg = cfg.replace(n_layers=n_layers)
    return cfg


# ---------------------------------------------------------------- serving


def compare_chunk_step(cfg, params, prompts, chunk: int) -> None:
    """Pallas vs jnp ``lm_prefill_chunk`` on the same chunks and carries.

    Chunk 1 starts every slot from the empty carry with a full chunk of
    prompt; chunk 2 continues from the Pallas carry with ragged lengths
    (one slot a single token, as in decode).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.lm import lm_prefill_chunk, lm_state_init

    s = SERVE_SLOTS

    def make_step():
        def step(p, tokens, lengths, states):
            mask = jnp.arange(chunk)[None, :] < lengths[:, None]
            logits, new = lm_prefill_chunk(cfg, p, tokens, states,
                                           length_mask=mask)
            last = jnp.take_along_axis(
                logits, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)
            return last[:, 0], new
        return step

    states = lm_state_init(cfg, s, 1)
    tok1 = jnp.asarray(np.stack([p[:chunk] for p in prompts[:s]]), jnp.int32)
    len1 = jnp.full((s,), chunk, jnp.int32)
    tok2 = jnp.asarray(np.stack([p[chunk:2 * chunk] for p in prompts[:s]]),
                       jnp.int32)
    len2 = jnp.asarray([chunk, 1, 5, chunk, 2, 9, chunk, 1][:s], jnp.int32)

    pallas, ref = compile_both(make_step, params, tok1, len1, states,
                               what="serve")

    got1, st1 = pallas(params, tok1, len1, states)
    want1, _ = ref(params, tok1, len1, states)
    got2, _ = pallas(params, tok2, len2, st1)
    want2, _ = ref(params, tok2, len2, st1)
    for name, got, want in (("chunk1", got1, want1), ("chunk2", got2, want2)):
        got, want = np.asarray(got), np.asarray(want)
        check(np.isfinite(got).all(), f"serve {name}: non-finite logits")
        errs = [rel_err(got[i], want[i]) for i in range(s)]
        agree = float((got.argmax(-1) == want.argmax(-1)).mean())
        print(f"[serve] {name} pallas vs jnp last-token logits: max rel L2 "
              f"{max(errs):.3e} (tolerance {LOGITS_REL_L2:g}), argmax "
              f"agreement {agree:.3f}", flush=True)
        check(max(errs) <= LOGITS_REL_L2,
              f"serve {name}: logits rel L2 {max(errs):.3e} > "
              f"{LOGITS_REL_L2:g}")


def serve_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.factory import build
    from repro.serving import StreamingEngine

    cfg = model_config("aaren")
    print(f"[serve] {cfg.name} attn_mode={cfg.attn_mode} "
          f"layers={cfg.n_layers} d_model={cfg.d_model} "
          f"param_dtype={cfg.param_dtype}", flush=True)
    require_pallas()
    api = build(cfg)
    t0 = time.perf_counter()
    params = api.init(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    print(f"[serve] init params {time.perf_counter() - t0:.1f}s (set-up)",
          flush=True)

    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, SERVE_REQUESTS)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in lens]

    eng = StreamingEngine(api, params, n_slots=SERVE_SLOTS)
    compare_chunk_step(cfg, params, prompts, eng.chunk)

    print(f"[serve] engine warm-up (compile) {eng.warmup():.1f}s (set-up), "
          f"chunk {eng.chunk}", flush=True)
    tokens = jnp.zeros((SERVE_SLOTS, eng.chunk), jnp.int32)
    lengths = jnp.ones((SERVE_SLOTS,), jnp.int32)
    require_kernel(eng._step_fn.lower(params, tokens, lengths, eng.states)
                   .compile().as_text(), "engine step")
    for p in prompts:
        eng.submit(p, MAX_NEW)
    out = eng.run()
    check(len(out) == SERVE_REQUESTS,
          f"serve: {len(out)}/{SERVE_REQUESTS} requests finished")
    short = {r: len(t) for r, t in out.items() if len(t) != MAX_NEW}
    check(not short, f"serve: requests short of {MAX_NEW} tokens: {short}")
    check(all(0 <= t < cfg.vocab for toks in out.values() for t in toks),
          "serve: token id outside the vocabulary")
    check(eng.n_quarantined == 0 and not eng.errors and eng.n_shed == 0,
          f"serve: quarantined {eng.n_quarantined}, errored "
          f"{len(eng.errors)}, shed {eng.n_shed}")
    print(f"[serve] finished {len(out)}/{SERVE_REQUESTS} requests, "
          f"{sum(map(len, out.values()))} tokens (prompts {int(lens.min())}-"
          f"{int(lens.max())} tokens); quarantined 0, errored 0, shed 0",
          flush=True)
    report_memory("serve")


# --------------------------------------------------------------- training


def grad_stats_fn(api):
    """(params, batch) -> (loss, global grad norm), as the train step
    computes them before clipping."""
    import jax
    import jax.numpy as jnp

    def stats(params, batch):
        (loss, _), grads = jax.value_and_grad(api.loss, has_aux=True)(
            params, batch)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree.leaves(grads)))
        return loss, gnorm

    return stats


def train_parts(cfg, steps: int):
    from repro.models.factory import build
    from repro.train.optim import make_optimizer, warmup_cosine
    from repro.train.state import make_train_step

    api = build(cfg)
    opt = make_optimizer(cfg.optimizer, warmup_cosine(3e-4, 1, steps))
    return api, opt, make_train_step(api.loss, opt)


def data_iter(cfg, seed: int):
    from repro.data.synthetic import SyntheticLMIterator

    return SyntheticLMIterator(vocab=cfg.vocab, seq_len=TRAIN_SEQ,
                               batch=TRAIN_BATCH, seed=seed)


def train_loop(step, state, cfg, seed: int, steps: int, label: str,
               loop_kw: dict | None = None):
    """``run_train_loop`` for ``steps`` steps; every loss must be finite.

    The jitted step is compiled ahead, under the loop's mesh, to check that
    its program holds the kernel; the loop's first call then finds it in
    the compilation cache.
    """
    import jax

    from repro.distributed.context import mesh_plan_session
    from repro.train.loop import LoopConfig, loop_plan, run_train_loop

    loop_cfg = LoopConfig(total_steps=steps, log_every=1, seed=seed,
                          guard=False, **(loop_kw or {}))
    jitted = jax.jit(step, donate_argnums=(0,))
    t0 = time.perf_counter()
    with mesh_plan_session(loop_plan(loop_cfg)):
        program = jitted.lower(state, next(data_iter(cfg, seed)),
                               jax.random.PRNGKey(seed)).compile()
    require_kernel(program.as_text(), f"{label} step")
    print(f"[{label}] compile train step {time.perf_counter() - t0:.1f}s "
          "(set-up)", flush=True)
    result = run_train_loop(jitted, state, data_iter(cfg, seed), loop_cfg)
    losses = [m["loss"] for _, m in result.history]
    check(len(losses) == steps, f"{label}: {len(losses)}/{steps} steps ran")
    check(all(math.isfinite(x) for x in losses),
          f"{label}: non-finite loss {losses}")
    check(result.skipped_steps == 0, f"{label}: guard skipped steps")
    for s, m in result.history:
        print(f"[{label}] step {s} loss {m['loss']:.6f} grad_norm "
              f"{m['grad_norm']:.6f}", flush=True)
    return result


def train_phase(seed: int) -> None:
    import jax
    import numpy as np

    from repro.train.state import build_train_state

    cfg = model_config("aaren", TRAIN_LAYERS)
    print(f"[train] {cfg.name} cut to {TRAIN_LAYERS} of 32 layers at full "
          f"width (d_model={cfg.d_model}, heads={cfg.n_heads}, "
          f"head_dim={cfg.resolved_head_dim}, d_ff={cfg.d_ff}); batch "
          f"{TRAIN_BATCH} x {TRAIN_SEQ}, {TRAIN_STEPS} steps, guard off",
          flush=True)
    require_pallas()
    api, opt, step = train_parts(cfg, TRAIN_STEPS)
    t0 = time.perf_counter()
    state = build_train_state(api, opt, jax.random.PRNGKey(seed))
    jax.block_until_ready(state)
    print(f"[train] init state {time.perf_counter() - t0:.1f}s (set-up)",
          flush=True)

    batch = next(data_iter(cfg, seed))
    pallas, ref = compile_both(lambda: grad_stats_fn(api), state.params,
                               batch, what="train")
    loss_k, gn_k = (float(x) for x in pallas(state.params, batch))
    loss_r, gn_r = (float(x) for x in ref(state.params, batch))
    del pallas, ref
    e_loss = abs(loss_k - loss_r) / abs(loss_r)
    e_gn = abs(gn_k - gn_r) / abs(gn_r)
    print(f"[train] pallas vs jnp: loss {loss_k:.6f} vs {loss_r:.6f} (rel "
          f"{e_loss:.2e}, tolerance {LOSS_REL:g}); grad norm {gn_k:.6f} vs "
          f"{gn_r:.6f} (rel {e_gn:.2e}, tolerance {GNORM_REL:g})", flush=True)
    check(np.isfinite([loss_k, gn_k]).all(), "train: non-finite grad stats")
    check(e_loss <= LOSS_REL, f"train: loss rel err {e_loss:.2e}")
    check(e_gn <= GNORM_REL, f"train: grad norm rel err {e_gn:.2e}")

    result = train_loop(step, state, cfg, seed, TRAIN_STEPS, "train")
    del state, result
    gc.collect()
    report_memory("train")

    cfg = model_config("softmax", TRAIN_LAYERS)
    print(f"[train-softmax] same cut, attn_mode=softmax (flash kernels), "
          f"{SOFTMAX_STEPS} steps", flush=True)
    require_pallas()
    api, opt, step = train_parts(cfg, SOFTMAX_STEPS)
    state = build_train_state(api, opt, jax.random.PRNGKey(seed))
    result = train_loop(step, state, cfg, seed, SOFTMAX_STEPS,
                        "train-softmax")
    del state, result
    gc.collect()
    report_memory("train-softmax")


# ------------------------------------------------------------ four chips


def shard_bytes(tree) -> dict:
    """Bytes of ``tree`` held on each device."""
    import jax

    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for sh in leaf.addressable_shards:
            out[sh.device] = out.get(sh.device, 0) + sh.data.nbytes
    return out


def four_chip_phase(seed: int) -> None:
    import jax

    from repro.train.loop import LoopConfig, loop_plan
    from repro.train.state import build_train_state

    check(len(jax.devices()) == 4, f"--four-chips needs 4 devices, found "
          f"{len(jax.devices())}")
    cfg = model_config("aaren", TRAIN_LAYERS)
    print(f"[mesh] {cfg.name} cut to {TRAIN_LAYERS} of 32 layers at full "
          f"width; batch {TRAIN_BATCH} x {TRAIN_SEQ}; one step per mesh",
          flush=True)
    require_pallas()
    api, opt, step = train_parts(cfg, 1)
    batch = next(data_iter(cfg, seed))
    key = jax.random.PRNGKey(seed)
    loss_fn = jax.jit(lambda p, b: api.loss(p, b)[0])
    ref = None

    for label, kw in (("data=4", {"fsdp": 4}),
                      ("seq=4", {"context_parallel": 4})):
        plan = loop_plan(LoopConfig(total_steps=1, **kw))
        print(f"[mesh {label}] plan {plan.describe()}", flush=True)
        t0 = time.perf_counter()
        state = build_train_state(api, opt, key, mesh=plan.build_mesh())
        jax.block_until_ready(state)
        print(f"[mesh {label}] init state {time.perf_counter() - t0:.1f}s "
              "(set-up)", flush=True)
        per_dev = shard_bytes(state.params)
        total = sum(x.nbytes for x in jax.tree.leaves(state.params))
        for d, b in sorted(per_dev.items(), key=lambda x: x[0].id):
            print(f"[mesh {label}] {d} holds {b} param bytes "
                  f"({b / total:.3f} of {total})", flush=True)
        check(len(per_dev) == 4, f"{label}: params on {len(per_dev)} devices")
        if plan.data == 4:
            check(max(per_dev.values()) <= 0.3 * total,
                  f"{label}: a device holds more than 0.3 of the params")

        if ref is None:
            # One-chip loss of the same params and batch, in this process.
            one = jax.device_put(state.params, jax.devices()[0])
            ref = float(loss_fn(one, batch))
            del one
            print(f"[mesh] one-chip loss {ref:.6f}", flush=True)

        result = train_loop(step, state, cfg, seed, 1, f"mesh {label}", kw)
        loss = result.history[0][1]["loss"]
        err = abs(loss - ref) / abs(ref)
        print(f"[mesh {label}] loss {loss:.6f} vs one-chip {ref:.6f} (rel "
              f"{err:.2e}, tolerance {MESH_LOSS_REL:g})", flush=True)
        check(err <= MESH_LOSS_REL, f"{label}: loss rel err {err:.2e}")
        del state, result
        gc.collect()
        report_memory(f"mesh {label}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data=4 / seq=4 training path")
    args = ap.parse_args()

    if not (SRC / "repro").is_dir():
        fail(f"the repro package is not at {SRC}")
    sys.path.insert(0, str(SRC))
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (platform {dev.platform!r}); this script "
             "runs on the chip only")
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {enable_compile_cache()}",
          flush=True)
    if args.four_chips:
        four_chip_phase(args.seed)
    else:
        serve_phase(args.seed)
        gc.collect()
        train_phase(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
