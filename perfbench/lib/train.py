"""The training driver: the program's jitted train step on packed documents.

Set-up builds one object, the compiled step with its state, and drives it
from the seed through its first ``CHECK_STEPS`` steps, through the same
call and feed as the window; the window then continues from that state.
The reference follows those steps on batches it lays out again itself
from the documents drawn (``traffic.reference_batch``).
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from lib import device, traffic
from lib import weights as W

CHECK_STEPS = 3
CHIPS = (1,)                   # the step and its state live on one chip
RKEY = jax.random.PRNGKey(0)   # the step's compression key; unused


def parts(cfg: dict):
    """(api, optimizer, jitted train step) as the configuration states."""
    from repro.models.factory import build
    from repro.train.optim import adamw
    from repro.train.state import make_train_step

    from lib.model import arch_config

    o = cfg["optimizer"]
    lr = o["lr"]
    opt = adamw(lambda step: jnp.full((), lr, jnp.float32), b1=o["b1"],
                b2=o["b2"], eps=o["eps"], weight_decay=o["weight_decay"],
                moment_dtype=jnp.dtype(o["moment_dtype"]))
    api = build(arch_config(cfg))
    step = make_train_step(api.loss, opt, max_grad_norm=o["max_grad_norm"])
    return api, opt, jax.jit(step, donate_argnums=(0,))


def batches_for(cell: dict, seed: int, wrap=None):
    """Endless (packed batch, documents drawn for it) of the cell, packed
    by the program's packer (or by ``wrap(packer)``, for the tests)."""
    from repro.data.packing import pack_documents

    wl, cfg = cell["workload"], cell["config"]
    pack = pack_documents if wrap is None else wrap(pack_documents)
    return traffic.packed_batches(cell["traffic"], cfg["vocab_size"], seed,
                                  wl["rows"], wl["seq_len"], pack)


def _worst(gap, ref_norms: dict) -> float:
    """Worst leaf of ``gap(leaf, scale)``; each leaf's scale is the larger
    of the reference's norm of it and the median leaf's.  Leaves whose
    reference norm is under a thousandth of the median leaf's are left
    out: rounding alone moves them."""
    med = float(np.median(list(ref_norms.values())))
    return max(gap(k, max(r, med)) for k, r in ref_norms.items()
               if r >= 1e-3 * med)


def gaps(prog: dict, ref: dict) -> float:
    """Worst leaf gap between two sets of leaf norms: |prog - ref| over
    the leaf's scale (see :func:`_worst`)."""
    return _worst(lambda k, s: abs(prog[k] - ref[k]) / s, ref)


def diffs(prog: dict, ref: dict) -> float:
    """Worst leaf of the norm of the difference of two sets of arrays,
    over the leaf's scale (see :func:`_worst`)."""
    norms = {k: float(np.linalg.norm(v)) for k, v in ref.items()}
    return _worst(lambda k, s: float(np.linalg.norm(prog[k] - ref[k])) / s,
                  norms)


def first_steps(cfg: dict, seed: int, program: tuple, batches: list):
    """Build the state from the seed and take one step per batch, with
    ``program`` = (api, optimizer, step) as :func:`parts` gives them.

    Returns (state, (losses, the first clipped gradient's leaf norms,
    leaf norms of the parameters' change after the last step, the first
    clipped gradient's leaves on the host)).  The first gradient is the
    one the optimizer got: its first moment after one step, over 1 - b1.
    """
    from repro.train.state import init_train_state

    api, opt, step = program
    abstract = api.abstract()
    key = W.base_key(seed)
    state = jax.jit(lambda k: init_train_state(
        W.program_tree(abstract, cfg, k), opt))(key)
    b1 = cfg["optimizer"]["b1"]
    losses, grad1 = [], None
    for batch in batches:
        state, metrics = step(state, batch, RKEY)
        losses.append(float(metrics["loss"]))
        if grad1 is None:
            grad1 = W.leaf_arrays(state.opt_state["m"], 1 / (1 - b1))
    delta = {k: float(v) for k, v in jax.jit(
        lambda p, k: W.leaf_norms(p, cfg, minus_key=k))(
            state.params, key).items()}
    norms = {k: float(np.linalg.norm(v)) for k, v in grad1.items()}
    return state, (losses, norms, delta, grad1)


def numbers(got: tuple, want: tuple) -> dict:
    """What the check reads of a run against the reference.

    Compared: ``grad1_diff``, the worst leaf's norm of the difference of
    the first gradients; ``grad1_gap`` and ``delta3_gap``, the worst leaf
    gaps between the norms of the first gradient and of the change after
    the last step.  Reported only: the first step's loss gap and the
    worst loss gap over the steps, which the float8 control does not
    separate from sound runs (PERF.md).
    """
    return {"grad1_diff": diffs(got[3], want[3]),
            "grad1_gap": gaps(got[1], want[1]),
            "delta3_gap": gaps(got[2], want[2]),
            "loss1_gap": abs(got[0][0] - want[0][0]) / abs(want[0][0]),
            "loss_gap": max(abs(a - b) / abs(b)
                            for a, b in zip(got[0], want[0]))}


def run(cell: dict, seed: int, seconds: float, devs: list, t_start: float,
        tracer=None, hooks=None) -> dict:
    from repro.obs.trace import span

    from lib.cell import reference

    wl, cfg = cell["workload"], cell["config"]
    api, opt, step = parts(cfg)
    if hooks and "step" in hooks:
        step = hooks["step"](step)
    feed = batches_for(cell, seed, hooks and hooks.get("pack"))
    drawn = [next(feed) for _ in range(CHECK_STEPS)]
    first = [b for b, _ in drawn]
    state, got = first_steps(cfg, seed, (api, opt, step), first)
    jax.block_until_ready(state)
    setup_s = time.perf_counter() - t_start

    if tracer is not None:
        tracer.arm(seconds)
    tokens = 0
    steps = 0
    pending = None
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter()
        if tracer is not None:
            tracer.poll(now - t0)
        if now - t0 >= seconds:
            break
        with span("bench.next_batch"):
            batch, _ = next(feed)
        with span("bench.train_step"):
            state, metrics = step(state, batch, RKEY)
        tokens += int((batch["segment_ids"] != 0).sum())
        steps += 1
        if pending is not None:
            with span("bench.wait_step"):
                jax.block_until_ready(pending)
        pending = metrics["loss"]
    jax.block_until_ready(state)
    t_close = time.perf_counter()
    if tracer is not None:
        tracer.stop()
    last_loss = float(pending) if pending is not None else float("nan")
    peak = device.memory_peak(devs)
    del state, pending, metrics
    gc.collect()

    finite = all(np.isfinite(got[0])) and np.isfinite(last_loss)
    out = {"setup_s": setup_s, "window_s": t_close - t0, "tokens": tokens,
           "steps": steps, "attempted": steps, "failed": 0 if finite else 1,
           "memory_peak_bytes": peak}
    try:
        mine = [traffic.reference_batch(b, d) for b, d in drawn]
    except traffic.PackingError as e:
        return dict(out, compared={}, correct=False,
                    verdict={"packing": str(e), "losses": got[0]})
    t = time.perf_counter()
    want = reference(cfg).train(cfg, seed, mine, "f32")
    ref_s = time.perf_counter() - t
    lim = wl["limits"]
    found = numbers(got, want)
    compared = {k: (found[k], lim[k]) for k in lim}
    return dict(
        out, compared=compared,
        correct=bool(finite and all(v <= l for v, l in compared.values())),
        verdict={"losses": got[0], "ref_losses": want[0],
                 "loss1_gap": found["loss1_gap"],
                 "loss_gap": found["loss_gap"], "last_loss": last_loss,
                 "reference_s": ref_s})
