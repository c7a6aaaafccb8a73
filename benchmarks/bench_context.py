"""Context-parallelism benchmark: tokens/s and per-device peak activation
bytes vs ``seq`` mesh-axis size at fixed global N, on emulated CPU devices.

Writes ``BENCH_context.json``.  The claim under test (DESIGN.md
§Context-parallelism): each device materialises only its 1/P sequence shard
— activations shrink ~1/P per device — while the cross-device traffic is one
``(m, u, w)`` carry per boundary, so the memory win is not bought with an
activation-sized collective.

Peak activation bytes come from XLA's ``compiled.memory_analysis()``
(``temp_size_in_bytes`` of the SPMD per-device executable: the non-I/O
buffers, i.e. activations + workspace).  Throughput on *emulated* devices is
reported for completeness but is not a hardware claim — 8 fake devices share
one physical CPU, so tokens/s stays roughly flat while the per-device bytes
drop.

The ``composed`` row exercises the full 2x2x2 (data x seq x model)
``MeshPlan`` (DESIGN.md §Parallelism): loss parity against the seq-only
rows plus the per-axis wire accounting — the roofline's analytic
``predict_axis_exchange`` next to ``collective_bytes_by_axis`` counted from
the compiled HLO, one entry per mesh axis, so a collective landing on the
wrong axis (or an "other" partition) shows up as a ratio drifting from 1.

This module keeps its import side-effect free: the 8-device XLA flag must be
set before jax initialises, so ``run()`` (the ``benchmarks/run.py`` harness
hook) re-executes this file as a subprocess with the flag in the
environment, mirroring how launch/dryrun.py forces 512 hosts.  The child is
pinned to the CPU (``JAX_PLATFORMS=cpu``): its mesh is the emulated one, and
on an accelerator host the harness parent already holds the chip, which a
second process cannot open.  Its output says so (``config.platform``).

Usage::

    python benchmarks/run.py --only context         # harness (subprocess)
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src:. python benchmarks/bench_context.py   # direct
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

SEQ_SIZES = (1, 2, 4, 8)
OUT = "BENCH_context.json"


def run():
    """Harness hook: re-exec on 8 emulated CPU devices, then emit the rows."""
    from benchmarks.common import emit

    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                        + env.get("XLA_FLAGS", ""))
    env["JAX_PLATFORMS"] = "cpu"
    subprocess.run([sys.executable, os.path.abspath(__file__)], check=True,
                   env=env)
    with open(OUT) as f:
        data = json.load(f)
    for point in data["points"]:
        emit(f"context_seq{point['seq_axis']}_tokens_per_s", 0.0,
             f"{point['tokens_per_s']:.0f}")
        emit(f"context_seq{point['seq_axis']}_act_bytes_per_device", 0.0,
             str(point["peak_activation_bytes_per_device"]))
    comp = data.get("composed")
    if comp:
        emit("context_composed_loss_drift", 0.0,
             f"{comp['loss_drift_vs_seq_axis_1']:.2e}")
        for ax, b in sorted(comp["measured_axis_bytes"].items()):
            emit(f"context_composed_{ax}_bytes", 0.0, str(int(b)))


def main():
    import jax
    import jax.numpy as jnp

    from repro.configs.base import ArchConfig
    from repro.distributed.context import (
        ContextParallel, use_context_parallel)
    from repro.launch.mesh import make_host_mesh
    from repro.models.factory import build
    from repro.sharding import ShardingRules, use_rules

    n_dev = len(jax.devices())
    if n_dev < max(SEQ_SIZES):
        raise SystemExit(
            f"need {max(SEQ_SIZES)} devices, have {n_dev}; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8")

    cfg = ArchConfig(
        name="bench-context", family="dense", n_layers=2, d_model=128,
        n_heads=4, n_kv_heads=4, d_ff=256, vocab=512, pattern=("attn",),
        mlp_pattern=("swiglu",), attn_mode="aaren", param_dtype="float32",
        compute_dtype="float32", remat="none")
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    batch_size, seq_len = 2, 2048  # global tokens fixed across seq sizes
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch_size, seq_len), 0, cfg.vocab)
    batch = {"tokens": tokens}

    points = []
    for sp in SEQ_SIZES:
        mesh = make_host_mesh(context_parallel=sp)
        cp = ContextParallel(mesh)
        with use_rules(ShardingRules(mesh)), use_context_parallel(cp):
            step = jax.jit(jax.value_and_grad(
                lambda p, b: api.loss(p, b)[0]))
            compiled = step.lower(params, batch).compile()
            mem = compiled.memory_analysis()
            temp = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
            loss, g = compiled(params, batch)  # warmup
            jax.block_until_ready(g)
            iters = 3
            t0 = time.perf_counter()
            for _ in range(iters):
                loss, g = compiled(params, batch)
            jax.block_until_ready(g)
            dt = (time.perf_counter() - t0) / iters
        points.append({
            "seq_axis": sp,
            "tokens_per_s": batch_size * seq_len / dt,
            "step_time_s": dt,
            "peak_activation_bytes_per_device": temp,
            "loss": float(loss),
        })
        print(f"seq={sp}: {points[-1]['tokens_per_s']:.0f} tok/s, "
              f"{temp/1e6:.2f} MB/device temp, loss {float(loss):.4f}",
              flush=True)

    # Composed 2x2x2 plan: loss parity + per-axis predicted vs measured
    # wire bytes (DESIGN.md §Parallelism).
    from repro.distributed.context import mesh_plan_session
    from repro.roofline.analysis import (
        axis_seconds, collective_bytes_by_axis, predict_axis_exchange)
    from repro.sharding import MeshPlan

    plan = MeshPlan(data=2, seq=2, model=2)
    with mesh_plan_session(plan):
        step = jax.jit(jax.value_and_grad(lambda p, b: api.loss(p, b)[0]))
        compiled = step.lower(params, batch).compile()
        measured = collective_bytes_by_axis(
            compiled.as_text(), {"data": 2, "seq": 2, "model": 2})
        loss_c, g = compiled(params, batch)
        jax.block_until_ready(g)
        t0 = time.perf_counter()
        for _ in range(3):
            loss_c, g = compiled(params, batch)
        jax.block_until_ready(g)
        dt_c = (time.perf_counter() - t0) / 3
    param_bytes = 4 * sum(int(x.size) for x in jax.tree.leaves(params))
    predicted = predict_axis_exchange(
        plan, batch=batch_size, seq_len=seq_len, n_heads=cfg.n_heads,
        head_dim=cfg.d_model // cfg.n_heads, d_model=cfg.d_model,
        n_layers=cfg.n_layers, param_bytes=param_bytes, attn_mode="aaren")
    composed = {
        "plan": plan.describe(),
        "loss": float(loss_c),
        "loss_drift_vs_seq_axis_1": abs(float(loss_c) - points[0]["loss"]),
        "tokens_per_s": batch_size * seq_len / dt_c,
        "measured_step_s": dt_c,
        "predicted_axis_bytes": {k: float(v) for k, v in predicted.items()},
        # predicted wire seconds per axis (V5E link bw) next to the measured
        # wall step — the roofline's time-domain counterpart
        # (roofline.analysis.axis_seconds / RooflineReport.measured_step_s).
        "predicted_axis_seconds": axis_seconds(predicted),
        "measured_axis_bytes": {k: float(v["total"])
                                for k, v in measured.items()},
    }
    print(f"composed {plan.describe()}: loss {float(loss_c):.4f} "
          f"(drift {composed['loss_drift_vs_seq_axis_1']:.2e})", flush=True)
    for ax in sorted(set(predicted) | set(composed["measured_axis_bytes"])):
        p_b = predicted.get(ax, 0.0)
        m_b = composed["measured_axis_bytes"].get(ax, 0.0)
        print(f"  axis {ax:>8}: predicted {p_b/1e3:.1f} KB, "
              f"measured {m_b/1e3:.1f} KB", flush=True)

    report = {
        "config": {"model": cfg.name, "batch": batch_size,
                   "seq_len": seq_len, "devices": n_dev,
                   "platform": (f"{jax.devices()[0].platform}, {n_dev} "
                                "emulated host devices"),
                   "kernel_mode": os.environ.get("REPRO_KERNEL_MODE",
                                                 "auto")},
        "points": points,
        "composed": composed,
    }
    from benchmarks.common import write_bench
    write_bench("context", report)

    losses = [p["loss"] for p in points]
    spread = max(losses) - min(losses)
    assert spread < 1e-4, f"loss drifts across seq sizes: {losses}"
    assert composed["loss_drift_vs_seq_axis_1"] < 1e-4, composed
    assert composed["measured_axis_bytes"].get("other", 0.0) == 0.0, \
        f"collective off every plan axis: {composed['measured_axis_bytes']}"


if __name__ == "__main__":
    main()
