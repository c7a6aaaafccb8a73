import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Per cell, two kinds of lowering:

1. **Full lowering** (the deliverable): the production step function —
   scan-over-layers, microbatched grad accumulation, remat — lowered and
   compiled against the 16×16 or 2×16×16 mesh with every input abstract
   (``ShapeDtypeStruct``).  Success proves the sharding config is coherent;
   ``memory_analysis()`` proves it fits.

2. **Cost probes** (the roofline source): XLA's HloCostAnalysis counts a
   while-loop body ONCE, not × trip-count, so the scanned full lowering
   under-reports FLOPs/bytes by ~n_layers×.  The probes lower *unrolled*
   1-period and 2-period variants of the same cell (single microbatch,
   identical sharding); the per-period increment Δ = c(2P) − c(P) scales to
   the full depth:  total(L) = c(P) + (L−P)·Δ/P, × n_microbatches for train.
   Optimizer flops/bytes (excluded from the grad probe) are added
   analytically — they are exact functions of the sharded parameter bytes.

Collective wire bytes get the same treatment (parsed per probe, scaled).

Usage::

    python -m repro.launch.dryrun --arch llama3-405b --shape train_4k
    python -m repro.launch.dryrun --arch all --shape all --mesh both --out r.json
    python -m repro.launch.dryrun --list
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ALL_ARCHS, SHAPES, get_config
from repro.distributed.grad import microbatch_grads
from repro.launch.mesh import make_production_mesh
from repro.models import blocks
from repro.models.factory import build, input_axes, input_specs
from repro.models.param import count_params
from repro.roofline.analysis import (
    collective_bytes, collective_bytes_by_axis, model_flops,
    predict_axis_exchange, roofline_report)
from repro.sharding import (
    MeshPlan, ShardingRules, param_shardings, plan_from_mesh, spec_for_axes,
    use_rules)
from repro.train.optim import make_optimizer, warmup_cosine
from repro.train.state import (
    abstract_train_state, make_train_step, train_state_shardings)


# ---------------------------------------------------------------------------
# sharding helpers
# ---------------------------------------------------------------------------


def _axes_shardings(specs_tree, axes_tree, sr: ShardingRules):
    """Zip a ShapeDtypeStruct tree with a logical-axes tree (list leaves)."""
    flat_s, treedef = jax.tree.flatten(specs_tree)
    flat_a = jax.tree.flatten(axes_tree, is_leaf=blocks.AXES_IS_LEAF)[0]
    assert len(flat_s) == len(flat_a), (len(flat_s), len(flat_a))
    out = [NamedSharding(sr.mesh, spec_for_axes(tuple(a), s.shape, sr))
           for s, a in zip(flat_s, flat_a)]
    return jax.tree.unflatten(treedef, out)


def _sharded_bytes(specs_tree, shardings_tree) -> int:
    """Per-device bytes of a sharded SDS tree."""
    total = 0
    for s, sh in zip(jax.tree.leaves(specs_tree),
                     jax.tree.leaves(shardings_tree)):
        n = int(np.prod(s.shape)) if s.shape else 1
        shards = 1
        for part in sh.spec:
            if part is None:
                continue
            names = (part,) if isinstance(part, str) else part
            shards *= int(np.prod([sh.mesh.shape[a] for a in names]))
        total += n * s.dtype.itemsize // max(shards, 1)
    return total


def _batch_shards(sr: ShardingRules, batch: int) -> int:
    spec = spec_for_axes(("batch",), (batch,), sr)
    part = spec[0] if spec else None
    if part is None:
        return 1
    names = (part,) if isinstance(part, str) else part
    return int(np.prod([sr.mesh.shape[a] for a in names]))


def _microbatches(cfg, batch: int, sr: ShardingRules) -> int:
    per = batch // _batch_shards(sr, batch)
    mb = max(min(cfg.n_microbatches, per), 1)
    while per % mb:
        mb -= 1
    return max(mb, 1)


def _active_params(cfg, api) -> int:
    """Parameter count with MoE experts scaled to the active top-k."""
    total = count_params(api.specs())
    if not cfg.n_experts:
        return total
    from repro.models.moe import moe_specs

    expert = count_params(
        {k: v for k, v in moe_specs(cfg).items() if k != "router"})
    n_moe = sum(m == "moe" for m in cfg.mlp_pattern)
    n_moe_layers = n_moe * cfg.n_layers // len(cfg.mlp_pattern)
    inactive = expert * n_moe_layers * (
        1.0 - cfg.n_experts_per_tok / cfg.n_experts)
    return int(total - inactive)


# ---------------------------------------------------------------------------
# lowering builders
# ---------------------------------------------------------------------------


def _lower(cfg, shape, sr, *, batch: int, n_microbatches: int,
           with_optimizer: bool, grad_compression: str = "none"):
    """Lower one step function for this cell.  Returns (lowered, extras)."""
    api = build(cfg)
    abstract_batch = input_specs(cfg, shape, batch_override=batch)
    batch_shardings = _axes_shardings(
        abstract_batch, input_axes(cfg, shape), sr)
    pspecs = api.specs()
    pshard = param_shardings(pspecs, sr)
    mesh = sr.mesh
    extras = {"api": api, "pspecs": pspecs, "pshard": pshard,
              "batch_shardings": batch_shardings,
              "abstract_batch": abstract_batch}

    with use_rules(sr):
        if shape.kind == "train":
            key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
            if with_optimizer:
                opt = make_optimizer(
                    cfg.optimizer, warmup_cosine(3e-4, 100, 1000))
                step_fn = make_train_step(
                    api.loss, opt, n_microbatches=n_microbatches,
                    grad_compression=grad_compression)
                astate = abstract_train_state(api.abstract(), opt)
                state_shardings = train_state_shardings(api, astate, sr)
                oshard = state_shardings.opt_state
                assert (jax.tree.structure(astate.opt_state)
                        == jax.tree.structure(oshard)), "opt shard mismatch"
                # donate the train state: lets XLA update params/opt-state
                # in place instead of double-buffering them (SPerf A3)
                lowered = jax.jit(
                    step_fn,
                    in_shardings=(state_shardings, batch_shardings,
                                  NamedSharding(mesh, P())),
                    donate_argnums=(0,),
                ).lower(astate, abstract_batch, key_sds)
                extras["astate"] = astate
                extras["oshard"] = oshard
            else:  # pure grad probe (optimizer cost added analytically)
                def grad_fn(params, b, key):
                    return microbatch_grads(
                        api.loss, params, b, n_microbatches,
                        compression=grad_compression, key=key)

                lowered = jax.jit(
                    grad_fn,
                    in_shardings=(pshard, batch_shardings,
                                  NamedSharding(mesh, P())),
                ).lower(api.abstract(), abstract_batch, key_sds)
        elif shape.kind == "prefill":
            lowered = jax.jit(
                api.prefill, in_shardings=(pshard, batch_shardings),
            ).lower(api.abstract(), abstract_batch)
        else:  # decode
            lowered = jax.jit(
                api.decode_step, in_shardings=(pshard, batch_shardings),
            ).lower(api.abstract(), abstract_batch)
    return lowered, extras


def _probe_cfg(cfg, n_layers: int):
    kw = dict(n_layers=n_layers, scan_layers=False)
    if cfg.is_encdec:
        kw["n_enc_layers"] = n_layers
    return cfg.replace(**kw)


def _analyze(compiled, mesh_shape=None):
    cost = compiled.cost_analysis() or {}
    text = compiled.as_text()
    coll = collective_bytes(text)
    wire = sum(v for k, v in coll.items() if k != "n_ops")
    out = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "wire": wire,
        "coll": coll,
    }
    if mesh_shape is not None:
        out["by_axis"] = {
            label: d["total"]
            for label, d in collective_bytes_by_axis(text, mesh_shape).items()
        }
    return out


def _opt_cost(cfg, params_bytes_pc: int, opt_bytes_pc: int,
              n_param_elems_pc: float) -> dict:
    """Analytic optimizer+clip cost per chip (flops tiny, bytes exact-ish):
    read params+grads+opt state, write params+opt state; ~18 flops/elem."""
    grad_bytes = n_param_elems_pc * 4  # f32 accumulated grads
    return {
        "flops": 18.0 * n_param_elems_pc,
        "bytes": 2.0 * (params_bytes_pc + opt_bytes_pc) + 2.0 * grad_bytes,
        "wire": 0.0,
    }


# ---------------------------------------------------------------------------
# per-cell driver
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             attn_mode: str = "aaren", verbose: bool = True,
             probes: bool = True, cfg_overrides: dict | None = None,
             rules_override: dict | None = None,
             grad_compression: str = "none",
             context_parallel: int = 1, model_parallel: int = 16,
             data_plane: int = 16, plan: MeshPlan | None = None) -> dict:
    cfg = get_config(arch, attn_mode=attn_mode, **(cfg_overrides or {}))
    shape = SHAPES[shape_name]
    if plan is None:
        plan = MeshPlan.production(
            multi_pod=multi_pod, context_parallel=context_parallel,
            data_plane=data_plane, model=model_parallel)
    mesh = make_production_mesh(plan=plan)
    if rules_override:
        from repro.sharding.rules import DEFAULT_RULES

        rules = dict(DEFAULT_RULES)
        rules.update(rules_override)
        sr = ShardingRules(mesh, rules)
    else:
        sr = ShardingRules(mesh)
    mesh_name = plan.describe()
    mesh_shape = dict(mesh.shape)
    n_chips = plan.total
    period = len(cfg.pattern)

    # ---- 1. full lowering: compile + memory proof -------------------------
    mb = (_microbatches(cfg, shape.global_batch, sr)
          if shape.kind == "train" else 1)
    t0 = time.time()
    lowered, ex = _lower(cfg, shape, sr, batch=shape.global_batch,
                         n_microbatches=mb, with_optimizer=True,
                         grad_compression=grad_compression)
    compiled = lowered.compile()
    compile_s = time.time() - t0
    try:
        mem = compiled.memory_analysis()
    except Exception:
        mem = None

    state_bytes = _sharded_bytes(ex["api"].abstract(), ex["pshard"])
    opt_bytes_pc = 0
    if shape.kind == "train":
        opt_bytes_pc = _sharded_bytes(ex["astate"].opt_state, ex["oshard"])
        state_bytes += opt_bytes_pc
    elif shape.kind == "decode":
        state_bytes += _sharded_bytes(
            ex["abstract_batch"]["states"], ex["batch_shardings"]["states"])

    # ---- 2. cost probes: unrolled 1P / 2P, single microbatch --------------
    n_layers = cfg.n_layers
    if probes:
        probe_batch = (shape.global_batch // mb if shape.kind == "train"
                       else shape.global_batch)
        c1 = _analyze(_lower(_probe_cfg(cfg, period), shape, sr,
                             batch=probe_batch, n_microbatches=1,
                             with_optimizer=False,
                             grad_compression=grad_compression)[0].compile(),
                      mesh_shape)
        c2 = _analyze(_lower(_probe_cfg(cfg, 2 * period), shape, sr,
                             batch=probe_batch, n_microbatches=1,
                             with_optimizer=False,
                             grad_compression=grad_compression)[0].compile(),
                      mesh_shape)
        scale = {}
        for k in ("flops", "bytes", "wire"):
            per_layer = max(c2[k] - c1[k], 0.0) / period
            total = c1[k] + per_layer * (n_layers - period)
            scale[k] = total * mb
        coll_scaled = {}
        for k in c1["coll"]:
            if k == "n_ops":
                coll_scaled[k] = c1["coll"][k]
                continue
            per_layer = max(c2["coll"][k] - c1["coll"][k], 0.0) / period
            coll_scaled[k] = (c1["coll"][k]
                              + per_layer * (n_layers - period)) * mb
        # per-mesh-axis wire bytes, probe-scaled the same way (composed-mesh
        # accounting: which axis carries the traffic, DESIGN.md §Parallelism)
        wire_by_axis = {}
        for label in set(c1["by_axis"]) | set(c2["by_axis"]):
            a1 = c1["by_axis"].get(label, 0.0)
            a2 = c2["by_axis"].get(label, 0.0)
            per_layer = max(a2 - a1, 0.0) / period
            wire_by_axis[label] = (a1 + per_layer * (n_layers - period)) * mb
        if shape.kind == "train":
            params_bytes_pc = _sharded_bytes(ex["api"].abstract(),
                                             ex["pshard"])
            n_elems_pc = sum(
                int(np.prod(s.shape)) for s in jax.tree.leaves(
                    ex["api"].abstract())) / n_chips
            oc = _opt_cost(cfg, params_bytes_pc, opt_bytes_pc, n_elems_pc)
            for k in ("flops", "bytes", "wire"):
                scale[k] += oc[k]
    else:
        scale = _analyze(compiled, mesh_shape)
        coll_scaled = scale.pop("coll")
        wire_by_axis = scale.pop("by_axis")

    # ---- 3. roofline -------------------------------------------------------
    n_tokens = shape.global_batch * (
        shape.seq_len if shape.kind != "decode" else 1)
    mf = model_flops(count_params(ex["pspecs"]), n_tokens, shape.kind,
                     _active_params(cfg, ex["api"]))
    rep = roofline_report(
        arch=arch, shape=shape_name, mesh=mesh_name, n_chips=n_chips,
        cost={"flops": scale["flops"], "bytes accessed": scale["bytes"]},
        hlo_text="", model_flops_total=mf, bytes_per_device=state_bytes)
    rep.wire_bytes = scale["wire"]
    rep.collective_s = scale["wire"] / 50e9
    rep.collectives = coll_scaled
    # structural HBM-traffic floor: weights touched fwd(+bwd, per microbatch)
    # + optimizer/state traffic
    params_pc = _sharded_bytes(ex["api"].abstract(), ex["pshard"])
    if shape.kind == "train":
        floor = params_pc * (2 * mb + 3)
    else:
        floor = params_pc + (state_bytes - params_pc) * 2
    rep.memory_floor_s = floor / 819e9

    # predicted per-axis exchange volume for the composed plan (the roofline
    # side of the measured wire_by_axis attribution)
    predicted_exchange = predict_axis_exchange(
        plan, batch=shape.global_batch, seq_len=shape.seq_len,
        n_heads=cfg.n_heads, head_dim=cfg.resolved_head_dim,
        d_model=cfg.d_model, n_layers=cfg.n_layers,
        param_bytes=4 * sum(int(np.prod(s.shape))
                            for s in jax.tree.leaves(ex["api"].abstract())),
        attn_mode=attn_mode, train=shape.kind == "train")

    result = rep.row()
    result.update(
        attn_mode=attn_mode, compile_s=round(compile_s, 1),
        n_params=count_params(ex["pspecs"]),
        n_active_params=_active_params(cfg, ex["api"]),
        n_microbatches=mb,
        memory_analysis=str(mem) if mem is not None else None,
        collectives=coll_scaled,
        wire_bytes_by_axis=wire_by_axis,
        predicted_exchange_bytes=predicted_exchange,
    )
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name} "
              f"(attn={attn_mode}) compiled in {compile_s:.0f}s")
        print(f"  persistent state: {state_bytes/2**30:.3f} GiB/device")
        if mem is not None:
            print(f"  memory_analysis: args={mem.argument_size_in_bytes/2**30:.2f} "
                  f"temp={mem.temp_size_in_bytes/2**30:.2f} "
                  f"out={mem.output_size_in_bytes/2**30:.2f} GiB")
        print(f"  roofline/chip: flops={rep.hlo_flops:.3e} "
              f"bytes={rep.hlo_bytes:.3e} wire={rep.wire_bytes:.3e}")
        print(f"  terms: compute={rep.compute_s*1e3:.2f}ms "
              f"memory={rep.memory_s*1e3:.2f}ms "
              f"collective={rep.collective_s*1e3:.2f}ms "
              f"-> {rep.dominant}-bound; useful-flops "
              f"{rep.useful_flops_frac:.2f}; mfu-bound {rep.mfu:.3f}")
        if wire_by_axis:
            axes_s = " ".join(f"{k}={v:.3e}" for k, v in
                              sorted(wire_by_axis.items()))
            pred_s = " ".join(f"{k}={v:.3e}" for k, v in
                              sorted(predicted_exchange.items()))
            print(f"  wire by axis: {axes_s}")
            print(f"  predicted exchange: {pred_s or '(trivial plan)'}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--attn-mode", default="aaren",
                    choices=["aaren", "softmax"])
    ap.add_argument("--context-parallel", type=int, default=1,
                    help="seq-axis width, carved out of the data plane "
                         "(must divide --data-plane)")
    ap.add_argument("--model-parallel", type=int, default=16,
                    help="model-axis width (tensor/expert parallelism)")
    ap.add_argument("--data-plane", type=int, default=16,
                    help="width of the data-parallel plane the seq axis is "
                         "carved from")
    ap.add_argument("--no-probes", action="store_true",
                    help="skip the unrolled cost probes (compile check only)")
    ap.add_argument("--out", default=None, help="JSON output path")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    archs = list(ALL_ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    if args.list:
        for a in archs:
            for s in shapes:
                print(f"{a} {s}")
        return

    results, failures = [], []
    jsonl = open(args.out + "l", "a") if args.out else None  # incremental
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    res = run_cell(
                        arch, shape, multi_pod=mp, attn_mode=args.attn_mode,
                        probes=not args.no_probes,
                        context_parallel=args.context_parallel,
                        model_parallel=args.model_parallel,
                        data_plane=args.data_plane)
                    results.append(res)
                    if jsonl:
                        jsonl.write(json.dumps(res) + "\n")
                        jsonl.flush()
                except Exception as e:
                    traceback.print_exc()
                    failures.append((arch, shape, mp, repr(e)))
                    if jsonl:
                        jsonl.write(json.dumps(
                            {"FAIL": [arch, shape, mp, repr(e)]}) + "\n")
                        jsonl.flush()
    if jsonl:
        jsonl.close()
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"results": results, "failures": failures}, f, indent=1)
    print(f"\n{len(results)} cells OK, {len(failures)} failed")
    for f_ in failures:
        print("  FAIL:", f_)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
