"""Training launcher: single-host (real devices) or mesh-sharded runs.

On a real fleet this is the per-host entry point (jax.distributed handles
cross-host init); on this CPU container it runs the identical code path over
host devices — the fault-tolerant loop, checkpointing, and sharding logic are
the same objects the dry-run compiles for the production mesh.

Example::

    python -m repro.launch.train --arch phi3-mini-3.8b --smoke \
        --steps 100 --batch 8 --seq-len 64 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config, smoke_config
from repro.data.synthetic import SyntheticLMIterator
from repro.launch.compile_cache import enable_compile_cache
from repro.models.factory import build
from repro.train.guard import GuardConfig
from repro.train.loop import LoopConfig, loop_plan, run_train_loop
from repro.train.optim import make_optimizer, warmup_cosine
from repro.train.state import build_train_state, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--attn-mode", default="aaren",
                    choices=["aaren", "softmax"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "bf16", "int8"])
    ap.add_argument("--context-parallel", type=int, default=1,
                    help="size of the seq mesh axis (sequence sharding; "
                         "1 = off)")
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="size of the model mesh axis (tensor parallelism; "
                         "1 = off)")
    ap.add_argument("--fsdp", type=int, default=0,
                    help="size of the data mesh axis (batch + ZeRO weight "
                         "sharding); 0 = auto (remaining devices), 1 = off")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--guard", action="store_true",
                    help="guarded numerics: skip non-finite steps, back off "
                         "LR, flag grad-norm spikes (train/guard.py)")
    ap.add_argument("--guard-backoff", type=float, default=0.5,
                    help="LR multiplier applied per non-finite step")
    ap.add_argument("--guard-recover-every", type=int, default=50,
                    help="finite steps before one backoff level is restored")
    ap.add_argument("--guard-spike-window", type=int, default=32,
                    help="rolling grad-norm window for spike detection")
    ap.add_argument("--events", default=None,
                    help="path of the JSONL event log to write "
                         "(repro.obs.events; off when omitted)")
    ap.add_argument("--metrics-out", default=None,
                    help="path of the metrics-snapshot JSON dumped at loop "
                         "exit (installs a metrics registry for the run)")
    args = ap.parse_args()
    print(f"compile cache: {enable_compile_cache()}")

    cfg = (smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    cfg = cfg.replace(attn_mode=args.attn_mode)
    api = build(cfg)
    print(f"arch={cfg.name} attn_mode={cfg.attn_mode} "
          f"pattern={cfg.effective_pattern()[:6]}")

    guard = None
    if args.guard:
        guard = GuardConfig(backoff=args.guard_backoff,
                            recover_every=args.guard_recover_every,
                            spike_window=args.guard_spike_window)
    opt = make_optimizer(cfg.optimizer,
                         warmup_cosine(args.lr, args.steps // 10, args.steps))
    # donate the state: in-place param/opt updates (no double-buffering)
    step_fn = jax.jit(make_train_step(
        api.loss, opt, n_microbatches=args.microbatches,
        grad_compression=args.grad_compression, guard=guard),
        donate_argnums=(0,))

    data = SyntheticLMIterator(
        vocab=cfg.vocab, seq_len=args.seq_len, batch=args.batch,
        seed=args.seed)
    loop_cfg = LoopConfig(
        total_steps=args.steps, ckpt_dir=args.ckpt_dir,
        save_every=args.save_every, log_every=max(args.steps // 20, 1),
        seed=args.seed, guard=args.guard,
        context_parallel=args.context_parallel,
        model_parallel=args.model_parallel, fsdp=args.fsdp,
        events=args.events, metrics_out=args.metrics_out)
    # The state is created on the loop's mesh, each leaf where its sharding
    # puts it (one device when the plan is trivial).
    plan = loop_plan(loop_cfg)
    state = build_train_state(
        api, opt, jax.random.PRNGKey(args.seed), guard=guard,
        mesh=plan.build_mesh() if plan is not None else None)
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(state.params))
    print(f"params: {n/1e6:.2f}M")

    def on_log(step, m):
        guard_s = (f" lr_scale={m['guard_lr_scale']:.3f}"
                   if "guard_lr_scale" in m else "")
        print(f"step {step:6d} loss={m['loss']:.4f} "
              f"gnorm={m.get('grad_norm', 0):.3f}"
              f"{guard_s} {m['step_time_s']*1e3:.0f}ms")

    result = run_train_loop(step_fn, state, data, loop_cfg, on_log=on_log)
    print(f"done at step {int(result.state.step)}; "
          f"stragglers observed: {len(result.stragglers)}")
    if args.events:
        print(f"event log: {args.events}")
    if args.metrics_out:
        print(f"metrics snapshot: {args.metrics_out}")
    if args.guard:
        print(f"guard: skipped {result.skipped_steps} non-finite steps, "
              f"{result.spike_steps} grad-norm spikes, final lr_scale "
              f"{result.final_lr_scale:.3f}")


if __name__ == "__main__":
    main()
