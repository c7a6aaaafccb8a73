"""Fault-tolerant training loop.

Production posture (DESIGN.md §6):

* **Checkpoint/restart** — async sharded checkpoints every ``save_every``
  steps (+ data-iterator state + step) with atomic LATEST pointer; on start
  the loop auto-resumes from the newest valid checkpoint.
* **Preemption** — SIGTERM/SIGINT set a flag; the loop finishes the current
  step, writes a synchronous checkpoint, and exits cleanly (TPU preemption
  notice / k8s eviction pattern).
* **Straggler mitigation** — per-step wall time feeds an EWMA + variance
  estimate; steps slower than ``mu + straggler_k * sigma`` are logged with
  their step index to a ``stragglers`` list the caller can export.  On a real
  fleet this signal feeds the reshard/evict controller; here it drives the
  loop's own bookkeeping and is unit-tested with an injected slow step.
* **Crash-equivalence** — the loop is a pure function of (checkpoint state,
  data stream); tests kill it mid-run and verify bit-identical continuation.
* **Guarded numerics** — with a guarded train step (train/guard.py) the loop
  accumulates skipped-step / spike counters and the final LR-backoff scale
  into :class:`LoopResult`; ``LoopConfig.guard=True`` additionally asserts
  the step really is guarded (fail fast, not silently unprotected).
* **Observability** (DESIGN.md §Observability) — the loop reports through
  ``repro.obs``: per-step instruments into the ambient metrics registry
  (tokens/s, token-utilization, a step-time histogram, grad-norm, the guard
  counters, stragglers), structured events into the ambient JSONL sink
  (``train_step`` records carry the ``on_log`` metrics dict verbatim;
  ``straggler`` records replace eyeballing the stragglers list), and a
  metrics-snapshot JSON dumped at loop exit (``LoopConfig.metrics_out``).
  ``LoopConfig.events`` opens a file sink when none is ambient.  The
  in-memory ``history``/``stragglers`` lists remain on :class:`LoopResult`
  for programmatic callers; the event log is the durable record.
"""

from __future__ import annotations

import dataclasses
import signal
import time
from typing import Any, Callable

import jax
import numpy as np

from repro.checkpoint import Checkpointer, latest_step, restore_checkpoint
from repro.distributed.context import mesh_plan_session
from repro.obs import events as obs_events
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.export import write_snapshot
from repro.train.state import TrainState


@dataclasses.dataclass
class LoopConfig:
    total_steps: int
    ckpt_dir: str | None = None
    save_every: int = 100
    log_every: int = 10
    straggler_k: float = 3.0
    # Straggler cold-start guard: the EWMA variance needs a few samples
    # before mu + k*sigma means anything — with near-identical early steps
    # sigma ~ 0 and every step would flag.  No step is flagged until this
    # many post-compile samples have fed the estimate, and sigma is floored
    # at 5% of the mean so a flat-variance regime needs a genuinely slow
    # step (not timer jitter) to flag.
    straggler_warmup: int = 10
    seed: int = 0
    # Observability (repro.obs): path of a JSONL event log to open for this
    # run (skipped when a sink is already ambient — the launcher owns it
    # then), and path to dump the metrics-registry snapshot at loop exit.
    events: str | None = None
    metrics_out: str | None = None
    install_signal_handlers: bool = True
    # Composed parallelism (DESIGN.md §Parallelism): the three knobs below
    # are the per-axis sizes of one MeshPlan (data x seq x model).  Any of
    # them > 1 runs every train_step inside a mesh_plan_session — composed
    # mesh built, sharding rules installed, attention dispatched to the
    # cross-device prefix-scan / ring-flash paths when seq > 1
    # (distributed/context.py).
    #
    # context_parallel: size of the `seq` mesh axis (sequence sharding).
    context_parallel: int = 1
    # model_parallel: size of the `model` mesh axis (tensor/expert
    # parallelism: heads/mlp/vocab dims shard here via the rule table).
    model_parallel: int = 1
    # fsdp: size of the `data` mesh axis (batch sharding + ZeRO-style
    # weight sharding and the gradient psum plane).  0 = auto: soak up
    # whatever devices context_parallel x model_parallel leave over (the
    # pre-plan behaviour); 1 = explicitly off.
    fsdp: int = 0
    # Sequence packing (DESIGN.md §Packing): expect packed batches — each
    # row several documents separated by `segment_ids` (0 = padding).  The
    # loop then validates the batch shape once and reports per-step
    # `token_util` (real tokens / row slots) next to the loss, so the
    # packing win the subsystem exists for is visible in the logs.  The
    # model side needs no switch: lm_loss keys off the batch arrays.
    pack_sequences: bool = False
    # Guarded numerics (DESIGN.md §Fault-tolerance): expect a *guarded*
    # train step (make_train_step(guard=GuardConfig())).  The loop then
    # verifies the guard metrics are actually present (a silently unguarded
    # step is the failure mode this knob exists to catch) and accumulates
    # skip/spike counters into LoopResult.  Guard counters are collected
    # regardless whenever the metrics carry them.
    guard: bool = False


@dataclasses.dataclass
class LoopResult:
    state: TrainState
    history: list        # (step, metrics dict) tuples
    stragglers: list     # (step, seconds, threshold) tuples
    preempted: bool = False
    resumed_from: int | None = None
    # guarded-numerics counters (0 / None when the step is unguarded)
    skipped_steps: int = 0       # non-finite steps whose update was skipped
    spike_steps: int = 0         # grad-norm spike anomalies flagged
    final_lr_scale: float = 1.0  # backoff LR multiplier at exit
    preempt_signal: int | None = None  # signal that triggered preemption


def loop_plan(cfg: LoopConfig):
    """The MeshPlan the loop runs under, or None on a single device.

    One MeshPlan from the three LoopConfig knobs.  None (the common
    single-device config: cp = mp = 1, fsdp auto) skips the session
    entirely — no mesh is built.  Launchers build the train state on
    ``loop_plan(cfg).build_mesh()`` so it starts where the loop needs it.
    """
    if cfg.context_parallel > 1 or cfg.model_parallel > 1 or cfg.fsdp > 1:
        from repro.sharding import MeshPlan

        return MeshPlan.host(
            data=cfg.fsdp if cfg.fsdp > 0 else None,
            seq=cfg.context_parallel, model=cfg.model_parallel)
    return None


def run_train_loop(
    train_step: Callable,            # (state, batch, key) -> (state, metrics)
    state: TrainState,
    data_iter,                       # yields batches; .state()/.restore()
    cfg: LoopConfig,
    *,
    on_log: Callable[[int, dict], None] | None = None,
    _test_hooks: dict | None = None,
) -> LoopResult:
    ckpt = Checkpointer(cfg.ckpt_dir) if cfg.ckpt_dir else None
    resumed_from = None

    # ---- auto-resume ------------------------------------------------------
    if ckpt is not None and latest_step(cfg.ckpt_dir) is not None:
        state, step_at_save, extra = restore_checkpoint(cfg.ckpt_dir, state)
        if hasattr(data_iter, "restore") and "data" in extra:
            data_iter.restore(extra["data"])
        resumed_from = step_at_save

    # ---- preemption flag --------------------------------------------------
    # First SIGTERM/SIGINT: finish the current step, write a synchronous
    # final checkpoint, exit cleanly (the k8s/TPU grace-period pattern).
    # A second signal means the grace period is being cut short — stop
    # immediately (the finally block still flushes the async writer; the
    # previous checkpoint stays intact by save atomicity).
    preempt: dict = {"flag": False, "signum": None}

    def _handler(signum, frame):
        if preempt["flag"]:
            raise KeyboardInterrupt(f"second signal {signum} during "
                                    "preemption drain")
        preempt["flag"] = True
        preempt["signum"] = signum

    prev_handlers = {}
    if cfg.install_signal_handlers:
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                prev_handlers[sig] = signal.signal(sig, _handler)
            except ValueError:   # non-main thread (tests)
                pass

    history: list = []
    stragglers: list = []
    ewma_t, ewma_var = None, 0.0
    n_obs = 0
    hooks = _test_hooks or {}
    skipped_steps, spike_steps, lr_scale = 0, 0, 1.0

    own_log = None
    own_reg = None

    plan = loop_plan(cfg)

    try:
        # Composed-mesh session (no-op scope when the plan is trivial):
        # train_step traces inside it, so the mixers see the ambient mesh.
        with mesh_plan_session(plan):
            # Event sink: open a file-backed log when asked and none is
            # ambient (a launcher-installed sink wins — one log per run, not
            # one per loop call).  Opened inside the mesh session so the
            # run_meta header records the mesh shape.
            if cfg.events is not None and obs_events.current() is None:
                own_log = obs_events.install(obs_events.EventLog(cfg.events))
            # Same ownership rule for the metrics registry: a snapshot was
            # asked for but nothing ambient will collect.
            if cfg.metrics_out is not None and obs_metrics.current() is None:
                own_reg = obs_metrics.install(obs_metrics.MetricsRegistry())
            while int(state.step) < cfg.total_steps and not preempt["flag"]:
                step = int(state.step)
                batch = next(data_iter)
                token_util = None
                if cfg.pack_sequences:
                    if "segment_ids" not in batch:
                        raise ValueError(
                            "pack_sequences=True but the batch has no "
                            "segment_ids; use a packing iterator "
                            "(repro.data.packing.PackedLMIterator)")
                    seg = np.asarray(batch["segment_ids"])
                    token_util = float((seg != 0).mean())
                key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), step)
                t0 = time.perf_counter()
                with obs_trace.span("train.step"):
                    state, metrics = train_step(state, batch, key)
                    jax.block_until_ready(state.params)
                dt = time.perf_counter() - t0
                if "sleep" in hooks and step in hooks["sleep"]:
                    dt += hooks["sleep"][step]  # injected straggler (tests)
                if "preempt_at" in hooks and step >= hooks["preempt_at"]:
                    preempt["flag"] = True      # injected preemption (tests)

                # per-step instruments (no-ops without an ambient registry)
                n_tokens = 0
                if isinstance(batch, dict) and "tokens" in batch:
                    n_tokens = int(np.asarray(batch["tokens"]).size)
                obs_metrics.observe("train_step_time_s", dt)
                if n_tokens:
                    obs_metrics.inc("train_tokens_total", n_tokens)
                    obs_metrics.set_gauge("train_tokens_per_s",
                                          n_tokens / max(dt, 1e-9))
                if token_util is not None:
                    obs_metrics.set_gauge("train_token_util", token_util)
                if "grad_norm" in metrics:
                    obs_metrics.set_gauge("train_grad_norm",
                                          float(metrics["grad_norm"]))

                # guarded-numerics counters (train/guard.py metrics)
                if "guard_skipped" in metrics:
                    d_skip = int(float(metrics["guard_skipped"]))
                    d_spike = int(float(metrics["guard_spike"]))
                    skipped_steps += d_skip
                    spike_steps += d_spike
                    lr_scale = float(metrics["guard_lr_scale"])
                    if d_skip:
                        obs_metrics.inc("train_guard_skipped_total", d_skip)
                    if d_spike:
                        obs_metrics.inc("train_guard_spike_total", d_spike)
                    obs_metrics.set_gauge("train_guard_lr_scale", lr_scale)
                elif cfg.guard:
                    raise ValueError(
                        "LoopConfig.guard=True but the train step emits no "
                        "guard metrics — build it with "
                        "make_train_step(..., guard=GuardConfig()) and "
                        "init_train_state(..., guard=cfg)")

                # straggler EWMA (skip the compile step)
                if step > 0:
                    if ewma_t is None:
                        ewma_t = dt
                    else:
                        n_obs += 1
                        sigma = max(float(np.sqrt(ewma_var)), 0.05 * ewma_t)
                        thresh = ewma_t + cfg.straggler_k * sigma
                        if dt > thresh and n_obs >= cfg.straggler_warmup:
                            stragglers.append((step, dt, float(thresh)))
                            obs_metrics.inc("train_straggler_total")
                            obs_events.emit("straggler", step=step, dt_s=dt,
                                            threshold_s=float(thresh))
                        delta = dt - ewma_t
                        ewma_t += 0.1 * delta
                        ewma_var = 0.9 * (ewma_var + 0.1 * delta * delta)

                if step % cfg.log_every == 0:
                    m = {k: float(v) for k, v in metrics.items()}
                    m["step_time_s"] = dt
                    if token_util is not None:
                        m["token_util"] = token_util
                    history.append((step, m))
                    # the event record carries the on_log dict verbatim —
                    # the durable form of the same log line
                    obs_events.emit("train_step", step=step, **m)
                    if on_log:
                        on_log(step, m)

                new_step = int(state.step)
                if ckpt is not None and new_step % cfg.save_every == 0:
                    extra = {"data": data_iter.state()} if hasattr(
                        data_iter, "state") else {}
                    ckpt.save_async(new_step, state, extra=extra)
                if "crash_at" in hooks and new_step >= hooks["crash_at"]:
                    raise KeyboardInterrupt("injected crash")

        # ---- final / preemption checkpoint --------------------------------
        if ckpt is not None:
            extra = {"data": data_iter.state()} if hasattr(
                data_iter, "state") else {}
            ckpt.save_sync(int(state.step), state, extra=extra)
    finally:
        if ckpt is not None:
            ckpt.wait()
        for sig, h in prev_handlers.items():
            signal.signal(sig, h)
        obs_events.emit("run_end", step=int(state.step),
                        preempted=bool(preempt["flag"]),
                        skipped_steps=skipped_steps, spike_steps=spike_steps,
                        lr_scale=lr_scale, n_stragglers=len(stragglers))
        if cfg.metrics_out is not None:
            write_snapshot(cfg.metrics_out)
        if own_reg is not None:
            obs_metrics.uninstall()
        if own_log is not None:
            obs_events.uninstall()
            own_log.close()

    return LoopResult(state=state, history=history, stragglers=stragglers,
                      preempted=preempt["flag"], resumed_from=resumed_from,
                      skipped_steps=skipped_steps, spike_steps=spike_steps,
                      final_lr_scale=lr_scale,
                      preempt_signal=preempt["signum"])
