"""TrainState + the jit-able train step builder.

``make_train_step`` composes: microbatch grad accumulation (scan) →
gradient compression → global-norm clipping → optimizer update.  The result
is one pure function ``(state, batch, key) -> (state, metrics)`` that the
fault-tolerant loop jits (single host) or pjits (production mesh — the
dry-run lowers exactly this function for the ``train_4k`` cells).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.distributed.grad import microbatch_grads
from repro.train.guard import (
    GuardConfig,
    abstract_guard_state,
    all_finite,
    guard_update,
    init_guard_state,
)
from repro.train.optim import Optimizer, clip_by_global_norm


class TrainState(NamedTuple):
    step: jax.Array          # () int32
    params: Any
    opt_state: Any
    guard: Any = None        # GuardState when built with guard=, else None


def init_train_state(params, optimizer: Optimizer,
                     guard: GuardConfig | None = None) -> TrainState:
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=optimizer.init(params),
        guard=init_guard_state(guard) if guard is not None else None,
    )


def build_train_state(api, optimizer: Optimizer, key: jax.Array, *,
                      guard: GuardConfig | None = None,
                      mesh=None) -> TrainState:
    """Initialise params and optimizer state in one jitted program.

    With a ``mesh``, every leaf is created where
    :func:`train_state_shardings` puts it: no device ever holds the whole
    state on the way.  ``optimizer`` must be the one
    ``make_optimizer(api.cfg.optimizer, ...)`` builds.
    """

    def init(k):
        return init_train_state(api.init(k), optimizer, guard=guard)

    if mesh is None:
        return jax.jit(init)(key)
    from repro.sharding.rules import ShardingRules

    shardings = train_state_shardings(api, jax.eval_shape(init, key),
                                      ShardingRules(mesh))
    return jax.jit(init, out_shardings=shardings)(key)


def train_state_shardings(api, state_shape: TrainState, sr) -> TrainState:
    """``NamedSharding`` tree of a train state under the rules ``sr``.

    Params follow the logical-axis rules; the optimizer state follows
    ``opt_param_specs(api.cfg.optimizer, ...)``; the step counter and the
    guard state are replicated.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.sharding.rules import param_shardings
    from repro.train.optim import opt_param_specs

    replicated = NamedSharding(sr.mesh, PartitionSpec())
    return TrainState(
        step=replicated,
        params=param_shardings(api.specs(), sr),
        opt_state=param_shardings(
            opt_param_specs(api.cfg.optimizer, api.specs()), sr),
        guard=jax.tree.map(lambda _: replicated, state_shape.guard))


def abstract_train_state(abstract_params, optimizer: Optimizer,
                         guard: GuardConfig | None = None) -> TrainState:
    """ShapeDtypeStruct twin of :func:`init_train_state` (dry-run)."""
    opt = jax.eval_shape(optimizer.init, abstract_params)
    return TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        params=abstract_params,
        opt_state=opt,
        guard=abstract_guard_state(guard) if guard is not None else None,
    )


def make_train_step(loss_fn, optimizer: Optimizer, *,
                    n_microbatches: int = 1,
                    grad_compression: str = "none",
                    max_grad_norm: float = 1.0,
                    guard: GuardConfig | None = None):
    """loss_fn: (params, batch) -> (loss, metrics dict).

    ``guard``: guarded numerics (DESIGN.md §Fault-tolerance).  The returned
    step then expects ``state.guard`` to hold a :class:`GuardState` (use
    ``init_train_state(..., guard=cfg)``), skips the update on non-finite
    loss/grads via ``lax.cond`` (params + opt state untouched; the step
    counter still advances), applies the backoff LR scale through the
    optimizer's ``lr_scale`` hook, and emits ``guard_skipped`` /
    ``guard_spike`` / ``guard_lr_scale`` metrics every step.
    """

    def train_step(state: TrainState, batch, key: jax.Array):
        grads, loss, metrics = microbatch_grads(
            loss_fn, state.params, batch, n_microbatches,
            compression=grad_compression, key=key)
        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm

        if guard is None:
            new_params, new_opt = optimizer.update(
                grads, state.opt_state, state.params, state.step)
            return TrainState(state.step + 1, new_params, new_opt,
                              state.guard), metrics

        if state.guard is None:
            raise ValueError(
                "make_train_step(guard=...) needs a guarded TrainState; "
                "build it with init_train_state(params, opt, guard=cfg)")
        finite = all_finite(loss, grads)
        g, apply, spike = guard_update(guard, state.guard, finite, gnorm)

        def do_update(operand):
            gr, opt_state, params = operand
            return optimizer.update(gr, opt_state, params, state.step,
                                    lr_scale=state.guard.lr_scale)

        def skip_update(operand):
            _, opt_state, params = operand
            return params, opt_state

        new_params, new_opt = jax.lax.cond(
            apply, do_update, skip_update,
            (grads, state.opt_state, state.params))
        metrics["guard_skipped"] = 1.0 - apply.astype(jnp.float32)
        metrics["guard_spike"] = spike.astype(jnp.float32)
        metrics["guard_lr_scale"] = g.lr_scale
        return TrainState(state.step + 1, new_params, new_opt, g), metrics

    return train_step
