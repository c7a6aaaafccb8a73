"""Inference engines.

Two serving modes, matching the paper's efficiency analysis (§4.5):

* :func:`generate` — wave-based batched generation for *any* arch: prefill
  the whole batch, then jit'd one-token decode steps.  KV-cache archs carry
  O(B·N) cache; Aaren archs carry O(B) state.
* :class:`StreamingEngine` — **chunked-prefill continuous batching** for
  position-free-state models.  The engine is a scheduler/step-function
  split (DESIGN.md §Serving): pure-Python bookkeeping decides what each of
  the ``n_slots`` persistent decode slots feeds next, and exactly two
  fixed-shape jitted functions touch the device —

  - ``step(params, batch (S, C + 3), states, key)`` advances a *mixed*
    batch: mid-prefill slots consume up to C prompt tokens, decoding slots
    carry one valid token, padding is ⊕-identity in the carry scan.  One
    trace per (S, C), ever — no per-prompt-length recompilation, and a
    refill longer than one chunk never stalls the decode of other slots.
    The step also guards and samples each row's last-valid logits, so the
    host reads back S token ids and S finite flags, never the logits.
  - ``reset(states, mask (S,))`` re-initialises freed slots' carries in
    place, addressed by the explicit batch-axis metadata of
    :func:`repro.models.lm.lm_state_batch_axes` (shape-matching heuristics
    break when a state dim equals ``n_slots``).

  Because the Aaren decode state is a position-free constant-size tuple
  ``(m, u, w)`` per layer/head (no KV cache, no RoPE phase), admitting a
  queued request is a masked ``where`` against the zero state — no cache
  reshaping, no position bookkeeping.  This is the systems-level payoff of
  the paper's O(1)-state formulation, and the engine exercises it literally.

``decode_state_bytes`` measures the per-request inference state — the
quantity plotted in the paper's Figure 5 (left).

Sampling keys: both engines draw the token-t sample of request ``rid`` from
``fold_in(fold_in(base_key, rid), t)`` (:func:`request_key`), so streaming
and wave generation produce identical samples for the same submission order
regardless of slot scheduling, refill timing, or chunk size.
"""

from __future__ import annotations

import dataclasses
import time
import weakref
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.factory import ModelAPI
from repro.obs import events as obs_events
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serving.sampler import greedy_sampler


def _jit(fn):
    """Single indirection over ``jax.jit`` so tests can count traces."""
    return jax.jit(fn)


class EngineOverloaded(RuntimeError):
    """Admission queue full — the request was shed, not queued."""


#: error strings recorded in ``StreamingEngine.errors``
ERR_DEADLINE = "deadline exceeded"
ERR_POISONED = "non-finite logits (slot quarantined)"


def decode_state_bytes(states: Any) -> int:
    """Total bytes of a decode-state pytree (Fig. 5-left measurement)."""
    return int(sum(
        np.prod(x.shape) * x.dtype.itemsize for x in jax.tree.leaves(states)))


def request_key(base_key: jax.Array, request_id: int, step: int) -> jax.Array:
    """Sampling key for generated token ``step`` of request ``request_id``.

    Keyed on (request, position) only — never on engine scheduling — so any
    two engines given the same base key and submission order sample
    identically, and every (request, step) pair gets a distinct key.
    """
    return jax.random.fold_in(jax.random.fold_in(base_key, request_id), step)


# One vmapped + jitted sampling function per sampler: per-row key
# derivation and the sample itself, on device.  The streaming engine traces
# it into its step, so a tick's tokens come back with the step's output;
# generate() calls it as a program of its own after each decode step.
# Keyed weakly per sampler function; jax.jit is used directly (not `_jit`)
# because the cache outlives any single engine, so per-engine trace-count
# tests must not see it.
_BATCHED_SAMPLERS: "weakref.WeakKeyDictionary[Callable, Callable]" = (
    weakref.WeakKeyDictionary())


def _batched_sampler(sampler: Callable) -> Callable:
    fn = _BATCHED_SAMPLERS.get(sampler)
    if fn is None:
        def sample_batch(logits, base_key, rids, steps):
            """(B, 1, V) logits + (B,) rids/steps -> (B, 1) int32."""
            def one(row, rid, st):
                k = jax.random.fold_in(
                    jax.random.fold_in(base_key, rid), st)
                return sampler(row[None], k)[0, 0]
            return jax.vmap(one)(logits, rids, steps)[:, None]

        fn = jax.jit(sample_batch)
        _BATCHED_SAMPLERS[sampler] = fn
    return fn


def _sample_rows(sampler: Callable, logits: jax.Array, base_key: jax.Array,
                 rids, steps) -> jax.Array:
    """Sample each row of (B, 1, V) logits with its own request/step key.

    ``jit_safe`` samplers (the built-ins) take one fused vmapped call;
    custom samplers fall back to eager per-row calls so instrumented
    samplers see concrete keys.  Wave generation samples here; the
    streaming engine runs the same vmapped sampler inside its step (or
    this eager path for custom samplers), so the two stay
    sample-for-sample identical.
    """
    if getattr(sampler, "jit_safe", False):
        return _batched_sampler(sampler)(
            logits, base_key, jnp.asarray(rids, jnp.int32),
            jnp.asarray(steps, jnp.int32))
    toks = [sampler(logits[i:i + 1], request_key(base_key, rid, st))
            for i, (rid, st) in enumerate(zip(rids, steps))]
    return jnp.concatenate(toks, axis=0)


# ---------------------------------------------------------------------------
# Wave generation
# ---------------------------------------------------------------------------

# Jitted prefill/decode per ModelAPI, keyed weakly so repeated generate()
# calls (and a warmup call before a timed one) reuse one trace instead of
# rebuilding fresh jit wrappers — the old per-call lambdas recompiled on
# every invocation.
_GEN_FNS: "weakref.WeakKeyDictionary[ModelAPI, dict]" = (
    weakref.WeakKeyDictionary())


def _generate_fns(api: ModelAPI, cache_len: int, ragged: bool = False):
    fns = _GEN_FNS.setdefault(api, {})
    # Close over the member functions, NOT over `api`: a value that captured
    # the key would pin it strongly and defeat the weak eviction.
    if "decode" not in fns:
        decode_step = api.decode_step
        fns["decode"] = jax.jit(lambda pr, sb: decode_step(pr, sb))
    pf_key = ("prefill", cache_len, ragged)
    if pf_key not in fns:
        # cache_len is a static model property — close over it, don't trace.
        prefill = api.prefill
        if ragged:
            fns[pf_key] = jax.jit(lambda pr, toks, lens: prefill(
                pr, {"tokens": toks, "cache_len": cache_len,
                     "lengths": lens}))
        else:
            fns[pf_key] = jax.jit(lambda pr, toks: prefill(
                pr, {"tokens": toks, "cache_len": cache_len}))
    return fns[pf_key], fns["decode"]


def generate(
    api: ModelAPI,
    params: Any,
    prompts: jax.Array,                 # (B, P) int32
    max_new_tokens: int,
    *,
    sampler: Callable = greedy_sampler,
    key: jax.Array | None = None,
    cache_len: int | None = None,
    prompt_lengths: jax.Array | None = None,
):
    """Wave-based generation.  Returns (tokens (B, max_new), final states).

    ``prompt_lengths``: optional (B,) true lengths of *right-padded* ragged
    prompts.  The prefill then masks each row's padded tail in-kernel
    (``flash_mha(q_lens=, kv_lens=)`` / the Aaren ⊕-identity mask), row
    ``i``'s first sample reads the logits at its true last token, and
    decode continues from exact per-row states — KV caches carry the
    per-row prompt lengths so the padded gap is masked and RoPE/window use
    true absolute positions (``models/attention.softmax_step``).  Generated
    tokens therefore match running each prompt alone, unlike the legacy
    left-padded approximation where pad tokens were attended as real
    context (tests/test_serving.py pins this parity).
    """
    b, p = prompts.shape
    if b == 0 or p == 0:
        raise ValueError(f"empty prompts: shape {(b, p)} needs B >= 1 "
                         "and P >= 1")
    if max_new_tokens <= 0:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if cache_len is None:
        cache_len = p + max_new_tokens
    key = key if key is not None else jax.random.PRNGKey(0)
    ragged = prompt_lengths is not None
    pattern = api.cfg.effective_pattern()
    if "attn" in pattern and cache_len < p + max_new_tokens:
        # Global-attention KV rings silently overwrite the earliest context
        # once they wrap — a wrong answer, not a feature (sliding-window
        # layers cap their own cache at `window` by design).
        raise ValueError(
            f"cache_len={cache_len} < prompt {p} + max_new "
            f"{max_new_tokens}: the global-attention ('attn') KV cache "
            "must be non-wrapping — a wrapped ring silently drops context")
    if ragged:
        lens_np = np.asarray(prompt_lengths)
        if lens_np.shape != (b,):
            raise ValueError(f"prompt_lengths shape {lens_np.shape} != "
                             f"({b},)")
        if (lens_np < 1).any() or (lens_np > p).any():
            raise ValueError(
                f"prompt_lengths must lie in [1, {p}] (padded width); got "
                f"{lens_np.tolist()}")
        if cache_len < p + max_new_tokens:
            # The ragged decode mask maps slots [0, prompt_lens) to the true
            # prompt prefix; a wrapping ring would overwrite those slots with
            # decode-era keys while the mask still reads them as prompt.
            raise ValueError(
                f"ragged prefill needs a non-wrapping cache: cache_len="
                f"{cache_len} < padded prompt {p} + max_new "
                f"{max_new_tokens}")
        if "attn_local" in pattern and api.cfg.window < p:
            # The per-layer cache is min(window, cache_len): window < P means
            # a trailing-window ring, and ragged rows would need per-row ring
            # indices (ROADMAP carried-over item).  Fail at the API boundary
            # with the config named, not mid-trace inside the layer.
            raise NotImplementedError(
                f"ragged prefill (prompt_lengths=) is not supported for "
                f"'attn_local' layers with window ({api.cfg.window}) < "
                f"padded prompt length ({p}): the trailing-window ring "
                "cache needs per-row ring indices. Use window >= padded "
                "prompt length, or pad each prompt separately.")
    prefill, decode = _generate_fns(api, cache_len, ragged=ragged)

    if ragged:
        lens = jnp.asarray(prompt_lengths, jnp.int32)
        logits, states = prefill(params, prompts, lens)
        # Row i's prompt ends at lens[i] - 1 — gather its logits per row.
        last = jnp.take_along_axis(
            logits, (lens - 1)[:, None, None], axis=1)          # (B, 1, V)
    else:
        logits, states = prefill(params, prompts)
        last = logits[:, -1:]
    rids = list(range(b))
    tok = _sample_rows(sampler, last, key, rids, [0] * b)
    out = [tok]
    for t in range(1, max_new_tokens):
        logits, states = decode(params, {"token": tok, "states": states})
        tok = _sample_rows(sampler, logits, key, rids, [t] * b)
        out.append(tok)
    return jnp.concatenate(out, axis=1), states


# ---------------------------------------------------------------------------
# Chunked-prefill continuous batching
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Slot:
    """Scheduler-side bookkeeping for one decode slot."""

    request_id: int
    pending: np.ndarray | None   # prompt tokens not yet consumed (None once decoding)
    tokens: list                 # generated token ids
    remaining: int               # generated tokens still owed
    n_sampled: int = 0           # per-request step counter (key schedule)
    last_token: int = 0          # input token while decoding
    deadline: float | None = None  # absolute perf_counter() cutoff
    # perf_counter() of the last emitted token (inter-token latency); pure
    # wall-clock bookkeeping, deliberately NOT serialised by snapshot().
    last_emit_at: float | None = None
    # Prefix-cache bookkeeping: the full prompt (for prefix extraction at
    # insert time), prompt tokens folded into the carry so far (cache-hit
    # admits start at the matched length), and the prompt's precomputed
    # grid-hash dict ({boundary_len: hash}, None when no cache is attached
    # or after a restore — insertion is then skipped for this request).
    prompt: np.ndarray | None = None
    consumed: int = 0
    hashes: dict | None = None


@dataclasses.dataclass
class _Queued:
    """An admitted-but-not-yet-slotted request.

    Fresh submissions have ``pending == prompt`` and zeroed progress
    fields.  Migrated requests (:meth:`StreamingEngine.inject_request`)
    arrive mid-life: ``tokens``/``n_sampled`` record emitted progress and
    either ``carry`` holds the exact exported device carry (drain path —
    ``pending`` is then just the tokens not yet folded into it) or the
    carry is gone (crash path) and ``pending`` replays prompt + emitted
    tokens from the ⊕-identity init.
    """

    request_id: int
    pending: np.ndarray          # tokens still to fold into the carry
    remaining: int               # generated tokens still owed
    deadline: float | None = None
    prompt: np.ndarray | None = None   # original prompt (cache + re-export)
    tokens: list = dataclasses.field(default_factory=list)
    n_sampled: int = 0
    carry: Any = None            # host-array carry tree, or None


def _validate_request(prompt, max_new_tokens: int,
                      deadline_s: float | None) -> np.ndarray:
    """Validate submit() arguments; returns the canonical int32 prompt.

    Shared by the engine and the router so both shed/reject *before* any
    id allocation or bookkeeping — nothing is half-admitted.
    """
    prompt = np.asarray(prompt)
    if prompt.ndim > 1:
        raise ValueError(f"prompt must be 1-D, got shape {prompt.shape}")
    if not np.issubdtype(prompt.dtype, np.integer):
        raise ValueError(f"prompt must hold token ids (integers), got "
                         f"dtype {prompt.dtype}")
    prompt = prompt.astype(np.int32).reshape(-1)
    if prompt.size == 0:
        raise ValueError("empty prompt")
    if max_new_tokens <= 0:
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if deadline_s is not None and deadline_s < 0:
        raise ValueError(f"deadline_s must be >= 0, got {deadline_s}")
    return prompt


class StreamingEngine:
    """Chunked-prefill continuous batching over ``n_slots`` decode slots.

    Position-free-state models only (aaren/rglru/ssd mixers — see module
    docstring).  Requests are queued with :meth:`submit`; :meth:`run` (or
    repeated :meth:`step`) advances all slots in lock-step: each tick is ONE
    fixed-shape jitted call in which some slots consume a chunk of prompt,
    others decode a token, and freed slots are refilled from the queue the
    very next tick — decode never waits for a full-prompt prefill.

    ``chunk`` is the prefill chunk size (prompt tokens consumed per slot per
    tick).  All-Aaren patterns accept any chunk (masked positions are
    ⊕-identity in the prefix scan); RG-LRU/SSD carries advance strictly
    token-by-token, so mixed patterns require ``chunk == 1``.

    ``prefix_cache`` (optional :class:`~repro.serving.prefix_cache
    .PrefixCache`) caches prompt-prefix carries across requests: an
    admitted prompt whose longest cached prefix has length L skips L
    tokens of prefill (the carry is injected through the same
    masked-``where`` path as a reset), and prefills that cross a wanted
    chunk boundary copy the slot carry out.  Because carries are
    position-free O(layers·heads) tuples, a cached 1k-token system prompt
    costs kilobytes, not a paged KV block.  The cache binds to this
    engine's chunk grid at construction; attaching it to an engine with a
    different ``chunk`` raises.

    Slot-carry lifecycle invariant (DESIGN.md §Serving): **free slots
    always hold the ⊕-identity init carry.**  Every exit path — completion,
    deadline expiry, quarantine, restore — resets the slot's rows of
    ``self.states`` eagerly in the same tick; ``_admit`` relies on it and
    only writes state for cache hits.

    Degradation under faults (DESIGN.md §Fault-tolerance):

    * ``max_queue`` bounds the admission queue — :meth:`submit` sheds load
      with :class:`EngineOverloaded` instead of letting latency grow without
      bound (``None`` = unbounded, the pre-fault-tolerance behaviour).
    * ``submit(..., deadline_s=)`` attaches a per-request deadline; expired
      requests error out (``self.errors``) whether still queued or mid-slot,
      freeing capacity for live traffic.
    * ``guard_logits`` (default on) checks each tick's last-valid logits for
      NaN/±inf per slot.  A poisoned slot is **quarantined**: its request
      errors, its carry is reset through the same masked-``where`` path that
      admits new requests, and — because slots are independent batch rows —
      its batch-mates' outputs are byte-identical to an uninjected run.
    * :meth:`snapshot` / :meth:`restore` serialise the whole engine (device
      carries + scheduler bookkeeping) for crash recovery; ``save`` /
      ``load`` route them through the checkpoint layer's atomic writes.
    """

    def __init__(self, api: ModelAPI, params: Any, *, n_slots: int = 4,
                 chunk: int | None = None,
                 sampler: Callable = greedy_sampler,
                 key: jax.Array | None = None,
                 max_queue: int | None = None,
                 guard_logits: bool = True,
                 prefix_cache=None):
        pattern = api.cfg.effective_pattern()
        if any(m in ("attn", "attn_local") for m in pattern):
            raise ValueError(
                "StreamingEngine requires position-free decode state "
                "(aaren/rglru/ssd mixers only); use generate() for "
                "KV-cache models.")
        pure_aaren = all(m == "aaren" for m in pattern)
        if chunk is None:
            chunk = 16 if pure_aaren else 1
        if chunk > 1 and not pure_aaren:
            raise ValueError(
                f"chunk={chunk} needs an all-aaren pattern; rglru/ssd "
                "carries advance one token at a time (use chunk=1).")
        self.api = api
        self.params = params
        self.n_slots = n_slots
        self.chunk = chunk
        self.sampler = sampler
        self.key = key if key is not None else jax.random.PRNGKey(0)
        self.max_queue = max_queue
        self.guard_logits = guard_logits

        from repro.models.lm import (
            lm_prefill_chunk,
            lm_state_batch_axes,
            lm_state_init,
        )

        cfg = api.cfg
        # cache_len is irrelevant for position-free states; use 1.
        self._init_states = lm_state_init(cfg, n_slots, 1)
        self.states = self._init_states
        batch_axes = lm_state_batch_axes(cfg)
        self._batch_axes = batch_axes
        self._expert_layers = "moe" in cfg.mlp_pattern

        def step(pr, batch, states, key):
            """One tick: advance the mixed batch, then guard and sample.

            ``batch`` (S, C + 3) int32 packs each row's C tokens, its valid
            length, its request id and its sample index (the key schedule
            of :func:`request_key`), so the tick uploads one array.  Returns
            ``(picked, last, new_states)``: ``picked`` (S, 2) int32 holds
            each row's sampled token and whether its logits are all
            finite; ``last`` (S, 1, V) is the last-valid logits, left on
            the device for samplers that are not ``jit_safe``.  A model
            with expert layers appends one row to ``picked``: the tick's
            (token, expert) pairs on the held experts and the most that
            one held expert took at one layer.
            """
            tokens = batch[:, :chunk]
            lengths = batch[:, chunk]
            rids, steps = batch[:, chunk + 1], batch[:, chunk + 2]
            mask = jnp.arange(chunk)[None, :] < lengths[:, None]
            logits, new_states, *counts = lm_prefill_chunk(
                cfg, pr, tokens, states, length_mask=mask)
            # An all-padding row (lengths == 0) keeps its carry bit-for-bit.
            # The ⊕-identity mask guarantees this *mathematically* but not
            # bitwise: a masked leaf folded into an EMPTY carry contributes
            # exp(NEG_INF - NEG_INF) = 1 to u (the finite sentinel cancels
            # against itself; any real m annihilates it later).  The slot
            # lifecycle invariant — free slots hold the init carry — is a
            # bitwise contract, so pin it here with the same masked-where
            # used by reset.
            live = lengths > 0

            def keep(old, new, ax):
                if ax < 0:
                    return new
                sel = live.reshape(
                    (1,) * ax + (n_slots,) + (1,) * (new.ndim - ax - 1))
                return jnp.where(sel, new, old)

            new_states = jax.tree.map(keep, states, new_states, batch_axes)
            # A slot scheduled with lengths == 0 (all-padding row) has no
            # valid position: `lengths - 1` would gather index −1 — position
            # 0's logits under clip semantics, silently, and the *last*
            # position's under NumPy semantics.  Clamp to 0; the scheduler
            # never samples such a slot, and its carry is untouched (the
            # whole row enters the scan as ⊕-identity leaves).
            last_idx = jnp.maximum(lengths - 1, 0)
            last = jnp.take_along_axis(
                logits, last_idx[:, None, None], axis=1)  # (S, 1, V)
            # Guard and sample exactly the returned bits: the barrier keeps
            # XLA from fusing the sampler into the unembedding, so a token
            # equals what _sample_rows draws from `last` (wave parity).
            last = jax.lax.optimization_barrier(last)
            finite = jnp.isfinite(last).all(axis=(1, 2))
            # Read at trace time (warmup), not captured at construction, so
            # a sampler swapped in before warmup() is the one traced.
            sample = self._batched_sample
            if sample is None:   # not jit_safe: the host samples `last`
                toks = jnp.zeros((n_slots,), jnp.int32)
            else:
                toks = sample(last, key, rids, steps)[:, 0]
            picked = jnp.stack([toks.astype(jnp.int32),
                                finite.astype(jnp.int32)], axis=1)
            if counts:          # a model with expert layers
                picked = jnp.concatenate([picked, counts[0][None]], axis=0)
            return picked, last, new_states

        def reset(states, mask):
            """Zero the carries of slots where mask (S,) is True."""

            def leaf(batched, fresh, ax):
                if ax < 0:
                    return batched
                sel = mask.reshape(
                    (1,) * ax + (n_slots,) + (1,) * (batched.ndim - ax - 1))
                return jnp.where(sel, fresh, batched)

            return jax.tree.map(leaf, states, self._init_states, batch_axes)

        self._step_fn = _jit(step)
        self._reset_fn = _jit(reset)
        # jit-safe samplers sample inside the step; custom samplers keep
        # the eager per-row path on the step's logits (concrete keys for
        # instrumented samplers — tests rely on this).
        self._batched_sample = (_batched_sampler(sampler)
                                if getattr(sampler, "jit_safe", False)
                                else None)

        # Prefix cache (serving/prefix_cache.py): the gather/inject slot
        # entry points are created lazily by _ensure_slot_io() on first
        # cache hit / insert / migration — a cache-less, never-migrated
        # engine keeps exactly two jitted functions (pinned by the
        # trace-count test).  Both take the slot index / mask as *traced*
        # arguments, so each is one trace for any slot.
        self.prefix_cache = prefix_cache
        if prefix_cache is not None:
            prefix_cache.bind(
                chunk, jax.tree.map(np.asarray, lm_state_init(cfg, 1, 1)))
        self._gather_fn = None
        self._inject_fn = None

        self.active: list[_Slot | None] = [None] * n_slots
        self.queue: list[_Queued] = []
        self.finished: dict[int, list[int]] = {}
        self.errors: dict[int, str] = {}       # rid -> error string
        self.n_shed = 0                        # submits rejected (queue full)
        self.n_quarantined = 0                 # slots reset on poisoned logits
        # Latency bookkeeping for IN-FLIGHT requests only: entries are
        # evicted the moment a request leaves the system (completed,
        # deadline-expired, or quarantined), after their TTFT/latency has
        # been folded into the obs layer (serve_ttft_s histogram +
        # first_token / request_* events).  A long-lived engine therefore
        # holds O(queued + active) entries, not O(all requests ever).
        self.submitted_at: dict[int, float] = {}
        self.first_token_at: dict[int, float] = {}
        self._next_id = 0

    # ------------------------------------------------------------------ API
    def submit(self, prompt, max_new_tokens: int, *,
               deadline_s: float | None = None,
               request_id: int | None = None) -> int:
        """Queue a request.  prompt: (P,) int32, P >= 1.  Returns its id.

        ``deadline_s``: optional wall-clock budget from submission; a
        request that hasn't *finished* within it errors out (recorded in
        ``self.errors``, slot/queue capacity reclaimed).  Raises
        :class:`EngineOverloaded` when the admission queue is at
        ``max_queue`` — shed at the door, not queued into unbounded latency.

        ``request_id``: caller-allocated id (the replicated router assigns
        tier-wide-unique ids so two replicas seeded alike never reuse a
        ``(rid, step)`` sampling key).  Must not collide with a request
        this engine already knows.
        """
        prompt = _validate_request(prompt, max_new_tokens, deadline_s)
        if request_id is not None and (
                request_id in self.submitted_at
                or request_id in self.finished
                or request_id in self.errors):
            raise ValueError(f"request_id {request_id} already in use")
        if (self.max_queue is not None
                and len(self.queue) >= self.max_queue):
            self.n_shed += 1
            obs_metrics.inc("serve_shed_total")
            obs_events.emit("request_shed", queue_depth=len(self.queue),
                            max_queue=self.max_queue)
            raise EngineOverloaded(
                f"admission queue full ({len(self.queue)}/{self.max_queue} "
                "queued); retry later or raise max_queue")
        rid = self._next_id if request_id is None else int(request_id)
        self._next_id = max(self._next_id, rid + 1)
        now = time.perf_counter()
        deadline = now + deadline_s if deadline_s is not None else None
        self.queue.append(_Queued(
            request_id=rid, pending=prompt, remaining=int(max_new_tokens),
            deadline=deadline, prompt=prompt))
        self.submitted_at[rid] = now
        obs_metrics.inc("serve_requests_total")
        obs_metrics.set_gauge("serve_queue_depth", len(self.queue))
        obs_events.emit("request_submitted", rid=rid,
                        prompt_len=int(prompt.size),
                        max_new=int(max_new_tokens))
        return rid

    def warmup(self) -> float:
        """Trace + compile both fixed-shape entry points before serving.

        Pure warm-up: results are discarded, ``self.states`` is untouched.
        Returns the wall seconds spent (≈ compile time).
        """
        t0 = time.perf_counter()
        batch = np.zeros((self.n_slots, self.chunk + 3), np.int32)
        batch[:, self.chunk] = 1
        picked, last, states = self._step_fn(
            self.params, jnp.asarray(batch), self.states, self.key)
        states = self._reset_fn(states, jnp.zeros((self.n_slots,), bool))
        if self.prefix_cache is not None:
            # The cache's gather/inject entry points compile here too — the
            # first cache hit must not pay jit compile inside a TTFT.
            gather, inject = self._ensure_slot_io()
            carry = gather(states, jnp.int32(0))
            states = inject(states, carry,
                            jnp.zeros((self.n_slots,), bool))
        jax.block_until_ready((picked, last, states))
        return time.perf_counter() - t0

    def step(self) -> int:
        """One engine tick: admit, advance the mixed batch, sample.

        Returns the number of tokens emitted this tick (0 when idle).
        Under tracing, the whole tick is the span ``engine.tick``, and each
        phase a span inside it: ``engine.schedule`` (``engine.expire``,
        ``engine.admit``), ``engine.step`` (the dispatch), ``engine.wait``,
        ``engine.guard``, ``engine.sample`` and ``engine.reset``.
        """
        with obs_trace.span("engine.tick"):
            return self._tick()

    def _tick(self) -> int:
        with obs_trace.span("engine.schedule"):
            with obs_trace.span("engine.expire"):
                self._expire_deadlines()
            with obs_trace.span("engine.admit"):
                self._admit()
            n_active = sum(s is not None for s in self.active)
            obs_metrics.set_gauge("serve_queue_depth", len(self.queue))
            obs_metrics.set_gauge("serve_slot_occupancy",
                                  n_active / self.n_slots)
            if n_active == 0:
                return 0

            # Free slots stay all-padding (lengths == 0): their rows enter
            # the scan as ⊕-identity leaves and their carries are untouched,
            # preserving the lifecycle invariant between ticks.  (They used
            # to be fed token 0 with lengths == 1, quietly accumulating
            # garbage that the next admit's reset had to paper over.)
            # One host array per tick (the step's only upload): tokens,
            # then each row's valid length, request id and sample index.
            c = self.chunk
            batch = np.zeros((self.n_slots, c + 3), np.int32)
            tokens, lengths = batch[:, :c], batch[:, c]
            prefill_toks, decode_toks = 0, 0
            for i, slot in enumerate(self.active):
                if slot is None:
                    continue
                batch[i, c + 1] = slot.request_id
                batch[i, c + 2] = slot.n_sampled
                if slot.pending is not None:  # mid-prefill: feed next chunk
                    take = min(slot.pending.size, self.chunk)
                    tokens[i, :take] = slot.pending[:take]
                    lengths[i] = take
                    prefill_toks += take
                else:                         # decoding: feed last sample
                    tokens[i, 0] = slot.last_token
                    lengths[i] = 1
                    decode_toks += 1
            if prefill_toks:
                obs_metrics.inc("serve_prefill_tokens_total", prefill_toks)
            if decode_toks:
                obs_metrics.inc("serve_decode_tokens_total", decode_toks)

        with obs_trace.span("engine.step"):
            picked, last, self.states = self._step_fn(
                self.params, jnp.asarray(batch), self.states, self.key)
        # The tick's one host read: S token ids and S finite flags (and
        # the expert counts, for a model with expert layers).
        with obs_trace.span("engine.wait"):
            picked = np.asarray(picked)
        if self._expert_layers:
            pairs, load = (int(v) for v in picked[-1])
            picked = picked[:-1]
            obs_metrics.inc("serve_moe_pairs_total", pairs)
            obs_metrics.set_gauge("serve_moe_expert_load_max", load)

        # Slot quarantine: a poisoned carry (hardware fault, numerics bug)
        # shows up as NaN/±inf in that slot's logits.  The step checks each
        # row of the (S, 1, V) last-valid logits on the device; reset ONLY
        # the poisoned rows.  Healthy batch-mates never see a different
        # code path, so their outputs stay byte-identical.
        if self.guard_logits:
            with obs_trace.span("engine.guard"):
                finite_rows = picked[:, 1]
                poisoned = np.zeros((self.n_slots,), bool)
                for i, slot in enumerate(self.active):
                    if slot is not None and not finite_rows[i]:
                        poisoned[i] = True
                        self.errors[slot.request_id] = ERR_POISONED
                        self.n_quarantined += 1
                        self.active[i] = None
                        obs_metrics.inc("serve_quarantine_total")
                        self._request_done(slot.request_id, "quarantine",
                                           slot=i)
                if poisoned.any():
                    self._reset(poisoned)

        emitted = 0
        completed = np.zeros((self.n_slots,), bool)
        with obs_trace.span("engine.sample"):
            # Prefill bookkeeping first: decide which rows sample this tick.
            ready: list[int] = []
            for i, slot in enumerate(self.active):
                if slot is None:
                    continue
                if slot.pending is not None:
                    take = int(lengths[i])
                    slot.pending = slot.pending[take:]
                    slot.consumed += take
                    self._maybe_cache_prefix(i, slot)
                    if slot.pending.size:     # prompt not done — no sample
                        continue
                    slot.pending = None
                ready.append(i)
            toks = self._sample_ready(picked, last, ready)
            now = time.perf_counter()
            for i in ready:
                slot = self.active[i]
                if slot is None:              # defensive; ready rows are live
                    continue
                t = toks[i]
                rid = slot.request_id
                if not slot.tokens:
                    self.first_token_at[rid] = now
                    sub = self.submitted_at.get(rid)
                    if sub is not None:
                        obs_metrics.observe("serve_ttft_s", now - sub)
                        obs_events.emit("first_token", rid=rid,
                                        ttft_s=now - sub)
                elif slot.last_emit_at is not None:
                    obs_metrics.observe("serve_itl_s",
                                        now - slot.last_emit_at)
                slot.last_emit_at = now
                slot.last_token = t
                slot.tokens.append(t)
                slot.n_sampled += 1
                slot.remaining -= 1
                emitted += 1
                if slot.remaining <= 0:
                    self.finished[rid] = slot.tokens
                    self.active[i] = None
                    completed[i] = True
                    obs_metrics.inc("serve_requests_completed_total")
                    self._request_done(rid, "request_completed",
                                       n_tokens=len(slot.tokens))
        if completed.any():
            # Slot-carry lifecycle invariant (DESIGN.md §Serving): a freed
            # slot's carry returns to the ⊕-identity init in the same tick,
            # never lingering until the next admit.
            self._reset(completed)
        return emitted

    def run(self) -> dict[int, list[int]]:
        """Serve until queue + slots drain.  Returns {request_id: tokens}."""
        while self.queue or any(s is not None for s in self.active):
            self.step()
        return self.finished

    # ------------------------------------------------------------ migration
    def export_requests(self, *, reason: str = "drain") -> list[dict]:
        """Lift every queued + active request out as migration descriptors.

        The payoff of the paper's O(1) state: an active request's entire
        context is its per-layer ``(m, u, w)`` carry — a few KB gathered
        through the same jitted slot entry point the prefix cache uses —
        so moving it to another engine costs a dict copy, not a KV-cache
        transfer.  Each descriptor carries the exact host-array carry plus
        the tokens not yet folded into it (mid-prefill: the unconsumed
        prompt tail; decoding: just the last sampled token), the emitted
        tokens, the step counter, and the deadline as *remaining* budget.
        Feed descriptors to another engine's :meth:`inject_request`; the
        continuation is byte-identical because sampling keys are
        ``(request_id, step)``-absolute.

        The engine is left empty (queue + slots cleared, carries reset to
        the ⊕-identity init per the lifecycle invariant); ``finished`` /
        ``errors`` are untouched for the caller to harvest.
        """
        now = time.perf_counter()

        def _remaining(deadline):
            return None if deadline is None else deadline - now

        descs: list[dict] = []
        occupied = np.zeros((self.n_slots,), bool)
        for i, slot in enumerate(self.active):
            if slot is None:
                continue
            occupied[i] = True
            gather, _ = self._ensure_slot_io()
            carry = jax.tree.map(
                np.asarray, gather(self.states, jnp.int32(i)))
            if slot.pending is not None:
                pending = np.asarray(slot.pending, np.int32)
            else:
                pending = np.asarray([slot.last_token], np.int32)
            descs.append({
                "request_id": slot.request_id,
                "prompt": (None if slot.prompt is None
                           else np.asarray(slot.prompt, np.int32)),
                "tokens": list(slot.tokens),
                "remaining": slot.remaining,
                "n_sampled": slot.n_sampled,
                "deadline_remaining_s": _remaining(slot.deadline),
                "pending": pending,
                "carry": carry,
            })
            self.active[i] = None
            self._request_done(slot.request_id, "request_migrated",
                               reason=reason, n_tokens=len(slot.tokens),
                               active=True)
        for q in self.queue:
            descs.append({
                "request_id": q.request_id,
                "prompt": q.prompt,
                "tokens": list(q.tokens),
                "remaining": q.remaining,
                "n_sampled": q.n_sampled,
                "deadline_remaining_s": _remaining(q.deadline),
                "pending": np.asarray(q.pending, np.int32),
                "carry": q.carry,
            })
            self._request_done(q.request_id, "request_migrated",
                               reason=reason, n_tokens=len(q.tokens),
                               active=False)
        self.queue = []
        if occupied.any():
            self._reset(occupied)
        if descs:
            obs_metrics.inc("serve_migrated_total", len(descs))
        obs_metrics.set_gauge("serve_queue_depth", 0)
        obs_metrics.set_gauge("serve_slot_occupancy", 0.0)
        return descs

    def inject_request(self, desc: dict, *, force: bool = False) -> int:
        """Admit a migration descriptor from :meth:`export_requests`.

        Two shapes, one contract (byte-identical continuation, since
        sampling keys are ``(request_id, step)``-absolute):

        * **carry present** (drain): the exported carry seeds the slot at
          admission and only ``desc["pending"]`` is folded on top.
        * **carry absent** (crash — the device state died with the
          replica): the prompt plus every emitted token is replayed from
          the ⊕-identity init, so the loss is bounded by re-folding work,
          never by request or token loss.

        ``force=True`` bypasses the ``max_queue`` bound: a migrated
        request was already admitted tier-wide, and shedding it would turn
        a replica loss into a request loss.  ``submitted_at`` is re-seeded
        (the PR 9 restore contract) so latency accounting restarts at
        injection.
        """
        rid = int(desc["request_id"])
        if (rid in self.submitted_at or rid in self.finished
                or rid in self.errors):
            raise ValueError(f"request_id {rid} already known to this engine")
        if (not force and self.max_queue is not None
                and len(self.queue) >= self.max_queue):
            self.n_shed += 1
            obs_metrics.inc("serve_shed_total")
            obs_events.emit("request_shed", queue_depth=len(self.queue),
                            max_queue=self.max_queue)
            raise EngineOverloaded(
                f"admission queue full ({len(self.queue)}/{self.max_queue} "
                "queued); inject elsewhere or force=True")
        remaining = int(desc["remaining"])
        if remaining < 1:
            raise ValueError(f"request {rid}: remaining={remaining} < 1 "
                             "(finished requests are not migratable)")
        carry = desc.get("carry")
        prompt = desc.get("prompt")
        prompt = None if prompt is None else np.asarray(prompt, np.int32)
        tokens = [int(t) for t in desc.get("tokens", [])]
        if carry is not None:
            pending = np.asarray(desc["pending"], np.int32)
        else:
            if prompt is None:
                raise ValueError(
                    f"request {rid}: carry-less descriptor needs the "
                    "original prompt to recompute from")
            pending = (np.concatenate(
                [prompt, np.asarray(tokens, np.int32)]) if tokens
                else prompt)
        dl = desc.get("deadline_remaining_s")
        now = time.perf_counter()
        self.queue.append(_Queued(
            request_id=rid, pending=pending, remaining=remaining,
            deadline=None if dl is None else now + dl,
            prompt=prompt, tokens=tokens,
            n_sampled=int(desc.get("n_sampled", len(tokens))),
            carry=carry))
        self._next_id = max(self._next_id, rid + 1)
        self.submitted_at[rid] = now
        obs_metrics.inc("serve_injected_total")
        obs_metrics.set_gauge("serve_queue_depth", len(self.queue))
        obs_events.emit("request_injected", rid=rid,
                        n_tokens=len(tokens), carried=carry is not None)
        return rid

    # -------------------------------------------------- snapshot / restore
    def snapshot(self) -> dict:
        """Serialise the whole engine: device carries + scheduler bookkeeping.

        Returns ``{"tree": <pytree of host arrays>, "meta": <JSON-able>}``.
        Deadlines are stored as *remaining* seconds (wall-clock budgets
        survive a restart; absolute ``perf_counter`` values do not).
        """
        now = time.perf_counter()

        def _remaining(deadline):
            return None if deadline is None else deadline - now

        def _slot_meta(slot: _Slot | None):
            if slot is None:
                return None
            return {
                "request_id": slot.request_id,
                "pending": (None if slot.pending is None
                            else slot.pending.tolist()),
                "tokens": list(slot.tokens),
                "remaining": slot.remaining,
                "n_sampled": slot.n_sampled,
                "last_token": slot.last_token,
                "deadline_remaining_s": _remaining(slot.deadline),
                "prompt": (None if slot.prompt is None
                           else slot.prompt.tolist()),
                "consumed": slot.consumed,
            }

        tree = {
            "states": jax.tree.map(np.asarray, self.states),
            "key": np.asarray(self.key),
        }
        def _queued_meta(q: _Queued):
            if q.carry is not None and q.prompt is None:
                # Can't serialise the device carry here and can't rebuild
                # it from scratch without the original prompt.  Only
                # reachable by migrating a restored slot (restore() drops
                # prompts) and snapshotting before it re-slots.
                raise RuntimeError(
                    f"request {q.request_id}: queued migrated carry with "
                    "no original prompt cannot be snapshotted")
            if q.carry is not None:
                # Snapshot in recompute form: replay prompt + emitted from
                # the ⊕-identity init.  Byte-identical continuation (keys
                # are (rid, step)-absolute); costs re-folding on restore.
                pending = list(q.prompt.tolist()) + list(q.tokens)
            else:
                pending = q.pending.tolist()
            return {
                "request_id": q.request_id,
                "prompt": None if q.prompt is None else q.prompt.tolist(),
                "pending": pending,
                "tokens": list(q.tokens),
                "n_sampled": q.n_sampled,
                "max_new": q.remaining,      # legacy field name
                "deadline_remaining_s": _remaining(q.deadline),
            }

        meta = {
            "active": [_slot_meta(s) for s in self.active],
            "queue": [_queued_meta(q) for q in self.queue],
            "finished": {str(k): v for k, v in self.finished.items()},
            "errors": {str(k): v for k, v in self.errors.items()},
            "n_shed": self.n_shed,
            "n_quarantined": self.n_quarantined,
            "next_id": self._next_id,
            "n_slots": self.n_slots,
            "chunk": self.chunk,
        }
        return {"tree": tree, "meta": meta}

    def restore(self, snap: dict) -> None:
        """Restore engine state from a :meth:`snapshot` dict.

        The engine must be constructed with the same model config and
        ``n_slots``/``chunk`` as the snapshotting engine.
        """
        meta = snap["meta"]
        if meta["n_slots"] != self.n_slots or meta["chunk"] != self.chunk:
            raise ValueError(
                f"snapshot taken with n_slots={meta['n_slots']}, "
                f"chunk={meta['chunk']}; this engine has "
                f"n_slots={self.n_slots}, chunk={self.chunk}")
        now = time.perf_counter()

        def _absolute(remaining):
            return None if remaining is None else now + remaining

        def _slot(m):
            if m is None:
                return None
            prompt = m.get("prompt")
            return _Slot(
                request_id=m["request_id"],
                pending=(None if m["pending"] is None
                         else np.asarray(m["pending"], np.int32)),
                tokens=list(m["tokens"]),
                remaining=m["remaining"],
                n_sampled=m["n_sampled"],
                last_token=m["last_token"],
                deadline=_absolute(m["deadline_remaining_s"]),
                prompt=(None if prompt is None
                        else np.asarray(prompt, np.int32)),
                # hashes stays None: restored in-flight prefills skip cache
                # insertion (their grid hashes died with the old process).
                consumed=int(m.get("consumed", 0)),
            )

        self.states = jax.tree.map(jnp.asarray, snap["tree"]["states"])
        self.key = jnp.asarray(snap["tree"]["key"])
        self.active = [_slot(m) for m in meta["active"]]
        self.queue = [
            _Queued(
                request_id=q["request_id"],
                pending=np.asarray(q.get("pending", q["prompt"]), np.int32),
                remaining=int(q["max_new"]),
                deadline=_absolute(q["deadline_remaining_s"]),
                prompt=(None if q.get("prompt") is None
                        else np.asarray(q["prompt"], np.int32)),
                tokens=list(q.get("tokens", [])),
                n_sampled=int(q.get("n_sampled", 0)))
            for q in meta["queue"]
        ]
        self.finished = {int(k): list(v) for k, v in meta["finished"].items()}
        self.errors = {int(k): v for k, v in meta["errors"].items()}
        self.n_shed = int(meta["n_shed"])
        self.n_quarantined = int(meta["n_quarantined"])
        self._next_id = int(meta["next_id"])
        # Lifecycle invariant holds across restore too: free slots carry the
        # ⊕-identity init even if the snapshot predates the eager-reset fix
        # (or was taken by a buggy build).
        free = np.asarray([s is None for s in self.active], bool)
        if free.any():
            self._reset(free)
        # Absolute perf_counter() values don't survive a restart, but wiping
        # the latency maps outright made every restored request's terminal
        # event drop ``total_s`` and its first token miss the TTFT
        # histogram.  Re-seed submission at *restore* time: post-restore
        # latencies deliberately exclude pre-crash time (a restore is a new
        # clock epoch), which under- rather than over-states them.
        self.submitted_at = {
            rid: now
            for rid in ([s.request_id for s in self.active if s is not None]
                        + [q.request_id for q in self.queue])
        }
        self.first_token_at = {}
        obs_metrics.set_gauge("serve_queue_depth", len(self.queue))
        obs_metrics.set_gauge(
            "serve_slot_occupancy",
            sum(s is not None for s in self.active) / self.n_slots)

    def save(self, directory: str, step: int) -> str:
        """Atomic crash-safe engine checkpoint (checkpoint/io.py layer)."""
        from repro.checkpoint import save_checkpoint
        snap = self.snapshot()
        return save_checkpoint(directory, step, snap["tree"],
                               extra={"engine": snap["meta"]})

    def load(self, directory: str, step: int | None = None) -> int:
        """Restore from :meth:`save`; falls back past corrupt steps.

        Returns the step the engine was restored from.
        """
        from repro.checkpoint import restore_checkpoint
        template = {
            "states": jax.tree.map(np.asarray, self._init_states),
            "key": np.asarray(self.key),
        }
        tree, step_restored, extra = restore_checkpoint(
            directory, template, step)
        self.restore({"tree": tree, "meta": extra["engine"]})
        return step_restored

    # ------------------------------------------------------------ internals
    def _ensure_slot_io(self):
        """Create the jitted gather/inject slot entry points on first use.

        Lazy so a cache-less, never-migrated engine keeps exactly two
        jitted functions (pinned by the trace-count test); ``warmup()``
        forces creation when a prefix cache is attached so the first hit
        doesn't pay compile inside a TTFT.  Both take the slot index /
        mask as *traced* arguments — one trace each for any slot.
        """
        if self._gather_fn is None:
            from repro.models.lm import lm_state_put_slot, lm_state_take_slot

            cfg = self.api.cfg

            def gather(states, idx):
                """Copy out slot ``idx``'s carry (size-1 slot axis)."""
                return lm_state_take_slot(cfg, states, idx)

            def inject(states, carry, mask):
                """Seed every masked slot's carry from a size-1 carry."""
                return lm_state_put_slot(cfg, states, carry, mask)

            self._gather_fn = _jit(gather)
            self._inject_fn = _jit(inject)
        return self._gather_fn, self._inject_fn

    def _reset(self, mask: np.ndarray) -> None:
        """Return the carries of the slots in ``mask`` (S,) to the
        ⊕-identity init."""
        with obs_trace.span("engine.reset"):
            self.states = self._reset_fn(self.states, jnp.asarray(mask))

    def _sample_ready(self, picked: np.ndarray, last,
                      ready: list[int]) -> dict[int, int]:
        """The tokens of the rows in ``ready``; returns {row: token id}.

        jit-safe samplers already sampled every row inside the step (keys
        derived on device); their tokens are column 0 of ``picked``, read
        with the finite flags in the tick's one host read.  Custom
        samplers keep the eager per-row path on the step's device
        ``last`` logits and see concrete keys.
        """
        if not ready:
            return {}
        if self._batched_sample is not None:
            obs_metrics.inc("serve_sampled_in_step_total", len(ready))
            return {i: int(picked[i, 0]) for i in ready}
        out: dict[int, int] = {}
        for i in ready:
            slot = self.active[i]
            tok = self.sampler(
                last[i:i + 1],
                request_key(self.key, slot.request_id, slot.n_sampled))
            out[i] = int(tok[0, 0])
        return out

    def _request_done(self, rid: int, kind: str, **data) -> None:
        """Terminal per-request accounting: emit the event, evict the
        latency maps (the fix for unbounded ``first_token_at`` growth —
        whatever ends a request's life funnels through here)."""
        now = time.perf_counter()
        sub = self.submitted_at.pop(rid, None)
        ft = self.first_token_at.pop(rid, None)
        if sub is not None:
            data["total_s"] = now - sub
            if ft is not None:
                data["ttft_s"] = ft - sub
        obs_events.emit(kind, rid=rid, **data)

    def _expire_deadlines(self):
        """Error out queued + active requests whose deadline has passed."""
        now = time.perf_counter()
        kept = []
        for q in self.queue:
            if q.deadline is not None and now > q.deadline:
                self.errors[q.request_id] = ERR_DEADLINE
                obs_metrics.inc("serve_deadline_expired_total")
                self._request_done(q.request_id, "deadline_expired",
                                   queued=True)
            else:
                kept.append(q)
        self.queue = kept
        expired = np.zeros((self.n_slots,), bool)
        for i, slot in enumerate(self.active):
            if (slot is not None and slot.deadline is not None
                    and now > slot.deadline):
                self.errors[slot.request_id] = ERR_DEADLINE
                self.active[i] = None
                expired[i] = True
                obs_metrics.inc("serve_deadline_expired_total")
                self._request_done(slot.request_id, "deadline_expired",
                                   queued=False)
        if expired.any():
            # Eager carry reset, same as the quarantine path — leaving the
            # dead request's carry in ``self.states`` until the next admit
            # violated the lifecycle invariant (a snapshot taken in the gap
            # captured another tenant's state in a "free" slot).
            self._reset(expired)

    def _admit(self):
        """Move queued requests into free slots.

        Free slots already hold ⊕-identity init carries (every exit path
        resets eagerly — the lifecycle invariant), so admission only
        *writes* state for prefix-cache hits: the cached carry is injected
        into the slot row and the matched prompt tokens are skipped.

        Each admission reports its queue wait, submission (or injection,
        or restore) to slot: the ``request_admitted`` event and the
        ``serve_queue_wait_s`` histogram.
        """
        now = time.perf_counter()
        for i in range(self.n_slots):
            if self.active[i] is not None or not self.queue:
                continue
            q = self.queue.pop(0)
            # Every queued request has a submission time.
            wait = now - self.submitted_at.get(q.request_id, now)
            obs_metrics.observe("serve_queue_wait_s", wait)
            obs_events.emit("request_admitted", rid=q.request_id, slot=i,
                            queue_wait_s=wait)
            slot = _Slot(request_id=q.request_id, pending=q.pending,
                         tokens=list(q.tokens), remaining=q.remaining,
                         n_sampled=q.n_sampled,
                         deadline=q.deadline, prompt=q.prompt)
            migrated = q.carry is not None or q.n_sampled > 0
            if q.carry is not None:
                # Drain-migrated: seed the slot with the exported carry;
                # q.pending holds only the tokens not yet folded into it.
                mask = np.zeros((self.n_slots,), bool)
                mask[i] = True
                _, inject = self._ensure_slot_io()
                self.states = inject(
                    self.states, jax.tree.map(jnp.asarray, q.carry),
                    jnp.asarray(mask))
            elif self.prefix_cache is not None and not migrated:
                # Migrated requests skip the cache both ways: their grid
                # hashes died with the donor engine, and a recompute-path
                # pending (prompt + generated tokens) is not a prompt.
                match_len, carry, hashes = self.prefix_cache.lookup(
                    q.pending)
                slot.hashes = hashes
                if match_len:
                    mask = np.zeros((self.n_slots,), bool)
                    mask[i] = True
                    _, inject = self._ensure_slot_io()
                    self.states = inject(
                        self.states, jax.tree.map(jnp.asarray, carry),
                        jnp.asarray(mask))
                    slot.pending = q.pending[match_len:]
                    slot.consumed = match_len
            self.active[i] = slot

    def _maybe_cache_prefix(self, i: int, slot: _Slot) -> None:
        """Copy slot ``i``'s carry into the prefix cache when the prefill
        just crossed a chunk-grid boundary the cache wants (seen >= k times
        or pinned).  Runs after ``_step_fn``, so ``self.states`` row ``i``
        is exactly the carry of ``prompt[:consumed]``."""
        cache = self.prefix_cache
        if (cache is None or slot.hashes is None
                or slot.consumed % self.chunk != 0):
            return
        h = slot.hashes.get(slot.consumed)
        if h is None or not cache.wants(slot.consumed, h):
            return
        gather, _ = self._ensure_slot_io()
        carry = gather(self.states, jnp.int32(i))
        cache.insert(slot.prompt[:slot.consumed], h,
                     jax.tree.map(np.asarray, carry))
