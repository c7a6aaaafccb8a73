"""Serving launcher: batched generation / streaming engine demo.

Warm-up (trace + compile) runs before the timed section, and compile vs
steady-state throughput are reported separately — wall time that includes
jit tracing says nothing about serving speed.

Observability (DESIGN.md §Observability): ``--events`` writes the JSONL
event log, ``--metrics-out`` dumps the metrics-registry snapshot at exit,
and ``--metrics-port`` serves live Prometheus text at ``/metrics`` (plus
the snapshot document at ``/metrics.json``) while the engine runs.

A slot quarantined for non-finite logits makes the run exit non-zero: on a
device, poisoned logits are a fault to report, not a degraded success.

Example::

    python -m repro.launch.serve --arch phi3-mini-3.8b --smoke \
        --requests 8 --max-new 32 --engine streaming --chunk 16 \
        --events serve_events.jsonl --metrics-out serve_metrics.json
"""

from __future__ import annotations

import argparse
import contextlib
import time

import jax

from repro.configs import get_config, smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.factory import build
from repro.obs.events import EventLog, use_events
from repro.obs.export import serve_metrics, write_snapshot
from repro.obs.metrics import MetricsRegistry, use_metrics
from repro.serving import (
    EngineOverloaded,
    PrefixCache,
    StreamingEngine,
    decode_state_bytes,
    generate,
)
from repro.serving.sampler import greedy_sampler, temperature_sampler


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--attn-mode", default="aaren",
                    choices=["aaren", "softmax"])
    ap.add_argument("--engine", default="streaming",
                    choices=["streaming", "wave"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=0,
                    help="prefill chunk size (0 = engine default)")
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-queue", type=int, default=0,
                    help="admission queue bound; overflow submits are shed "
                         "(0 = unbounded).  With --replicas > 1 this bounds "
                         "the router's front queue; the tier sheds only "
                         "when every replica is saturated AND the front "
                         "queue is full")
    ap.add_argument("--replicas", type=int, default=1,
                    help="run N streaming-engine replicas behind the "
                         "occupancy-aware router (each with --slots slots; "
                         "requests are dispatched per --route-policy, and "
                         "a prefix cache is shared tier-wide)")
    ap.add_argument("--route-policy", default="least-occupancy",
                    choices=["least-occupancy", "round-robin", "jsq"],
                    help="replica dispatch policy (--replicas > 1): "
                         "emptiest batch first, strict rotation, or "
                         "join-shortest-queue")
    ap.add_argument("--drain", type=int, default=None, metavar="R",
                    help="mid-run, drain replica R: its queued + active "
                         "requests carry-migrate to the survivors "
                         "byte-identically (demo of failover; needs "
                         "--replicas >= 2)")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="per-request wall-clock deadline; expired requests "
                         "error out (0 = none)")
    ap.add_argument("--prefix-cache-mb", type=float, default=0.0,
                    help="prompt-prefix carry cache budget in MiB "
                         "(streaming engine only; 0 = off)")
    ap.add_argument("--prefix-cache-min-hits", type=int, default=2,
                    help="boundary must be seen this many times before its "
                         "carry is cached (pinned prefixes skip this)")
    ap.add_argument("--pin-prefix", action="append", default=[],
                    metavar="IDS",
                    help="comma-separated token ids of a prefix to pin "
                         "(always cached, never evicted); repeatable")
    ap.add_argument("--prefix-cache-dir", default=None,
                    help="directory to load the prefix cache from at start "
                         "and save it to at exit (crc'd checkpoint chunks)")
    ap.add_argument("--events", default=None,
                    help="path of the JSONL event log to write "
                         "(repro.obs.events; off when omitted)")
    ap.add_argument("--metrics-out", default=None,
                    help="path of the metrics-snapshot JSON dumped at exit")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus text at /metrics on this port "
                         "while the engine runs (0 = ephemeral port)")
    args = ap.parse_args()
    print(f"compile cache: {enable_compile_cache()}")

    # Ambient observability for the whole serve run: the engine's
    # instruments/events land here.  A registry is installed whenever any
    # obs output was asked for (the exposition endpoints need one even if
    # only --metrics-port was given).
    obs = contextlib.ExitStack()
    registry = None
    want_obs = (args.events is not None or args.metrics_out is not None
                or args.metrics_port is not None)
    if want_obs:
        registry = obs.enter_context(use_metrics(MetricsRegistry()))
        if args.events is not None:
            log = obs.enter_context(use_events(EventLog(args.events)))
            obs.callback(log.close)
    http = None
    if args.metrics_port is not None:
        http = serve_metrics(registry, args.metrics_port)
        print(f"metrics: http://{http.server_address[0]}:"
              f"{http.server_address[1]}/metrics")

    with obs:
        _run(args)
        if args.metrics_out is not None:
            write_snapshot(args.metrics_out, registry)
            print(f"metrics snapshot: {args.metrics_out}")
    if http is not None:
        http.shutdown()


def _run(args):

    cfg = (smoke_config(args.arch) if args.smoke else get_config(args.arch))
    cfg = cfg.replace(attn_mode=args.attn_mode)
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(args.seed))
    sampler = (greedy_sampler if args.temperature == 0
               else temperature_sampler(args.temperature, top_k=50))

    key = jax.random.PRNGKey(args.seed + 1)
    prompts = jax.random.randint(
        key, (args.requests, args.prompt_len), 0, cfg.vocab)
    n_tokens = args.requests * args.max_new

    if args.engine == "wave":
        if args.prefix_cache_mb:
            # KV-cache (softmax) archs have no position-free carry to cache;
            # the flag is a clean no-op rather than a crash so one launch
            # script can serve both arch families.
            print("[wave] --prefix-cache-mb ignored: prefix-state caching "
                  "needs the streaming engine's position-free carries")
        # Warm up prefill + decode at the serving shapes (cache_len pinned so
        # the timed call hits the same trace), then time steady state.
        cache_len = args.prompt_len + args.max_new
        t0 = time.perf_counter()
        generate(api, params, prompts, 2, sampler=sampler,
                 cache_len=cache_len)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        toks, states = generate(api, params, prompts, args.max_new,
                                sampler=sampler, cache_len=cache_len)
        jax.block_until_ready(toks)
        steady_s = time.perf_counter() - t0
        print(f"[wave] compile+first-run {compile_s:.2f}s | steady "
              f"{steady_s:.2f}s for {toks.shape} "
              f"({n_tokens / steady_s:.0f} tok/s); decode state "
              f"{decode_state_bytes(states) / 2**20:.3f} MiB")
    elif args.replicas > 1:
        _run_router(args, api, params, sampler, prompts)
    else:
        cache = None
        if args.prefix_cache_mb:
            cache = PrefixCache(max_bytes=int(args.prefix_cache_mb * 2**20),
                                min_hits=args.prefix_cache_min_hits)
        eng = StreamingEngine(api, params, n_slots=args.slots,
                              chunk=args.chunk or None, sampler=sampler,
                              max_queue=args.max_queue or None,
                              prefix_cache=cache)
        if cache is not None:
            for spec in args.pin_prefix:
                cache.pin([int(t) for t in spec.split(",") if t.strip()])
            if args.prefix_cache_dir:
                try:
                    got = cache.load(args.prefix_cache_dir)
                    print(f"[streaming] prefix cache: restored step {got} "
                          f"({len(cache)} entries)")
                except FileNotFoundError:
                    pass   # first run: nothing to restore yet
        compile_s = eng.warmup()
        deadline = args.deadline_s or None
        for i in range(args.requests):
            try:
                eng.submit(prompts[i], args.max_new, deadline_s=deadline)
            except EngineOverloaded:
                pass   # shed at the door; counted in eng.n_shed
        t0 = time.perf_counter()
        out = eng.run()
        steady_s = time.perf_counter() - t0
        served = sum(len(v) for v in out.values())
        print(f"[streaming] compile {compile_s:.2f}s | steady {steady_s:.2f}s"
              f" for {len(out)} requests / {served} tokens "
              f"({served / steady_s:.0f} tok/s) over {args.slots} slots, "
              f"chunk {eng.chunk}; per-slot state "
              f"{decode_state_bytes(eng.states) / args.slots / 2**10:.1f} KiB"
              f" (constant in sequence length)")
        if eng.n_shed or eng.errors or eng.n_quarantined:
            print(f"[streaming] degraded: shed {eng.n_shed}, errored "
                  f"{len(eng.errors)} (deadline/poison), quarantined "
                  f"{eng.n_quarantined} slots")
        if cache is not None:
            st = cache.stats()
            print(f"[streaming] prefix cache: {st['entries']} entries / "
                  f"{st['bytes'] / 2**10:.1f} KiB, hit rate "
                  f"{st['hit_rate']:.0%}, {st['prefill_tokens_saved']} "
                  "prefill tokens saved")
            if args.prefix_cache_dir:
                cache.save(args.prefix_cache_dir, 0)
                print(f"[streaming] prefix cache saved to "
                      f"{args.prefix_cache_dir}")
        if eng.n_quarantined:
            raise SystemExit(f"[streaming] {eng.n_quarantined} slots "
                             "quarantined for non-finite logits")


def _run_router(args, api, params, sampler, prompts):
    """--replicas > 1: the replicated tier (serving/router.py)."""
    from repro.serving import ReplicatedRouter

    cache = None
    if args.prefix_cache_mb:
        cache = PrefixCache(max_bytes=int(args.prefix_cache_mb * 2**20),
                            min_hits=args.prefix_cache_min_hits)
    router = ReplicatedRouter(
        api, params, n_replicas=args.replicas, n_slots=args.slots,
        chunk=args.chunk or None, sampler=sampler,
        policy=args.route_policy, max_queue=args.max_queue or None,
        prefix_cache=cache)
    compile_s = sum(e.warmup() for e in router.engines[:1])
    deadline = args.deadline_s or None
    for i in range(args.requests):
        try:
            router.submit(prompts[i], args.max_new, deadline_s=deadline)
        except EngineOverloaded:
            pass   # tier-wide shed; counted in router.n_shed
    t0 = time.perf_counter()
    if args.drain is not None:
        for _ in range(3):                 # let the victim pick up work
            router.step()
        n = router.drain(args.drain)
        print(f"[router] drained replica {args.drain}: {n} requests "
              "carry-migrated to survivors")
    out = router.run()
    steady_s = time.perf_counter() - t0
    served = sum(len(v) for v in out.values())
    st = router.stats()
    print(f"[router] compile {compile_s:.2f}s | steady {steady_s:.2f}s for "
          f"{len(out)} requests / {served} tokens "
          f"({served / steady_s:.0f} tok/s aggregate) over "
          f"{args.replicas}x{args.slots} slots, policy "
          f"{args.route_policy}")
    print(f"[router] tier: alive {st['alive']}/{st['n_replicas']}, shed "
          f"{st['shed']}, rerouted {st['rerouted']}, migrated "
          f"{st['migrated']}, failed-over {st['failed_over']}, errors "
          f"{st['errors']}")
    if cache is not None:
        cst = cache.stats()
        print(f"[router] shared prefix cache: {cst['entries']} entries, "
              f"hit rate {cst['hit_rate']:.0%}, "
              f"{cst['prefill_tokens_saved']} prefill tokens saved")
    n_quarantined = sum(e.n_quarantined for e in router.engines)
    if n_quarantined:
        raise SystemExit(f"[router] {n_quarantined} slots quarantined for "
                         "non-finite logits")


if __name__ == "__main__":
    main()
