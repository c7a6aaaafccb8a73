#!/usr/bin/env python3
"""Find a serving cell's knee once, on the chip: the highest arrival rate
at which the queue does not grow across the window, on every arrival
order tried.

    python3 perfbench/sweep.py --workload <cell> --rates 2.5,3,3.5 \
        --seeds 1,2,3 --seconds 40

One process, one set of weights; a fresh engine for each rate and seed
(each seed is another order of the same requests and gaps).  Prints one
JSON line a run: requests due and finished, the TTFT median and 90th
percentile, and the requests waiting for a slot at each eighth of the
window.  The queue grows at a rate where, on some seed, more than
``--grow`` requests wait at the close and more wait then than at five
eighths of the window.  The last line names the knee and four fifths of it.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]


def grows(waiting: list[int], limit: int) -> bool:
    """Whether the queue, sampled at each eighth of a window and at its
    close, grew."""
    return waiting[-1] > limit and waiting[-1] > waiting[len(waiting) // 2]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--grow", type=int, default=4)
    args = ap.parse_args()

    import jax

    from repro.serving import StreamingEngine

    from lib import device, model, serve
    from lib.cell import load_cell

    jax.config.update("jax_compilation_cache_dir",
                      str(HERE.parent / ".jax_cache"))
    device.devices(1)
    cell = load_cell(args.workload)
    wl = cell["workload"]
    seeds = [int(s) for s in args.seeds.split(",")]
    api, params = model.build(cell["config"], seeds[0])
    stable = {}
    for rate in (float(r) for r in args.rates.split(",")):
        cell["workload"] = dict(wl, rate=rate)
        stable[rate] = True
        for seed in seeds:
            eng = StreamingEngine(api, params, n_slots=wl["slots"],
                                  chunk=wl["chunk"])
            eng.warmup()
            reqs = serve.requests_for(cell, seed, args.seconds)
            waiting = []

            class Probe:        # samples the queue at each eighth
                def poll(self, elapsed):
                    if len(waiting) < 7 and elapsed >= (len(waiting) + 1) \
                            * args.seconds / 8 - 0.05:
                        waiting.append(len(eng.queue))

                def stop(self):
                    pass

            t = time.perf_counter()
            rec = serve.drive(eng, reqs, args.seconds, Probe())
            s = serve.summarize(rec, args.seconds)
            waiting.append(len(eng.queue))
            grew = grows(waiting, args.grow)
            stable[rate] &= not grew
            print(json.dumps({
                "rate": rate, "seed": seed, "due": s["attempted"],
                "finished": s["completed"],
                "ttft_p50_ms": float(np.percentile(s["ttft_s"], 50) * 1e3),
                "ttft_p90_ms": float(np.percentile(s["ttft_s"], 90) * 1e3),
                "tpot_p90_ms": (float(np.percentile(s["tpot_s"], 90) * 1e3)
                                if s["tpot_s"].size else None),
                "waiting_at_eighths": waiting, "grew": grew,
                "ticks": s["ticks"],
                "tick_ms": 1e3 * s["window_s"] / max(s["ticks"], 1),
                "seconds": time.perf_counter() - t}), flush=True)
            del eng
    ok = [r for r in stable if stable[r]]
    knee = max(ok) if ok else None
    print(json.dumps({"knee": knee, "cell_rate": knee and 0.8 * knee,
                      "stable": stable}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
