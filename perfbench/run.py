#!/usr/bin/env python3
"""Chip benchmark of the Aaren serve and train paths.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the accelerator this process finds:
set-up (weights drawn from the seed on the device, compilation from the
persistent cache in ``.jax_cache/`` of the checkout, warm-up of the cell's
own shapes), then ``--seconds`` of measured work, then the check of what
the timed path produced against the plain reference.  With ``--trace 1``
the profiler records part of the window and the per-layer metrics are
reported instead of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), then ``compared``: each number checked, with its limit.  Without a
TPU, or with fewer chips than the cell asks for, it prints no result and
exits with code 2.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent
CACHE_DIR = CHECKOUT / ".jax_cache"
TRACE_SECONDS = 10.0


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def fail(msg: str) -> int:
    print(f"perfbench: {msg}", file=sys.stderr)
    return 2


def main(argv=None, *, allow_cpu: bool = False,
         hooks: dict | None = None) -> int:
    """One run.  ``allow_cpu`` and ``hooks`` are for the benchmark's own
    tests: they skip the look for a chip and break the timed path."""
    args = parse(argv)
    if not (CHECKOUT / "src" / "repro").is_dir():
        return fail(f"the program is not in this checkout ({CHECKOUT}/src)")
    for p in (str(HERE), str(CHECKOUT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    # Before the program is imported: its spans are read once at import,
    # and its compile cache takes the directory from this variable.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    if args.trace:
        os.environ["REPRO_TRACE"] = "1"

    from lib import cell as cells
    from lib import device
    from lib.profile import Tracer, breakdown

    cell = cells.load_cell(args.workload)
    bench = cells.benchmark()
    entry = next((w for w in bench["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        return fail(f"no cell {args.workload!r} in BENCHMARK.json")
    metrics = cells.metrics_for(args.workload, bool(args.trace), bench)
    driver = cells.driver(cell["workload"]["driver"])
    if entry["chips"] not in driver.CHIPS:
        return fail(f"the {cell['workload']['driver']!r} driver places its "
                    f"work on {driver.CHIPS} chips, not {entry['chips']}")

    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        devs = device.devices(entry["chips"], allow_cpu=allow_cpu)
    except device.NoAccelerator as e:
        return fail(str(e))
    desc = device.describe(devs)
    peaks = device.peaks(desc["kind"]) if desc["platform"] == "tpu" else None


    tracer = Tracer(TRACE_SECONDS) if args.trace else None
    try:
        res = driver.run(cell, args.seed, args.seconds, devs, T_START,
                         tracer=tracer, hooks=hooks)
        trace = tracer.reduce() if tracer else None
    finally:
        if tracer:
            tracer.stop()
            tracer.close()

    run = {"cell": cell, "result": res, "trace": trace, "device": desc,
           "peaks": peaks, "chips": entry["chips"], "seconds": args.seconds}
    out = {}
    for m in metrics:
        try:
            value = cells.reader(m["name"])(run)
        except Exception:  # a reader's fault drops its metric, not the run
            traceback.print_exc()
            value = None
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = dict(desc, memory_peak_bytes=res["memory_peak_bytes"])
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": out, "device": dev}
    if trace is not None:
        dev.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        line["breakdown"] = breakdown(trace)
    line["compared"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in res["compared"].items()}
    print(json.dumps({k: v for k, v in res.get("verdict", {}).items()
                      if k != "ok"}, default=str), file=sys.stderr)
    for k, (v, lim) in res["compared"].items():
        print(f"compared {k} {v!r} limit {lim!r}", file=sys.stderr)
    if not res["compared"]:
        print(f"compared nothing: {res.get('verdict')}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
