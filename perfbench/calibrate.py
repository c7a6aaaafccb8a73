#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 perfbench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, in one process: the numbers a sound run of the program
gives (the lower readings), the control's (the plain reference computed in
float8, in the program's place: the upper readings), and for a training
cell the program with half of its batch left out and, once, the compiled
step's memory by XLA's count.  Prints one JSON line a seed.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]


def serve_seed(cell: dict, seed: int, seconds: float) -> dict:
    from lib import serve
    from lib.cell import reference

    reqs, rec, finished, _, _ = serve.window(cell, seed, seconds)
    ref = reference(cell["config"])
    return {"program": serve.check(cell, seed, reqs, rec, finished, ref),
            "control": serve.check(cell, seed, reqs, rec, finished, ref,
                                   "fp8"),
            "completed": serve.summarize(rec, seconds)["completed"]}


def train_seed(cell: dict, seed: int, faults: bool,
               memory: bool = False) -> dict:
    from lib import train
    from lib.cell import reference

    cfg = cell["config"]
    ref = reference(cfg)
    from lib import traffic

    feed = train.batches_for(cell, seed)
    drawn = [next(feed) for _ in range(train.CHECK_STEPS)]
    batches = [b for b, _ in drawn]
    mine = [traffic.reference_batch(b, d) for b, d in drawn]
    parts = train.parts(cfg)

    def program(rows=None):
        bs = [{k: v[:rows] for k, v in b.items()} for b in batches]
        state, got = train.first_steps(cfg, seed, parts, bs)
        del state
        gc.collect()
        return got

    t = time.perf_counter()
    prog = program()
    t_prog = time.perf_counter() - t
    half = program(rows=cell["workload"]["rows"] // 2) if faults else None
    t = time.perf_counter()
    want = ref.train(cfg, seed, mine, "f32")
    t_ref = time.perf_counter() - t
    ctrl = ref.train(cfg, seed, mine, "fp8")
    out = {"program": train.numbers(prog, want),
           "control": train.numbers(ctrl, want),
           "losses": prog[0], "ref_losses": want[0], "control_losses": ctrl[0],
           "program_s": t_prog, "reference_s": t_ref}
    if half is not None:
        out["half_batch"] = train.numbers(half, want)
    if memory:
        out["step_memory"] = step_memory(cfg, parts, batches[0])
    return out


def step_memory(cfg: dict, parts: tuple, batch: dict) -> dict:
    """What the compiled train step holds on the device, by XLA's count."""
    import jax

    from repro.train.state import init_train_state

    from lib import train
    from lib import weights as W

    api, opt, step = parts
    state = jax.eval_shape(lambda k: init_train_state(
        W.program_tree(api.abstract(), cfg, k), opt), W.base_key(0))
    ma = step.lower(state, batch, train.RKEY).compile().memory_analysis()
    return {k: int(getattr(ma, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes",
        "generated_code_size_in_bytes")}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--fault-seeds", type=int, default=3,
                    help="training: seeds that also run the half batch")
    args = ap.parse_args()

    import jax

    from lib import device
    from lib.cell import load_cell

    jax.config.update("jax_compilation_cache_dir",
                      str(HERE.parent / ".jax_cache"))
    cell = load_cell(args.workload)
    device.devices(1)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        if cell["workload"]["driver"] == "serve":
            out = serve_seed(cell, seed, args.seconds)
        else:
            out = train_seed(cell, seed, i < args.fault_seeds, i == 0)
        out.update(seed=seed, seconds=time.perf_counter() - t)
        print(json.dumps(out, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
