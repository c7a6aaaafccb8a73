"""Records ``data/serve_spans.xplane.pb`` on the chip: four ticks of the
serving engine at the 4-layer configuration (16 slots, chunk 16) with the
program's spans on, under JAX's profiler with its default options (as the
benchmark's traced runs use it).  The ticks admit, complete and reset
slots, and one garbage collection is forced between the second and the
third, so that every span the engine and its stalls write is in the file.

    python3 perfbench/tests/record_serve_spans.py <output directory>
"""

from __future__ import annotations

import gc
import glob
import os
import shutil
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
os.environ["REPRO_TRACE"] = "1"
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main(out: str) -> None:
    import jax
    import numpy as np

    from lib import cell, model
    from repro.serving import StreamingEngine

    cfg = cell.load_json("configs", "phi3-mini-3.8b-4l")
    api, params = model.build(cfg, 7)
    eng = StreamingEngine(api, params, n_slots=16, chunk=16)
    eng.warmup()
    rng = np.random.default_rng(7)
    # Twelve requests done after two ticks, eight that prefill over three;
    # four of them wait for the slots the first twelve free.
    for prompt_len, max_new in [(8, 2)] * 12 + [(40, 64)] * 8:
        eng.submit(rng.integers(0, cfg["vocab_size"], prompt_len), max_new)
    logdir = tempfile.mkdtemp()
    jax.profiler.start_trace(logdir)
    for tick in range(4):
        if tick == 2:
            gc.collect()
        eng.step()
    jax.block_until_ready(eng.states)
    jax.profiler.stop_trace()
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    os.makedirs(out, exist_ok=True)
    shutil.copy(path, os.path.join(out, "serve_spans.xplane.pb"))
    shutil.rmtree(logdir)


if __name__ == "__main__":
    main(sys.argv[1])
