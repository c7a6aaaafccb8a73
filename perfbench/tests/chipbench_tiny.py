"""A copy of the benchmark at a tiny size, for its CPU tests.

``make_checkout(tmp)`` copies the benchmark's directory next to a link to
the program, adds a tiny configuration and one tiny cell per driver with
the real cells' limits, and writes a ``BENCHMARK.json`` that names them.
``run_cell`` drives one run of that copy in a child process on the CPU,
with the look for a chip skipped and, optionally, the timed path broken.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent

TINY = {"name": "tiny", "hidden_size": 128, "intermediate_size": 256,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 32,
        "num_hidden_layers": 2, "vocab_size": 512}

# Tiny cells: the real cells' workload files with the tiny configuration,
# shorter sequences and lengths scaled to them.
CELLS = {
    "tiny-chat": ("phi3-serve-chat", {}, {
        "prompt_len": {"median": 16, "min": 4, "max": 64},
        "output_len": {"median": 8, "min": 4, "max": 32}}),
    "tiny-train": ("phi3-train-packed", {"seq_len": 512}, {
        "doc_len": {"mean": 100, "min": 8, "max": 512}}),
}


def make_checkout(tmp: Path) -> Path:
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / BENCH.name,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    bench = root / BENCH.name
    for base in ("phi3-mini-3.8b", "phi3-mini-3.8b-4l"):
        cfg = json.loads((bench / "configs" / f"{base}.json").read_text())
        cfg.update(TINY, name=f"tiny-{base}")
        (bench / "configs" / f"tiny-{base}.json").write_text(json.dumps(cfg))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = []
    for name, (real, wl_over, mix_over) in CELLS.items():
        wl = json.loads((bench / "workloads" / f"{real}.json").read_text())
        mix = json.loads((bench / "traffic" / f"{wl['traffic']}.json")
                         .read_text())
        for key, val in mix_over.items():
            mix[key] = dict(mix[key], **val)
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(mix))
        wl.update(wl_over, config=f"tiny-{wl['config']}", traffic=name)
        (bench / "workloads" / f"{name}.json").write_text(json.dumps(wl))
        entry = next(w for w in spec["workloads"] if w["name"] == real)
        cells.append(dict(entry, name=name, config=wl["config"],
                          traffic=name))
        for m in spec["end_to_end"] + spec["per_layer"]:
            if real in m.get("workloads", []):
                m["workloads"].append(name)
    spec["workloads"] = cells
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


DRIVER = """
import sys
sys.path.insert(0, {bench!r})
import run
{hooks}
sys.exit(run.main({argv!r}, allow_cpu=True, hooks=HOOKS))
"""


def run_cell(root: Path, cell: str, *, seed: int = 5, seconds: float = 4,
             trace: int = 0, hooks: str = "HOOKS = None",
             timeout: float = 600) -> tuple[int, dict | None, str]:
    """(exit code, result line or None, standard error) of one run."""
    argv = ["--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    script = DRIVER.format(bench=str(root / BENCH.name), hooks=hooks,
                           argv=argv)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("REPRO_TRACE", None)
    proc = subprocess.run([sys.executable, "-c", script], cwd=root, env=env,
                          capture_output=True, text=True, timeout=timeout)
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1]) if lines else None
    return proc.returncode, result, proc.stderr
