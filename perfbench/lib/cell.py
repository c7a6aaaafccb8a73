"""Finds the files of a cell by name: its workload, configuration, traffic
mix, driver and metric readers.  Adding a cell, a configuration, a mix or a metric
is adding files; nothing here names one."""

from __future__ import annotations

import importlib
import importlib.util
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]      # the benchmark's directory

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _checked(name: str) -> str:
    if not NAME.match(name):
        raise ValueError(f"bad name {name!r}")
    return name


def load_json(kind: str, name: str) -> dict:
    path = ROOT / kind / f"{_checked(name)}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


def benchmark() -> dict:
    return json.loads((ROOT.parent / "BENCHMARK.json").read_text())


def load_cell(name: str) -> dict:
    """The workload file with its configuration and traffic mix resolved."""
    wl = load_json("workloads", name)
    return {"name": name, "workload": wl,
            "config": load_json("configs", wl["config"]),
            "traffic": load_json("traffic", wl["traffic"])}


def metrics_for(cell: str, trace: bool, bench: dict) -> list[dict]:
    """The BENCHMARK.json metrics that ``cell`` reports in this kind of run.

    End-to-end metrics without a ``workloads`` key are reported by every
    cell; a per-layer metric without one by every cell that reports the
    end-to-end metric it moves.
    """
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in names
                             else [])]


def reader(metric: str):
    """``read(run) -> float | None`` from ``metrics/<metric>.py``."""
    path = ROOT / "metrics" / f"{_checked(metric)}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {metric!r} ({path})")
    mod_name = "perfbench_metric_" + re.sub(r"[^A-Za-z0-9_]", "_", metric)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def driver(name: str):
    """The driver module ``lib/<name>.py`` that a workload file names: its
    ``run(cell, seed, seconds, devices, t_start, tracer, hooks)`` and the
    chip counts ``CHIPS`` it places work on."""
    if not (ROOT / "lib" / f"{_checked(name)}.py").is_file():
        raise FileNotFoundError(f"no driver named {name!r}")
    return importlib.import_module(f"lib.{name}")


def reference(config: dict):
    """The plain reference module named by the configuration file."""
    path = ROOT / "configs" / f"{_checked(config['reference'])}.py"
    spec = importlib.util.spec_from_file_location(
        "perfbench_ref_" + re.sub(r"[^A-Za-z0-9_]", "_", config["reference"]),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
