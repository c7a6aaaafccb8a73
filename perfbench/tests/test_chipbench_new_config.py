"""A configuration of another layer layout is added as new files only: a
tiny Aaren decoder with sparse experts (``data/tiny-moe.json``) and its
plain reference (``data/tiny_moe_reference.py``), with a cell per driver,
run through the unchanged harness and proved correct."""

import json
import shutil
from pathlib import Path

import pytest

from chipbench_tiny import BENCH, make_checkout, run_cell

DATA = Path(__file__).resolve().parent / "data"


def add_moe_config(root: Path) -> list[Path]:
    """Adds the tiny expert configuration and a cell per driver to the
    checkout as new files and entries; returns the files added."""
    bench = root / BENCH.name
    added = [bench / "configs" / "tiny-moe.json",
             bench / "configs" / "tiny_moe_reference.py"]
    shutil.copy(DATA / "tiny-moe.json", added[0])
    shutil.copy(DATA / "tiny_moe_reference.py", added[1])
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-moe", "source": "test data",
                            "file": "perfbench/configs/tiny-moe.json",
                            "reduced": [], "why": "sparse experts"})
    for cell in ("tiny-chat", "tiny-train"):
        wl = json.loads((bench / "workloads" / f"{cell}.json").read_text())
        wl["config"] = "tiny-moe"
        name = f"tiny-moe-{cell.split('-')[1]}"
        added.append(bench / "workloads" / f"{name}.json")
        added[-1].write_text(json.dumps(wl))
        spec["workloads"].append({"name": name, "config": "tiny-moe",
                                  "traffic": wl["traffic"], "chips": 1,
                                  "why": "sparse experts"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if cell in m.get("workloads", []):
                m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return added


@pytest.mark.parametrize("cell", ["tiny-moe-chat", "tiny-moe-train"])
def test_expert_configuration_is_new_files_only(tmp_path, cell):
    root = make_checkout(tmp_path)
    added = add_moe_config(root)
    bench = root / BENCH.name
    for path in bench.rglob("*"):
        rel = path.relative_to(bench)
        if path.is_file() and path not in added and (BENCH / rel).is_file():
            assert path.read_bytes() == (BENCH / rel).read_bytes(), rel
    rc, line, err = run_cell(root, cell)
    assert rc == 0, err[-3000:]
    assert line["correct"] is True, (line, err[-3000:])
    assert line["failed"] == 0 and line["attempted"] > 0
