"""The Mellum cell's pieces on the CPU: a tiny float32 configuration of
its layout (a held share of 2 of 8 experts, four layer kinds in a
period) with the cell's reference module, served through the unchanged
``lib/serve.py``, and the two expert readers' counts at the cell's
shapes."""

import importlib.util
import json
import shutil
import sys
from pathlib import Path

import pytest

from chipbench_tiny import BENCH, make_checkout, run_cell

DATA = Path(__file__).resolve().parent / "data"
sys.path[:0] = [str(BENCH)]

from lib.cell import load_json  # noqa: E402


def metric(name: str):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]


def test_tiny_mellum_chat_is_correct(tmp_path):
    root = make_checkout(tmp_path)
    bench = root / BENCH.name
    shutil.copy(DATA / "tiny-mellum.json", bench / "configs")
    wl = json.loads((bench / "workloads" / "tiny-chat.json").read_text())
    wl["config"] = "tiny-mellum"
    (bench / "workloads" / "tiny-mellum-chat.json").write_text(
        json.dumps(wl))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-mellum", "source": "test data",
                            "file": "perfbench/configs/tiny-mellum.json",
                            "reduced": ["num_experts"],
                            "why": "a held share of sparse experts"})
    spec["workloads"].append({"name": "tiny-mellum-chat",
                              "config": "tiny-mellum", "traffic": "tiny-chat",
                              "chips": 1, "why": "a held share"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny-chat" in m.get("workloads", []):
            m["workloads"].append("tiny-mellum-chat")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    rc, line, err = run_cell(root, "tiny-mellum-chat")
    assert rc == 0, err[-3000:]
    assert line["correct"] is True, (line, err[-3000:])
    assert line["failed"] == 0 and line["attempted"] > 0


def test_expert_roofline_counts_at_the_cells_shapes():
    cfg = load_json("configs", "mellum2-12b-a2.5b-ep4")
    roof = metric("expert_roofline.chat")
    assert roof.matrix_bytes(cfg) == 16 * 2304 * 896 * 2 == 66_060_288
    assert roof.tick_seconds(cfg, PEAKS) == pytest.approx(6.78e-3, abs=5e-6)
    gate_up = "%_expert_gmm.7 = bf16[8192,896]{1,0:T(8,128)(2,1)} custom-call"
    down = "%_expert_gmm.8 = bf16[8192,2304]{1,0:T(8,128)(2,1)} custom-call"
    assert roof.matrices(gate_up, cfg) == 2
    assert roof.matrices(down, cfg) == 1


def _run(ops, steps):
    cfg = load_json("configs", "mellum2-12b-a2.5b-ep4")
    trace = {"devices": {"/device:TPU:0": ops},
             "spans": [(s, s + 10, "engine.step") for s in steps]}
    return {"cell": {"config": cfg}, "trace": trace, "peaks": PEAKS}


def test_expert_readers_on_a_made_trace():
    """Two ticks of 28 layers, each call taking twice its least time."""
    roof = metric("expert_roofline.chat")
    cfg = load_json("configs", "mellum2-12b-a2.5b-ep4")
    one = roof.matrix_bytes(cfg) / PEAKS["hbm_bytes_per_s"] * 1e9   # ns
    ops, t = [], 0
    for tick in range(2):
        for layer in range(28):
            for width, n in ((896, 2), (2304, 1)):
                ops.append((t, t + 2 * n * one,
                            f"%_expert_gmm.{layer} = bf16[8192,{width}] x"))
                t += 2 * n * one + 100
        ops.append((t, t + 1000, "%fusion.1 = bf16[64,16,2304] fusion"))
        t += 1000
    run = _run(ops, [0, t / 2, t])
    assert metric("expert_roofline.chat").read(run) == pytest.approx(50.0)
    ms = metric("expert_ms.chat").read(run)
    assert ms == pytest.approx(2 * roof.tick_seconds(cfg, PEAKS) * 1e3,
                               rel=1e-3)


def test_expert_readers_read_nothing_without_the_kernel():
    run = _run([(0, 1000, "%fusion.1 = bf16[64,16,2304] fusion")],
               [0, 2000, 4000])
    assert metric("expert_roofline.chat").read(run) is None
    assert metric("expert_ms.chat").read(run) is None
    assert metric("expert_roofline.chat").read(dict(run, trace=None)) is None
