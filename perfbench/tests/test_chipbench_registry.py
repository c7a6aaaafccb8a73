"""A later change adds a traffic mix, a cell, a per-layer metric or a
driver as new files (and entries in BENCHMARK.json), and the harness finds
them by name; a cell on more chips than its driver places work on fails."""

import json

import pytest

from chipbench_tiny import make_checkout, run_cell

METRIC = '''"""Requests finished in the window."""


def read(run):
    return float(run["result"]["completed"])
'''


def test_new_files_are_found_without_edits(tmp_path):
    root = make_checkout(tmp_path)
    bench = root / "perfbench"
    mix = json.loads((bench / "traffic" / "tiny-chat.json").read_text())
    mix["prompt_len"]["median"] = 8
    (bench / "traffic" / "tiny-short.json").write_text(json.dumps(mix))
    wl = json.loads((bench / "workloads" / "tiny-chat.json").read_text())
    wl["traffic"] = "tiny-short"
    (bench / "workloads" / "tiny-short-chat.json").write_text(json.dumps(wl))
    (bench / "metrics" / "completed_requests.py").write_text(METRIC)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny-short-chat",
                              "config": wl["config"],
                              "traffic": "tiny-short", "chips": 1,
                              "why": "short prompts"})
    spec["end_to_end"][0]["workloads"].append("tiny-short-chat")
    spec["per_layer"].append({"name": "completed_requests", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "engine host", "moves": "ttft_p90_ms",
                              "workloads": ["tiny-short-chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    rc, line, err = run_cell(root, "tiny-short-chat", trace=1)
    assert rc == 0, err[-3000:]
    assert line["metrics"]["completed_requests"]["value"] > 0
    assert line["metrics"]["completed_requests"]["unit"] == "1"
    assert "window_s" in line["device"] and "breakdown" in line


DRIVER = '''"""Waits out the window and checks nothing but that it waited."""

import time

CHIPS = (1,)


def run(cell, seed, seconds, devs, t_start, tracer=None, hooks=None):
    setup_s = time.perf_counter() - t_start
    t0 = time.perf_counter()
    time.sleep(seconds)
    waited = time.perf_counter() - t0
    return {"setup_s": setup_s, "attempted": 1, "failed": 0,
            "memory_peak_bytes": 0, "correct": waited >= seconds,
            "compared": {"waited_s": (waited, seconds)}}
'''


def test_a_new_driver_is_found_by_name(tmp_path):
    root = make_checkout(tmp_path)
    bench = root / "perfbench"
    (bench / "lib" / "idle.py").write_text(DRIVER)
    wl = json.loads((bench / "workloads" / "tiny-chat.json").read_text())
    wl["driver"] = "idle"
    (bench / "workloads" / "tiny-idle.json").write_text(json.dumps(wl))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny-idle", "config": wl["config"],
                              "traffic": wl["traffic"], "chips": 1,
                              "why": "waits"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    rc, line, err = run_cell(root, "tiny-idle", seconds=0.5)
    assert rc == 0, err[-3000:]
    assert line["correct"] is True
    assert set(line["metrics"]) == {"setup_s"}
    assert line["compared"]["waited_s"]["limit"] == 0.5


@pytest.mark.parametrize("cell", ["tiny-chat", "tiny-train"])
def test_a_cell_on_more_chips_than_its_driver_places_fails(tmp_path, cell):
    root = make_checkout(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        w["chips"] = 4
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    rc, line, err = run_cell(root, cell)
    assert rc == 2 and line is None
    assert "places its work" in err
