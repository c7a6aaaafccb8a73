"""The program's own host spans and the readers of them.

On the CPU: one tick of a tiny engine, a garbage collection and a packed
batch under JAX's profiler, reduced as a traced run's trace is.  On the
chip: ``data/serve_spans.xplane.pb``, four ticks of the serving engine at
the 4-layer configuration (16 slots, chunk 16) recorded on one TPU v5e by
``record_serve_spans.py``; and ``data/serve_ticks.xplane.pb``, four ticks
of a program without these spans, on which the new readers read nothing.
"""

import gc
import glob
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
sys.path[:0] = [str(BENCH), str(Path(__file__).resolve().parent)]

from chipbench_tiny import make_checkout, run_cell  # noqa: E402
from lib import cell, readers  # noqa: E402
from lib.profile import breakdown, reduce_file  # noqa: E402

TICK = ("engine.tick", "engine.schedule", "engine.expire", "engine.admit",
        "engine.step", "engine.wait", "engine.guard", "engine.sample")


def read(metric, trace):
    return cell.reader(metric)({"trace": trace})


def named(trace, name):
    return sorted((s, e) for s, e, n in trace["spans"] if n == name)


def inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """One tick of a fresh engine (so its step traces and compiles inside
    ``engine.step``) that admits two requests and completes one, then
    ``gc.collect()`` and ``pack_documents``, all under the profiler."""
    import jax

    from repro.configs import smoke_config
    from repro.data.packing import pack_documents
    from repro.models.factory import build
    from repro.obs import trace as obs_trace
    from repro.serving import StreamingEngine

    api = build(smoke_config("phi3-mini-3.8b", n_layers=2, d_model=64,
                             d_ff=128, vocab=64))
    params = api.init(jax.random.PRNGKey(0))
    logdir = tmp_path_factory.mktemp("trace")
    prev = obs_trace.set_enabled(True)
    try:
        eng = StreamingEngine(api, params, n_slots=2, chunk=4)
        eng.submit(np.arange(1, 4), 1)            # done on its first token
        eng.submit(np.arange(1, 9), 4)
        jax.profiler.start_trace(str(logdir))
        eng.step()
        gc.collect()
        pack_documents([np.arange(1, 6), np.arange(1, 4)], 8)
        jax.profiler.stop_trace()
    finally:
        obs_trace.set_enabled(prev)
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    return reduce_file(path)


@pytest.fixture(scope="module")
def chip_trace():
    return reduce_file(str(DATA / "serve_spans.xplane.pb"))


def test_one_tick_nests_every_engine_span(cpu_trace):
    spans = {n: named(cpu_trace, n) for n in TICK + ("engine.reset",)}
    assert all(len(v) == 1 for v in spans.values()), spans
    one = {n: v[0] for n, v in spans.items()}
    assert inside(one["engine.expire"], one["engine.schedule"])
    assert inside(one["engine.admit"], one["engine.schedule"])
    assert one["engine.expire"][1] <= one["engine.admit"][0]
    # the tick's phases in order, none inside another
    order = ["engine.schedule", "engine.step", "engine.wait", "engine.guard",
             "engine.sample", "engine.reset"]
    for a, b in zip(order, order[1:]):
        assert one[a][1] <= one[b][0], (a, b)
    assert all(inside(one[n], one["engine.tick"]) for n in order)


def test_a_first_tick_traces_and_compiles_inside_engine_step(cpu_trace):
    step = named(cpu_trace, "engine.step")[0]
    traced = named(cpu_trace, "jax.trace")
    assert any(inside(t, step) for t in traced)
    assert any(inside(c, step) for c in named(cpu_trace, "jax.compile"))
    assert read("compiles.chat", cpu_trace) == len(traced)


def test_gc_collect_yields_a_host_gc_span(cpu_trace):
    sample = named(cpu_trace, "engine.sample")[0]
    late = [g for g in named(cpu_trace, "host.gc") if g[0] >= sample[1]]
    assert late
    assert read("gc_ms.chat", cpu_trace) >= sum(e - s for s, e in late) * 1e-6


def test_pack_documents_yields_a_data_pack_span(cpu_trace):
    (pack,) = named(cpu_trace, "data.pack")
    assert read("pack_ms.train", cpu_trace) == pytest.approx(
        (pack[1] - pack[0]) * 1e-6)


def test_recorded_ticks_hold_every_engine_span(chip_trace):
    assert list(chip_trace["devices"]) == ["/device:TPU:0"]
    for name in TICK:
        assert len(named(chip_trace, name)) == 4, name
    for sched in named(chip_trace, "engine.schedule"):
        assert sum(inside(a, sched)
                   for a in named(chip_trace, "engine.admit")) == 1
    # completions reset slots after sampling; the forced collection shows
    assert named(chip_trace, "engine.reset")
    assert named(chip_trace, "host.gc")


def test_recorded_idle_gaps_each_have_an_owner(chip_trace):
    gaps = breakdown(chip_trace)["idle_gaps"]
    assert gaps
    for label, seconds in gaps:
        assert seconds < 1e-3 or not label.startswith(
            ("after ", "no host span")), (label, seconds)


def test_readers_on_the_recorded_ticks(chip_trace):
    guard = read("guard_ms.chat", chip_trace)
    tick = readers.device_ms_per_span({"trace": chip_trace}, "engine.step")
    assert 0 < guard < tick
    assert read("compiles.chat", chip_trace) == 0
    assert read("gc_ms.chat", chip_trace) > 0
    assert read("pack_ms.train", chip_trace) is None


def test_a_program_without_the_spans_reads_nothing():
    old = reduce_file(str(DATA / "serve_ticks.xplane.pb"))
    for metric in ("guard_ms.chat", "gc_ms.chat", "compiles.chat",
                   "pack_ms.train"):
        assert read(metric, old) is None, metric


@pytest.mark.parametrize("cell_name, metrics", [
    ("tiny-chat", {"guard_ms.chat", "gc_ms.chat", "compiles.chat"}),
    ("tiny-train", {"pack_ms.train"}),
])
def test_traced_runs_report_the_span_metrics(tmp_path, cell_name, metrics):
    root = make_checkout(tmp_path)
    rc, line, err = run_cell(root, cell_name, trace=1)
    assert rc == 0, err[-3000:]
    assert metrics <= set(line["metrics"]), json.dumps(line["metrics"])
    if cell_name == "tiny-chat":
        assert line["metrics"]["compiles.chat"]["value"] == 0
