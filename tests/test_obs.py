"""Observability layer (repro.obs): registry, event log, trace gate,
exporters — plus the train-loop and serving-engine instrumentation riding
on them (DESIGN.md §Observability).

The smoke tests here are the acceptance criteria of the subsystem: one
training run and one serving run, each leaving behind a schema-valid JSONL
event log and a metrics snapshot with the named instruments.
"""

import gc
import json
import threading
import urllib.request

import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.data.synthetic import CopyTaskIterator, SyntheticLMIterator
from repro.models.factory import build
from repro.obs import events as obs_events
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.events import (
    EventLog,
    read_events,
    run_metadata,
    use_events,
    validate_event,
    validate_events,
)
from repro.obs.export import (
    prometheus_text,
    serve_metrics,
    snapshot_document,
    write_snapshot,
)
from repro.obs.metrics import (
    Counter,
    Histogram,
    MetricsRegistry,
    use_metrics,
)
from repro.serving import EngineOverloaded, StreamingEngine
from repro.train.guard import GUARD_METRIC_KEYS, GuardConfig
from repro.train.loop import LoopConfig, run_train_loop
from repro.train.optim import make_optimizer, warmup_cosine
from repro.train.state import init_train_state, make_train_step


@pytest.fixture(scope="module")
def model():
    cfg = smoke_config("phi3-mini-3.8b", n_layers=2, d_model=64, d_ff=128,
                       vocab=64)
    api = build(cfg)
    return api, api.init(jax.random.PRNGKey(0))


def _train_setup(api, guard=None):
    opt = make_optimizer("adamw", warmup_cosine(1e-3, 5, 40))
    state = init_train_state(api.init(jax.random.PRNGKey(0)), opt,
                             guard=guard)
    step = jax.jit(make_train_step(api.loss, opt, guard=guard))
    return state, step


def _data():
    return CopyTaskIterator(vocab=64, seq_len=17, batch=8)


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2.5)
    reg.gauge("g").set(1.0)
    reg.gauge("g").set(-3.5)
    h = reg.histogram("h", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.5, 10.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["counters"]["c"]["value"] == 3.5
    assert snap["gauges"]["g"]["value"] == -3.5
    assert snap["histograms"]["h"]["counts"] == [1, 2, 1]  # + Inf overflow
    assert snap["histograms"]["h"]["count"] == 4
    np.testing.assert_allclose(snap["histograms"]["h"]["sum"], 11.05)
    # snapshot is plain data — must round-trip through JSON untouched
    assert json.loads(json.dumps(snap)) == snap


def test_registry_get_or_create_and_kind_conflicts():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x")
    reg.histogram("h", buckets=(1.0, 2.0))
    with pytest.raises(ValueError, match="buckets"):
        reg.histogram("h", buckets=(1.0, 3.0))
    with pytest.raises(ValueError, match="negative"):
        reg.counter("x").inc(-1)


def test_histogram_quantile():
    h = Histogram("q", buckets=(0.01, 0.1, 1.0))
    assert np.isnan(h.quantile(0.5))
    for _ in range(99):
        h.observe(0.05)
    h.observe(50.0)
    assert h.quantile(0.5) == 0.1      # bucket upper bound
    assert h.quantile(1.0) == 1.0      # +Inf bucket reports last bound


def test_helpers_noop_without_registry():
    assert obs_metrics.current() is None
    # must not raise, must not create anything
    obs_metrics.inc("nope")
    obs_metrics.set_gauge("nope", 1.0)
    obs_metrics.observe("nope", 1.0)
    assert obs_metrics.current() is None


def test_use_metrics_scopes_and_restores():
    assert obs_metrics.current() is None
    with use_metrics(MetricsRegistry()) as reg:
        obs_metrics.inc("scoped_total")
        assert reg.snapshot()["counters"]["scoped_total"]["value"] == 1
    assert obs_metrics.current() is None


def test_registry_thread_safety():
    """Engine submit threads race the step loop: 8 threads x 1000 incs and
    observes must lose nothing."""
    reg = MetricsRegistry()

    def work():
        for _ in range(1000):
            reg.counter("t_total").inc()
            reg.histogram("t_h", buckets=(0.5,)).observe(0.1)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = reg.snapshot()
    assert snap["counters"]["t_total"]["value"] == 8000
    assert snap["histograms"]["t_h"]["count"] == 8000


# ---------------------------------------------------------------------------
# Event log
# ---------------------------------------------------------------------------


def test_event_log_envelope_and_validation():
    log = EventLog(path=None)
    log.emit("thing", a=1, b="two")
    validate_events(log.records)
    assert log.records[0]["kind"] == "run_meta"
    rec = log.records[1]
    assert rec["kind"] == "thing"
    assert rec["data"] == {"a": 1, "b": "two"}
    assert rec["seq"] == 1 and rec["t_s"] >= 0


def test_event_log_file_roundtrip(tmp_path):
    p = str(tmp_path / "sub" / "events.jsonl")   # dir is created
    log = EventLog(p)
    log.emit("alpha", x=1)
    log.emit("beta")
    log.close()
    recs = read_events(p)
    validate_events(recs)
    assert [r["kind"] for r in recs] == ["run_meta", "alpha", "beta"]
    assert recs[0]["data"]["git_sha"] != ""
    with pytest.raises(ValueError, match="closed"):
        log.emit("late")


def test_validate_rejects_malformed():
    log = EventLog(path=None)
    log.emit("e")
    good = log.records[1]
    with pytest.raises(ValueError, match="missing envelope"):
        validate_event({k: v for k, v in good.items() if k != "seq"})
    with pytest.raises(ValueError, match="schema"):
        validate_event({**good, "schema": 999})
    bad_order = [log.records[0], good, good]     # seq not increasing
    with pytest.raises(ValueError, match="seq not increasing"):
        validate_events(bad_order)
    with pytest.raises(ValueError, match="run_meta"):
        validate_events([good])
    with pytest.raises(ValueError, match="empty"):
        validate_events([])


def test_ambient_emit_noop_and_scoped():
    assert obs_events.current() is None
    assert obs_events.emit("dropped") is None
    with use_events(EventLog(path=None)) as log:
        obs_events.emit("kept", n=1)
    assert obs_events.current() is None
    assert [r["kind"] for r in log.records] == ["run_meta", "kept"]


def test_run_metadata_provenance():
    meta = run_metadata({"extra_key": "v"})
    for k in ("git_sha", "jax_version", "backend", "device_count",
              "kernel_mode", "utc"):
        assert k in meta, k
    assert meta["extra_key"] == "v"
    assert meta["device_count"] == len(jax.devices())


# ---------------------------------------------------------------------------
# Trace gate
# ---------------------------------------------------------------------------


def test_span_off_is_shared_null():
    prev = obs_trace.set_enabled(False)
    try:
        assert obs_trace.span("a") is obs_trace.span("b")  # no allocation
        with obs_trace.span("a"):
            pass
    finally:
        obs_trace.set_enabled(prev)


def test_span_on_wraps_named_scope():
    prev = obs_trace.set_enabled(True)
    try:
        s1, s2 = obs_trace.span("x"), obs_trace.span("x")
        assert s1 is not s2
        with s1:            # enters named_scope + TraceAnnotation
            y = jax.numpy.ones((2,)) * 2
        assert float(y.sum()) == 4.0
    finally:
        obs_trace.set_enabled(prev)


def test_tracing_off_costs_no_span_no_gc_hook_and_no_event(model, rng):
    """Off is free: every span of a tick is the shared null object, no GC
    callback is installed, and an engine with no sink emits nothing."""
    prev = obs_trace.set_enabled(False)
    try:
        for name in ("engine.schedule", "engine.expire", "engine.admit",
                     "engine.step", "engine.wait", "engine.guard",
                     "engine.reset", "engine.sample", "data.pack"):
            assert obs_trace.span(name) is obs_trace._NULL
        assert obs_trace._gc_span not in gc.callbacks
        api, params = model
        idle = EventLog(path=None)               # never installed
        assert obs_events.current() is None
        eng = StreamingEngine(api, params, n_slots=2)
        eng.submit(jax.random.randint(rng, (5,), 0, 64), 2)
        eng.run()
        assert [r["kind"] for r in idle.records] == ["run_meta"]
    finally:
        obs_trace.set_enabled(prev)


def test_gc_span_hook_follows_the_trace_gate():
    prev = obs_trace.set_enabled(True)
    try:
        assert gc.callbacks.count(obs_trace._gc_span) == 1
        obs_trace.set_enabled(True)              # idempotent
        assert gc.callbacks.count(obs_trace._gc_span) == 1
        gc.collect()                             # opens and closes its span
        assert getattr(obs_trace._OPEN, "stack", []) == []
    finally:
        obs_trace.set_enabled(False)
        assert obs_trace._gc_span not in gc.callbacks
        obs_trace.set_enabled(prev)


def test_jax_traces_total_rises_once_then_stays_flat(model, rng):
    """A recompile in steady serving is a bug: the first tick traces the
    engine's step, ten more ticks of the same shapes trace nothing."""
    api, params = model
    reg = MetricsRegistry()
    with use_metrics(reg):
        eng = StreamingEngine(api, params, n_slots=2, chunk=4)
        for i in range(2):       # prefilled and sampled on the first tick
            eng.submit(jax.random.randint(jax.random.fold_in(rng, i), (4,),
                                          0, 64), 20)
        eng.step()
        traces = reg.peek("jax_traces_total")
        compiles = reg.peek("jax_backend_compiles_total")
        assert traces >= 1 and compiles >= 1
        for _ in range(10):
            eng.step()
        assert not eng.finished                 # no new shape (no reset)
        assert reg.peek("jax_traces_total") == traces
        assert reg.peek("jax_backend_compiles_total") == compiles
    jax.jit(lambda x: x + 1)(1.0)               # no registry: not counted
    assert reg.peek("jax_traces_total") == traces


def test_request_admitted_carries_rid_slot_and_queue_wait(model, rng):
    api, params = model
    reg = MetricsRegistry()
    log = EventLog(path=None)
    with use_metrics(reg), use_events(log):
        eng = StreamingEngine(api, params, n_slots=1)
        rids = [eng.submit(jax.random.randint(rng, (3,), 0, 64), 2)
                for _ in range(3)]
        eng.run()
    admitted = [r["data"] for r in log.records
                if r["kind"] == "request_admitted"]
    assert [a["rid"] for a in admitted] == rids
    assert all(a["slot"] == 0 and a["queue_wait_s"] >= 0 for a in admitted)
    # the third request waited for both before it
    assert admitted[2]["queue_wait_s"] >= admitted[1]["queue_wait_s"]
    hist = reg.snapshot()["histograms"]["serve_queue_wait_s"]
    assert hist["count"] == 3
    assert hist["sum"] == pytest.approx(
        sum(a["queue_wait_s"] for a in admitted))


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------


def _sample_registry():
    reg = MetricsRegistry()
    reg.counter("serve_shed_total").inc(3)
    reg.gauge("serve_queue_depth").set(2)
    h = reg.histogram("serve_ttft_s", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    return reg


def test_prometheus_text_exposition():
    text = prometheus_text(_sample_registry().snapshot())
    assert "# TYPE serve_shed_total counter\nserve_shed_total 3" in text
    assert "# TYPE serve_queue_depth gauge\nserve_queue_depth 2" in text
    # buckets are cumulative in the text form
    assert 'serve_ttft_s_bucket{le="0.1"} 1' in text
    assert 'serve_ttft_s_bucket{le="1"} 2' in text
    assert 'serve_ttft_s_bucket{le="+Inf"} 3' in text
    assert "serve_ttft_s_count 3" in text
    assert prometheus_text({}).strip() == ""    # empty snapshot still valid


def test_snapshot_document_and_write(tmp_path):
    doc = snapshot_document(_sample_registry())
    assert doc["schema"] == 1
    assert "git_sha" in doc["meta"]
    assert doc["metrics"]["counters"]["serve_shed_total"]["value"] == 3
    # ambient-less document is valid + empty
    empty = snapshot_document()
    assert empty["metrics"] == {"counters": {}, "gauges": {},
                                "histograms": {}}
    p = str(tmp_path / "m.json")
    write_snapshot(p, _sample_registry())
    assert json.load(open(p))["metrics"]["gauges"][
        "serve_queue_depth"]["value"] == 2


def test_serve_metrics_http_endpoints():
    reg = _sample_registry()
    server = serve_metrics(reg, port=0)
    try:
        host, port = server.server_address[:2]
        base = f"http://{host}:{port}"
        text = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert "serve_shed_total 3" in text
        doc = json.loads(
            urllib.request.urlopen(f"{base}/metrics.json").read())
        assert doc["metrics"]["counters"]["serve_shed_total"]["value"] == 3
        reg.counter("serve_shed_total").inc()     # live, not a snapshot
        text = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert "serve_shed_total 4" in text
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope")
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# Train-loop instrumentation (acceptance smoke: training)
# ---------------------------------------------------------------------------


def test_train_loop_smoke_events_and_metrics(model, tmp_path):
    """One guarded training run with obs on: schema-valid JSONL event log +
    snapshot carrying every named train instrument."""
    api, _ = model
    state, step = _train_setup(api, guard=GuardConfig())
    events_path = str(tmp_path / "events.jsonl")
    metrics_path = str(tmp_path / "metrics.json")
    res = run_train_loop(
        step, state, _data(),
        LoopConfig(total_steps=6, log_every=2, guard=True,
                   events=events_path, metrics_out=metrics_path,
                   install_signal_handlers=False))
    assert int(res.state.step) == 6
    # loop cleaned up its own ambient installs
    assert obs_events.current() is None
    assert obs_metrics.current() is None

    recs = read_events(events_path)
    validate_events(recs)
    kinds = [r["kind"] for r in recs]
    assert kinds[0] == "run_meta"
    assert kinds.count("train_step") == 3        # steps 0, 2, 4
    assert kinds[-1] == "run_end"
    end = recs[-1]["data"]
    assert end["step"] == 6 and end["preempted"] is False

    snap = json.load(open(metrics_path))
    assert snap["schema"] == 1 and "git_sha" in snap["meta"]
    m = snap["metrics"]
    assert m["histograms"]["train_step_time_s"]["count"] == 6
    assert m["counters"]["train_tokens_total"]["value"] == 6 * 8 * 17
    assert m["gauges"]["train_tokens_per_s"]["value"] > 0
    assert m["gauges"]["train_grad_norm"]["value"] > 0
    assert m["gauges"]["train_guard_lr_scale"]["value"] == 1.0


def test_train_step_events_carry_on_log_metrics_verbatim(model):
    """Satellite: the train_step event's data must equal the dict handed to
    on_log — guard metrics included, not renamed, not rounded."""
    api, _ = model
    state, step = _train_setup(api, guard=GuardConfig())
    seen = {}
    log = EventLog(path=None)
    with use_events(log):
        run_train_loop(
            step, state, _data(),
            LoopConfig(total_steps=4, log_every=1, guard=True,
                       install_signal_handlers=False),
            on_log=lambda s, m: seen.setdefault(s, dict(m)))
    by_step = {r["data"]["step"]: r["data"] for r in log.records
               if r["kind"] == "train_step"}
    assert set(by_step) == set(seen)
    for s, m in seen.items():
        assert by_step[s] == {"step": s, **m}
        for k in GUARD_METRIC_KEYS:
            assert k in by_step[s], k


def test_ambient_sink_wins_over_loop_config(model, tmp_path):
    """A launcher-installed sink owns the log: LoopConfig.events must not
    open a second file over it."""
    api, _ = model
    state, step = _train_setup(api)
    unused = tmp_path / "unused.jsonl"
    log = EventLog(path=None)
    with use_events(log):
        run_train_loop(
            step, state, _data(),
            LoopConfig(total_steps=2, events=str(unused),
                       install_signal_handlers=False))
    assert not unused.exists()
    assert any(r["kind"] == "run_end" for r in log.records)


def test_straggler_cold_start_does_not_flag(model):
    """Near-identical early step times (sigma ~ 0) must not flag stragglers
    during warmup — the cold-start edge of the EWMA estimator."""
    api, _ = model
    state, step = _train_setup(api)
    res = run_train_loop(
        step, state, _data(),
        LoopConfig(total_steps=8, straggler_warmup=10,
                   install_signal_handlers=False))
    # 8 steps < warmup 10: nothing may flag, however tight the variance
    assert res.stragglers == []


def test_straggler_still_flags_after_warmup(model):
    """The warmup guard must not kill real detection: a 10s step past the
    warmup window still flags (mirrors test_loop_straggler_detection) and
    emits the straggler event + counter."""
    api, _ = model
    state, step = _train_setup(api)
    reg = MetricsRegistry()
    log = EventLog(path=None)
    with use_metrics(reg), use_events(log):
        res = run_train_loop(
            step, state, _data(),
            LoopConfig(total_steps=30, install_signal_handlers=False),
            _test_hooks={"sleep": {20: 10.0}})
    assert any(s[0] == 20 for s in res.stragglers), res.stragglers
    assert reg.snapshot()["counters"]["train_straggler_total"]["value"] >= 1
    ev = [r for r in log.records if r["kind"] == "straggler"]
    assert any(r["data"]["step"] == 20 for r in ev)


# ---------------------------------------------------------------------------
# Serving-engine instrumentation (acceptance smoke: serving)
# ---------------------------------------------------------------------------


def test_engine_smoke_events_and_metrics(model, rng, tmp_path):
    """One serving run with obs on: TTFT/ITL histograms, token counters,
    occupancy gauge, schema-valid event log, snapshot on disk."""
    api, params = model
    prompts = jax.random.randint(rng, (4, 40), 0, 64)
    reg = MetricsRegistry()
    log = EventLog(path=None)
    with use_metrics(reg), use_events(log):
        eng = StreamingEngine(api, params, n_slots=2, chunk=8)
        rids = [eng.submit(prompts[i], 5) for i in range(4)]
        out = eng.run()
    assert sorted(out) == sorted(rids)

    validate_events(log.records)
    kinds = [r["kind"] for r in log.records]
    assert kinds.count("request_submitted") == 4
    assert kinds.count("first_token") == 4
    assert kinds.count("request_completed") == 4
    done = [r["data"] for r in log.records if r["kind"] == "request_completed"]
    for d in done:
        assert d["n_tokens"] == 5
        assert d["total_s"] >= d["ttft_s"] > 0

    snap = reg.snapshot()
    assert snap["counters"]["serve_requests_total"]["value"] == 4
    assert snap["counters"]["serve_requests_completed_total"]["value"] == 4
    assert snap["histograms"]["serve_ttft_s"]["count"] == 4
    # 4 requests x 5 tokens = 20 emitted; 4 first tokens -> 16 ITL samples
    assert snap["histograms"]["serve_itl_s"]["count"] == 16
    # prompts are 40 tokens each, chunk-grid rounded; decode = 20 - 4 extra
    assert snap["counters"]["serve_prefill_tokens_total"]["value"] == 160
    assert snap["counters"]["serve_decode_tokens_total"]["value"] == 16
    assert 0 < snap["gauges"]["serve_slot_occupancy"]["value"] <= 1.0

    p = str(tmp_path / "serve.json")
    write_snapshot(p, reg)
    assert json.load(open(p))["metrics"]["counters"][
        "serve_requests_total"]["value"] == 4


def test_engine_shed_and_deadline_instruments(model, rng):
    api, params = model
    prompts = jax.random.randint(rng, (4, 4), 0, 64)
    reg = MetricsRegistry()
    log = EventLog(path=None)
    with use_metrics(reg), use_events(log):
        eng = StreamingEngine(api, params, n_slots=1, max_queue=2)
        eng.submit(prompts[0], 2)
        eng.submit(prompts[1], 2, deadline_s=0.0)   # expires before admit
        with pytest.raises(EngineOverloaded):
            eng.submit(prompts[2], 2)
        eng.run()
    snap = reg.snapshot()
    assert snap["counters"]["serve_shed_total"]["value"] == 1
    assert snap["counters"]["serve_deadline_expired_total"]["value"] == 1
    kinds = [r["kind"] for r in log.records]
    assert "request_shed" in kinds
    expired = [r["data"] for r in log.records
               if r["kind"] == "deadline_expired"]
    assert expired and expired[0]["queued"] is True


def test_engine_latency_maps_evicted(model, rng):
    """Satellite: a long-lived engine must not grow per-request latency maps
    without bound — every terminal path (complete, deadline, quarantine)
    evicts."""
    from repro.testing import poison_engine_slot

    api, params = model
    eng = StreamingEngine(api, params, n_slots=2)
    key = rng
    for wave in range(5):                       # 5 waves x 4 requests
        key = jax.random.fold_in(key, wave)
        prompts = jax.random.randint(key, (4, 6), 0, 64)
        for i in range(4):
            eng.submit(prompts[i], 3)
        eng.run()
    assert len(eng.finished) == 20
    assert eng.submitted_at == {}
    assert eng.first_token_at == {}

    # deadline expiry (queued) evicts too
    p = jax.random.randint(key, (2, 4), 0, 64)
    r0 = eng.submit(p[0], 1000, deadline_s=0.0)
    eng.run()
    assert r0 in eng.errors
    assert eng.submitted_at == {} and eng.first_token_at == {}

    # quarantine evicts as well
    r1 = eng.submit(p[1], 6)
    eng.step(), eng.step()
    poison_engine_slot(eng, 0)
    eng.run()
    assert r1 in eng.errors
    assert eng.submitted_at == {} and eng.first_token_at == {}


def test_engine_obs_off_still_serves(model, rng):
    """No registry, no sink: the engine must behave identically (obs calls
    are no-ops, not requirements)."""
    assert obs_metrics.current() is None and obs_events.current() is None
    api, params = model
    prompts = jax.random.randint(rng, (2, 5), 0, 64)
    eng = StreamingEngine(api, params, n_slots=2)
    rids = [eng.submit(prompts[i], 4) for i in range(2)]
    out = eng.run()
    assert sorted(out) == sorted(rids)
    assert eng.submitted_at == {} and eng.first_token_at == {}


# ---------------------------------------------------------------------------
# Loop + registry integration via SyntheticLMIterator (token accounting)
# ---------------------------------------------------------------------------


def test_loop_token_utilization_gauge(model):
    """Packed batches: the token_util the loop logs must land in the gauge."""
    from repro.data.packing import PackedLMIterator

    api, _ = model
    state, step = _train_setup(api)
    it = PackedLMIterator(vocab=64, seq_len=17, batch=8, seed=3)
    reg = MetricsRegistry()
    with use_metrics(reg):
        res = run_train_loop(
            step, state, it,
            LoopConfig(total_steps=3, pack_sequences=True,
                       install_signal_handlers=False))
    util = reg.snapshot()["gauges"]["train_token_util"]["value"]
    assert 0 < util <= 1.0
    # gauge holds the LAST step's utilization; recompute it independently
    ref_it = PackedLMIterator(vocab=64, seq_len=17, batch=8, seed=3)
    batches = [next(ref_it) for _ in range(3)]
    want = float((np.asarray(batches[-1]["segment_ids"]) != 0).mean())
    assert util == pytest.approx(want)
    assert res.history[0][1]["token_util"] == pytest.approx(
        float((np.asarray(batches[0]["segment_ids"]) != 0).mean()))


def test_loop_metrics_out_installs_own_registry(model, tmp_path):
    """metrics_out alone (no ambient registry) still produces a populated
    snapshot — the loop installs and tears down its own."""
    api, _ = model
    state, step = _train_setup(api)
    p = str(tmp_path / "m.json")
    run_train_loop(
        step, state, SyntheticLMIterator(vocab=64, seq_len=16, batch=4),
        LoopConfig(total_steps=2, metrics_out=p,
                   install_signal_handlers=False))
    assert obs_metrics.current() is None
    snap = json.load(open(p))
    assert snap["metrics"]["histograms"]["train_step_time_s"]["count"] == 2
    assert snap["metrics"]["counters"]["train_tokens_total"][
        "value"] == 2 * 4 * 16


# ---------------------------------------------------------------------------
# Labeled series (per-replica metrics)
# ---------------------------------------------------------------------------


def test_series_key_grammar_and_split():
    sk = obs_metrics.series_key
    assert sk("c") == "c"
    assert sk("c", {}) == "c"
    # keys sort, values stringify, quotes/backslashes/newlines escape
    assert sk("c", {"b": 1, "a": "x"}) == 'c{a="x",b="1"}'
    assert sk("c", {"v": 'a"b\\c\nd'}) == 'c{v="a\\"b\\\\c\\nd"}'
    with pytest.raises(ValueError):
        sk('c{a="1"}', {"b": 2})        # labels go in labels=, not the name
    assert obs_metrics.split_series_key("c") == ("c", "")
    assert obs_metrics.split_series_key('c{a="x",b="1"}') == ("c", 'a="x",b="1"')


def test_labeled_series_are_distinct_and_peekable():
    reg = MetricsRegistry()
    reg.counter("req", labels={"replica": 0}).inc(2)
    reg.counter("req", labels={"replica": 1}).inc(5)
    reg.counter("req").inc()                      # unlabeled is its own series
    assert reg.peek("req", {"replica": "0"}) == 2
    assert reg.peek("req", {"replica": 1}) == 5   # int/str label values agree
    assert reg.peek("req") == 1
    assert reg.peek("req", {"replica": 7}) is None
    assert reg.peek("absent") is None
    snap = reg.snapshot()["counters"]
    assert set(snap) == {"req", 'req{replica="0"}', 'req{replica="1"}'}


def test_label_scope_ambient_merge_and_override():
    reg = MetricsRegistry()
    with use_metrics(reg):
        with obs_metrics.label_scope(replica=0):
            obs_metrics.inc("ticks")
            with obs_metrics.label_scope(shard=2):     # nested scopes merge
                obs_metrics.inc("ticks")
            # explicit labels= wins over the ambient scope on key clash
            obs_metrics.inc("ticks", labels={"replica": 9})
            obs_metrics.set_gauge("depth", 3.0)
        obs_metrics.inc("ticks")                       # outside: unlabeled
    assert reg.peek("ticks", {"replica": 0}) == 1
    assert reg.peek("ticks", {"replica": 0, "shard": 2}) == 1
    assert reg.peek("ticks", {"replica": 9}) == 1
    assert reg.peek("ticks") == 1
    assert reg.peek("depth", {"replica": 0}) == 3.0
    assert obs_metrics.current_labels() is None


def test_label_scope_is_thread_local():
    reg = MetricsRegistry()
    seen = []

    def work(i):
        with obs_metrics.label_scope(replica=i):
            obs_metrics.inc("t")
            seen.append(obs_metrics.current_labels()["replica"])

    with use_metrics(reg):
        with obs_metrics.label_scope(replica="main"):
            ts = [threading.Thread(target=work, args=(i,)) for i in range(3)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            assert obs_metrics.current_labels() == {"replica": "main"}
    assert sorted(seen) == ["0", "1", "2"]
    for i in range(3):
        assert reg.peek("t", {"replica": i}) == 1


def test_prometheus_text_labeled_series():
    reg = MetricsRegistry()
    reg.counter("req", labels={"replica": 0}).inc(2)
    reg.counter("req", labels={"replica": 1}).inc(3)
    reg.gauge("occ", labels={"replica": 0}).set(0.5)
    reg.histogram("lat", buckets=(0.1, 1.0), labels={"replica": 1}).observe(0.5)
    text = prometheus_text(reg.snapshot())
    lines = text.splitlines()
    # TYPE emitted once per base name, not once per labeled series
    assert lines.count("# TYPE req counter") == 1
    assert 'req{replica="0"} 2' in lines
    assert 'req{replica="1"} 3' in lines
    assert 'occ{replica="0"} 0.5' in lines
    # histogram merges its le bucket label with the series labels
    assert 'lat_bucket{replica="1",le="1"} 1' in lines
    assert 'lat_bucket{replica="1",le="+Inf"} 1' in lines
    assert 'lat_sum{replica="1"} 0.5' in lines
    assert 'lat_count{replica="1"} 1' in lines
