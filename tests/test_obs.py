"""Observability subsystem: Prometheus metrics exposition, span tracer
with XLA compile attribution, flight-recorder passivity (zero added
compiles, byte-identical telemetry), per-peer verdict explains, and the
stdlib telemetry daemon's HTTP/SSE endpoints."""
import json
import pathlib
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import tiny_config
from repro.obs import (Counter, FlightRecorder, Gauge, Histogram,
                       MetricsRegistry, ObsService, SpanTracer)
from repro.sim import SimEngine, get_scenario
from repro.sim.telemetry import Telemetry, coerce_native

CFG = tiny_config()
ROUNDS = 2


# ------------------------------------------------------------- metrics

def test_counter_inc_and_labels():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "requests")
    c.inc()
    c.inc(2, method="get")
    c.inc(1, method="get")
    assert c.value() == 1.0
    assert c.value(method="get") == 3.0
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_set_and_inc():
    g = MetricsRegistry().gauge("temp", "temperature")
    assert isinstance(g, Gauge)
    g.set(3.5, room="a")
    g.inc(0.5, room="a")
    assert g.value(room="a") == 4.0


def test_histogram_cumulative_buckets():
    h = MetricsRegistry().histogram("lat_ms", "latency",
                                    buckets=(1.0, 10.0))
    assert isinstance(h, Histogram)
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count() == 3
    text = h.render()
    assert 'lat_ms_bucket{le="1"} 1' in text
    assert 'lat_ms_bucket{le="10"} 2' in text
    assert 'lat_ms_bucket{le="+Inf"} 3' in text
    assert "lat_ms_sum 55.5" in text
    assert "lat_ms_count 3" in text


def test_registry_render_exposition_format():
    reg = MetricsRegistry()
    reg.counter("a_total", "first").inc(2)
    reg.gauge("b_now", "second").set(1.5, peer='uid "x"\nodd\\')
    text = reg.render()
    assert text.endswith("\n")
    assert "# HELP a_total first" in text
    assert "# TYPE a_total counter" in text
    assert "# TYPE b_now gauge" in text
    # label escaping: backslash, quote, newline
    assert r'b_now{peer="uid \"x\"\nodd\\"} 1.5' in text
    # metrics render sorted by name
    assert text.index("a_total") < text.index("b_now")


def test_registry_idempotent_and_kind_mismatch():
    reg = MetricsRegistry()
    c1 = reg.counter("x_total", "x")
    c2 = reg.counter("x_total", "x")
    assert c1 is c2
    with pytest.raises(ValueError):
        reg.gauge("x_total", "x")


# -------------------------------------------------------------- tracer

def test_disabled_tracer_is_noop():
    tr = SpanTracer(enabled=False)
    span = tr.begin("work")
    assert span is None
    tr.end(span)                       # must not raise
    with tr.span("ctx"):
        pass
    tr.instant("evt")
    assert not [e for e in tr.to_chrome()["traceEvents"]
                if e.get("ph") == "X"]


def test_tracer_chrome_export(tmp_path):
    tr = SpanTracer()
    with tr.span("round", cat="round", tid="val-0", round=3):
        with tr.span("stage", cat="stage", tid="val-0"):
            pass
    tr.instant("join", uid="peer-1")
    tr.counter("peers", {"active": 4})
    out = tmp_path / "trace.json"
    tr.to_chrome_json(str(out))
    trace = json.loads(out.read_text())
    events = trace["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    assert {e["name"] for e in spans} == {"round", "stage"}
    assert all(e["dur"] >= 0 and "ts" in e for e in spans)
    assert [e for e in events if e.get("ph") == "i"]
    assert [e for e in events if e.get("ph") == "C"]
    # Perfetto needs integer tids + thread_name metadata
    names = [e for e in events if e.get("ph") == "M"
             and e.get("name") == "thread_name"]
    assert {m["args"]["name"] for m in names} >= {"val-0"}
    assert all(isinstance(e["tid"], int) for e in spans)


def test_tracer_attributes_backend_compile():
    tr = SpanTracer()
    with tr.span("compile_here", cat="stage"):
        # a fresh program shape forces one backend_compile inside the span
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(173)).block_until_ready()
    assert tr.xla_compile_s > 0
    assert tr.xla_compile_events >= 1
    span = [e for e in tr.to_chrome()["traceEvents"]
            if e.get("ph") == "X"][0]
    assert span["args"]["xla_compiles"] >= 1


def test_tracer_drops_beyond_max_events():
    tr = SpanTracer(max_events=2)
    for i in range(5):
        tr.instant(f"e{i}")
    trace = tr.to_chrome()
    assert trace["otherData"]["dropped_events"] == 3


# ----------------------------------------------- telemetry determinism

def _jnp_telemetry():
    t = Telemetry("t", seed=0)
    t.record_round(round=0, honest_share=jnp.float32(0.625),
                   mu={"p": np.float64(1.5)}, arr=np.arange(3),
                   count=np.int64(7))
    return t


def test_jnp_scalars_coerced_at_record_time():
    t = _jnp_telemetry()
    rec = t.rounds[0]
    assert type(rec["honest_share"]) is float
    assert type(rec["mu"]["p"]) is float
    assert rec["arr"] == [0, 1, 2] and type(rec["count"]) is int


def test_jnp_scalar_export_byte_identical_across_runs():
    a = json.dumps(_jnp_telemetry().to_dict(), sort_keys=True)
    b = json.dumps(_jnp_telemetry().to_dict(), sort_keys=True)
    assert a == b
    assert _jnp_telemetry().to_json() == _jnp_telemetry().to_json()


def test_coerce_native_passthrough():
    assert coerce_native({"s": "x", "b": b"y", "n": None, "i": 3}) == \
        {"s": "x", "b": b"y", "n": None, "i": 3}


def test_stage_ms_diverted_to_perf_side_channel():
    t = Telemetry("t", seed=0)
    t.record_round(round=0, honest_share=1.0,
                   stage_ms={"val-0": {"fast_filter": 1.5}})
    assert "stage_ms" not in t.rounds[0]
    assert t.perf == [{"stage_ms": {"val-0": {"fast_filter": 1.5}},
                       "round": 0}]
    assert "perf" not in t.to_dict()
    assert t.to_dict(include_perf=True)["perf"] == t.perf
    # wall-clock noise must not perturb the deterministic export
    u = Telemetry("t", seed=0)
    u.record_round(round=0, honest_share=1.0,
                   stage_ms={"val-0": {"fast_filter": 99.9}})
    assert u.to_json() == t.to_json()


# ------------------------------------------- engine + recorder + daemon

@pytest.fixture(scope="module")
def runs():
    """One scenario twice: obs-off reference, then obs-on + recorder."""
    ref = SimEngine.from_scenario(
        get_scenario("byzantine_wave", rounds=ROUNDS, seed=7),
        CFG, batch=2, seq_len=32)
    ref_tel = ref.run()
    recorder = FlightRecorder(trace=True)
    obs = SimEngine.from_scenario(
        get_scenario("byzantine_wave", rounds=ROUNDS, seed=7),
        CFG, batch=2, seq_len=32, obs=recorder)
    obs_tel = obs.run()
    return {"ref": ref, "ref_tel": ref_tel, "obs": obs,
            "obs_tel": obs_tel, "recorder": recorder}


def test_obs_is_passive(runs):
    # the acceptance invariant: observability adds ZERO compiles and the
    # seeded telemetry export stays byte-identical
    assert runs["obs_tel"].to_json() == runs["ref_tel"].to_json()
    ref_traces = {uid: dict(v.trace_counts)
                  for uid, v in runs["ref"].validators.items()}
    obs_traces = {uid: dict(v.trace_counts)
                  for uid, v in runs["obs"].validators.items()}
    assert obs_traces == ref_traces


def test_stage_ms_recorded_with_and_without_obs(runs):
    for tel in (runs["ref_tel"], runs["obs_tel"]):
        assert len(tel.perf) == ROUNDS
        for entry in tel.perf:
            for per_stage in entry["stage_ms"].values():
                assert per_stage and all(ms >= 0
                                         for ms in per_stage.values())
                assert "aggregate" in per_stage


def test_round_feed_and_metrics(runs):
    rec = runs["recorder"]
    seq, fresh = rec.wait_rounds(0, timeout=0.0)
    assert seq == ROUNDS and len(fresh) == ROUNDS
    assert len(rec.recent_rounds()) == ROUNDS
    text = rec.metrics.render()
    for name in ("gauntlet_rounds_total", "gauntlet_stage_ms_bucket",
                 "sim_honest_share", "gauntlet_compiled_calls_total"):
        assert name in text, name
    rounds_total = sum(
        rec.metrics.counter("gauntlet_rounds_total").value(validator=uid)
        for uid in runs["obs"].validators)
    assert rounds_total == ROUNDS * len(runs["obs"].validators)


def test_explain_records(runs):
    rec = runs["recorder"]
    first = rec.explain(round_idx=0)
    assert first, "no explain records for round 0"
    for r in first:
        assert r["round"] == 0 and r["uid"] and r["why"]
    flagged = [r for r in rec.explain() if r.get("audit_flag")]
    for r in flagged:
        assert "audit" in r["why"].lower()
    uid = first[0]["uid"]
    assert all(r["uid"] == uid for r in rec.explain(uid=uid))


def test_round_spans_in_trace(runs):
    events = runs["recorder"].tracer.to_chrome()["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    cats = {e["cat"] for e in spans}
    assert {"round", "stage", "dispatch"} <= cats
    n_validators = len(runs["obs"].validators)
    rounds = [e for e in spans if e["cat"] == "round"]
    assert len(rounds) == ROUNDS * n_validators


def _get(url, timeout=10.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read()


def test_daemon_endpoints(runs):
    service = ObsService(runs["recorder"], port=0).start()
    try:
        assert _get(service.url("/healthz")) == b"ok\n"
        text = _get(service.url("/metrics")).decode()
        assert "# TYPE gauntlet_rounds_total counter" in text
        topo = json.loads(_get(service.url("/v1/system/topology")))
        assert topo["peers"] and topo["validators"]
        json.dumps(topo)               # JSON-clean: no inf/nan leaked
        rounds = json.loads(_get(service.url("/v1/rounds")))
        assert len(rounds) == ROUNDS
        explains = json.loads(_get(service.url("/v1/explain?round=0")))
        assert explains and all("why" in r for r in explains)
        with pytest.raises(urllib.error.HTTPError):
            _get(service.url("/nope"))
    finally:
        service.stop()


def test_daemon_sse_replays_backlog(runs):
    service = ObsService(runs["recorder"], port=0).start()
    try:
        resp = urllib.request.urlopen(
            service.url("/v1/rounds/stream"), timeout=10)
        records = []
        while len(records) < ROUNDS:
            line = resp.readline()
            assert line, "SSE stream closed before replaying backlog"
            if line.startswith(b"data: "):
                records.append(json.loads(line[6:]))
        assert [r["round"] for r in records] == list(range(ROUNDS))
    finally:
        service.stop()


# ------------------------------------------------------ profiler clock

ENTRY_POINTS = ("primary", "baselines", "sync_scores", "fingerprint",
                "sketch")


def test_entry_points_compile_to_distinct_modules(runs):
    for v in runs["obs"].validators.values():
        names = [getattr(v, "_" + e).__name__ for e in ENTRY_POINTS]
        assert names == [f"validator_{e}" for e in ENTRY_POINTS]
        assert set(v.trace_counts) <= set(ENTRY_POINTS)


@pytest.fixture(scope="module")
def profiled_round(tmp_path_factory):
    """One traced round under a ``jax.profiler`` trace, read back."""
    from jax.profiler import ProfileData
    log_dir = str(tmp_path_factory.mktemp("profile"))
    engine = SimEngine.from_scenario(
        get_scenario("byzantine_wave", rounds=1, seed=7), CFG, batch=2,
        seq_len=32, obs=FlightRecorder(trace=True))
    jax.profiler.start_trace(log_dir)
    try:
        engine.run()
    finally:
        jax.profiler.stop_trace()
    path = next(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    return [e.name for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_stage_spans_on_profiler_clock(profiled_round):
    names = set(profiled_round)
    for stage in ("fast_filter", "primary_eval", "aggregate"):
        assert f"gauntlet.stage.{stage}" in names
    assert "gauntlet.round.round-0" in names
    # each jitted entry point dispatched under its own name
    assert "PjitFunction(validator_primary)" in names
    assert "PjitFunction(validator_sync_scores)" in names
