"""Unified telemetry plane tests (docs/observability.md).

Covers: histogram quantile accuracy on known distributions, the
StageTimer percentile/trace-ring upgrades, the TelemetryHub zero-fill
scrape contract (JSON + Prometheus + ZMQ socket), cross-process span
round-trips through the real wire (tracing fleet, legacy mid-less
producer), the multi-process Perfetto merge (>= 3 pids, consistent
ordering), flight-recorder postmortems (incl. the supervisor death
dump), the replay shard ``telemetry`` RPC, and the doc/vocabulary lock.
"""

import json
import os
import re
import threading
import time
import types

import numpy as np
import pytest

from blendjax import wire
from blendjax.obs.flight import FlightRecorder, flight_recorder
from blendjax.obs.histogram import (
    LatencyHistogram,
    bucket_bounds,
    bucket_index,
)
from blendjax.obs.hub import TelemetryHub, scrape_socket
from blendjax.obs.spans import (
    SpanRecorder,
    export_chrome_trace,
    make_span,
    span_trace,
)
from blendjax.utils.timing import (
    AUTOSCALE_EVENTS,
    AUTOSCALE_STAGES,
    FEED_STAGES,
    FLEET_EVENTS,
    GATEWAY_EVENTS,
    GATEWAY_STAGES,
    HA_EVENTS,
    HA_STAGES,
    PIPE_EVENTS,
    PIPE_STAGES,
    REPLAY_EVENTS,
    REPLAY_STAGES,
    SCENARIO_EVENTS,
    SCENARIO_STAGES,
    SERVE_EVENTS,
    SERVE_STAGES,
    WEIGHT_EVENTS,
    WEIGHT_STAGES,
    EventCounters,
    StageTimer,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# latency histograms
# ---------------------------------------------------------------------------


def _quantile_err(values, hist, q):
    values = sorted(values)
    true = values[min(int(q * len(values)), len(values) - 1)]
    est = hist.quantile(q)
    return abs(est - true) / true


@pytest.mark.parametrize("dist", ["uniform", "exponential", "bimodal"])
def test_histogram_quantiles_within_bucket_error(dist):
    """p50/p90/p99 land within the log-bucket relative error bound
    (bucket width <= 12.5% -> estimate within ~7% + sampling noise) for
    distributions shaped like real stage latencies."""
    rng = np.random.default_rng(42)
    if dist == "uniform":
        values = rng.uniform(1e-4, 1e-1, 20000)
    elif dist == "exponential":
        values = rng.exponential(5e-3, 20000) + 1e-6
    else:  # fast path + slow tail, the shape quarantine storms produce
        values = np.concatenate([
            rng.normal(2e-4, 2e-5, 18000).clip(1e-5),
            rng.normal(5e-2, 5e-3, 2000).clip(1e-3),
        ])
    h = LatencyHistogram()
    for v in values:
        h.add(float(v))
    assert h.n == len(values)
    for q in (0.5, 0.9, 0.99):
        assert _quantile_err(values, h, q) < 0.10, (dist, q)
    # the max is exact, not bucketed
    assert h.max_s == pytest.approx(float(values.max()))
    p = h.percentiles()
    assert p["p50_ms"] <= p["p90_ms"] <= p["p99_ms"] <= p["max_ms"]


def test_histogram_buckets_and_range():
    # sub-microsecond underflow and beyond-range overflow both clamp
    assert bucket_index(0.0) == 0
    assert bucket_index(1e-9) == 0
    lo, hi = bucket_bounds(bucket_index(1e-3))
    assert lo <= 1e-3 < hi
    assert hi / lo <= 1.2  # <= one sub-bucket width apart
    h = LatencyHistogram()
    h.add(5000.0)  # beyond the top octave
    assert h.n == 1 and h.max_s == 5000.0
    assert h.quantile(0.5) > 1000.0  # clamped into the top bucket


def test_histogram_merge_equals_union():
    rng = np.random.default_rng(7)
    a_vals = rng.exponential(1e-3, 5000)
    b_vals = rng.exponential(5e-2, 5000)
    a, b, u = LatencyHistogram(), LatencyHistogram(), LatencyHistogram()
    for v in a_vals:
        a.add(float(v))
        u.add(float(v))
    for v in b_vals:
        b.add(float(v))
        u.add(float(v))
    merged = LatencyHistogram()
    merged.merge(a).merge(b)
    assert merged.n == u.n
    assert merged.counts == u.counts
    assert merged.quantile(0.99) == u.quantile(0.99)
    assert merged.max_s == u.max_s


def test_histogram_dict_round_trip():
    h = LatencyHistogram()
    for v in (1e-5, 2e-4, 3e-3, 0.5):
        h.add(v)
    d = json.loads(json.dumps(h.to_dict()))  # must survive JSON
    r = LatencyHistogram.from_dict(d)
    assert r.counts == h.counts
    assert r.n == h.n and r.max_s == h.max_s
    assert LatencyHistogram.from_dict(None).n == 0


# ---------------------------------------------------------------------------
# StageTimer upgrades
# ---------------------------------------------------------------------------


def test_stagetimer_summary_has_percentiles():
    t = StageTimer()
    for ms in (1, 1, 2, 50):
        t.add("recv", ms / 1e3)
    s = t.summary()["recv"]
    assert s["count"] == 4
    for key in ("p50_ms", "p90_ms", "p99_ms", "max_ms"):
        assert key in s
    assert s["max_ms"] == pytest.approx(50.0, rel=1e-6)
    # upper-rank convention: the median of {1,1,2,50} reports the 3rd
    # smallest event's bucket
    assert 0.8 <= s["p50_ms"] <= 2.2
    assert t.percentiles("never")["p99_ms"] == 0.0


def test_stagetimer_histograms_opt_out():
    t = StageTimer(histograms=False)
    t.add("recv", 0.01)
    assert "p99_ms" not in t.summary()["recv"]
    assert t.percentiles("recv") == {
        "p50_ms": 0.0, "p90_ms": 0.0, "p99_ms": 0.0, "max_ms": 0.0,
    }


def test_stagetimer_add_bulk_lands_at_mean():
    t = StageTimer()
    t.add_bulk("scatter", 1.0, 100)  # 100 intervals of 10 ms mean
    s = t.summary()["scatter"]
    assert s["count"] == 100
    assert 9.0 <= s["p50_ms"] <= 11.0
    t.add_bulk("scatter", 0.0, 0)  # no-op, no div-by-zero


def test_stagetimer_snapshot_copies_histograms():
    t = StageTimer()
    t.add("recv", 0.001)
    snap = t.snapshot()["recv"]
    assert snap["count"] == 1
    snap["hist"].add(100.0)  # mutating the copy...
    assert t.summary()["recv"]["max_ms"] < 1e4  # ...never touches the live one


# ---------------------------------------------------------------------------
# TelemetryHub
# ---------------------------------------------------------------------------


def test_scrape_zero_fill_contract():
    """Every canonical counter AND stage appears (zeroed) in a scrape
    before its first event — the health() dashboard contract, extended
    to the hub surfaces (ISSUE-9 satellite, regression-locked)."""
    hub = TelemetryHub()
    hub.register("fresh", counters=EventCounters(), timer=StageTimer())
    snap = hub.scrape()
    for name in FLEET_EVENTS + REPLAY_EVENTS + SERVE_EVENTS \
            + GATEWAY_EVENTS + WEIGHT_EVENTS + SCENARIO_EVENTS \
            + HA_EVENTS + AUTOSCALE_EVENTS + PIPE_EVENTS:
        assert snap["counters"][name] == 0, name
    for stage in FEED_STAGES + REPLAY_STAGES + SERVE_STAGES \
            + GATEWAY_STAGES + WEIGHT_STAGES + SCENARIO_STAGES \
            + HA_STAGES + AUTOSCALE_STAGES + PIPE_STAGES:
        rec = snap["stages"][stage]
        assert rec["count"] == 0, stage
        assert rec["p99_ms"] == 0.0
    # ... and in the Prometheus rendering, without any event either
    prom = hub.to_prometheus(snap)
    assert 'blendjax_events_total{event="quarantines"} 0' in prom
    assert 'blendjax_events_total{event="serve_cache_hits"} 0' in prom
    assert 'blendjax_events_total{event="weight_adopted"} 0' in prom
    assert 'blendjax_events_total{event="scenario_pushes"} 0' in prom
    assert 'blendjax_events_total{event="ha_ckpt_saves"} 0' in prom
    assert 'blendjax_events_total{event="autoscale_ticks"} 0' in prom
    assert ('blendjax_stage_latency_seconds{stage="weight_swap",'
            'quantile="0.99"} 0') in prom
    assert ('blendjax_stage_latency_seconds{stage="scenario_push",'
            'quantile="0.99"} 0') in prom
    assert ('blendjax_stage_latency_seconds{stage="shard_gather",'
            'quantile="0.99"} 0') in prom
    assert ('blendjax_stage_latency_seconds{stage="queue_wait",'
            'quantile="0.99"} 0') in prom
    assert ('blendjax_stage_latency_seconds{stage="ha_snapshot",'
            'quantile="0.99"} 0') in prom


def test_hub_merges_histograms_across_components():
    """The aggregate p99 must be a quantile of the UNION of intervals,
    not a mean of per-component percentiles: a fast fleet + a slow
    fleet merge into a bimodal distribution whose p99 sits in the slow
    mode."""
    hub = TelemetryHub()
    fast, slow = StageTimer(), StageTimer()
    for _ in range(990):
        fast.add("recv", 1e-4)
    for _ in range(10):
        slow.add("recv", 1e-1)
    hub.register("fleet0", timer=fast)
    hub.register("fleet1", timer=slow)
    rec = hub.scrape()["stages"]["recv"]
    assert rec["count"] == 1000
    assert rec["p50_ms"] < 1.0          # the fast mode
    assert rec["p99_ms"] > 50.0         # the slow mode — NOT the mean
    # counters sum across components
    a, b = EventCounters(), EventCounters()
    a.incr("retries", 2)
    b.incr("retries", 3)
    hub.register("ca", counters=a)
    hub.register("cb", counters=b)
    assert hub.scrape()["counters"]["retries"] == 5


def test_hub_remote_fetch_and_errors():
    remote_timer = StageTimer()
    remote_timer.add("shard_gather", 0.002)

    def fetch():
        return {
            "counters": {"replay_shard_quarantined": 1},
            "stages": {
                name: {
                    "count": rec["count"], "total_s": rec["total_s"],
                    "hist": rec["hist"].to_dict(),
                }
                for name, rec in remote_timer.snapshot().items()
            },
        }

    hub = TelemetryHub()
    hub.register_remote("shard0", fetch)
    hub.register_remote("shard1", lambda: (_ for _ in ()).throw(
        TimeoutError("shard 1 is dead")
    ))
    snap = hub.scrape()
    assert snap["counters"]["replay_shard_quarantined"] == 1
    assert snap["stages"]["shard_gather"]["count"] == 1
    assert snap["stages"]["shard_gather"]["p50_ms"] > 0
    assert "shard 1 is dead" in snap["remote_errors"]["shard1"]
    assert "shard0" in snap["components"]


def test_hub_zmq_scrape_socket():
    hub = TelemetryHub("socktest")
    counters = EventCounters()
    counters.incr("quarantines")
    hub.register("c", counters=counters)
    try:
        addr = hub.serve()
        snap = scrape_socket(addr, "json")
        assert snap["hub"] == "socktest"
        assert snap["counters"]["quarantines"] == 1
        prom = scrape_socket(addr, "prometheus")
        assert 'blendjax_events_total{event="quarantines"} 1' in prom
        # a malformed request still gets a JSON scrape, not a hang
        import zmq

        s = zmq.Context.instance().socket(zmq.REQ)
        s.setsockopt(zmq.LINGER, 0)
        s.connect(addr)
        try:
            s.send(b"\x00garbage")
            assert s.poll(2000, zmq.POLLIN)
            assert json.loads(s.recv())["hub"] == "socktest"
        finally:
            s.close(0)
    finally:
        hub.close()


def test_hub_probe_failure_survives_scrape():
    hub = TelemetryHub()
    hub.register("bad", probe=lambda: 1 / 0)
    snap = hub.scrape()
    assert "ZeroDivisionError" in snap["components"]["bad"]["probe_error"]


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_span_recorder_ring_and_export(tmp_path):
    rec = SpanRecorder(capacity=8)
    for i in range(12):
        rec.record(make_span(f"s{i}", 1000 + i, dur_us=5, trace=f"t{i}"))
    assert len(rec) == 8 and rec.dropped == 4
    path = tmp_path / "t.json"
    n = rec.export_chrome_trace(str(path))
    assert n == 8
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert [e["ts"] for e in events] == sorted(e["ts"] for e in events)
    assert span_trace(events[0]) == "t4"  # oldest survivors kept in order


def test_export_merges_files_and_recorders(tmp_path):
    a = SpanRecorder()
    a.record(make_span("a", 100, dur_us=1, pid=1))
    f1 = tmp_path / "one.json"
    a.export_chrome_trace(str(f1))
    b = SpanRecorder()
    b.record(make_span("b", 50, dur_us=1, pid=2))
    out = tmp_path / "merged.json"
    n = export_chrome_trace(str(out), b, str(f1),
                            [make_span("c", 75, dur_us=1, pid=3)])
    assert n == 3
    events = json.loads(out.read_text())["traceEvents"]
    assert [e["name"] for e in events] == ["b", "c", "a"]  # ts-sorted
    assert {e["pid"] for e in events} == {1, 2, 3}


# ---------------------------------------------------------------------------
# span round-trip through the real wire
# ---------------------------------------------------------------------------

from helpers import BLEND_SCRIPTS, FAKE_BLENDER  # noqa: E402

ENV_SCRIPT = f"{BLEND_SCRIPTS}/env.blend.py"


@pytest.fixture
def fake_blender(monkeypatch):
    monkeypatch.setenv("BLENDJAX_BLENDER", FAKE_BLENDER)


def test_span_round_trip_and_multiprocess_merge(fake_blender, tmp_path):
    """The tentpole acceptance: a tracing pool over a real producer
    fleet (separate processes) yields ONE Perfetto file with consumer-
    and producer-side spans for the same correlation ids across >= 3
    pids, with consistent ordering (each producer span nested inside
    its client span's window)."""
    from blendjax.btt.envpool import launch_env_pool

    with launch_env_pool(
        scene="", script=ENV_SCRIPT, num_instances=2, background=True,
        horizon=1_000_000, timeoutms=30000, start_port=13600,
        pipeline_depth=2, trace=True,
    ) as pool:
        pool.reset()
        for step in range(4):  # both RPC modes leave spans
            if step % 2 == 0:
                pool.step([1.0, 2.0])
            else:
                pool.step_async([3.0, 4.0])
                pool.step_wait_full()
        spans = pool.spans.snapshot()
        path = tmp_path / "merged.json"
        n = pool.spans.export_chrome_trace(str(path))
    assert n == len(spans) > 0
    pids = {s["pid"] for s in spans}
    assert len(pids) >= 3  # consumer + 2 producer processes
    by_trace = {}
    for s in spans:
        t = span_trace(s)
        if t is not None:
            by_trace.setdefault(t, []).append(s)
    paired = 0
    for t, group in by_trace.items():
        client = [s for s in group if s.get("cat") == "envpool"]
        producer = [s for s in group if s.get("cat") == "producer"]
        if not (client and producer):
            continue
        paired += 1
        c, p = client[0], producer[0]
        assert p["pid"] != c["pid"]
        # consistent ordering: the producer's span sits inside the
        # client RPC window (same-host wall clocks; small tolerance for
        # clock granularity)
        assert p["ts"] >= c["ts"] - 2000
        assert p["ts"] + p["dur"] <= c["ts"] + c["dur"] + 2000
    assert paired >= 4
    # the exported file parses and carries every pid
    doc = json.loads(path.read_text())
    assert {e["pid"] for e in doc["traceEvents"]} == pids
    # spans never leak into user-visible info dicts
    assert all(wire.SPANS_KEY not in s.get("args", {}) for s in spans)


def test_tracing_pool_against_legacy_producer_stays_clean():
    """A producer that ignores the span context (reference-style REP
    loop, no mid echo either) must neither break the tracing pool nor
    leak span keys into infos — the client-side span still lands."""
    import zmq

    from blendjax.btt.envpool import EnvPool
    from helpers.producers import free_port

    addr = f"tcp://127.0.0.1:{free_port()}"
    stop = threading.Event()

    def legacy_server():
        ctx = zmq.Context.instance()
        rep = ctx.socket(zmq.REP)
        rep.setsockopt(zmq.LINGER, 0)
        rep.setsockopt(zmq.RCVTIMEO, 100)
        rep.bind(addr)
        t = 0
        try:
            while not stop.is_set():
                try:
                    req = wire.recv_message(rep)
                except zmq.Again:
                    continue
                t += 1
                obs = 0.0 if req["cmd"] == "reset" else req["action"]
                wire.send_message(rep, {
                    "obs": obs, "reward": 0.0, "done": False, "time": t,
                })
        finally:
            rep.close(0)

    thread = threading.Thread(target=legacy_server, daemon=True)
    thread.start()
    pool = EnvPool([addr], timeoutms=5000, trace=True)
    try:
        obs, infos = pool.reset()
        obs, rew, done, infos = pool.step([2.0])
        assert infos[0]["healthy"]
        assert wire.SPANS_KEY not in infos[0]
        assert wire.SPAN_KEY not in infos[0]
        spans = pool.spans.snapshot()
        assert [s["name"] for s in spans] == ["env_rpc", "env_rpc"]
        assert all(s.get("cat") == "envpool" for s in spans)
    finally:
        stop.set()
        pool.close()
        thread.join(timeout=3)


def test_untraced_pool_requests_carry_no_span_context(fake_blender):
    """Default pools must not pay (or ask) for spans: the producer only
    attaches spans when the request carries wire.SPAN_KEY."""
    from blendjax.btt.envpool import launch_env_pool

    with launch_env_pool(
        scene="", script=ENV_SCRIPT, num_instances=1, background=True,
        horizon=1_000_000, timeoutms=30000, start_port=13640,
    ) as pool:
        pool.reset()
        obs, rew, done, infos = pool.step([1.0])
        assert pool.spans is None
        assert wire.SPANS_KEY not in infos[0]


# ---------------------------------------------------------------------------
# replay shard telemetry + spans
# ---------------------------------------------------------------------------


def test_shard_telemetry_rpc_and_hub_merge():
    from blendjax.replay.service import start_shard_thread
    from blendjax.replay.shard_client import ShardedReplay

    with start_shard_thread(64, shard_id=0) as handle:
        buf = ShardedReplay(
            [handle.address], seed=3, counters=EventCounters(),
            trace=True,
        )
        try:
            for i in range(8):
                buf.append({"obs": np.full(4, i, np.float32),
                            "reward": np.float32(i)})
            buf.sample(4)
            # client-side RPC spans AND the shard's piggybacked storage
            # spans share correlation ids (same pid here: thread shard)
            spans = buf.spans.snapshot()
            cats = {s.get("cat") for s in spans}
            assert "replay_client" in cats and "replay_shard" in cats
            shard_names = {
                s["name"] for s in spans if s.get("cat") == "replay_shard"
            }
            assert "shard0:append" in shard_names
            assert "shard0:gather" in shard_names
            # the telemetry RPC ships counters + histograms, and the hub
            # merges them as a remote
            tel = buf.shard_telemetry(0)
            assert tel["shard_id"] == 0
            assert tel["stages"]["shard_srv_append"]["count"] == 8
            assert tel["stages"]["shard_srv_append"]["hist"]["n"] == 8
            hub = TelemetryHub()
            buf.register_with_hub(hub)
            snap = hub.scrape()
            assert snap["stages"]["shard_srv_append"]["count"] == 8
            assert snap["stages"]["shard_srv_append"]["p99_ms"] > 0
            # client-side REPLAY_STAGES percentiles ride the same scrape
            assert snap["stages"]["shard_append"]["count"] == 8
        finally:
            buf.close()


def test_shard_quarantine_lands_in_flight_recorder():
    from blendjax.replay.service import start_shard_thread
    from blendjax.replay.shard_client import ShardedReplay

    with start_shard_thread(32, shard_id=0) as handle:
        buf = ShardedReplay([handle.address], counters=EventCounters())
        try:
            buf.quarantine_shard(0, reason="test quarantine xyz")
            ours = [e for e in flight_recorder.snapshot()
                    if e["event"] == "replay_shard_quarantined"
                    and e["details"].get("reason") == "test quarantine xyz"]
            assert ours and ours[-1]["target"] == "shard0"
        finally:
            buf.close()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_recorder_ring_and_dump(tmp_path):
    fr = FlightRecorder(capacity=4)
    for i in range(7):
        fr.note("retry", target=f"env{i}", attempt=i)
    assert len(fr) == 4 and fr.dropped == 3
    path = fr.dump(str(tmp_path / "pm.json"), reason="unit",
                   extra={"target": "env6"})
    doc = json.loads(open(path).read())
    assert doc["format"] == "blendjax.postmortem/1"
    assert doc["reason"] == "unit"
    assert doc["events_dropped"] == 3
    assert [e["target"] for e in doc["events"]] == [
        "env3", "env4", "env5", "env6",
    ]
    assert all(re.fullmatch(r"[0-9a-f]{12}", e["digest"])
               for e in doc["events"])
    assert doc["extra"]["target"] == "env6"


def test_flight_dump_default_dir_env(tmp_path, monkeypatch):
    fr = FlightRecorder()
    fr.note("quarantine", target="env0")
    # no path, no env var -> skipped, not scattered into cwd
    monkeypatch.delenv("BJX_POSTMORTEM_DIR", raising=False)
    assert fr.dump(reason="nowhere") is None
    monkeypatch.setenv("BJX_POSTMORTEM_DIR", str(tmp_path))
    path = fr.dump(reason="via env!")
    assert path is not None and path.startswith(str(tmp_path))
    assert "via-env" in os.path.basename(path)


def test_supervisor_death_dumps_postmortem(tmp_path):
    """The chaos acceptance seam, unit-level: a supervised death writes
    a postmortem JSON naming the dead target, with the health snapshot
    attached (the process-level version runs in the chaos pack with
    $BJX_POSTMORTEM_DIR)."""
    from blendjax.btt.supervise import FleetSupervisor

    launcher = types.SimpleNamespace(launch_info=None)
    counters = EventCounters()
    timer = StageTimer()
    timer.add("recv", 0.001)
    hub = TelemetryHub()
    sup = FleetSupervisor(
        launcher, pool=None, counters=counters, timer=timer, hub=hub,
        postmortem_dir=str(tmp_path),
    )
    sup._on_death(1, -9)
    assert counters.get("deaths") == 1
    assert sup.last_postmortem is not None
    doc = json.loads(open(sup.last_postmortem).read())
    assert doc["extra"]["target"] == "instance1"
    assert doc["extra"]["exit_code"] == -9
    assert doc["extra"]["health"]["deaths"] == 1
    assert any(
        e["event"] == "producer_death" and e["target"] == "instance1"
        for e in doc["events"]
    )
    # the death is visible through the hub too (registered at init)
    snap = hub.scrape()
    assert snap["counters"]["deaths"] == 1
    assert snap["components"]["fleet0"]["probe"]["deaths"] == 1
    # health() carries the timer's percentile surface
    assert sup.health()["stages"]["recv"]["p50_ms"] > 0


def test_aggregate_health_merges_stage_histograms():
    from blendjax.btt.supervise import FleetSupervisor, aggregate_health

    sups = []
    for fid, lat in ((0, 1e-4), (1, 1e-1)):
        timer = StageTimer()
        for _ in range(100):
            timer.add("recv", lat)
        sups.append(FleetSupervisor(
            types.SimpleNamespace(launch_info=None), pool=None,
            counters=EventCounters(), timer=timer, fleet_id=fid,
            postmortem_dir=None,
        ))
    agg = aggregate_health(sups)
    rec = agg["stages"]["recv"]
    assert rec["count"] == 200
    assert rec["p99_ms"] > 50.0   # union quantile, not a mean
    assert rec["p50_ms"] < 110.0
    assert agg["fleets"][0]["stages"]["recv"]["count"] == 100


# ---------------------------------------------------------------------------
# vocabulary lock: docs <-> tuples
# ---------------------------------------------------------------------------


def _doc_table_names(path, heading):
    """Backticked names from the first column of the markdown table
    under ``heading`` (split on ``/`` compounds)."""
    text = open(path).read()
    section = text.split(heading, 1)[1]
    # stop at the next heading
    section = re.split(r"\n#{1,6} ", section, 1)[0]
    names = []
    for line in section.splitlines():
        if not line.startswith("|") or line.startswith("|---"):
            continue
        first = line.split("|")[1]
        names.extend(re.findall(r"`([a-z0-9_]+)`", first))
    return names


def test_documented_counters_exist_in_tuples():
    """Every FLEET_EVENTS/REPLAY_EVENTS name the docs tabulate must
    exist in the tuples — they drifted once before (ISSUE-9)."""
    names = _doc_table_names(
        os.path.join(REPO, "docs", "fault_tolerance.md"),
        "## Counter reference",
    )
    assert len(names) >= 15
    vocab = set(FLEET_EVENTS + REPLAY_EVENTS)
    missing = [n for n in names if n not in vocab]
    assert not missing, f"documented but not in tuples: {missing}"
    # and the reverse: every canonical counter is documented somewhere
    # in the fault-tolerance doc (table or prose)
    text = open(os.path.join(REPO, "docs", "fault_tolerance.md")).read()
    undocumented = [n for n in vocab if f"`{n}`" not in text]
    assert not undocumented, f"in tuples but undocumented: {undocumented}"


def test_documented_stages_exist_in_tuples():
    names = _doc_table_names(
        os.path.join(REPO, "docs", "observability.md"),
        "## Stage vocabulary",
    )
    vocab = set(FEED_STAGES + REPLAY_STAGES)
    documented = [n for n in names if n != "shard_srv"]
    missing = [n for n in documented if n not in vocab]
    assert not missing, f"documented but not in tuples: {missing}"
    # every canonical stage appears in the table
    absent = [n for n in vocab if n not in set(documented)]
    assert not absent, f"in tuples but not tabulated: {absent}"


def test_documented_serve_counters_exist_in_tuples():
    """The serving tier's vocabulary lock (ISSUE-10 satellite): every
    ``SERVE_EVENTS`` counter docs/serving.md tabulates exists in the
    tuple, and every tuple name is tabulated — both directions, the
    same contract the fleet/replay vocabularies keep."""
    names = _doc_table_names(
        os.path.join(REPO, "docs", "serving.md"),
        "## Counter vocabulary",
    )
    vocab = set(SERVE_EVENTS)
    missing = [n for n in names if n not in vocab]
    assert not missing, f"documented but not in tuples: {missing}"
    absent = [n for n in vocab if n not in set(names)]
    assert not absent, f"in tuples but not tabulated: {absent}"


def test_documented_serve_stages_exist_in_tuples():
    names = _doc_table_names(
        os.path.join(REPO, "docs", "serving.md"),
        "## Stage vocabulary",
    )
    vocab = set(SERVE_STAGES)
    missing = [n for n in names if n not in vocab]
    assert not missing, f"documented but not in tuples: {missing}"
    absent = [n for n in vocab if n not in set(names)]
    assert not absent, f"in tuples but not tabulated: {absent}"


def test_documented_gateway_counters_exist_in_tuples():
    """The gateway vocabulary lock (ISSUE-11 satellite): every
    ``GATEWAY_EVENTS`` counter docs/serving.md tabulates exists in the
    tuple and every tuple name is tabulated — both directions, same
    contract as the fleet/replay/serve vocabularies."""
    names = _doc_table_names(
        os.path.join(REPO, "docs", "serving.md"),
        "## Gateway counter vocabulary",
    )
    vocab = set(GATEWAY_EVENTS)
    missing = [n for n in names if n not in vocab]
    assert not missing, f"documented but not in tuples: {missing}"
    absent = [n for n in vocab if n not in set(names)]
    assert not absent, f"in tuples but not tabulated: {absent}"


def test_documented_gateway_stages_exist_in_tuples():
    names = _doc_table_names(
        os.path.join(REPO, "docs", "serving.md"),
        "## Gateway stage vocabulary",
    )
    vocab = set(GATEWAY_STAGES)
    missing = [n for n in names if n not in vocab]
    assert not missing, f"documented but not in tuples: {missing}"
    absent = [n for n in vocab if n not in set(names)]
    assert not absent, f"in tuples but not tabulated: {absent}"


def test_documented_weight_counters_exist_in_tuples():
    """The weight-bus vocabulary lock (ISSUE-13 satellite): every
    ``WEIGHT_EVENTS`` counter docs/weight_bus.md tabulates exists in
    the tuple and every tuple name is tabulated — both directions,
    same contract as the other vocabularies."""
    names = _doc_table_names(
        os.path.join(REPO, "docs", "weight_bus.md"),
        "## Counter vocabulary",
    )
    vocab = set(WEIGHT_EVENTS)
    missing = [n for n in names if n not in vocab]
    assert not missing, f"documented but not in tuples: {missing}"
    absent = [n for n in vocab if n not in set(names)]
    assert not absent, f"in tuples but not tabulated: {absent}"


def test_documented_weight_stages_exist_in_tuples():
    names = _doc_table_names(
        os.path.join(REPO, "docs", "weight_bus.md"),
        "## Stage vocabulary",
    )
    vocab = set(WEIGHT_STAGES)
    missing = [n for n in names if n not in vocab]
    assert not missing, f"documented but not in tuples: {missing}"
    absent = [n for n in vocab if n not in set(names)]
    assert not absent, f"in tuples but not tabulated: {absent}"


def test_documented_scenario_counters_exist_in_tuples():
    """The scenario-plane vocabulary lock (ISSUE-14 tentpole): every
    ``SCENARIO_EVENTS`` counter docs/scenarios.md tabulates exists in
    the tuple and every tuple name is tabulated — both directions,
    same contract as the other vocabularies."""
    names = _doc_table_names(
        os.path.join(REPO, "docs", "scenarios.md"),
        "## Counter vocabulary",
    )
    vocab = set(SCENARIO_EVENTS)
    missing = [n for n in names if n not in vocab]
    assert not missing, f"documented but not in tuples: {missing}"
    absent = [n for n in vocab if n not in set(names)]
    assert not absent, f"in tuples but not tabulated: {absent}"


def test_documented_scenario_stages_exist_in_tuples():
    names = _doc_table_names(
        os.path.join(REPO, "docs", "scenarios.md"),
        "## Stage vocabulary",
    )
    vocab = set(SCENARIO_STAGES)
    missing = [n for n in names if n not in vocab]
    assert not missing, f"documented but not in tuples: {missing}"
    absent = [n for n in vocab if n not in set(names)]
    assert not absent, f"in tuples but not tabulated: {absent}"


def test_documented_ha_counters_exist_in_tuples():
    """The learner-failover vocabulary lock (ISSUE-15 tentpole): every
    ``HA_EVENTS`` counter docs/fault_tolerance.md tabulates exists in
    the tuple and every tuple name is tabulated — both directions,
    same contract as the other vocabularies."""
    names = _doc_table_names(
        os.path.join(REPO, "docs", "fault_tolerance.md"),
        "## HA counter vocabulary",
    )
    vocab = set(HA_EVENTS)
    missing = [n for n in names if n not in vocab]
    assert not missing, f"documented but not in tuples: {missing}"
    absent = [n for n in vocab if n not in set(names)]
    assert not absent, f"in tuples but not tabulated: {absent}"


def test_documented_ha_stages_exist_in_tuples():
    names = _doc_table_names(
        os.path.join(REPO, "docs", "fault_tolerance.md"),
        "## HA stage vocabulary",
    )
    vocab = set(HA_STAGES)
    missing = [n for n in names if n not in vocab]
    assert not missing, f"documented but not in tuples: {missing}"
    absent = [n for n in vocab if n not in set(names)]
    assert not absent, f"in tuples but not tabulated: {absent}"


def test_documented_autoscale_counters_exist_in_tuples():
    """The autoscale vocabulary lock (ISSUE-18 tentpole): every
    ``AUTOSCALE_EVENTS`` counter docs/autoscaling.md tabulates exists
    in the tuple and every tuple name is tabulated — both directions,
    same contract as the other vocabularies."""
    names = _doc_table_names(
        os.path.join(REPO, "docs", "autoscaling.md"),
        "## Counter vocabulary",
    )
    vocab = set(AUTOSCALE_EVENTS)
    missing = [n for n in names if n not in vocab]
    assert not missing, f"documented but not in tuples: {missing}"
    absent = [n for n in vocab if n not in set(names)]
    assert not absent, f"in tuples but not tabulated: {absent}"


def test_documented_pipe_counters_exist_in_tuples():
    """The MPMD-pipeline vocabulary lock (ISSUE-19 tentpole): every
    ``PIPE_EVENTS`` counter docs/pipeline.md tabulates exists in the
    tuple and every tuple name is tabulated — both directions, same
    contract as the other vocabularies."""
    names = _doc_table_names(
        os.path.join(REPO, "docs", "pipeline.md"),
        "## Counter vocabulary",
    )
    vocab = set(PIPE_EVENTS)
    missing = [n for n in names if n not in vocab]
    assert not missing, f"documented but not in tuples: {missing}"
    absent = [n for n in vocab if n not in set(names)]
    assert not absent, f"in tuples but not tabulated: {absent}"


def test_documented_pipe_stages_exist_in_tuples():
    names = _doc_table_names(
        os.path.join(REPO, "docs", "pipeline.md"),
        "## Stage vocabulary",
    )
    vocab = set(PIPE_STAGES)
    missing = [n for n in names if n not in vocab]
    assert not missing, f"documented but not in tuples: {missing}"
    absent = [n for n in vocab if n not in set(names)]
    assert not absent, f"in tuples but not tabulated: {absent}"


def test_documented_autoscale_stages_exist_in_tuples():
    names = _doc_table_names(
        os.path.join(REPO, "docs", "autoscaling.md"),
        "## Stage vocabulary",
    )
    vocab = set(AUTOSCALE_STAGES)
    missing = [n for n in names if n not in vocab]
    assert not missing, f"documented but not in tuples: {missing}"
    absent = [n for n in vocab if n not in set(names)]
    assert not absent, f"in tuples but not tabulated: {absent}"


# ---------------------------------------------------------------------------
# telemetry overhead sanity (the bench carry, structure only)
# ---------------------------------------------------------------------------


def test_telemetry_overhead_measurement_shape():
    from benchmarks.feed_bound import measure_telemetry_overhead

    r = measure_telemetry_overhead(seconds=0.6, batch=4, nmsgs=8)
    assert set(r) >= {
        "telemetry_overhead_x", "enabled_batches_per_sec",
        "disabled_batches_per_sec", "stages",
    }
    assert r["telemetry_overhead_x"] > 0.5  # sanity, not the bench floor
    assert r["stages"]["scatter"]["p99_ms"] >= r["stages"]["scatter"]["p50_ms"]


def test_bench_headline_carries_telemetry_overhead():
    import bench

    fb = {
        "feed_limit_batches_per_sec": {"legacy": 100.0, "arena": 140.0},
        "arena_over_legacy": 1.4,
        "telemetry_overhead_x": 0.97,
        "stages": {},
    }
    out = bench.assemble({"host_stream": {"items_per_sec": 1.0}}, feed_bound=fb)
    line = bench.headline(out)
    assert line["telemetry_overhead_x"] == 0.97
    assert len(json.dumps(line)) + 1 <= bench.HEADLINE_BYTE_BUDGET
    # and it is the FIRST casualty of the tail byte budget, never the
    # driver fields
    assert ("telemetry_overhead_x",) == bench.HEADLINE_TRIM_ORDER[0]
