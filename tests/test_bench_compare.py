"""scripts/bench_compare.py — the bench-trajectory guardrail.

Locks: metric extraction from every artifact shape the repo actually
contains (headline line, full line, jsonl stdout, driver capture
wrapper incl. pre-r05 truncated tails), the regression verdict + exit
code, per-metric floor overrides, and the new/vanished metric
semantics.
"""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

spec = importlib.util.spec_from_file_location(
    "bench_compare", os.path.join(REPO, "scripts", "bench_compare.py")
)
bench_compare = importlib.util.module_from_spec(spec)
spec.loader.exec_module(bench_compare)


HEADLINE = {
    "headline": True, "metric": "x_images_per_sec", "value": 100.0,
    "vs_baseline": 1.2, "feed_arena_x": 1.4, "replay_sample_x": 4.0,
    "rl_pipelined_x": 1.8, "telemetry_overhead_x": 0.98,
}


def _write(tmp_path, name, content):
    p = tmp_path / name
    p.write_text(content)
    return str(p)


def test_extract_headline_and_full_line(tmp_path):
    path = _write(tmp_path, "h.json", json.dumps(HEADLINE))
    m = bench_compare.extract_metrics(path)
    assert m["value"] == 100.0
    assert m["telemetry_overhead_x"] == 0.98
    # full-artifact nesting maps onto headline names
    full = {
        "metric": "m", "value": 80.0,
        "feed_bound": {"arena_over_legacy": 1.35,
                       "telemetry_overhead_x": 0.97},
        "replay_bench": {
            "replay_sample_x": 3.9,
            "sharded": {"replay_shard_x": 0.25, "shm_rpc_x": 1.6,
                        "replay_degraded_x": 1.1},
        },
        "rl_steps_per_sec": 12000.0,
    }
    m = bench_compare.extract_metrics(
        _write(tmp_path, "f.json", json.dumps(full))
    )
    assert m["feed_arena_x"] == 1.35
    assert m["replay_shard_x"] == 0.25
    assert m["shm_rpc_x"] == 1.6  # ISSUE-12: floor-guarded transport win
    assert m["rl_steps_per_sec"] == 12000.0


def test_extract_bench_stdout_jsonl_headline_wins(tmp_path):
    full = {"metric": "m", "value": 80.0,
            "feed_bound": {"arena_over_legacy": 1.30}}
    head = dict(HEADLINE, value=81.0, feed_arena_x=1.31)
    path = _write(
        tmp_path, "out.jsonl",
        "noise line\n" + json.dumps(full) + "\n" + json.dumps(head) + "\n",
    )
    m = bench_compare.extract_metrics(path)
    assert m["value"] == 81.0          # the LAST line wins
    assert m["feed_arena_x"] == 1.31


def test_extract_driver_wrapper_and_truncated_tail(tmp_path):
    # the r04 shape: one truncated full line, no parseable JSON at all
    tail = ('"stages": {"recv": 1}}, "rl_steps_per_sec": 11327.2, '
            '"rl_vs_baseline": 5.664}\n')
    wrapper = {"n": 5, "cmd": "bench", "rc": 0, "tail": tail,
               "parsed": None}
    m = bench_compare.extract_metrics(
        _write(tmp_path, "r04.json", json.dumps(wrapper))
    )
    assert m["rl_steps_per_sec"] == 11327.2
    # the r05 shape: truncated full line + complete headline; the
    # parsed headline overrides any regex salvage
    tail = ('"rl_steps_per_sec": 12381.0, "trunc...\n'
            + json.dumps(HEADLINE) + "\n")
    wrapper = {"n": 5, "cmd": "bench", "rc": 0, "tail": tail}
    m = bench_compare.extract_metrics(
        _write(tmp_path, "r05.json", json.dumps(wrapper))
    )
    assert m["rl_steps_per_sec"] == 12381.0   # salvaged
    assert m["value"] == 100.0                # parsed headline


def test_regression_verdict_and_exit_code(tmp_path):
    old = _write(tmp_path, "old.json", json.dumps(HEADLINE))
    bad = dict(HEADLINE, feed_arena_x=0.9)  # 1.4 -> 0.9: x0.64 < 0.90
    new = _write(tmp_path, "new.json", json.dumps(bad))
    assert bench_compare.main([old, new]) == 1
    # same artifact: clean
    assert bench_compare.main([old, old]) == 0
    # loosening the floor waives exactly that metric
    assert bench_compare.main([old, new, "--floor", "feed_arena_x=0.5"]) == 0


def test_new_and_vanished_metric_semantics(tmp_path):
    old = _write(tmp_path, "old.json", json.dumps(HEADLINE))
    fewer = {k: v for k, v in HEADLINE.items() if k != "rl_pipelined_x"}
    fewer["rl_sharded_x"] = 2.0  # new metric
    new = _write(tmp_path, "new.json", json.dumps(fewer))
    # default: a vanished metric is reported, not fatal; a new metric
    # never fails retroactively
    assert bench_compare.main([old, new]) == 0
    # --strict: a vanished metric IS a regression
    assert bench_compare.main([old, new, "--strict"]) == 1


def test_json_output_shape(tmp_path, capsys):
    old = _write(tmp_path, "old.json", json.dumps(HEADLINE))
    assert bench_compare.main([old, old, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["regressions"] == 0
    assert {r["metric"] for r in out["rows"]} >= {"value", "feed_arena_x"}
    assert all(r["status"] == "ok" for r in out["rows"])


def test_telemetry_overhead_floor_is_tight(tmp_path):
    """telemetry_overhead_x guards the <=5% overhead promise: a drop
    from 1.0 to 0.90 (10% overhead) must fail even though every other
    floor would tolerate x0.90."""
    old = _write(tmp_path, "old.json",
                 json.dumps({"headline": True, "value": 1.0,
                             "telemetry_overhead_x": 1.0}))
    new = _write(tmp_path, "new.json",
                 json.dumps({"headline": True, "value": 1.0,
                             "telemetry_overhead_x": 0.90}))
    assert bench_compare.main([old, new]) == 1


SERVE_HEADLINE = {
    "headline": True, "metric": "x_images_per_sec", "value": 100.0,
    "serve_qps": 2650.0, "serve_p99_ms": 6.4, "serve_int8_x": 0.98,
}


def test_serve_metrics_extract_from_headline_and_nest(tmp_path):
    m = bench_compare.extract_metrics(
        _write(tmp_path, "h.json", json.dumps(SERVE_HEADLINE))
    )
    assert m["serve_qps"] == 2650.0 and m["serve_p99_ms"] == 6.4
    full = {
        "metric": "m", "value": 80.0,
        "serve_bench": {"serve_qps": 2600.0, "serve_p99_ms": 7.0,
                        "serve_int8_x": 1.0},
    }
    m = bench_compare.extract_metrics(
        _write(tmp_path, "f.json", json.dumps(full))
    )
    assert m["serve_int8_x"] == 1.0 and m["serve_p99_ms"] == 7.0


def test_lower_is_better_ceiling_for_p99(tmp_path):
    """serve_p99_ms inverts the verdict: a latency DROP passes however
    large, and an increase past the ceiling is the regression — the
    floor logic must not read a 2x latency jump as a 2x improvement."""
    old = _write(tmp_path, "old.json", json.dumps(SERVE_HEADLINE))
    better = dict(SERVE_HEADLINE, serve_p99_ms=2.0)   # x0.31: improvement
    assert bench_compare.main(
        [old, _write(tmp_path, "b.json", json.dumps(better))]
    ) == 0
    worse = dict(SERVE_HEADLINE, serve_p99_ms=12.8)   # x2.0 > 1.30 ceiling
    assert bench_compare.main(
        [old, _write(tmp_path, "w.json", json.dumps(worse))]
    ) == 1
    # --ceiling overrides per metric, like --floor does
    assert bench_compare.main(
        [old, _write(tmp_path, "w2.json", json.dumps(worse)),
         "--ceiling", "serve_p99_ms=2.5"]
    ) == 0


def test_serve_qps_floor_guards_throughput(tmp_path):
    old = _write(tmp_path, "old.json", json.dumps(SERVE_HEADLINE))
    bad = dict(SERVE_HEADLINE, serve_qps=1500.0)  # x0.57 < 0.80 floor
    assert bench_compare.main(
        [old, _write(tmp_path, "bad.json", json.dumps(bad))]
    ) == 1


def test_direction_rides_json_rows(tmp_path, capsys):
    old = _write(tmp_path, "old.json", json.dumps(SERVE_HEADLINE))
    assert bench_compare.main([old, old, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    by_metric = {r["metric"]: r for r in out["rows"]}
    assert by_metric["serve_p99_ms"]["direction"] == "down"
    assert by_metric["serve_qps"]["direction"] == "up"


def test_unknown_file_raises(tmp_path):
    with pytest.raises(ValueError, match="no known bench metrics"):
        bench_compare.extract_metrics(
            _write(tmp_path, "junk.json", "not json at all")
        )
