#!/usr/bin/env python3
"""Diff two bench headline artifacts with per-metric regression floors.

The repo accumulates a trajectory of bench artifacts (``BENCH_r0x.json``)
but nothing ever *enforced* it — a PR could halve ``feed_arena_x`` and
only a human reading JSON would notice.  This tool turns the trajectory
into a guardrail::

    python scripts/bench_compare.py BENCH_r05.json BENCH_new.json
    make benchdiff OLD=BENCH_r05.json NEW=BENCH_new.json

Each metric present in BOTH artifacts is compared as ``new / old``
against its floor (see ``DEFAULT_FLOORS``; override per metric with
``--floor metric=ratio``).  **Lower-is-better** metrics (latencies:
``DEFAULT_CEILINGS``, e.g. ``serve_p99_ms``) invert the test — an
*increase* past the ceiling is the regression (``--ceiling
metric=ratio`` overrides or declares one).  Any violation is a
regression: the offending rows are printed and the exit code is
non-zero, so CI can gate on it.  Metrics present in only one artifact
are listed as skipped
— a new metric must not fail the diff retroactively, and a *vanished*
metric is reported (``--strict`` turns vanished metrics into failures).

Accepted input shapes (auto-detected, so both the raw ``bench.py``
stdout and the driver's capture wrapper work):

- the compact headline line (``{"headline": true, ...}``),
- the full artifact line (first line of ``bench.py`` stdout),
- a ``.jsonl``/multi-line capture of both (later lines win),
- the driver wrapper (``{"cmd": ..., "tail": "..."}`` — JSON lines are
  recovered from the tail, e.g. ``BENCH_r05.json``).
"""

from __future__ import annotations

import argparse
import json
import re
import sys

#: metric -> minimum acceptable new/old ratio (higher-is-better
#: metrics).  Floors are loose enough for shared-CI noise on
#: paired-window medians; tighten per-deployment via --floor.
DEFAULT_FLOORS = {
    "value": 0.85,                  # headline images/sec
    "vs_baseline": 0.85,
    "feed_arena_x": 0.90,
    "replay_sample_x": 0.85,
    # raised 0.80 -> 0.85 with the ShmRPC arm (ISSUE-12): the shm
    # transport lifted the absolute value ~1.6x, so the relative guard
    # can afford to be tighter without tripping on CI noise
    "replay_shard_x": 0.85,
    "shm_rpc_x": 0.85,              # shm over loopback-zmq service arm
    "replay_degraded_x": 0.85,
    "rl_steps_per_sec": 0.80,
    "rl_pipelined_x": 0.85,
    "rl_sharded_x": 0.80,
    "telemetry_overhead_x": 0.95,   # itself a ratio; must stay ~free
    "serve_qps": 0.80,              # serving tier headline (docs/serving.md)
    "serve_int8_x": 0.80,
    "serve_prefill_x": 0.80,        # batched prefill admission vs serial
    "gateway_qps": 0.80,            # serve-fleet aggregate through the gateway
    "gateway_scale_x": 0.80,        # QPS at N replicas over 1 (drained fleet)
    # sharded data plane: QPS at N gateway workers over 1 (same fleet,
    # same worker processes, set_active_workers(1) arm) — the
    # front/worker split's whole claim, so it gets a tighter floor
    "gateway_shard_x": 0.85,
    # live weight rollouts must stay ~free for serving traffic: QPS in
    # the buckets around a hot-swap over steady state (docs/weight_bus.md)
    "weight_swap_qps_dip_x": 0.80,
    # heterogeneous 2-scenario fleet (ready-first) over the lock-step
    # homogeneous batch path — the scenario plane's throughput claim
    # (docs/scenarios.md); the absolute ratio scales with the
    # fast/slow physics gap, so guard the trajectory, not a constant
    "scenario_hetero_x": 0.80,
    # async train-state checkpointing must stay ~free for the update
    # loop: throughput with the TrainCheckpointer attached over
    # checkpointing off (docs/fault_tolerance.md "Learner failover")
    "ckpt_overhead_x": 0.90,
    # MPMD pipeline: N stage processes' 1F1B schedule over the 1-stage
    # same-harness baseline at the calibrated compute stand-in — the
    # whole claim of the stage-process tier (docs/pipeline.md), so it
    # gets the tighter shard-style floor
    "pipe_mpmd_x": 0.85,
}

#: metric -> maximum acceptable new/old ratio for LOWER-is-better
#: metrics: a ``serve_p99_ms`` *increase* is the regression, so the
#: guardrail is a ceiling, not a floor.  Override via --ceiling.
DEFAULT_CEILINGS = {
    "serve_p99_ms": 1.30,           # tail latency; loopback-noise slack
    "gateway_p99_ms": 1.30,         # fleet tail latency through the gateway
    # publish -> first-serving-reply-at-new-version p99: a single-digit
    # millisecond tail measured over ~8 swaps, so the noise slack is
    # wider than the steady p99 ceilings
    "weight_swap_ms": 1.50,
    # union client-observed p99 under the labelled multi-scenario
    # traffic mix (docs/scenarios.md) — same slack as the single-shape
    # serve tail
    "serve_mix_p99_ms": 1.30,
    # SIGKILL -> first completed post-respawn learner update: seconds,
    # dominated by the child's jax import + first jitted update, so
    # the slack is wide — the guard catches a recovery-path regression
    # (e.g. an accidental full-buffer rewrite at restore), not noise
    "learner_recovery_s": 1.50,
    # autoscale decision -> verified-healthy commit at the new fleet
    # size: seconds, dominated by the replica spawn and the configured
    # healthy window, so the slack is wide — the guard catches a
    # settle-path regression (a stuck drain, a window that never
    # closes), not window-length noise (docs/autoscaling.md)
    "resize_settle_s": 1.50,
}

#: fallback floor for numeric metrics named via --metrics that have no
#: entry above
FALLBACK_FLOOR = 0.85


def _json_lines(text):
    out = []
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            continue  # torn/truncated capture line
        if isinstance(obj, dict):
            out.append(obj)
    return out


def _known_metrics():
    return tuple(DEFAULT_FLOORS) + tuple(DEFAULT_CEILINGS)


def _flatten(doc, metrics):
    """Fold one artifact dict's metric values into ``metrics``."""
    for key in _known_metrics():
        v = doc.get(key)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            metrics[key] = float(v)
    # full-artifact nesting -> headline names
    fb = doc.get("feed_bound")
    if isinstance(fb, dict):
        if isinstance(fb.get("arena_over_legacy"), (int, float)):
            metrics["feed_arena_x"] = float(fb["arena_over_legacy"])
        if isinstance(fb.get("telemetry_overhead_x"), (int, float)):
            metrics["telemetry_overhead_x"] = float(
                fb["telemetry_overhead_x"]
            )
    rb = doc.get("replay_bench")
    if isinstance(rb, dict):
        if isinstance(rb.get("replay_sample_x"), (int, float)):
            metrics["replay_sample_x"] = float(rb["replay_sample_x"])
        shard = rb.get("sharded")
        if isinstance(shard, dict):
            for k in ("replay_shard_x", "shm_rpc_x",
                      "replay_degraded_x"):
                if isinstance(shard.get(k), (int, float)):
                    metrics[k] = float(shard[k])
    sb = doc.get("serve_bench")
    if isinstance(sb, dict):
        for k in ("serve_qps", "serve_p99_ms", "serve_int8_x",
                  "serve_prefill_x"):
            if isinstance(sb.get(k), (int, float)) \
                    and not isinstance(sb.get(k), bool):
                metrics[k] = float(sb[k])
    gb = doc.get("gateway_bench")
    if isinstance(gb, dict):
        for k in ("gateway_qps", "gateway_p99_ms", "gateway_scale_x",
                  "gateway_shard_x"):
            if isinstance(gb.get(k), (int, float)) \
                    and not isinstance(gb.get(k), bool):
                metrics[k] = float(gb[k])
    wb = doc.get("weight_bench")
    if isinstance(wb, dict):
        for k in ("weight_swap_ms", "weight_swap_qps_dip_x"):
            if isinstance(wb.get(k), (int, float)) \
                    and not isinstance(wb.get(k), bool):
                metrics[k] = float(wb[k])
    sc = doc.get("scenario_bench")
    if isinstance(sc, dict):
        for k in ("scenario_hetero_x", "serve_mix_p99_ms"):
            if isinstance(sc.get(k), (int, float)) \
                    and not isinstance(sc.get(k), bool):
                metrics[k] = float(sc[k])
    ab = doc.get("autoscale_bench")
    if isinstance(ab, dict):
        # drain_error_x is deliberately NOT trajectory-guarded here:
        # its contract is an absolute zero (0/0 has no ratio), asserted
        # by the bench itself and tests/test_autoscale.py
        for k in ("resize_settle_s",):
            if isinstance(ab.get(k), (int, float)) \
                    and not isinstance(ab.get(k), bool):
                metrics[k] = float(ab[k])
    pb = doc.get("pipeline_bench")
    if isinstance(pb, dict):
        if isinstance(pb.get("pipe_mpmd_x"), (int, float)) \
                and not isinstance(pb.get("pipe_mpmd_x"), bool):
            metrics["pipe_mpmd_x"] = float(pb["pipe_mpmd_x"])


def _regex_salvage(text, metrics):
    """Recover flat metric values from a TRUNCATED capture (pre-r05
    driver tails cut the single big line mid-JSON — e.g.
    ``BENCH_r04.json`` — so no line parses whole).  Structured values
    folded afterwards win over these."""
    for metric in _known_metrics():
        hits = re.findall(
            rf'"{metric}":\s*(-?\d+(?:\.\d+)?(?:[eE][+-]?\d+)?)', text
        )
        if hits:
            metrics[metric] = float(hits[-1])


def extract_metrics(path):
    """Metric values from one artifact file (see module docstring for
    the accepted shapes)."""
    with open(path) as f:
        text = f.read()
    docs = []
    metrics = {}
    try:
        top = json.loads(text)
    except json.JSONDecodeError:
        top = None
    if isinstance(top, dict) and "tail" in top and "metric" not in top:
        # driver capture wrapper: recover the JSON lines from the tail
        # (the headline is the LAST line by the bench.py contract);
        # regex salvage first, so parsed lines override it
        _regex_salvage(top["tail"], metrics)
        docs = _json_lines(top["tail"])
        if isinstance(top.get("parsed"), dict):
            docs.append(top["parsed"])
    elif isinstance(top, dict):
        docs = [top]
    else:
        _regex_salvage(text, metrics)
        docs = _json_lines(text)
    for doc in docs:  # later lines win (headline overrides full line)
        _flatten(doc, metrics)
    if not metrics:
        raise ValueError(f"{path}: no known bench metrics found")
    return metrics


def compare(old, new, floors, strict=False, ceilings=None):
    """Row-per-metric comparison; returns (rows, regressions).

    A metric in ``ceilings`` is LOWER-is-better: the regression test is
    ``new/old <= ceiling`` (its row carries ``direction: "down"`` and
    the bound under ``floor``).  Everything else keeps the
    higher-is-better floor test.  A metric must not sit in both maps —
    ``ceilings`` wins (it is the more specific declaration).
    """
    ceilings = DEFAULT_CEILINGS if ceilings is None else ceilings
    rows = []
    regressions = 0
    for metric in sorted(set(old) | set(new)):
        o, n = old.get(metric), new.get(metric)
        if o is None or n is None:
            status = "vanished" if n is None else "new"
            ok = not (strict and n is None)
            rows.append({
                "metric": metric, "old": o, "new": n, "ratio": None,
                "floor": None, "status": status, "ok": ok,
            })
            if not ok:
                regressions += 1
            continue
        lower_better = metric in ceilings
        bound = (
            ceilings[metric] if lower_better
            else floors.get(metric, FALLBACK_FLOOR)
        )
        ratio = (n / o) if o else None
        if ratio is None:
            ok = True
        elif lower_better:
            ok = ratio <= bound
        else:
            ok = ratio >= bound
        rows.append({
            "metric": metric, "old": o, "new": n,
            "ratio": None if ratio is None else round(ratio, 3),
            "floor": bound,
            "direction": "down" if lower_better else "up",
            "status": "ok" if ok else "REGRESSION",
            "ok": ok,
        })
        if not ok:
            regressions += 1
    return rows, regressions


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("old", help="baseline artifact (e.g. BENCH_r05.json)")
    ap.add_argument("new", help="candidate artifact")
    ap.add_argument(
        "--floor", action="append", default=[], metavar="METRIC=RATIO",
        help="override a metric's regression floor (repeatable)",
    )
    ap.add_argument(
        "--ceiling", action="append", default=[], metavar="METRIC=RATIO",
        help="override (or declare) a LOWER-is-better metric's maximum "
             "acceptable new/old ratio (repeatable)",
    )
    ap.add_argument(
        "--strict", action="store_true",
        help="a metric present in OLD but missing from NEW fails the diff",
    )
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable output (one JSON object)")
    args = ap.parse_args(argv)

    floors = dict(DEFAULT_FLOORS)
    ceilings = dict(DEFAULT_CEILINGS)
    for spec in args.ceiling:
        metric, _, ratio = spec.partition("=")
        if not ratio:
            ap.error(f"--ceiling needs METRIC=RATIO, got {spec!r}")
        ceilings[metric] = float(ratio)
    # floors validate against the FULLY-built ceilings map, so a metric
    # declared lower-is-better on this very command line still refuses
    # a floor (compare() consults ceilings first — the floor would be
    # silently inert, faking a guardrail)
    for spec in args.floor:
        metric, _, ratio = spec.partition("=")
        if not ratio:
            ap.error(f"--floor needs METRIC=RATIO, got {spec!r}")
        if metric in ceilings:
            ap.error(
                f"{metric} is lower-is-better; use --ceiling "
                f"{metric}=RATIO"
            )
        floors[metric] = float(ratio)

    old = extract_metrics(args.old)
    new = extract_metrics(args.new)
    rows, regressions = compare(old, new, floors, strict=args.strict,
                                ceilings=ceilings)

    if args.as_json:
        print(json.dumps({
            "old": args.old, "new": args.new,
            "regressions": regressions, "rows": rows,
        }))
    else:
        width = max(len(r["metric"]) for r in rows)
        print(f"bench diff: {args.old} -> {args.new}")
        for r in rows:
            o = "-" if r["old"] is None else f"{r['old']:.3f}"
            n = "-" if r["new"] is None else f"{r['new']:.3f}"
            ratio = "-" if r["ratio"] is None else f"{r['ratio']:.3f}"
            kind = "ceiling" if r.get("direction") == "down" else "floor"
            floor = "-" if r["floor"] is None else f"{r['floor']:.2f}"
            print(
                f"  {r['metric']:<{width}}  {o:>10} -> {n:>10}  "
                f"x{ratio:>6} ({kind} {floor})  {r['status']}"
            )
        if regressions:
            print(f"{regressions} regression(s) below floor")
        else:
            print("no regressions")
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
