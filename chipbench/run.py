#!/usr/bin/env python3
"""chipbench — run one cell of ``BENCHMARK.json`` on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up every shape the cell uses (set-up), measures for ``--seconds``,
checks what the timed path produced against the plain reference, and prints
the contract's one JSON line last on stdout.  It runs on a TPU only: on any
other platform, with fewer chips than the cell asks for, or without the
program beside it, it exits non-zero and prints no result.

Nothing here names a cell, a configuration or a metric.  A cell resolves
through files named by ``BENCHMARK.json``: ``workloads/<cell>.json`` (driver
and limits), ``configs/<config>.json``, ``traffic/<traffic>.json`` and
``metrics/<metric>.json`` (its reader).  See ``README.md`` here.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # process start, as near as Python gives it

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path):
    with open(path) as f:
        return json.load(f)


def _resolve(name):
    """``package.module:function`` -> the function."""
    module, _, attr = name.partition(":")
    return getattr(importlib.import_module(module), attr)


def resolve_cell(name, root=ROOT):
    """Everything a cell is made of, found by the names in BENCHMARK.json."""
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    base = os.path.join(root, bench["paths"][0])
    workload = _load(os.path.join(base, "workloads", name + ".json"))
    workload["traffic"] = _load(
        os.path.join(base, "traffic", cell["traffic"] + ".json"))

    def metrics(kind):
        out = []
        for m in bench[kind]:
            if name in m.get("workloads", [name]):
                spec = _load(os.path.join(base, "metrics", m["name"] + ".json"))
                out.append(dict(m, reader=spec["reader"]))
        return out

    return types.SimpleNamespace(
        cell=cell, workload=workload,
        config=_load(os.path.join(root, configs[cell["config"]]["file"])),
        end_to_end=metrics("end_to_end"), per_layer=metrics("per_layer"))


def read_metrics(specs, obs, ctx):
    """Each metric through its own reader; one that finds nothing to read
    is left out of the line."""
    out = {}
    for m in specs:
        value = _resolve(m["reader"])(obs, ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(resolved, obs, ctx, device):
    """The contract's one JSON object, the numbers compared last."""
    traced = obs.get("trace")
    specs = resolved.per_layer if ctx.trace else resolved.end_to_end
    device = dict(device, memory_peak_bytes=obs["memory_peak_bytes"])
    line = {
        "correct": obs["checks"].correct,
        "attempted": int(obs["attempted"]),
        "failed": int(obs["failed"]),
        "metrics": read_metrics(specs, obs, ctx),
        "device": device,
    }
    if ctx.trace and traced:
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        line["breakdown"] = {"device_ops": traced["device_ops"],
                             "idle_gaps": traced["idle_gaps"]}
        line["end_to_end_traced"] = read_metrics(
            resolved.end_to_end, obs, ctx)
    line["compiles_in_window"] = obs["compiles_in_window"]
    line["reference_s"] = obs["reference_s"]
    line["checks"] = obs["checks"].rows
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # for the proof of `correct` alone; the benchmark's own runs use neither
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="compute in the precision below the configuration's")
    ap.add_argument("--fault", default=None,
                    help="break the timed path underneath (see the drivers)")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "blendjax")):
        sys.exit("chipbench: no blendjax package beside chipbench/: run it "
                 "from the root of a checkout")
    sys.path.insert(0, ROOT)
    from blendjax.btt.launcher import place_compile_cache

    resolved = resolve_cell(args.workload)
    # before jax reads its configuration: the cache at a fixed place inside
    # the checkout (or where the caller says), and every program kept in it
    place_compile_cache(os.environ)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from chipbench import common, flops

    device = common.device_record()
    if device["platform"] != "tpu":
        sys.exit(f"chipbench: found platform {device['platform']!r}, not a "
                 "TPU; there is no CPU mode (the rehearsal is "
                 "chipbench/tests)")
    if device["count"] < resolved.cell["chips"]:
        sys.exit(f"chipbench: the cell asks for {resolved.cell['chips']} "
                 f"chips, found {device['count']}")
    ctx = types.SimpleNamespace(
        cell=resolved.cell, workload=resolved.workload,
        config=resolved.config, peaks=flops.load_peaks(device["kind"]),
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        control=bool(args.control), fault=args.fault, t_start=T_START)
    obs = _resolve(resolved.workload["driver"])(ctx)
    line = result_line(resolved, obs, ctx, device)
    print(json.dumps({k: obs.get(k) for k in ("setup_s", "window_s", "notes")}
                     | {"compiles_in_window": obs["compiles_in_window"]},
                     default=str), flush=True)
    if obs.get("trace") and obs["trace"].get("layout"):
        print("trace layout: " + json.dumps(obs["trace"]["layout"]),
              file=sys.stderr)
    for row in obs["checks"].rows:
        print(f"check {row['name']}: {row['value']!r} (limit "
              f"{row['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
