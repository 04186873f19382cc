"""Driver: closed-loop serving through an in-thread ``PolicyServer``.

The harness's own process holds the chip: it makes the weights, builds
``SeqFormerModel`` and ``PolicyServer`` and runs ``serve_forever`` in a
thread, so that it can trace the device and read its memory.  The only child
is the jax-free load generator (``chipbench/traffic/closed_loop_clients.py``),
whose clients drive ``reset(prefix=)`` / ``step`` / ``close_episode`` over the
RPC wire.  Once the window has closed and the server's state is freed, the
plain reference runs once over a seeded sample of the finished episodes, the
longest among them, and every served prediction is held against it.
"""

from __future__ import annotations

import json
import os
import pickle
import subprocess
import sys
import threading
import time

import numpy as np

from chipbench import common
from chipbench.traffic import closed_loop_clients

FAULTS = (None, "answer_altered")


def _stages(timer):
    return {name: {"total_s": timer.total_s(name), "count": timer.count(name)}
            for name in ("queue_wait", "batch_assemble", "compute", "reply")}


def _warm(model, buckets, clients, prefix_lengths):
    """Compile the shapes this cell's traffic uses and no others: the
    buckets up to the client count's, and the prefix lengths.  All of it
    lands on the pad row, which no episode reads."""
    for b in buckets:
        model.step_rows(np.full(b, model.pad_slot, np.int64),
                        np.zeros((b, model.obs_dim), np.float32))
        if b >= clients:
            break
    for n in prefix_lengths:
        model.prefill_rows(np.asarray([model.pad_slot]),
                           np.zeros((n, model.obs_dim), np.float32))
    model.reset_rows(np.asarray([model.pad_slot]))


def run(ctx):
    import jax
    import jax.numpy as jnp

    from blendjax.serve.server import PolicyServer, SeqFormerModel
    from blendjax.utils.timing import EventCounters, StageTimer
    from chipbench import reference

    cfg, check = ctx.config, ctx.workload["check"]
    model_cfg, srv = cfg["model"], cfg["server"]
    traffic = dict(ctx.workload["traffic"], obs_dim=model_cfg["obs_dim"],
                   sample_episodes=check["sample_episodes"])
    if ctx.fault not in FAULTS:
        raise ValueError(f"unknown fault {ctx.fault!r}")
    if max(traffic["prefix_lengths"]) + traffic["steps_max"] > cfg["length"]:
        raise ValueError("an episode would outgrow the cache ring")
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    compute, cache = dtypes[cfg["compute_dtype"]], dtypes[cfg["cache_dtype"]]
    compiles = common.CompileCounter()
    # run the program as the configuration states: the TPU's default for a
    # float32 product is one bfloat16 pass, and the server has no option of
    # its own, so the process-wide one is set (the server's thread traces
    # its programs itself, so a thread-local context would not reach them)
    precision_before = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision",
                      cfg["matmul_precision"])

    params = reference.make_params(model_cfg, ctx.seed)
    model = SeqFormerModel(params, cfg["slots"], cfg["length"],
                           compute_dtype=compute, cache_dtype=cache)
    if ctx.fault == "answer_altered":
        real_step_rows = model.step_rows

        def step_rows(idx, obs):  # one answer altered where it is produced
            preds = np.array(real_step_rows(idx, obs))
            preds[0, 0] += 1.0
            return preds
        model.step_rows = step_rows
    counters, timer = EventCounters(), StageTimer()
    server = PolicyServer("tcp://127.0.0.1:*", model,
                          max_batch=srv["max_batch"], tick_ms=srv["tick_ms"],
                          buckets=srv["buckets"], counters=counters,
                          timer=timer)
    stop = threading.Event()
    thread = threading.Thread(target=server.serve_forever, args=(stop,),
                              daemon=True)
    child = None
    try:
        thread.start()
        child = subprocess.Popen(
            [sys.executable, os.path.abspath(closed_loop_clients.__file__),
             "--address", server.address, "--seed", str(ctx.seed),
             "--spec", json.dumps(traffic)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            env=common.child_env())
        _warm(model, server.buckets, traffic["clients"],
              traffic["prefix_lengths"])
        if child.stdout.readline().strip() != b"ready":
            raise RuntimeError("the load generator did not come up")
        compiles_before = compiles.n
        trace = common.TraceWindow(ctx.trace, ctx.seconds,
                                   delay_s=traffic["ramp_s"])

        # -- the ramp (set-up), then the window: the child's clock runs both,
        # and the server's counters and spans are read as it opens and closes
        trace.arm()
        child.stdin.write(f"go {ctx.seconds}\n".encode())
        child.stdin.flush()
        opens = time.monotonic() + traffic["ramp_s"]
        setup_s = opens - ctx.t_start
        time.sleep(traffic["ramp_s"])
        before, stages_before = counters.snapshot(), _stages(timer)
        time.sleep(max(0.0, opens + ctx.seconds - time.monotonic()))
        after, stages_after = counters.snapshot(), _stages(timer)
        blob = child.stdout.read()  # until the child has closed its end
        child.wait(timeout=60)
        if child.returncode != 0 or not blob:
            raise RuntimeError(
                f"the load generator failed (exit {child.returncode})")
        load = pickle.loads(blob)  # written by this program's own child
        compiles_in_window = compiles.n - compiles_before
        traced = trace.reduce()
        peak = common.memory_peak_bytes()
        stages = {name: {k: stages_after[name][k] - stages_before[name][k]
                         for k in ("total_s", "count")}
                  for name in stages_after}
    finally:
        stop.set()
        if child is not None:
            common.stop_children([child])
        thread.join(timeout=30)
        server.close()
        jax.config.update("jax_default_matmul_precision", precision_before)
    events = {k: after.get(k, 0) - before.get(k, 0) for k in after}

    # -- free the program's state, then the reference over the sample
    del model, server, params
    checks = common.Checks(check["limits"])
    t_ref = time.monotonic()
    checks.add("rpcs_failed", load["failed"], 0.0)
    checks.add("episodes_inexact", load["episodes"] - load["episodes_exact"],
               0.0)
    checks.add("no_episode_to_check", float(not load["sample"]), 0.0)
    gaps = _compare(ctx, reference, model_cfg, traffic, load["sample"],
                    cfg["control_quant"] if ctx.control else None)
    checks.add("pred_gap_max", gaps["max"])
    checks.add("pred_gap_rms", gaps["rms"])
    return {
        "attempted": load["attempted"], "failed": load["failed"],
        "setup_s": setup_s, "window_s": load["seconds"],
        "step_s": load["step_s"], "reset_s": load["reset_s"],
        "replies_in_window": load["replies_in_window"],
        "sum_pos_in_window": load["sum_pos_in_window"],
        "events": events, "stages": stages,
        "compiles_in_window": compiles_in_window,
        "memory_peak_bytes": peak, "trace": traced, "checks": checks,
        "reference_s": time.monotonic() - t_ref,
        "notes": {"episodes": load["episodes"],
                  "episodes_finished": load["episodes_finished"],
                  "episodes_checked": len(load["sample"]),
                  "prediction_gaps": gaps,
                  "client_errors": load["errors"]},
    }


def _compare(ctx, reference, model_cfg, traffic, sample, control_quant):
    """One reference pass over each sampled episode's prefix and served
    observations (padded to one length, which a causal model ignores), and
    every served prediction against the reference's at its position.  With
    ``control_quant`` the reference computed in that lower precision takes
    the served predictions' place."""
    import jax

    if not sample:
        nan = float("nan")
        return {"max": nan, "rms": nan, "n": 0}
    span = max(traffic["prefix_lengths"]) + traffic["steps_max"]
    seqs = np.zeros((len(sample), span, model_cfg["obs_dim"]), np.float32)
    where = []
    for i, (client, index, preds) in enumerate(sample):
        prefix, obs = closed_loop_clients.episode_plan(
            traffic, ctx.seed, client, index)
        n = len(prefix) + len(obs)
        seqs[i, :n] = np.concatenate([prefix, obs])
        where.append((len(prefix) - 1, len(preds)))
    params = reference.make_params(model_cfg, ctx.seed)

    def answers(quant):
        ref = np.asarray(jax.jit(
            lambda p, x: reference.forward(p, x, quant))(params, seqs))
        return np.concatenate([ref[i, lo:lo + n]
                               for i, (lo, n) in enumerate(where)])

    got = np.concatenate([preds for _, _, preds in sample])
    if control_quant:
        got = answers(control_quant)
    worst, rms = reference.prediction_gaps(got, answers(None))
    return {"max": worst, "rms": rms, "n": len(got)}
