"""Driver: closed-loop serving of a token world model (latent attention, a
held share of routed experts) through an in-thread ``PolicyServer``.

The arrangement is ``serve_closed.py``'s: the harness's own process holds
the chip, makes the weights (on the device, leaf by leaf from the seed),
builds ``SeqFormerModel`` and ``PolicyServer`` and runs ``serve_forever`` in
a thread; the only child is the jax-free load generator
(``chipbench/traffic/closed_loop_token_clients.py``), whose clients drive
``reset(prefix=)`` / ``step`` / ``close_episode`` with int32 token ids over
the RPC wire.  Once the window has closed and the server's pool is freed,
the plain reference (``chipbench/reference_sarvam.py``) runs once over a
seeded sample of the finished episodes, the longest among them, and every
served reply (the top 8 logits at the ids the server named, and the
logsumexp) is held against it.  That covers prefill through the expanded
path and decode through the absorbed path and the pool at the timed load.
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
from chipbench.drivers.serve_closed import _stages
from chipbench.traffic import closed_loop_token_clients

FAULTS = (None, "answer_altered", "shared_expert_left_out")
ALTERED_BY = 8.0  # logits; the logits' standard deviation is about 1


def _warm(model, buckets, clients, prefix_lengths):
    """Compile the shapes this cell's traffic uses and no others; all of it
    lands on the pad row, which no episode reads."""
    for b in buckets:
        model.step_rows(np.full(b, model.pad_slot, np.int64),
                        np.zeros((b, 1), np.int32))
        if b >= clients:
            break
    for n in prefix_lengths:
        model.prefill_rows(np.asarray([model.pad_slot]),
                           np.zeros((n, 1), np.int32))
    model.reset_rows(np.asarray([model.pad_slot]))
    model.drain_events()


def build_model(cfg, seed, fault=None):
    """(the seeded arrays, the served model over them)."""
    import jax
    import jax.numpy as jnp

    from blendjax.models import seqformer
    from blendjax.serve.server import SeqFormerModel
    from chipbench import reference_sarvam

    if not hasattr(seqformer, "describe_token_model"):
        raise SystemExit("chipbench: this program serves no token model "
                         "(blendjax.models.seqformer.describe_token_model)")
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    arrays = reference_sarvam.make_params(cfg, seed,
                                          dtypes[cfg["param_dtype"]])
    served = seqformer.describe_token_model(
        jax.tree.map(lambda x: x, arrays), cfg, cfg.get("held_first", 0))
    if fault == "shared_expert_left_out":
        for blk in served["blocks"]:
            blk.get("moe", {}).pop("shared", None)
    model = SeqFormerModel(served, cfg["slots"], cfg["length"],
                           compute_dtype=dtypes[cfg["compute_dtype"]],
                           cache_dtype=dtypes[cfg["cache_dtype"]])
    if fault == "answer_altered":
        real_step_rows = model.step_rows

        def step_rows(idx, obs):  # one answer altered where it is produced
            replies = np.array(real_step_rows(idx, obs))
            replies[0, 0] += ALTERED_BY
            return replies
        model.step_rows = step_rows
    return arrays, model


def run(ctx):
    import jax

    from blendjax.serve.server import PolicyServer
    from blendjax.utils.timing import EventCounters, StageTimer

    cfg, check = ctx.config, ctx.workload["check"]
    srv = cfg["server"]
    traffic = dict(ctx.workload["traffic"], vocab_size=cfg["vocab_size"],
                   obs_dim=1, sample_episodes=check["sample_episodes"])
    if ctx.fault not in FAULTS:
        raise ValueError(f"unknown fault {ctx.fault!r}")
    if max(traffic["prefix_lengths"]) + traffic["steps_max"] > cfg["length"]:
        raise ValueError("an episode would outgrow the cache ring")
    compiles = common.CompileCounter()
    precision_before = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision",
                      cfg["matmul_precision"])

    arrays, model = build_model(cfg, ctx.seed, ctx.fault)
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
            [sys.executable,
             os.path.abspath(closed_loop_token_clients.__file__),
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

    # -- free the pool and the programs, then the reference over the sample
    del model, server
    checks = common.Checks(check["limits"])
    t_ref = time.monotonic()
    checks.add("rpcs_failed", load["failed"], 0.0)
    checks.add("episodes_inexact", load["episodes"] - load["episodes_exact"],
               0.0)
    checks.add("no_episode_to_check", float(not load["sample"]), 0.0)
    gaps = compare(cfg, arrays, traffic, ctx.seed, load["sample"],
                   cfg["control_quant"] if ctx.control else None)
    for name in ("logit_gap_p50", "logit_gap_rms", "logit_gap_max",
                 "lse_gap_max"):
        checks.add(name, gaps[name])
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
                  "reply_gaps": gaps,
                  # the window's whole counters, for PERF.md's breakdown
                  "events": events, "stages": stages,
                  "client_errors": load["errors"]},
    }


def compare(cfg, arrays, traffic, seed, sample, control_quant):
    """One reference pass over each sampled episode's ids (padded to one
    length, which a causal model ignores): the reference's logits at the
    ids each served reply names, its logsumexp, and its logits' standard
    deviation, at every served position.  With ``control_quant`` the
    reference computed in that lower precision takes the served replies'
    place."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_sarvam

    nan = float("nan")
    if not sample:
        return {"logit_gap_p50": nan, "logit_gap_rms": nan,
                "logit_gap_max": nan, "lse_gap_max": nan, "n": 0}
    span = max(traffic["prefix_lengths"]) + traffic["steps_max"]
    most = traffic["steps_max"] + 1

    @jax.jit
    def view(params, ids, pos, served_ids):
        def at(quant):
            logits = reference_sarvam.forward(params, cfg, ids, quant)[pos]
            top, lse = reference_sarvam.served_view(logits, served_ids)
            return top, lse, logits.std(-1)
        return at(None), at(control_quant) if control_quant else None

    got, ref_top, ref_lse, ref_std = [], [], [], []
    for client, index, replies in sample:
        prefix, steps = closed_loop_token_clients.episode_plan(
            traffic, seed, client, index)
        n = len(replies)
        k = (replies.shape[1] - 1) // 2
        ids = np.zeros(span, np.int32)
        ids[:len(prefix) + len(steps)] = np.concatenate([prefix, steps])[:, 0]
        pos = np.minimum(len(prefix) - 1 + np.arange(most),
                         len(prefix) - 1 + n - 1)
        served_ids = np.zeros((most, k), np.int32)
        served_ids[:n] = replies[:, k:2 * k].astype(np.int32)
        (top, lse, std), control = jax.device_get(view(
            arrays, jnp.asarray(ids), jnp.asarray(pos),
            jnp.asarray(served_ids)))
        if control is not None:
            replies = np.concatenate(
                [control[0][:n], served_ids[:n], control[1][:n, None]], 1)
        got.append(replies)
        ref_top.append(top[:n])
        ref_lse.append(lse[:n])
        ref_std.append(std[:n])
    gaps = reference_sarvam.reply_gaps(
        np.concatenate(got), np.concatenate(ref_top),
        np.concatenate(ref_lse), np.concatenate(ref_std))
    return dict(gaps, n=int(sum(len(r) for r in got)))
