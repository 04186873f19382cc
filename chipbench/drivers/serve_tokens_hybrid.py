"""Driver: closed-loop serving of a token model of mixed layer kinds
(state-space, window and full differential attention over one shared K/V,
gated memory units) through an in-thread ``PolicyServer``.

The arrangement is ``serve_tokens.py``'s (that driver names the reference
of its own model, so this one stands beside it): the harness's process
holds the chip, makes the weights from the seed on the device, builds
``SeqFormerModel`` and ``PolicyServer`` and serves in a thread; the only
child is the jax-free load generator ``closed_loop_token_clients.py``.
Once the window has closed and the pool is freed, the plain reference
(``chipbench/reference_phi4flash.py``) runs once over a seeded sample of
the finished episodes and every served reply is held against it.

**Every slot has had a tenant.**  The warm-up admits a seeded prefix of the
traffic's shortest length into every slot of the pool, so that each holds
the recurrent state and the position of an earlier episode, as the slots
of a server that has been up do.  Whatever episode the sample draws, its
slot was used before it, and a reset that leaves the state behind
(``--fault state_not_reset``) is seen in every reply.
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
from chipbench.drivers.serve_tokens import ALTERED_BY, _warm
from chipbench.traffic import closed_loop_token_clients

FAULTS = (None, "answer_altered", "state_not_reset")


def build_model(cfg, seed, fault=None):
    """(the seeded arrays, the served model over them)."""
    import jax
    import jax.numpy as jnp

    from blendjax.models import seqformer
    from blendjax.serve.server import SeqFormerModel
    from chipbench import reference_phi4flash

    if not hasattr(seqformer, "hybrid_layer_kinds"):
        raise SystemExit("chipbench: this program serves no model of mixed "
                         "layer kinds (blendjax.models.seqformer."
                         "hybrid_layer_kinds)")
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    arrays = reference_phi4flash.make_params(cfg, seed,
                                             dtypes[cfg["param_dtype"]])
    served = seqformer.describe_token_model(
        jax.tree.map(lambda x: x, arrays), cfg)
    model = SeqFormerModel(served, cfg["slots"], cfg["length"],
                           compute_dtype=dtypes[cfg["compute_dtype"]],
                           cache_dtype=dtypes[cfg["cache_dtype"]])
    if fault == "answer_altered":
        real_step_rows = model.step_rows

        def step_rows(idx, obs):  # one answer altered where it is produced
            replies = np.array(real_step_rows(idx, obs))
            replies[0, 0] += ALTERED_BY * cfg["hidden_size"] ** 0.5
            return replies
        model.step_rows = step_rows
    return arrays, model


def _tenant_every_slot(model, length, vocab, seed):
    """Admit a seeded prefix of ``length`` ids (a length already compiled)
    into every slot: each then holds a tenant's recurrent state and
    position."""
    rng = np.random.default_rng((int(seed), 4))
    for slot in range(model.slots):
        model.prefill_rows(np.asarray([slot]), rng.integers(
            0, vocab, (length, 1), dtype=np.int32))


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
        raise ValueError("an episode would outgrow the full-length cache")
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
        _tenant_every_slot(model, min(traffic["prefix_lengths"]),
                           cfg["vocab_size"], ctx.seed)
        if ctx.fault == "state_not_reset":
            model.reset_rows = lambda idx: None  # the reset never lands
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
    import jax.numpy as jnp

    from chipbench import reference_phi4flash as reference

    nan = float("nan")
    if not sample:
        return {"logit_gap_p50": nan, "logit_gap_rms": nan,
                "logit_gap_max": nan, "lse_gap_max": nan, "n": 0}
    span = max(traffic["prefix_lengths"]) + traffic["steps_max"]
    most = traffic["steps_max"] + 1

    def view(ids, pos, served_ids, quant):
        x = reference.hidden(arrays, cfg, ids, quant)[pos]
        return [np.asarray(a) for a in reference.served_view(
            arrays, x, served_ids, quant)]

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
        args = jnp.asarray(ids), jnp.asarray(pos), jnp.asarray(served_ids)
        top, lse, std = view(*args, None)
        if control_quant:
            low_top, low_lse, _ = view(*args, control_quant)
            replies = np.concatenate(
                [low_top[:n], served_ids[:n], low_lse[:n, None]], 1)
        got.append(replies)
        ref_top.append(top[:n])
        ref_lse.append(lse[:n])
        ref_std.append(std[:n])
    gaps = reference.reply_gaps(
        np.concatenate(got), np.concatenate(ref_top),
        np.concatenate(ref_lse), np.concatenate(ref_std))
    return dict(gaps, n=int(sum(len(r) for r in got)))
