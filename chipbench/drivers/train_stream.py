"""Driver: world-model training from the stream.

Producers (``chipbench/traffic/episode_producer.py``, the real
``DataPublisher`` wire over the shm ring) -> ``RemoteIterableDataset`` ->
default ``JaxStream`` -> ``make_train_step`` on ``episode_loss_fn`` with the
flash kernel.  Set-up builds ONE compiled step with its state, drives it from
the seed through its first three steps by the window's own call and feed, and
hands that same object to the window.  Once the window has closed and the
program's state is freed, the plain reference follows those three steps.
"""

from __future__ import annotations

import collections
import functools
import os
import subprocess
import sys
import time

import numpy as np

from chipbench import common
from chipbench.traffic import episode_producer

FAULTS = (None, "state_unchanged", "half_batch")


def _launch_producers(traffic, model, seed, nice=10):
    if traffic["transport"] != "shm":
        raise ValueError(f"unknown transport {traffic['transport']!r}")
    env = common.child_env()
    lo, hi = traffic["amplitude"]
    addrs, procs = [], []
    for i in range(traffic["producers"]):
        addr = f"shm://bjx-chipbench-{os.getpid()}-{i}"
        cmd = ["nice", "-n", str(nice), sys.executable,
               os.path.abspath(episode_producer.__file__),
               "--addr", addr, "--btid", str(i), "--seed", str(seed),
               "--seq-len", str(traffic["episode_len"]),
               "--obs-dim", str(model["obs_dim"]),
               "--amp-lo", str(lo), "--amp-hi", str(hi)]
        if traffic["raw_buffers"]:
            cmd.append("--raw")
        procs.append(subprocess.Popen(cmd, env=env))
        addrs.append(addr)
    return addrs, procs


def _published(traffic, model, seed, btid, frameid):
    lo, hi = traffic["amplitude"]
    return np.stack([
        episode_producer.episode(seed, b, f, traffic["episode_len"],
                                 model["obs_dim"], lo, hi)
        for b, f in zip(btid, frameid)])


def run(ctx):
    import jax
    import jax.numpy as jnp
    import optax

    from blendjax.btt.dataset import RemoteIterableDataset
    from blendjax.btt.prefetch import JaxStream
    from blendjax.models import seqformer
    from blendjax.models.train import TrainState, make_train_step
    from blendjax.native import native_available, unlink_address
    from blendjax.ops.flash_attention import make_flash_attention
    from chipbench import reference

    cfg, traffic, check = ctx.config, ctx.workload["traffic"], \
        ctx.workload["check"]
    model, opt_cfg = cfg["model"], cfg["optimizer"]
    batch_size, seq = cfg["batch_size"], cfg["seq_len"]
    if traffic["episode_len"] != seq + 1 or model["max_len"] < seq:
        raise ValueError("episode_len must be seq_len + 1 <= max_len + 1")
    if ctx.fault not in FAULTS:
        raise ValueError(f"unknown fault {ctx.fault!r}")
    if not native_available():  # builds the shm ring once, before any child
        raise RuntimeError("the native shm ring did not build or load")
    compute = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["compute_dtype"]]
    n_ref = check["reference_steps"]
    compiles = common.CompileCounter()

    # -- the one object the window drives: the compiled step and its state
    attn = cfg["attention"]
    loss_fn = functools.partial(
        seqformer.episode_loss_fn, compute_dtype=compute,
        attn_fn=make_flash_attention(
            causal=attn["causal"], block_q=attn["block_q"],
            block_kv=attn["block_kv"]))
    if ctx.fault == "half_batch":
        whole = loss_fn

        def loss_fn(params, batch):  # the mean taken over half of the rows
            return whole(params, {"episode": batch["episode"][
                :batch["episode"].shape[0] // 2]})
    opt = optax.adam(opt_cfg["learning_rate"], b1=opt_cfg["b1"],
                     b2=opt_cfg["b2"], eps=opt_cfg["eps"])
    train_step = make_train_step(loss_fn, opt, donate=cfg["donate_state"])
    if ctx.fault == "state_unchanged":
        real_step = train_step

        def train_step(state, batch):  # returns its state as it got it
            kept = jax.tree.map(jnp.copy, state)
            _, loss = real_step(state, batch)
            return kept, loss
    state = TrainState.create(reference.make_params(model, ctx.seed), opt)
    grad_norms_of = jax.jit(lambda mu: reference.leaf_norms(mu)
                            / (1.0 - opt_cfg["b1"]))

    def transform(batch):
        return {"episode": batch["obs_seq"], "btid": batch["btid"],
                "frameid": batch["frameid"]}

    addrs, procs = _launch_producers(traffic, model, ctx.seed)
    stream = it = None
    try:
        ds = RemoteIterableDataset(addrs, max_items=10**9, timeoutms=60000)
        stream = JaxStream(ds, batch_size=batch_size,
                           num_workers=traffic["stream_workers"],
                           transform=transform)
        it = iter(stream)

        # -- set-up: the first steps, through the window's own call and feed
        kept, first_losses, grad_norms = [], [], None
        for i in range(n_ref):
            batch = next(it)
            kept.append(batch)
            state, loss = train_step(state, batch)
            first_losses.append(loss)
            if i == 0:
                grad_norms = grad_norms_of(state.opt_state[0].mu)
        delta = reference.delta_norms(
            state.params, reference.make_params(model, ctx.seed))
        jax.block_until_ready((state, delta, grad_norms))

        # -- the window
        trace = common.TraceWindow(ctx.trace, ctx.seconds)
        pending = collections.deque()
        losses, wait_s = [], 0.0
        compiles_before = compiles.n
        trace.arm()
        t0 = time.monotonic()
        setup_s = t0 - ctx.t_start
        while time.monotonic() - t0 < ctx.seconds:
            t_wait = time.monotonic()
            batch = next(it)
            wait_s += time.monotonic() - t_wait
            state, loss = train_step(state, batch)
            losses.append(loss)
            pending.append(loss)
            if len(pending) > traffic["run_ahead_steps"]:
                jax.block_until_ready(pending.popleft())
        jax.block_until_ready(state)
        window_s = time.monotonic() - t0
        compiles_in_window = compiles.n - compiles_before
        kept.append(batch)  # the window's last batch, for the feed's check
        losses = np.asarray(jnp.stack(losses), np.float64)
        traced = trace.reduce()
        peak = common.memory_peak_bytes()
    finally:
        if it is not None:
            it.close()
        if stream is not None:
            stream.close()
        common.stop_children(procs)
        for a in addrs:
            unlink_address(a)

    # -- what the program made, small enough to keep; then free its state
    first_losses = np.asarray(jnp.stack(first_losses), np.float64)
    grad_norms = np.asarray(grad_norms, np.float64)
    delta = np.asarray(delta, np.float64)
    got = [(np.asarray(b["episode"]), np.asarray(b["btid"]),
            np.asarray(b["frameid"])) for b in kept]
    del state, kept, batch, loss, pending
    steps = len(losses)
    failed = int(np.sum(~np.isfinite(losses)))

    # -- the comparison: feed exact, then the reference's three steps
    checks = common.Checks(check["limits"])
    t_ref = time.monotonic()
    published = [_published(traffic, model, ctx.seed, b, f)
                 for _, b, f in got]
    feed_gap = max(float(np.max(np.abs(e - p)))
                   for (e, _, _), p in zip(got, published))
    rows = np.concatenate([e.reshape(len(e), -1) for e, _, _ in got[:n_ref]])
    checks.add("feed_max_abs_diff", feed_gap, 0.0)
    checks.add("rows_repeated", len(rows) - len(np.unique(rows, axis=0)), 0.0)
    params0 = reference.make_params(model, ctx.seed)
    ref = reference.train_reference(
        params0, published[:n_ref], opt_cfg, check["reference_row_block"])
    if ctx.control:
        # the control: the reference in the program's place, computed in
        # the nearest precision below the one the configuration states
        low = reference.train_reference(
            params0, published[:n_ref], opt_cfg,
            check["reference_row_block"], quant=cfg["control_quant"])
        first_losses, grad_norms, delta = (
            low["losses"], low["grad_norms"], low["delta_norms"])
    del params0
    for i in range(n_ref):
        checks.add(f"loss{i + 1}_gap", abs(first_losses[i] - ref["losses"][i])
                   / abs(ref["losses"][i]))
    gap, leaf = reference.worst_leaf_gap(grad_norms, ref["grad_norms"])
    checks.add("grad_norm_gap", gap)
    moved = reference.moved_leaves(ref["grad_norms"])
    dgap, dleaf = reference.worst_leaf_gap(delta, ref["delta_norms"], moved)
    checks.add("delta_norm_gap", dgap)
    return {
        "attempted": steps, "failed": failed,
        "setup_s": setup_s, "window_s": window_s, "steps": steps,
        "tokens": steps * batch_size * seq,
        "batch_wait_s": wait_s,
        "compiles_in_window": compiles_in_window,
        "memory_peak_bytes": peak, "trace": traced, "checks": checks,
        "reference_s": time.monotonic() - t_ref,
        "notes": {"worst_grad_leaf": leaf, "worst_delta_leaf": dleaf,
                  "leaves_counted": int(moved.sum()),
                  "leaves": int(len(moved)),
                  "loss_first": float(losses[0]) if steps else None,
                  "loss_last": float(losses[-1]) if steps else None},
    }
