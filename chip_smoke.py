#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that blendjax still starts on the chip.

Run from the root of a checkout on a machine with a TPU attached::

    python chip_smoke.py

It drives the SeqFormer world model — the model every ROADMAP speed aim is
stated on — through the entry points a user would call, at the width the
benchmarks call their default (``obs_dim=32, d_model=1024, n_heads=8,
n_layers=8``, 512 positions, batch 8, bf16 compute, Adam), with random
weights made from a seed, and checks what comes out against the repo's own
references.  Four legs, each a child process so exactly one process holds
the chip at any moment (this parent never imports jax):

- ``kernels`` — every Pallas kernel the package ships, COMPILED, against
  its ``jax.numpy`` reference: ``flash_attention`` forward and all three
  gradients vs ``full_attention``, ``decode_frames_pallas`` vs
  ``decode_frames``;
- ``train``   — ``stream_producer.py`` producers -> ``RemoteIterableDataset``
  -> ``JaxStream`` -> ``make_train_step(episode_loss_fn + flash)`` for a
  handful of steps, then the ``block_until_ready`` fence check in the same
  process;
- ``serve``   — a ``ServerProcess`` child answering ``reset(prefix=)`` /
  ``step`` / ``close_episode``, replayed against serial ``decode_step``
  after the server has exited;
- ``mesh``    — the sharded SeqFormer step on ``data=1 x seq=2 x model=2``
  (``ring_flash`` then ``ulysses_flash``); needs four devices, otherwise
  reports ``"skipped"``.

Every leg prints one JSON line (platform, device_kind, device count, jax
version, cold-compile seconds, whether the lowered step holds the Mosaic
custom call).  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Any failed check, a leg past its deadline, or a platform other than
``tpu`` exits non-zero with no result line.  There is no CPU mode: the
rehearsal is ``tests/test_smoke_chip.py``, which calls the same leg
functions at ``TINY`` sizes on the virtual CPU mesh.  Step times printed
here are set-up diagnostics, NOT a benchmark.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))

#: whole-run budget (the driver allows 1200 s, compilation included)
TOTAL_DEADLINE_S = 1150.0
LEGS = ("kernels", "train", "serve", "mesh")

#: bf16 peak FLOP/s by ``device_kind`` (Google Cloud "TPU v5e" page: 197
#: TFLOP/s).  Only the fence check reads it; an unknown kind is an error.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12, "TPU v5e": 197e12}

# (name, B, T, Hq, Hkv, D, window)
_FULL_KERNEL_CASES = (
    ("causal_t512_d128", 2, 512, 8, 8, 128, None),
    ("window192_t512_d128", 2, 512, 8, 8, 128, 192),
    ("gqa8x2_t512_d128", 2, 512, 8, 2, 128, None),
    ("causal_t512_d64", 2, 512, 8, 8, 64, None),
    ("causal_t4096_d128", 1, 4096, 8, 8, 128, None),
    ("gqa8x2_window1024_t4096_d64", 1, 4096, 8, 2, 64, 1024),
)
_TINY_KERNEL_CASES = (
    ("causal_t64_d16", 1, 64, 4, 4, 16, None),
    ("gqa4x2_window96_t256_d8", 1, 256, 4, 2, 8, 96),
)

FULL = {
    "model": dict(obs_dim=32, d_model=1024, n_heads=8, n_layers=8),
    "seq": 512,
    "batch": 8,
    "kernel_cases": _FULL_KERNEL_CASES,
    "frames": (8, 480, 640, 3),
    "producers": 2,
    "train_steps": 6,
    "fence": dict(n=64, dim=4096),
    "serve": dict(slots=64, length=512, prefixes=(64, 128), clients=4,
                  solo_steps=8, rounds=8),
}
TINY = {
    "model": dict(obs_dim=4, d_model=32, n_heads=4, n_layers=2),
    "seq": 64,
    "batch": 2,
    "kernel_cases": _TINY_KERNEL_CASES,
    "frames": (2, 16, 16, 3),
    "producers": 1,
    "train_steps": 3,
    "fence": dict(n=2, dim=128),
    "serve": dict(slots=4, length=32, prefixes=(4, 6), clients=3,
                  solo_steps=2, rounds=3),
}

#: flash (bf16 in, f32 accumulate) against the float32 highest-precision
#: reference: worst element within this share of the reference's scale
KERNEL_TOL = 2e-2
#: bf16 train loss, flash vs full attention on the same params and batch
LOSS_TOL = 2e-2
#: client RPC deadline for calls that may sit behind a cold full-width
#: compile (the client default is 5 s with one retry)
COLD_RPC_MS = 300_000


class SmokeFailure(AssertionError):
    """A leg's check did not hold."""


def _check(cond, message):
    if not cond:
        raise SmokeFailure(message)


# ---------------------------------------------------------------------------
# helpers that need jax (imported inside: the parent stays jax-free)
# ---------------------------------------------------------------------------


def _tag():
    import jax

    from blendjax.utils.device import device_info

    return {**device_info(), "jax": jax.__version__}


def _has_mosaic(jitted, *args):
    """Does the lowered program hold a compiled Pallas kernel?  (The
    interpreter lowers to plain HLO, so this is False off-TPU.)"""
    return "tpu_custom_call" in jitted.lower(*args).as_text()


def _scaled_err(got, ref):
    """Worst absolute error as a share of the reference's scale."""
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    _check(np.isfinite(got).all(), "non-finite values")
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-6))


# ---------------------------------------------------------------------------
# leg: kernels
# ---------------------------------------------------------------------------


def leg_kernels(size):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from blendjax.ops.flash_attention import flash_attention, flash_block_size
    from blendjax.ops.image import decode_frames, decode_frames_pallas
    from blendjax.parallel.ring_attention import full_attention

    out = {"leg": "kernels", **_tag(), "cases": {}}
    compile_s = 0.0
    mosaic = True
    for name, b, t, hq, hkv, d, window in size["kernel_cases"]:
        blk = flash_block_size(t, d, jnp.bfloat16, window)
        keys = jax.random.split(jax.random.PRNGKey(len(name) + t), 4)
        q = jax.random.normal(keys[0], (b, t, hq, d), jnp.bfloat16)
        k = jax.random.normal(keys[1], (b, t, hkv, d), jnp.bfloat16)
        v = jax.random.normal(keys[2], (b, t, hkv, d), jnp.bfloat16)
        w = jax.random.normal(keys[3], (b, t, hq, d), jnp.float32)

        def flash_loss(q, k, v, w, blk=blk, window=window):
            o = flash_attention(q, k, v, True, None, blk, blk, None, window)
            return jnp.sum(o.astype(jnp.float32) * w), o

        def ref_loss(q, k, v, w, window=window):
            with jax.default_matmul_precision("highest"):
                o = full_attention(
                    q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), causal=True, window=window,
                )
            return jnp.sum(o * w), o

        flash = jax.jit(jax.value_and_grad(flash_loss, (0, 1, 2),
                                           has_aux=True))
        ref = jax.jit(jax.value_and_grad(ref_loss, (0, 1, 2), has_aux=True))
        mosaic = mosaic and _has_mosaic(flash, q, k, v, w)
        t0 = time.perf_counter()
        (_, o_f), g_f = jax.block_until_ready(flash(q, k, v, w))
        compile_s += time.perf_counter() - t0
        (_, o_r), g_r = ref(q, k, v, w)
        errs = {"out": _scaled_err(o_f, o_r)}
        for gname, gf, gr in zip(("dq", "dk", "dv"), g_f, g_r):
            errs[gname] = _scaled_err(gf, gr)
        out["cases"][name] = {k_: round(e, 5) for k_, e in errs.items()}
        for what, e in errs.items():
            _check(e <= KERNEL_TOL,
                   f"flash {name}: {what} off by {e:.4f} of scale "
                   f"(> {KERNEL_TOL}) against full_attention")

    frames = jnp.asarray(np.random.default_rng(0).integers(
        0, 256, size["frames"], dtype=np.uint8))
    for dtype, linearize, tol in ((jnp.float32, False, 1e-6),
                                  (jnp.bfloat16, True, 1e-2)):
        mosaic = mosaic and _has_mosaic(
            decode_frames_pallas, frames, dtype, linearize)
        t0 = time.perf_counter()
        got = jax.block_until_ready(
            decode_frames_pallas(frames, dtype, linearize))
        compile_s += time.perf_counter() - t0
        _check(got.shape == frames.shape and got.dtype == dtype,
               f"decode_frames_pallas returned {got.shape} {got.dtype}")
        err = _scaled_err(got, decode_frames(frames, dtype, linearize))
        name = f"decode_{jnp.dtype(dtype).name}_lin{int(linearize)}"
        out["cases"][name] = {"out": round(err, 7)}
        _check(err <= tol, f"{name}: off by {err} (> {tol}) against "
                           "decode_frames")
    out["cold_compile_s"] = round(compile_s, 1)
    out["mosaic"] = mosaic
    return out


# ---------------------------------------------------------------------------
# leg: train (+ fence, in the same process)
# ---------------------------------------------------------------------------


def _fence_check(n, dim, device_kind):
    """N chained dim^3 bf16 matmuls, timed to ``block_until_ready`` and to
    a value fetch.  ``block_until_ready`` is a real fence iff the rate it
    implies is under the chip's peak and the two clocks agree."""
    import jax
    import jax.numpy as jnp

    from blendjax.utils.fence import value_fence

    x = jax.random.normal(jax.random.PRNGKey(0), (dim, dim), jnp.bfloat16)
    w = (jax.random.normal(jax.random.PRNGKey(1), (dim, dim), jnp.float32)
         / dim ** 0.5).astype(jnp.bfloat16)

    @jax.jit
    def chain(x, w):
        for _ in range(n):
            x = x @ w
        return x

    value_fence(chain(x, w))  # compile, land the operands, warm the fetch
    block_s = fetch_s = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(chain(x, w))
        block_s = min(block_s, time.perf_counter() - t0)
        t0 = time.perf_counter()
        value_fence(chain(x, w))
        fetch_s = min(fetch_s, time.perf_counter() - t0)
    flops = 2.0 * dim ** 3 * n
    out = {
        "chain": [n, dim],
        "block_until_ready_s": round(block_s, 5),
        "value_fetch_s": round(fetch_s, 5),
        "block_implied_tflops": round(flops / block_s / 1e12, 1),
        "fetch_implied_tflops": round(flops / fetch_s / 1e12, 1),
        "note": "fence validity check, not a benchmark",
    }
    peak = PEAK_BF16_FLOPS.get(device_kind)
    if peak is not None:
        out["peak_tflops"] = peak / 1e12
        _check(flops / fetch_s <= peak * 1.02,
               f"a VALUE FETCH implies {out['fetch_implied_tflops']} "
               "TFLOP/s, above the chip's peak: the clock or the FLOP "
               "count is wrong")
        out["block_until_ready_fences"] = bool(
            flops / block_s <= peak * 1.02
            and abs(block_s - fetch_s) <= 0.25 * fetch_s
        )
    return out


def leg_train(size, require_peak=False):
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmarks._common import launch_fleet
    from blendjax.btt.dataset import RemoteIterableDataset
    from blendjax.btt.launcher import child_env
    from blendjax.btt.prefetch import JaxStream
    from blendjax.models import seqformer
    from blendjax.models.train import TrainState, make_train_step
    from blendjax.ops.flash_attention import make_flash_attention

    out = {"leg": "train", **_tag()}
    if require_peak:
        _check(out["device_kind"] in PEAK_BF16_FLOPS,
               f"no peak on file for device_kind {out['device_kind']!r}")
    model, T, B = size["model"], size["seq"], size["batch"]

    def transform(batch):
        # NO copy: the device batch is fed straight from arena memory, so
        # a recycle before the transfer has really finished would show up
        # as a checksum mismatch below
        ep = batch["obs_seq"]
        return {"episode": ep,
                "host_sum": ep.view(np.uint32).sum(dtype=np.uint32)}

    dev_sum = jax.jit(lambda ep: jnp.sum(
        jax.lax.bitcast_convert_type(ep, jnp.uint32), dtype=jnp.uint32))

    flash_loss = functools.partial(
        seqformer.episode_loss_fn,
        attn_fn=make_flash_attention(causal=True, block_q="auto",
                                     block_kv="auto"),
    )
    opt = optax.adam(1e-4)
    train_step = make_train_step(flash_loss, opt)
    state = TrainState.create(
        seqformer.init(jax.random.PRNGKey(0), max_len=T, **model), opt)

    producers = launch_fleet(
        size["producers"],
        ["--mode", "episode", "--seq-len", str(T + 1),
         "--obs-dim", str(model["obs_dim"])],
        "smoke", transport="shm", raw=True, ring_nonce=str(os.getpid()),
        env=child_env(),
    )
    try:
        ds = RemoteIterableDataset(producers.addrs, max_items=10**9,
                                   timeoutms=60000)
        # defaults on purpose: transfer_gate='auto', arena='auto'
        stream = JaxStream(ds, batch_size=B, num_workers=size["producers"],
                           transform=transform)
        try:
            out["arena"] = stream.arena_pool is not None
            out["transfer_gate_engaged"] = stream.gate is not None
            out["host_cores"] = os.cpu_count()
            _check(out["arena"], "the default arena path did not engage")
            losses, step_s, sums_ok = [], [], 0
            it = iter(stream)
            try:
                for i in range(size["train_steps"]):
                    batch = next(it)
                    ep = batch["episode"]
                    _check(ep.shape == (B, T + 1, model["obs_dim"]),
                           f"episode batch shape {ep.shape}")
                    if i == 0:
                        full = float(jax.jit(seqformer.episode_loss_fn)(
                            state.params, batch))
                        out["mosaic"] = _has_mosaic(train_step, state, batch)
                    t0 = time.perf_counter()
                    state, loss = train_step(state, batch)
                    losses.append(float(jax.block_until_ready(loss)))
                    step_s.append(time.perf_counter() - t0)
                    # checked AFTER the step, once later batches have been
                    # scattered into recycled arenas behind this one
                    _check(int(dev_sum(ep)) == int(batch["host_sum"]),
                           f"batch {i}: device checksum != host checksum "
                           "(arena recycled before the transfer landed?)")
                    sums_ok += 1
            finally:
                it.close()
        finally:
            stream.close()
    finally:
        producers.close()

    _check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    rel = abs(losses[0] - full) / max(abs(full), 1e-6)
    _check(rel <= LOSS_TOL,
           f"step-0 loss {losses[0]} (flash) vs {full} (full_attention): "
           f"{rel:.4f} > {LOSS_TOL}")
    out.update(
        width={**model, "seq": T, "batch": B},
        losses=[round(x, 5) for x in losses],
        loss_full_attention=round(full, 5),
        loss_rel_diff=round(rel, 6),
        checksums_matched=sums_ok,
        cold_compile_s=round(step_s[0], 1),
        warm_step_s=round(min(step_s[1:]), 4),
        warm_step_note="set-up diagnostic, not a benchmark",
    )
    del state
    out["fence"] = _fence_check(device_kind=out["device_kind"],
                                **size["fence"])
    return out


# ---------------------------------------------------------------------------
# leg: serve
# ---------------------------------------------------------------------------


def leg_serve(size, require_platform=None):
    """The server child holds the chip while it lives, so this process
    stays off jax until the server has exited, then replays every episode
    through serial ``decode_step`` and compares."""
    import numpy as np

    from blendjax.serve.client import ServeClient
    from blendjax.serve.server import ServerProcess
    from blendjax.utils.timing import EventCounters

    model, sv = size["model"], size["serve"]
    seed = 0
    rng = np.random.default_rng(7)
    n_clients = sv["clients"]
    steps_of = [sv["solo_steps"] + sv["rounds"]] + [sv["rounds"]] * (
        n_clients - 1)
    prefixes = [
        rng.standard_normal(
            (sv["prefixes"][i % len(sv["prefixes"])], model["obs_dim"])
        ).astype(np.float32)
        for i in range(n_clients)
    ]
    obs = [rng.standard_normal((n, model["obs_dim"])).astype(np.float32)
           for n in steps_of]
    got = [[] for _ in range(n_clients)]
    cold = {}
    counters = EventCounters()

    def timed(label, fn):
        t0 = time.perf_counter()
        reply = fn()
        cold[label] = max(cold.get(label, 0.0), time.perf_counter() - t0)
        return reply

    t_spawn = time.perf_counter()
    with ServerProcess(
        model="seqformer", seed=seed, obs_dim=model["obs_dim"],
        slots=sv["slots"], length=sv["length"], ready_timeout=300.0,
        extra_args=["--d-model", str(model["d_model"]),
                    "--n-heads", str(model["n_heads"]),
                    "--n-layers", str(model["n_layers"])],
    ) as sp:
        ready_s = time.perf_counter() - t_spawn
        clients = [ServeClient(sp.address, counters=counters)
                   for _ in range(n_clients)]
        default_ms = clients[0].timeoutms
        try:
            hello = clients[0].hello()
            if require_platform is not None:
                _check(hello.get("platform") == require_platform,
                       f"server hello says platform "
                       f"{hello.get('platform')!r}, not {require_platform!r}")

            def admit(i):
                reply = timed(
                    f"prefill_t{len(prefixes[i])}",
                    lambda: clients[i].reset(prefix=prefixes[i],
                                             timeout_ms=COLD_RPC_MS))
                _check(reply["pos"] == len(prefixes[i]), "prefill pos")
                got[i].append(reply["pred"])

            def step(i, k):
                got[i].append(timed(
                    "step",
                    lambda: clients[i].step(obs[i][k],
                                            timeout_ms=COLD_RPC_MS),
                )["pred"])

            # one live episode: every tick is the 1-row bucket
            admit(0)
            for k in range(sv["solo_steps"]):
                step(0, k)
            before = clients[0].stats()["counters"]
            # every episode live, stepped in lock-step rounds: ticks fill
            # a wider bucket
            for i in range(1, n_clients):
                admit(i)
            barrier = threading.Barrier(n_clients)
            errors = []

            def run(i):
                try:
                    base = sv["solo_steps"] if i == 0 else 0
                    for k in range(sv["rounds"]):
                        barrier.wait(timeout=COLD_RPC_MS / 1000)
                        step(i, base + k)
                except BaseException as exc:  # noqa: BLE001 - re-raised below
                    errors.append(exc)
                    barrier.abort()

            threads = [threading.Thread(target=run, args=(i,), daemon=True)
                       for i in range(n_clients)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            if errors:
                raise errors[0]
            after = clients[0].stats()["counters"]
            closed = [c.close_episode() for c in clients]
        finally:
            for c in clients:
                c.close()
    _check(all(closed), f"close_episode answers: {closed}")
    wide_steps = n_clients * sv["rounds"]
    wide_batches = after["serve_batches"] - before["serve_batches"]
    _check(wide_batches < wide_steps,
           f"{wide_steps} concurrent steps took {wide_batches} batches: "
           "no bucket wider than one row ever ran")
    retries = counters.snapshot().get("retries", 0)
    _check(retries == 0 and after.get("serve_dup_inflight", 0) == 0,
           f"{retries} RPC retries, "
           f"{after.get('serve_dup_inflight')} duplicate requests")

    # -- the server has exited: replay on this process's own jax ----------
    import jax
    import jax.numpy as jnp

    from blendjax.models import seqformer

    params = seqformer.init(
        jax.random.PRNGKey(seed), max_len=max(sv["length"], 8), **model)
    # the server's own default precision (float32 compute and cache)
    decode = jax.jit(lambda params, cache, row: seqformer.decode_step(
        params, cache, row, compute_dtype=jnp.float32))
    worst = 0.0
    for i in range(n_clients):
        cache = seqformer.init_cache(params, 1, dtype=jnp.float32,
                                     length=sv["length"], per_row=True)
        ref = []
        for row in prefixes[i]:
            pred, cache = decode(params, cache, row[None])
        ref.append(pred[0])
        for row in obs[i]:
            pred, cache = decode(params, cache, row[None])
            ref.append(pred[0])
        err = _scaled_err(np.stack(got[i]), np.stack(ref))
        worst = max(worst, err)
        _check(err <= KERNEL_TOL,
               f"client {i}: served predictions off by {err:.4f} of scale "
               "against serial decode_step")
    slow = {k: round(v, 1) for k, v in cold.items()
            if v * 1000 > default_ms}
    return {
        "leg": "serve", **_tag(),
        "server_hello": {k: hello.get(k) for k in
                         ("platform", "device_kind", "device_count", "model",
                          "slots", "buckets")},
        "width": {**model, "slots": sv["slots"], "length": sv["length"]},
        "ready_s": round(ready_s, 1),
        "cold_rpc_s": {k: round(v, 1) for k, v in cold.items()},
        "cold_compile_s": round(sum(cold.values()), 1),
        # calls that would have outlasted the client's default deadline
        # (and been retried) had the smoke not passed a cold-compile one
        "rpcs_past_default_timeout": slow,
        "client_default_timeout_ms": default_ms,
        "steps": sum(steps_of), "wide_batches": wide_batches,
        "wide_steps": wide_steps, "rpc_retries": retries,
        "pred_err_vs_serial_decode": round(worst, 6),
        # the serve path ships no Pallas kernel today (ROADMAP S4)
        "mosaic": False,
    }


# ---------------------------------------------------------------------------
# leg: mesh
# ---------------------------------------------------------------------------


def leg_mesh(size):
    import jax
    import numpy as np
    import optax

    from blendjax.models import seqformer
    from blendjax.parallel.mesh import make_mesh
    from blendjax.parallel.sharding import make_seqformer_train_step

    out = {"leg": "mesh", **_tag()}
    n = jax.device_count()
    if n < 4:
        out["skipped"] = f"{n} device"
        return out
    model, T, B = size["model"], size["seq"], size["batch"]
    mesh = make_mesh({"data": 1, "seq": 2, "model": 2})
    out["mesh_devices"] = [
        str(getattr(d, "coords", d.id)) for d in mesh.devices.flat]
    episodes = np.random.default_rng(0).standard_normal(
        (B, T + 1, model["obs_dim"])).astype(np.float32)
    batch_np = seqformer.make_episode_batch(episodes)

    def fresh():
        return seqformer.init(jax.random.PRNGKey(0), max_len=T, **model)

    ref = float(jax.jit(seqformer.loss_fn)(fresh(), batch_np))
    out.update(width={**model, "seq": T, "batch": B},
               loss_single_device=round(ref, 5), impls={})
    mosaic, compile_s = True, 0.0
    for impl in ("ring_flash", "ulysses_flash"):
        init_sharded, step, batch_sharding = make_seqformer_train_step(
            optax.adam(1e-4), mesh, attn_impl=impl)
        state = init_sharded(fresh())
        batch = jax.device_put(batch_np, batch_sharding)
        for what, leaf in (("params", state.params["blocks"][0]["wq"]["w"]),
                           ("batch", batch["obs"])):
            devs = {s.device for s in leaf.addressable_shards}
            _check(len(devs) == 4,
                   f"{impl}: {what} live on {len(devs)} devices, not 4")
        mosaic = mosaic and _has_mosaic(step, state, batch)
        t0 = time.perf_counter()
        state, loss = step(state, batch)
        loss = float(jax.block_until_ready(loss))
        dt = time.perf_counter() - t0
        compile_s += dt
        rel = abs(loss - ref) / max(abs(ref), 1e-6)
        out["impls"][impl] = {"loss": round(loss, 5),
                              "rel_diff": round(rel, 6),
                              "cold_compile_s": round(dt, 1)}
        _check(np.isfinite(loss) and rel <= LOSS_TOL,
               f"{impl}: sharded loss {loss} vs single-device {ref}")
        del state
    out["cold_compile_s"] = round(compile_s, 1)
    out["mosaic"] = mosaic
    return out


# ---------------------------------------------------------------------------
# process plumbing
# ---------------------------------------------------------------------------


def _leg_main(name):
    """Child entry: run one leg at FULL size on the TPU, print its line."""
    # SIGTERM (the parent's deadline) unwinds through the legs' finally
    # blocks, so producers and the server child are stopped
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    sys.path.insert(0, HERE)
    from blendjax.btt.launcher import place_compile_cache

    place_compile_cache(os.environ)  # before jax reads its configuration
    if name == "serve":
        result = leg_serve(FULL, require_platform="tpu")
    else:
        import jax

        if jax.default_backend() != "tpu":
            sys.exit(f"chip_smoke: leg {name} found backend "
                     f"{jax.default_backend()!r}, not a TPU")
        result = (leg_train(FULL, require_peak=True) if name == "train"
                  else {"kernels": leg_kernels, "mesh": leg_mesh}[name](FULL))
    print(json.dumps(result), flush=True)


def _run_leg(name, deadline_s):
    """Run one leg as a child; returns its parsed JSON line."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--leg", name],
        stdout=subprocess.PIPE, text=True, cwd=HERE, start_new_session=True,
    )
    try:
        stdout, _ = proc.communicate(timeout=deadline_s)
    except subprocess.TimeoutExpired:
        proc.terminate()  # the leg's handler stops what it started
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            pass
        sys.exit(f"chip_smoke: leg {name} passed its {deadline_s:.0f}s "
                 "deadline")
    finally:
        try:  # whatever is left in the leg's process group, the leg too
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            pass
        proc.wait()
    if proc.returncode != 0:
        sys.exit(f"chip_smoke: leg {name} failed (exit {proc.returncode})")
    lines = [ln for ln in stdout.splitlines() if ln.startswith("{")]
    if not lines:
        sys.exit(f"chip_smoke: leg {name} printed no result")
    print(lines[-1], flush=True)
    return json.loads(lines[-1])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--leg", choices=LEGS, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.leg:
        return _leg_main(args.leg)

    t_start = time.monotonic()
    pinned = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    if pinned and "tpu" not in pinned.split(","):
        sys.exit(f"chip_smoke: JAX_PLATFORMS={pinned} — this program runs "
                 "on a TPU only (the CPU rehearsal is "
                 "tests/test_smoke_chip.py)")
    sys.path.insert(0, HERE)
    try:
        import blendjax  # noqa: F401  (jax-free by design)
    except ImportError:
        sys.exit("chip_smoke: no blendjax package beside this file — run it "
                 "from the root of a checkout")
    # what runs is built from the files git would commit: rebuild the
    # native ring even if a prebuilt .so rode along
    subprocess.run(["make", "-B", "-s", "-C",
                    os.path.join(HERE, "blendjax", "native")], check=True)
    from blendjax.native import native_available

    if not native_available():
        sys.exit("chip_smoke: the native ring built but does not load")

    device = None
    for name in LEGS:
        left = TOTAL_DEADLINE_S - (time.monotonic() - t_start)
        result = _run_leg(name, max(left, 1.0))
        seen = {"platform": result.get("platform"),
                "kind": result.get("device_kind"),
                "count": result.get("device_count")}
        if seen["platform"] != "tpu":
            sys.exit(f"chip_smoke: leg {name} ran on {seen['platform']!r}")
        if name != "serve" and "skipped" not in result \
                and not result.get("mosaic"):
            sys.exit(f"chip_smoke: leg {name} lowered without the Mosaic "
                     "custom call (the kernel fell back)")
        if device not in (None, seen):
            sys.exit(f"chip_smoke: leg {name} saw {seen}, earlier {device}")
        device = seen
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
