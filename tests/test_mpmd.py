"""MPMD pipeline: numerics locks, exactly-once discipline, chaos drill.

The multi-process 1F1B schedule must be *numerically invisible*: K
updates through :class:`~blendjax.parallel.mpmd.MpmdTrain` produce the
same params as the single-process in-jit reference
(:func:`~blendjax.parallel.pipeline.make_pipeline_train` + SGD) and as
plain full-model SGD.  The wire discipline (BTMID reply cache +
``(update, mb)`` dedup) must make any resend free, and a SIGKILLed
stage under ``FleetWatchdog(restart=True)`` must come back
checkpoint-exact with no lost or double-applied microbatch
(``make chaos-pipeline`` runs the drill).
"""

import glob
import os
import signal
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from blendjax import wire
from blendjax.models.layers import dense_apply
from blendjax.parallel.mpmd import (
    MpmdStage,
    MpmdTrain,
    StageFleet,
    build_full_params,
    make_loss_fn,
    normalize_spec,
    reference_pieces,
    reference_stacked,
    stage_slice,
    start_stage_threads,
)
from blendjax.parallel.pipeline import microbatch
from blendjax.utils.timing import EventCounters


def _spec(n_procs, *, family="mse", n_layers=4, lr=0.05, seed=2):
    return normalize_spec({
        "family": family, "d_in": 4, "wire": 8, "d_out": 3,
        "n_layers": n_layers, "n_procs": n_procs, "lr": lr, "seed": seed,
    })


def _batches(spec, k, batch=12, seed=0):
    """K fixed (x, target-record) full batches for the spec's family."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(k):
        x = rng.standard_normal((batch, spec["d_in"])).astype(np.float32)
        if spec["family"] == "mse":
            tgt = {"y": rng.standard_normal(
                (batch, spec["d_out"])).astype(np.float32)}
        else:
            tgt = {
                "action": rng.integers(
                    0, spec["d_out"], batch).astype(np.int32),
                "adv": rng.standard_normal(batch).astype(np.float32),
                "w": np.ones(batch, np.float32),
            }
        out.append((x, tgt))
    return out


def _plain_sgd(spec, batches, m):
    """Full-model SGD with the stages' exact arithmetic: per-microbatch
    mean losses, gradients SUMMED over microbatches, ``p - lr*g/m``."""
    loss_fn = make_loss_fn(spec["family"])

    def model_loss(p, x, tgt):
        h = jnp.tanh(dense_apply(p["layers"][0], x))
        for layer in p["layers"][1:]:
            h = jnp.tanh(dense_apply(layer, h))
        return loss_fn(dense_apply(p["out"], h), tgt)

    grad_fn = jax.jit(jax.value_and_grad(model_loss))
    params = build_full_params(spec)
    losses = []
    for x, tgt in batches:
        xs = microbatch(np.asarray(x), m)
        tgts = microbatch({k: np.asarray(v) for k, v in tgt.items()}, m)
        gsum, lsum = None, 0.0
        for i in range(m):
            loss, g = grad_fn(params, xs[i],
                              {k: v[i] for k, v in tgts.items()})
            lsum += float(loss)
            gsum = g if gsum is None else jax.tree.map(jnp.add, gsum, g)
        params = jax.tree.map(
            lambda a, b: a - spec["lr"] * b / m, params, gsum
        )
        losses.append(lsum / m)
    return jax.tree.map(np.asarray, params), losses


def _assert_trees_close(got, want, **tol):
    tol.setdefault("rtol", 1e-4)
    tol.setdefault("atol", 1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), **tol
        ),
        got, want,
    )


def _run_driver(spec, batches, m, **driver_kw):
    """K updates through a thread-served stage fleet; returns the
    gathered full params, per-update losses, and the driver counters."""
    counters = EventCounters()
    with start_stage_threads(spec) as handle:
        driver = MpmdTrain(handle.addresses, spec, counters=counters,
                           **driver_kw)
        try:
            driver.hello_all()
            losses = [float(driver.update(x, tgt, m))
                      for x, tgt in batches]
            params = jax.tree.map(np.asarray, driver.gather_params())
            infos = driver.stage_infos()
        finally:
            driver.close()
    return params, losses, counters, infos


# ---------------------------------------------------------------------------
# numerics locks
# ---------------------------------------------------------------------------


def test_mpmd_matches_in_jit_1f1b_reference():
    """THE acceptance lock: K updates on a 2-stage process-model fleet
    allclose-match make_pipeline_train('1f1b') + SGD on the SAME spec
    — the schedule, the wire hops, and the split are numerically
    invisible."""
    from blendjax.parallel import make_mesh
    from blendjax.parallel.pipeline import make_pipeline_train

    spec = _spec(2)
    m = 4
    batches = _batches(spec, 3)
    got, losses, counters, infos = _run_driver(spec, batches, m)

    in_proj, stage_fn, out_proj, loss_fn = reference_pieces(spec)
    mesh = make_mesh({"pipe": spec["n_procs"]})
    train = jax.jit(make_pipeline_train(
        stage_fn, lambda pred, y: loss_fn(pred, {"y": y}), mesh,
        schedule="1f1b", in_proj=in_proj, out_proj=out_proj,
    ))
    stacked, proj = reference_stacked(build_full_params(spec), spec)
    ref_losses = []
    for x, tgt in batches:
        xs = microbatch(np.asarray(x), m)
        ys = microbatch(np.asarray(tgt["y"]), m)
        loss, (gs, gp) = train(stacked, proj, xs, ys)
        ref_losses.append(float(loss))
        stacked = jax.tree.map(
            lambda p, g: p - spec["lr"] * g, stacked, gs
        )
        proj = jax.tree.map(lambda p, g: p - spec["lr"] * g, proj, gp)

    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5)
    got_stacked, got_proj = reference_stacked(got, spec)
    _assert_trees_close((got_stacked, got_proj), (stacked, proj))
    # a clean run needed zero recovery machinery
    assert counters.get("pipe_restarts") == 0
    assert counters.get("pipe_updates") == len(batches)
    assert all(i["applied"] == len(batches) for i in infos)


def test_mpmd_pg_family_matches_plain_sgd():
    """The learner's pg loss through 3 unevenly-sliced stages (4 layers
    over 3 procs — the remainder path) equals full-model SGD."""
    spec = _spec(3, family="pg")
    # uneven split really happened: stage 0 carries the extra layer
    assert [stage_slice(4, 3, p) for p in range(3)] == \
        [(0, 2), (2, 3), (3, 4)]
    m = 3
    batches = _batches(spec, 3)
    got, losses, _, _ = _run_driver(spec, batches, m)
    want, ref_losses = _plain_sgd(spec, batches, m)
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5)
    _assert_trees_close(got, want)


def test_mpmd_single_stage_degenerates_to_plain_sgd():
    """n_procs=1 (the benchmark's baseline arm) is plain SGD with the
    wire in the loop."""
    spec = _spec(1, n_layers=2)
    batches = _batches(spec, 2)
    got, losses, _, _ = _run_driver(spec, batches, 2)
    want, ref_losses = _plain_sgd(spec, batches, 2)
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5)
    _assert_trees_close(got, want)


def test_ragged_microbatch_count_rejected():
    """A batch the microbatch count does not divide is rejected at the
    driver boundary with the actionable shape error — never silently
    reweighted across stages."""
    spec = _spec(2)
    with start_stage_threads(spec) as handle:
        driver = MpmdTrain(handle.addresses, spec)
        try:
            driver.hello_all()
            x, tgt = _batches(spec, 1, batch=10)[0]
            with pytest.raises(ValueError, match="divisible"):
                driver.update(x, tgt, 4)
        finally:
            driver.close()


# ---------------------------------------------------------------------------
# exactly-once wire discipline (direct stage handle() calls)
# ---------------------------------------------------------------------------


def test_stage_dedup_reply_cache_and_stale_records():
    """The three duplicate shapes a lossy/raced wire produces — same-mid
    resend, fresh-mid repeat of a seen (update, mb), and a record for an
    already-committed update — are all absorbed as acks, never a second
    compute; an update-sequence gap raises restart_needed."""
    spec = _spec(1, n_layers=2)
    counters = EventCounters()
    stage = MpmdStage("tcp://127.0.0.1:*", spec, 0, counters=counters)
    try:
        rng = np.random.default_rng(3)
        x = [rng.standard_normal((4, spec["d_in"])).astype(np.float32)
             for _ in range(2)]
        y = [rng.standard_normal((4, spec["d_out"])).astype(np.float32)
             for _ in range(2)]
        assert stage.handle({"cmd": "begin", "update": 1, "m": 2}) == \
            {"applied": 0}

        msg = {"cmd": "fwd", "update": 1, "mb": 0, "x": x[0]}
        wire.stamp_message_id(msg)
        r1 = stage.handle(msg)
        assert r1["ok"] and "dup" not in r1
        # same-mid resend: the cached reply, no second compute
        assert stage.handle(msg) == r1
        assert counters.get("pipe_dup_records") == 1
        # fresh-mid repeat of a seen (update, mb): (u, mb) dedup
        again = {"cmd": "fwd", "update": 1, "mb": 0, "x": x[0]}
        wire.stamp_message_id(again)
        assert stage.handle(again)["dup"] is True
        assert counters.get("pipe_dup_records") == 2

        for mb in range(2):
            stage.handle({"cmd": "tgt", "update": 1, "mb": mb,
                          "tgt": {"y": y[mb]}})
        stage.handle({"cmd": "fwd", "update": 1, "mb": 1, "x": x[1]})
        fin = stage.handle({"cmd": "finish", "update": 1})
        assert fin["ready"] and fin["bwd_done"] == 2
        assert counters.get("pipe_microbatches") == 2

        commit = stage.handle({"cmd": "commit", "update": 1})
        assert commit["applied"] == 1
        assert isinstance(commit["loss"], float)
        # idempotent commit replay (driver recovery races)
        assert stage.handle({"cmd": "commit", "update": 1}) == commit

        # a record for the committed past: stale-ack, not an error
        late = {"cmd": "fwd", "update": 1, "mb": 0, "x": x[0]}
        wire.stamp_message_id(late)
        assert stage.handle(late)["stale"] is True
        assert counters.get("pipe_microbatches") == 2  # no recompute

        # an update-sequence gap is the restart signal
        gap = stage.handle({"cmd": "begin", "update": 3, "m": 2})
        assert "restart_needed" in gap["error"]
    finally:
        stage.close()


# ---------------------------------------------------------------------------
# learner integration
# ---------------------------------------------------------------------------


def test_actor_learner_pipeline_mode_offline():
    """``ActorLearner(pipeline_stages=...)``: run_offline drives the
    stage fleet straight from the arena sampler and the learner's
    TrainState mirrors the fleet's committed params (the actor/bus/
    checkpoint lineage follows the pipeline, not a second SGD)."""
    from blendjax.models.actor_learner import ActorLearner
    from blendjax.replay import ReplayBuffer

    spec = _spec(2, family="pg")
    rng = np.random.default_rng(1)
    buf = ReplayBuffer(512, seed=0)
    for _ in range(96):
        buf.append({
            "obs": rng.standard_normal(spec["d_in"]).astype(np.float32),
            "action": int(rng.integers(0, spec["d_out"])),
            "reward": float(rng.standard_normal()),
        })

    with start_stage_threads(spec) as handle:
        driver = MpmdTrain(handle.addresses, spec)
        try:
            driver.hello_all()
            al = ActorLearner(
                None, obs_dim=spec["d_in"], num_actions=spec["d_out"],
                seed=1, replay=buf, pipeline_stages=driver,
            )
            assert al.pipeline_microbatches == spec["n_procs"]
            stats = al.run_offline(num_updates=3, batch_size=24)
            fleet_params = driver.gather_params()
            assert driver.updates_done == 3
        finally:
            driver.close()

    assert stats["updates"] == 3
    assert al.state.step == 3
    _assert_trees_close(al.state.params, fleet_params, rtol=1e-6)


def test_actor_learner_pipeline_mode_rejects_bad_specs():
    """The constructor guards: family, mesh exclusivity, and dimension
    agreement all fail fast (a silently mismatched pipeline would train
    a different model than the actor samples from)."""
    from blendjax.models.actor_learner import ActorLearner
    from blendjax.replay import ReplayBuffer

    class _FakeDriver:
        def __init__(self, spec):
            self.spec = normalize_spec(spec)

    buf = ReplayBuffer(64, seed=0)
    mse = _FakeDriver(_spec(2, family="mse"))
    with pytest.raises(ValueError, match="family='pg'"):
        ActorLearner(None, obs_dim=4, num_actions=3, replay=buf,
                     pipeline_stages=mse)
    pg = _FakeDriver(_spec(2, family="pg"))
    with pytest.raises(ValueError, match="obs_dim"):
        ActorLearner(None, obs_dim=7, num_actions=3, replay=buf,
                     pipeline_stages=pg)


# ---------------------------------------------------------------------------
# bench artifact schema
# ---------------------------------------------------------------------------


def test_pipe_bench_keys_schema():
    """The artifact contract bench.py's carry and scripts/bench_compare
    key off — drift here silently drops the floor guard."""
    from benchmarks._common import PIPE_BENCH_KEYS

    assert set(PIPE_BENCH_KEYS) >= {
        "pipe_stages", "layers", "microbatches", "work_us",
        "mpmd_updates_per_sec", "single_updates_per_sec",
        "pipe_mpmd_x", "pair_ratios", "pipe_counters", "stages",
    }


def test_bench_headline_carries_pipe_mpmd_x():
    """The ratio rides the assembled artifact AND the compact headline
    (within its byte budget) — the acceptance's carry clause."""
    import json

    import bench

    pb = {"phase": "pipeline_bench", "pipe_mpmd_x": 1.78,
          "pipe_stages": 3, "mpmd_updates_per_sec": 8.2,
          "single_updates_per_sec": 4.6}
    out = bench.assemble({"host_stream": {"items_per_sec": 1.0}},
                         pipeline_bench=pb)
    assert out["pipeline_bench"]["pipe_mpmd_x"] == 1.78
    assert out["pipeline_bench"]["mpmd_updates_per_sec"] == 8.2
    line = bench.headline(out)
    assert line["pipe_mpmd_x"] == 1.78
    assert len(json.dumps(line)) + 1 <= bench.HEADLINE_BYTE_BUDGET


def test_bench_compare_registers_pipe_floor():
    """scripts/bench_compare.py guards pipe_mpmd_x on the trajectory
    with a >= 0.85 floor and folds it out of the structured artifact."""
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_compare_pipe",
        os.path.join(repo, "scripts", "bench_compare.py"),
    )
    bc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bc)
    assert bc.DEFAULT_FLOORS["pipe_mpmd_x"] == 0.85
    metrics = {}
    bc._flatten({"pipeline_bench": {"pipe_mpmd_x": 1.9}}, metrics)
    assert metrics == {"pipe_mpmd_x": 1.9}


@pytest.mark.chaos
@pytest.mark.slow  # process-heavy; `make chaos-pipeline` runs it
def test_pipeline_benchmark_emits_schema():
    """A tiny end-to-end benchmark run (2-stage fleet, one window)
    emits every PIPE_BENCH_KEYS key with a real ratio (`make
    chaos-pipeline` runs it; the full-size run is `make pipebench`)."""
    from benchmarks import pipeline_benchmark
    from benchmarks._common import PIPE_BENCH_KEYS

    out = pipeline_benchmark.main([
        "--pipe-stages", "2", "--layers", "4", "--microbatches", "4",
        "--batch", "32", "--work-us", "800", "--rounds", "1",
        "--window-updates", "3",
    ])
    assert out["phase"] == "pipeline_bench"
    missing = [k for k in PIPE_BENCH_KEYS if k not in out]
    assert not missing, f"schema drifted: {missing}"
    assert out["pipe_mpmd_x"] > 0
    assert out["pipe_counters"]["pipe_updates"] > 0


# ---------------------------------------------------------------------------
# THE chaos drill: SIGKILL a stage mid-training
# ---------------------------------------------------------------------------


@pytest.mark.chaos
@pytest.mark.slow  # process-heavy; `make chaos-pipeline` runs it
def test_stage_kill_respawn_checkpoint_exact(tmp_path):
    """SIGKILL the middle stage process mid-update under
    ``FleetWatchdog(restart=True)``: the respawned incarnation restores
    its params from the per-stage checkpoint cut, the driver reconciles
    and replays, and after K updates the params EXACTLY match an
    uninterrupted plain-SGD run — no microbatch lost, none applied
    twice (resends land in the reply cache / stale-ack path, never a
    second compute).  Teardown leaves zero /dev/shm objects."""
    from blendjax.btt.watchdog import FleetWatchdog

    spec = _spec(3, n_layers=6)
    m = 3
    k_updates = 6
    kill_after = 3
    batches = _batches(spec, k_updates, batch=12)
    want, ref_losses = _plain_sgd(spec, batches, m)

    counters = EventCounters()
    with StageFleet(spec, ckpt_dir=str(tmp_path / "ck"),
                    ckpt_every=1) as fleet:
        bases = [b for b in fleet.shm_bases if b]
        with FleetWatchdog(fleet, interval=0.25, restart=True) as wd:
            driver = MpmdTrain(fleet.addresses, spec, counters=counters,
                               finish_timeout_s=10.0)
            try:
                driver.hello_all()
                losses = []
                for k, (x, tgt) in enumerate(batches):
                    if k == kill_after:
                        # fire mid-update: the driver is inside the
                        # feed/finish protocol when the stage dies
                        victim = fleet.launch_info.processes[1].pid
                        threading.Timer(
                            0.05, os.kill, (victim, signal.SIGKILL)
                        ).start()
                    losses.append(float(driver.update(x, tgt, m)))
                got = jax.tree.map(np.asarray, driver.gather_params())
                infos = driver.stage_infos()
            finally:
                driver.close()
            deadline = time.monotonic() + 10
            while not wd.deaths and time.monotonic() < deadline:
                time.sleep(0.1)

    # crash-exact: the interrupted run IS the uninterrupted run
    _assert_trees_close(got, want)
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-4)
    # the kill really happened and really healed
    assert [d[0] for d in wd.deaths] == [1]
    assert counters.get("pipe_stage_respawns") >= 1
    # every stage applied exactly K commits — none lost, none doubled
    assert [i["applied"] for i in infos] == [k_updates] * 3
    # the respawned incarnation restored from its checkpoint cut
    respawned = infos[1]["counters"]
    assert respawned.get("pipe_ckpt_restores", 0) >= 1
    # per-instance shm hygiene: the SIGKILLed incarnation's objects
    # were swept on respawn and again at teardown
    leaked = [p for b in bases for p in glob.glob(f"/dev/shm/{b}*")]
    assert not leaked, f"shm leaked: {leaked}"
