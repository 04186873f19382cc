"""Learner failover (ISSUE-15; docs/fault_tolerance.md "Learner
failover"): coordinated train-state checkpointing, supervised learner
respawn, and a resume the rest of the system cannot distinguish from no
crash.

- TrainCheckpointer: manifest commit semantics, async-off-the-loop
  skipping, retention, damaged-cut fallback;
- the cut's crash-exactness: restoring a manifest continues the replay
  DRAW STREAM bit-identically to the no-crash timeline, over a local
  buffer and over live shard services — including the reconcile path
  where the dead incarnation appended past the cut;
- LearnerSupervisor: death -> postmortem naming the learner with its
  last stats digest -> respawn, and THE full-stack chaos acceptance
  (live fleet + 2 replay shards + a subscribed serve replica, learner
  SIGKILLed mid-training).
"""

import json
import os
import signal
import threading
import time
import types

import numpy as np
import pytest

from blendjax.ha import (
    TrainCheckpointer,
    latest_manifest,
    restore_replay,
)
from blendjax.utils.timing import EventCounters

HERE = os.path.dirname(os.path.abspath(__file__))
ENV_SCRIPT = os.path.join(HERE, "blender", "env.blend.py")


@pytest.fixture
def fake_blender(monkeypatch):
    monkeypatch.setenv(
        "BLENDJAX_BLENDER", os.path.join(HERE, "helpers", "fake_blender.py")
    )


def _fill(buf, n, obs_dim=4, seed=0):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        buf.append({
            "obs": rng.standard_normal(obs_dim).astype(np.float32),
            "action": np.int32(rng.integers(0, 3)),
            "reward": np.float32(rng.standard_normal()),
            "next_obs": rng.standard_normal(obs_dim).astype(np.float32),
            "done": np.bool_(False),
        })


def _offline_learner(buf, checkpointer=None, seed=0):
    from blendjax.models.actor_learner import ActorLearner

    return ActorLearner(None, 4, 3, replay=buf, seed=seed,
                        checkpointer=checkpointer)


# ---------------------------------------------------------------------------
# TrainCheckpointer: the coordinated cut
# ---------------------------------------------------------------------------


def test_checkpointer_offline_cut_is_crash_exact(tmp_path):
    """THE manifest contract: restore(state + counters + replay) and
    the post-cut draw stream is bit-identical to the no-crash
    continuation; params and optimizer state restore bit-exactly."""
    import jax

    from blendjax.replay import ReplayBuffer

    counters = EventCounters()
    buf = ReplayBuffer(256, seed=0)
    _fill(buf, 128)
    ck = TrainCheckpointer(str(tmp_path), every_updates=2,
                           counters=counters)
    al = _offline_learner(buf, ck)
    al.run_offline(num_updates=5, batch_size=32)
    ck.join()
    assert counters.get("ha_ckpt_saves") >= 1
    cut = ck.checkpoint(al, block=True)  # deterministic final cut
    assert cut == 5
    man = latest_manifest(str(tmp_path))
    assert man["update"] == 5 and man["replay_kind"] == "local"

    # the no-crash timeline continues drawing after the cut...
    seq_no_crash = [buf.sample(16)[1].tolist() for _ in range(4)]

    # ...and the restored timeline draws the exact same stream
    buf2 = restore_replay(man, counters=EventCounters())
    ck2 = TrainCheckpointer(str(tmp_path), counters=EventCounters())
    al2 = _offline_learner(buf2)
    ck2.restore(al2, man, republish=False)
    assert al2._updates_done == 5
    seq_restored = [buf2.sample(16)[1].tolist() for _ in range(4)]
    assert seq_restored == seq_no_crash

    for a, b in zip(jax.tree.leaves(al.state),
                    jax.tree.leaves(al2.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert ck2.counters.get("ha_restores") == 1


def test_checkpointer_sharded_cut_and_reconcile(tmp_path):
    """The full-system cut over live shard services: bit-identical
    draws when nothing moved past the cut, and — the failover case —
    the slots a doomed incarnation appended past the cut are
    reconciled OUT of the restored draw domain (counted
    ``replay_shard_lost``) until the resumed actors rewrite them."""
    from blendjax.replay.service import start_shard_thread
    from blendjax.replay.shard_client import ShardedReplay

    shards = [
        start_shard_thread(64, shard_id=i,
                           data_dir=str(tmp_path / f"s{i}"))
        for i in range(2)
    ]
    try:
        addrs = [s.address for s in shards]
        rng = np.random.default_rng(7)
        buf = ShardedReplay(addrs, seed=3, counters=EventCounters())
        _fill(buf, 140, seed=7)  # full ring + wraparound
        for _ in range(3):
            buf.sample(8)
        ck = TrainCheckpointer(str(tmp_path / "ck"),
                               counters=EventCounters())
        al = _offline_learner(buf)
        ck.checkpoint(al, block=True)
        man = latest_manifest(str(tmp_path / "ck"))
        assert man["replay_kind"] == "sharded"

        # case A — nothing moved: restored draws == no-crash draws
        seq_no_crash = [buf.sample(8)[1].tolist() for _ in range(4)]
        bufA = restore_replay(man, addrs, counters=EventCounters())
        seqA = [bufA.sample(8)[1].tolist() for _ in range(4)]
        assert seqA == seq_no_crash
        assert bufA.counters.get("replay_shard_lost") == 0

        # case B — the doomed incarnation appends 10 rows past the cut
        # (sampling above consumed rng but never wrote): ring order
        # makes the overwritten slots deterministic
        head_at_cut = buf._head
        _fill(buf, 10, seed=11)
        rolled = {(head_at_cut + k) % buf.capacity for k in range(10)}
        ctrB = EventCounters()
        bufB = restore_replay(man, addrs, counters=ctrB)
        assert ctrB.get("replay_shard_lost") == len(rolled)
        for _ in range(6):
            _, idx, _ = bufB.sample(8)
            assert not (set(idx.tolist()) & rolled), \
                "drew a slot whose row was rolled back"
        # the resumed actors rewrite the same slots in the same ring
        # order and they re-enter the draw domain
        _fill(bufB, 10, seed=12)
        bufB.sample(32)
        del rng
    finally:
        for s in shards:
            s.close()


def test_reconcile_survives_uncommitted_later_cut(tmp_path):
    """Regression (caught by the chaos drill): the learner can die
    BETWEEN a later barrier's shard saves and that cut's manifest
    commit, so the shard's latest checkpoint legitimately postdates
    the last COMMITTED manifest.  ``written_since`` must still answer
    back to the committed cut (the tail mirror survives shard
    checkpoints) — only the genuinely-written slots leave the domain,
    never the whole range."""
    from blendjax.replay.service import start_shard_thread
    from blendjax.replay.shard_client import ShardedReplay

    shards = [
        start_shard_thread(64, shard_id=i,
                           data_dir=str(tmp_path / f"s{i}"))
        for i in range(2)
    ]
    try:
        addrs = [s.address for s in shards]
        buf = ShardedReplay(addrs, seed=3, counters=EventCounters())
        _fill(buf, 140, seed=7)
        ck = TrainCheckpointer(str(tmp_path / "ck"),
                               counters=EventCounters())
        al = _offline_learner(buf)
        ck.checkpoint(al, block=True)
        man = latest_manifest(str(tmp_path / "ck"))
        head_at_cut = buf._head
        # the doomed incarnation: appends, then ANOTHER barrier whose
        # shard saves land but whose manifest never commits, then more
        # appends, then death
        _fill(buf, 6, seed=11)
        for c in buf.clients:
            c.rpc("save")
        _fill(buf, 6, seed=12)
        rolled = {(head_at_cut + k) % buf.capacity for k in range(12)}

        ctr = EventCounters()
        buf2 = restore_replay(man, addrs, counters=ctr)
        assert ctr.get("replay_shard_lost") == len(rolled)
        for _ in range(6):
            _, idx, _ = buf2.sample(8)
            assert not (set(idx.tolist()) & rolled)
    finally:
        for s in shards:
            s.close()


def test_checkpointer_retention_and_damaged_fallback(tmp_path):
    """Retention keeps max_to_keep complete cuts (evictions counted);
    a damaged newest cut (torn component after a host crash) falls
    back to the previous manifest — counted and warned, never a
    half-cut restore."""
    from blendjax.replay import ReplayBuffer

    counters = EventCounters()
    buf = ReplayBuffer(64, seed=0)
    _fill(buf, 32)
    ck = TrainCheckpointer(str(tmp_path), max_to_keep=2,
                           counters=counters)
    al = _offline_learner(buf, ck)
    for _ in range(4):
        al.run_offline(num_updates=1, batch_size=16)
        ck.checkpoint(al, block=True)
    manifests = sorted(
        p for p in os.listdir(tmp_path) if p.startswith("manifest_")
    )
    assert len(manifests) == 2
    assert counters.get("ha_ckpt_evicted") == 2
    man = latest_manifest(str(tmp_path))
    assert man["update"] == 4
    # train steps retire with the manifests
    assert len(ck.train_mgr.all_steps()) <= 2

    # tear the newest cut's train npz: the manifest must stop counting
    with open(os.path.join(tmp_path, man["train"]), "r+b") as f:
        f.truncate(12)
    ctr2 = EventCounters()
    man2 = latest_manifest(str(tmp_path), counters=ctr2)
    assert man2["update"] == 3
    assert ctr2.get("ha_restore_fallbacks") == 1


def test_checkpointer_skips_while_serialize_inflight(tmp_path):
    """The bounded-stall contract: a due checkpoint with the previous
    serialization still in flight is SKIPPED (counted), never queued
    behind it."""
    from blendjax.replay import ReplayBuffer

    counters = EventCounters()
    buf = ReplayBuffer(64, seed=0)
    _fill(buf, 32)
    ck = TrainCheckpointer(str(tmp_path), every_updates=1,
                           counters=counters)
    al = _offline_learner(buf, ck)
    al.run_offline(num_updates=1, batch_size=16)
    ck.join()

    release = threading.Event()
    real = ck._serialize

    def slow_serialize(*args, **kwargs):
        release.wait(10)
        return real(*args, **kwargs)

    ck._serialize = slow_serialize
    al._updates_done += 1
    assert ck.maybe_checkpoint(al) == al._updates_done  # starts async
    al._updates_done += 1
    assert ck.maybe_checkpoint(al) is None              # skipped
    assert counters.get("ha_ckpt_skipped") == 1
    release.set()
    ck.join(timeout=10)
    assert counters.get("ha_ckpt_failures") == 0


def test_checkpoint_state_carries_curriculum(tmp_path):
    """The cut includes the curriculum: a restored learner's scheduler
    continues mid-interval with the pinned mix, tick counters and
    return EMAs — never restarted at the uniform mix."""
    from blendjax.replay import ReplayBuffer
    from blendjax.scenario import CurriculumScheduler

    buf = ReplayBuffer(64, seed=0)
    _fill(buf, 32)
    cur = CurriculumScheduler(["lite", "rich"], interval=4)
    cur.pin({"lite": 0.7, "rich": 0.3})
    cur.update()
    cur.observe_return("rich", 1.5)
    for _ in range(3):
        cur.tick()  # mid-interval: the gate state must survive too
    from blendjax.models.actor_learner import ActorLearner

    al = ActorLearner(None, 4, 3, replay=buf, curriculum=cur, seed=0)
    al._updates_done = 9
    aux = al.checkpoint_state()

    cur2 = CurriculumScheduler(["lite", "rich"], interval=4)
    al2 = ActorLearner(None, 4, 3, replay=buf, curriculum=cur2, seed=0)
    al2.load_checkpoint_state(al.state, aux)
    assert al2._updates_done == 9
    assert cur2.policy == "pinned"
    assert cur2.mix() == cur.mix()
    assert cur2.stats()["returns_ema"] == cur.stats()["returns_ema"]
    assert cur2._ticks == cur._ticks
    # a foreign catalog's checkpoint is refused, never misweighted
    cur3 = CurriculumScheduler(["other"])
    with pytest.raises(ValueError, match="same catalog"):
        cur3.load_state_dict(aux["curriculum"])


def test_learner_supervisor_postmortem_names_learner(tmp_path):
    """A learner death leaves an ``obs_artifacts``-style postmortem
    naming the dead learner with its last stats digest attached (the
    FleetSupervisor._on_death contract pointed at the learner)."""
    from blendjax.ha import LearnerSupervisor
    from blendjax.utils.timing import HA_EVENTS

    stats = {"pid": 4242, "updates": 17, "last_ckpt_update": 16}
    fake = types.SimpleNamespace(
        ckpt_dir=str(tmp_path),
        read_stats=lambda: dict(stats),
        launch_info=None,
    )
    counters = EventCounters()
    sup = LearnerSupervisor(fake, counters=counters,
                            postmortem_dir=str(tmp_path))
    sup._on_death(0, -9)
    assert counters.get("ha_learner_deaths") == 1
    assert sup.last_postmortem is not None
    doc = json.loads(open(sup.last_postmortem).read())
    assert doc["extra"]["target"] == "learner"
    assert doc["extra"]["exit_code"] == -9
    assert doc["extra"]["stats"]["updates"] == 17
    assert any(
        e["event"] == "learner_death" and e["target"] == "learner"
        for e in doc["events"]
    )
    h = sup.health()
    for name in HA_EVENTS:
        assert name in h
    assert h["ha_learner_deaths"] == 1
    assert h["learner_stats"]["last_ckpt_update"] == 16


# ---------------------------------------------------------------------------
# bench schema + headline carry + compare bounds
# ---------------------------------------------------------------------------


def test_ha_bench_schema_and_overhead_shape(tmp_path, capsys):
    from benchmarks import ha_benchmark
    from benchmarks._common import HA_BENCH_KEYS

    out = ha_benchmark.main(["--skip-recovery", "--skip-overhead"])
    capsys.readouterr()
    assert out["phase"] == "ha_bench"
    missing = [k for k in HA_BENCH_KEYS if k not in out]
    assert not missing, f"schema drifted: {missing}"

    rec = ha_benchmark.measure_ckpt_overhead(
        window_s=0.25, rounds=1, ckpt_every_s=0.1,
        directory=str(tmp_path),
    )
    assert rec["ckpt_overhead_x"] > 0.3   # structure, not the floor
    assert rec["ckpt_on_updates_per_sec"] > 0
    assert "ha_snapshot" in rec["stages"]


def test_bench_headline_carries_ha_metrics():
    import bench

    ha = {
        "phase": "ha_bench",
        "ckpt_overhead_x": 0.97,
        "learner_recovery_s": 2.5,
        "window_s": 1.5,
    }
    out = bench.assemble({"host_stream": {"items_per_sec": 1.0}}, ha_bench=ha)
    assert out["ha_bench"]["ckpt_overhead_x"] == 0.97
    line = bench.headline(out)
    assert line["ckpt_overhead_x"] == 0.97
    assert line["learner_recovery_s"] == 2.5
    assert len(json.dumps(line)) + 1 <= bench.HEADLINE_BYTE_BUDGET


def test_bench_compare_registers_ha_bounds():
    import importlib.util

    repo = os.path.dirname(HERE)
    spec = importlib.util.spec_from_file_location(
        "bench_compare_ha",
        os.path.join(repo, "scripts", "bench_compare.py"),
    )
    bc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bc)
    assert bc.DEFAULT_FLOORS["ckpt_overhead_x"] == 0.90
    assert bc.DEFAULT_CEILINGS["learner_recovery_s"] == 1.50


# ---------------------------------------------------------------------------
# chaos: supervised kill -> respawn -> resume
# ---------------------------------------------------------------------------


def _await_stats(lp, cond, timeout, what):
    deadline = time.monotonic() + timeout
    while True:
        s = lp.read_stats() or {}
        if cond(s):
            return s
        if time.monotonic() >= deadline:
            raise AssertionError(f"timed out waiting for {what}: {s}")
        time.sleep(0.1)


def _committed(ckpt_dir):
    """The newest complete manifest's update (0: none yet).  The stats
    file's ``last_ckpt_update`` is the cadence cursor: it moves at the
    cut, BEFORE the background serialization commits the manifest, so
    a kill between the two resumes from the manifest before it."""
    import glob

    paths = sorted(glob.glob(os.path.join(ckpt_dir, "manifest_*.json")))
    return int(os.path.basename(paths[-1])[len("manifest_"):-5]) \
        if paths else 0


@pytest.mark.chaos
def test_supervised_learner_kill_respawn_resume(fake_blender, tmp_path):
    """The tier-1 failover drill: SIGKILL the supervised learner
    process mid-training on a live fake-Blender fleet -> watchdog
    respawn -> the child resumes from the latest complete manifest
    (update counter continues from the cut, never from zero), with the
    death postmortem written."""
    from blendjax.btt.launcher import BlenderLauncher
    from blendjax.ha import LearnerProcess, LearnerSupervisor

    counters = EventCounters()
    with BlenderLauncher(
        scene="", script=ENV_SCRIPT, num_instances=2,
        named_sockets=["GYM"], background=True, start_port=15410,
    ) as bl:
        with LearnerProcess(
            ckpt_dir=str(tmp_path / "ck"),
            env_addresses=bl.launch_info.addresses["GYM"],
            obs_dim=1, num_actions=2, rollout_len=8, seed=1,
            ckpt_every=2, chunk_updates=2,
            action_values=[0.0, 1.0],
        ) as lp:
            with LearnerSupervisor(
                lp, interval=0.3, counters=counters,
                postmortem_dir=str(tmp_path / "pm"),
            ) as sup:
                pre = _await_stats(
                    lp,
                    lambda s: s.get("updates", 0) >= 3
                    and _committed(str(tmp_path / "ck")) >= 2,
                    90, "warmup + first checkpoint",
                )
                committed = _committed(str(tmp_path / "ck"))
                os.kill(lp.launch_info.processes[0].pid,
                        signal.SIGKILL)
                assert sup.await_deaths(1, 30)
                assert sup.await_respawns(1, 30)
                post = _await_stats(
                    lp,
                    lambda s: s.get("pid") not in (None, pre["pid"])
                    and s.get("updates", 0) > pre["updates"],
                    120, "post-respawn progress",
                )
    # resumed from a real cut (>= the manifest that was complete before
    # the kill — the learner may have committed another between the read
    # and the SIGKILL), never from zero
    assert post["resumed_from"] >= committed >= 2
    assert post["updates"] > pre["updates"]
    assert counters.get("ha_learner_deaths") == 1
    assert counters.get("ha_learner_respawns") == 1
    assert sup.last_postmortem is not None
    doc = json.loads(open(sup.last_postmortem).read())
    assert doc["extra"]["target"] == "learner"
    assert doc["extra"]["stats"]["updates"] >= pre["updates"]


@pytest.mark.chaos
@pytest.mark.slow
def test_kill_learner_full_stack_acceptance(fake_blender, tmp_path):
    """THE learner-failover chaos acceptance (ISSUE-15): SIGKILL the
    learner mid-training under live fleets + 2 replay shard processes
    + a subscribed serve replica -> supervised respawn -> resume from
    the latest manifest with the restored draw authority serving a
    probe draw (every acked row drawable), weight-bus versions
    STRICTLY MONOTONIC across the respawn (wall-clock version base +
    resume republish), and ZERO serve-client-visible errors — the
    serve tier keeps answering from its last good weights through the
    whole outage and rolls forward when the new incarnation
    publishes."""
    from blendjax.btt.launcher import BlenderLauncher
    from blendjax.ha import LearnerProcess, LearnerSupervisor
    from blendjax.replay.service import ShardFleet
    from blendjax.replay.shard_client import free_port
    from blendjax.serve.client import ServeClient
    from blendjax.serve.server import ServerProcess

    counters = EventCounters()
    bus_addr = f"tcp://127.0.0.1:{free_port()}"
    observed = []          # distinct weight versions, in arrival order
    client_errors = []
    stop = threading.Event()

    def client_loop(address):
        c = ServeClient(address, timeoutms=10000)
        obs = np.zeros(1, np.float32)
        try:
            c.reset()
            while not stop.is_set():
                r = c.step(obs)
                v = r.get("weight_version")
                if v is not None and (not observed
                                      or observed[-1] != v):
                    observed.append(v)
            c.close_episode()
        except Exception as exc:  # noqa: BLE001 - the assertion subject
            client_errors.append(f"{type(exc).__name__}: {exc}")
        finally:
            c.close()

    with ShardFleet(
        2, capacity_per_shard=128, data_dir=str(tmp_path / "shards"),
    ) as fleet:
        with BlenderLauncher(
            scene="", script=ENV_SCRIPT, num_instances=2,
            named_sockets=["GYM"], background=True, start_port=15470,
        ) as bl:
            with ServerProcess(
                model="policy", subscribe=bus_addr, obs_dim=1,
                num_actions=2, slots=8, seed=5,
            ) as server:
                t = threading.Thread(
                    target=client_loop, args=(server.address,),
                    daemon=True,
                )
                t.start()
                try:
                    with LearnerProcess(
                        ckpt_dir=str(tmp_path / "ck"),
                        env_addresses=bl.launch_info.addresses["GYM"],
                        replay_shards=fleet.addresses,
                        shard_capacity=128,
                        weight_bus=bus_addr, publish_every=1,
                        obs_dim=1, num_actions=2, rollout_len=8,
                        seed=1, replay_ratio=1, replay_batch=16,
                        ckpt_every=2, chunk_updates=2,
                        action_values=[0.0, 1.0], probe_batch=8,
                    ) as lp:
                        with LearnerSupervisor(
                            lp, interval=0.3, counters=counters,
                            postmortem_dir=str(tmp_path / "pm"),
                        ) as sup:
                            pre = _await_stats(
                                lp,
                                lambda s: s.get("updates", 0) >= 4
                                and s.get("last_ckpt_update", 0) >= 2,
                                120, "warmup + first checkpoint",
                            )
                            # the replica must have adopted at least
                            # one pre-kill version
                            deadline = time.monotonic() + 30
                            while not observed:
                                assert time.monotonic() < deadline, \
                                    "replica never adopted a version"
                                time.sleep(0.1)
                            pre_versions = list(observed)
                            os.kill(
                                lp.launch_info.processes[0].pid,
                                signal.SIGKILL,
                            )
                            assert sup.await_deaths(1, 30)
                            assert sup.await_respawns(1, 30)
                            post = _await_stats(
                                lp,
                                lambda s: s.get("pid")
                                not in (None, pre["pid"])
                                and s.get("updates", 0)
                                > pre["updates"],
                                150, "post-respawn progress",
                            )
                            # the serve tier rolls FORWARD: a version
                            # strictly above every pre-kill one
                            deadline = time.monotonic() + 60
                            while not (observed and observed[-1]
                                       > max(pre_versions)):
                                assert time.monotonic() < deadline, (
                                    f"no post-respawn version: "
                                    f"{observed} vs {pre_versions}"
                                )
                                time.sleep(0.2)
                finally:
                    stop.set()
                    t.join(timeout=15)

        # every shard survived the learner's death untouched
        assert all(p.poll() is None
                   for p in fleet.launch_info.processes)

    # resume from a real cut (>= the one read before the kill), with
    # the restored draw authority serving a probe draw
    assert post["resumed_from"] >= pre["last_ckpt_update"] >= 2
    assert post["updates"] > pre["updates"]
    assert post.get("probe_digest") not in (None, "underfilled")
    # weight versions: client-observed stream strictly monotonic across
    # the respawn, with zero client-visible errors of any kind
    assert client_errors == []
    assert observed == sorted(observed)
    assert len(set(observed)) == len(observed)
    assert observed[-1] > max(pre_versions)
    assert counters.get("ha_learner_deaths") == 1
    assert counters.get("ha_learner_respawns") == 1
    assert sup.last_postmortem is not None
