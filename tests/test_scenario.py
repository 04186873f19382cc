"""Scenario plane tests (docs/scenarios.md, ISSUE-14): catalog schema
+ JSON round trip, curriculum policies/apportionment, duplex
randomization pushes (bounded, chaos-safe), replay scenario strata
(in-band stamps, draw-stream determinism contract, checkpoints, `.btr`
prefill bit-identity), heterogeneous fan-in (per-shape arena groups,
ready-first collect), gateway per-scenario traffic records, the
bench schemas, and THE acceptance run: a 3-fleet / 2-scenario
training run at different physics rates with a pinned curriculum
shift and zero learner stalls."""

import os
import sys
import threading
import time

import numpy as np
import pytest

from blendjax.replay import ReplayBuffer
from blendjax.replay.prefill import prefill_from_btr, transition_to_message
from blendjax.scenario import (
    CurriculumScheduler,
    DomainRandomizer,
    ScenarioCatalog,
    ScenarioSpec,
    apportion,
)
from blendjax.utils.timing import EventCounters
from helpers.producers import free_port

HERE = os.path.dirname(os.path.abspath(__file__))
ENV_SCRIPT = os.path.join(HERE, "blender", "env.blend.py")
REPO = os.path.dirname(HERE)


@pytest.fixture
def fake_blender(monkeypatch):
    monkeypatch.setenv(
        "BLENDJAX_BLENDER", os.path.join(HERE, "helpers", "fake_blender.py")
    )


def two_scenarios(fast_us=0, slow_us=2000):
    return ScenarioCatalog([
        ScenarioSpec("lite", physics_rate_us=fast_us,
                     ranges={"density": (0.1, 0.4)}),
        ScenarioSpec("rich", physics_rate_us=slow_us,
                     ranges={"density": (0.6, 1.0)}),
    ])


# ---------------------------------------------------------------------------
# catalog
# ---------------------------------------------------------------------------


class TestCatalog:
    def test_json_round_trip_and_seeded_sampling(self):
        cat = ScenarioCatalog([
            ScenarioSpec("a", params={"scene": "x"},
                         ranges={"d": (0.0, 1.0), "tex": ["wood", "tin"]},
                         physics_rate_us=150, resolution=(32, 48)),
            ScenarioSpec("b"),
        ])
        back = ScenarioCatalog.from_json(cat.to_json())
        assert back.names() == ["a", "b"]
        # seeded draws are identical across the round trip
        s1 = cat.sample("a", np.random.default_rng(9))
        s2 = back.sample("a", np.random.default_rng(9))
        assert s1 == s2
        assert s1["scenario"] == "a"
        assert s1["physics_us"] == 150
        assert s1["resolution"] == [32, 48]
        assert 0.0 <= s1["d"] <= 1.0 and s1["tex"] in ("wood", "tin")
        # different seeds draw differently (the randomization is live)
        s3 = cat.sample("a", np.random.default_rng(10))
        assert s3["d"] != s1["d"]

    def test_env_kwargs_is_the_launch_subset(self):
        spec = ScenarioSpec("rich", physics_rate_us=4000)
        assert spec.env_kwargs() == {"scenario": "rich",
                                     "physics_us": 4000}

    def test_zero_physics_rate_still_rides_every_sample(self):
        """A free (0 us) scenario must still push ``physics_us``: a
        producer reassigned slow -> fast has to RESET its rate, not
        keep the old physics while relabelling."""
        spec = ScenarioSpec("free", physics_rate_us=0)
        assert spec.sample(np.random.default_rng(0))["physics_us"] == 0
        assert spec.env_kwargs()["physics_us"] == 0

    def test_schema_validation(self):
        with pytest.raises(ValueError, match="inverted"):
            ScenarioSpec("bad", ranges={"d": (1.0, 0.0)})
        with pytest.raises(ValueError, match="range"):
            ScenarioSpec("bad", ranges={"d": "not-a-range"})
        with pytest.raises(ValueError, match="physics_rate_us"):
            ScenarioSpec("bad", physics_rate_us=-1)
        with pytest.raises(ValueError, match="resolution"):
            ScenarioSpec("bad", resolution=(0, 4))
        with pytest.raises(ValueError, match="duplicate"):
            ScenarioCatalog([ScenarioSpec("x"), ScenarioSpec("x")])
        with pytest.raises(ValueError, match="unknown spec field"):
            ScenarioSpec.from_dict("x", {"rangs": {}})
        with pytest.raises(ValueError, match="not a scenario catalog"):
            ScenarioCatalog.from_json("{\"format\": \"other/1\"}")
        with pytest.raises(KeyError, match="unknown scenario"):
            two_scenarios().get("nope")

    def test_save_load_file(self, tmp_path):
        cat = two_scenarios()
        path = cat.save(str(tmp_path / "cat.json"))
        assert ScenarioCatalog.load(path).names() == cat.names()


# ---------------------------------------------------------------------------
# curriculum
# ---------------------------------------------------------------------------


class TestCurriculum:
    def test_apportion_deterministic_largest_remainder(self):
        assert apportion({"a": 0.5, "b": 0.5}, 3) == ["a", "a", "b"]
        assert apportion({"a": 2, "b": 1}, 3) == ["a", "a", "b"]
        assert apportion({"a": 1.0, "b": 0.0}, 2) == ["a", "a"]
        assert len(apportion({"a": 1, "b": 1, "c": 1}, 7)) == 7

    def test_prioritized_reweights_toward_hard_scenarios(self):
        ctr = EventCounters()
        cur = CurriculumScheduler(
            two_scenarios(), policy="prioritized", interval=2,
            floor=0.1, counters=ctr,
        )
        stats = {
            "lite": {"rows": 50, "eligible": 50, "priority_mass": 5.0},
            "rich": {"rows": 50, "eligible": 50, "priority_mass": 45.0},
        }
        mix = cur.update(stats)
        assert mix["rich"] > mix["lite"]
        assert mix["lite"] >= 0.1 - 1e-9  # the starvation floor
        assert abs(sum(mix.values()) - 1.0) < 1e-9
        assert ctr.get("scenario_curriculum_updates") == 1
        assert ctr.get("scenario_mix_changes") == 1
        # replay_mix is non-None exactly when the mix is non-uniform
        assert cur.replay_mix() is not None
        # interval gating: only every Nth tick runs an update
        assert cur.tick(lambda: stats) is None
        assert cur.tick(lambda: stats) is not None

    def test_uniform_policy_is_the_identity(self):
        cur = CurriculumScheduler(["a", "b"], policy="uniform",
                                  counters=EventCounters())
        assert cur.update() == {"a": 0.5, "b": 0.5}
        assert cur.replay_mix() is None  # the scenario-less identity

    def test_pin_switches_policy_and_validates(self):
        ctr = EventCounters()
        cur = CurriculumScheduler(["a", "b"], policy="uniform",
                                  counters=ctr)
        with pytest.raises(ValueError, match="unknown scenario"):
            cur.pin({"zzz": 1.0})
        cur.pin({"b": 1.0})
        assert cur.policy == "pinned"
        assert cur.update()["b"] == 1.0
        assert cur.assign(3) == ["b", "b", "b"]
        assert ctr.get("scenario_mix_changes") == 1

    def test_constructor_validation(self):
        with pytest.raises(ValueError, match="policy"):
            CurriculumScheduler(["a"], policy="nope")
        with pytest.raises(ValueError, match="floor"):
            CurriculumScheduler(["a", "b", "c"], floor=0.5)
        with pytest.raises(ValueError, match="at least one"):
            CurriculumScheduler([])


# ---------------------------------------------------------------------------
# randomizer (in-process duplex peers)
# ---------------------------------------------------------------------------


class TestRandomizer:
    def test_push_round_trip_and_confirmation(self):
        from blendjax.btb.duplex import DuplexChannel as ProducerDuplex

        addr = f"tcp://127.0.0.1:{free_port()}"
        prod = ProducerDuplex(addr, btid=0)
        ctr = EventCounters()
        rnd = DomainRandomizer(two_scenarios(), [addr], counters=ctr)
        try:
            assert rnd.assign(0, "rich") == 1
            msg = prod.recv(timeoutms=5000)
            assert msg["cmd"] == "scenario"
            assert msg["scenario"] == "rich"
            assert msg["params"]["physics_us"] == 2000
            assert 0.6 <= msg["params"]["density"] <= 1.0
            assert ctr.get("scenario_pushes") == 1
            assert ctr.get("scenario_samples") == 1
            assert rnd.assignments == ["rich"]
            # confirmation closes on the data plane: first stamped info
            rnd.note_info(0, {"scenario": "lite"})  # stale echo: no
            assert ctr.get("scenario_applies") == 0
            rnd.note_info(0, {"scenario": "rich"})
            rnd.note_info(0, {"scenario": "rich"})  # counted once
            assert ctr.get("scenario_applies") == 1
        finally:
            prod.close()
            rnd.close()

    def test_apply_assignment_pushes_only_changes(self):
        from blendjax.btb.duplex import DuplexChannel as ProducerDuplex

        addrs = [f"tcp://127.0.0.1:{free_port()}" for _ in range(2)]
        prods = [ProducerDuplex(a, btid=i) for i, a in enumerate(addrs)]
        ctr = EventCounters()
        rnd = DomainRandomizer(
            two_scenarios(), [[addrs[0]], [addrs[1]]], counters=ctr,
        )
        try:
            assert rnd.apply_assignment(["lite", "rich"]) == [0, 1]
            # re-applying the same assignment pushes nothing
            assert rnd.apply_assignment(["lite", "rich"]) == []
            assert ctr.get("scenario_pushes") == 2
            assert rnd.apply_assignment(["rich", "rich"]) == [0]
            assert prods[0].recv(timeoutms=5000)["scenario"] == "lite"
            assert prods[0].recv(timeoutms=5000)["scenario"] == "rich"
            with pytest.raises(ValueError, match="fleets"):
                rnd.apply_assignment(["lite"])
        finally:
            for p in prods:
                p.close()
            rnd.close()

    def test_dead_producer_push_is_bounded_not_wedged(self):
        """THE chaos property the duplex send must keep: pushing into a
        dead endpoint returns within the push timeout — the randomizer
        thread is never wedged — and once the pipe fills, failures are
        counted instead of blocked on."""
        ctr = EventCounters()
        dead = f"tcp://127.0.0.1:{free_port()}"  # nothing ever listens
        rnd = DomainRandomizer(
            two_scenarios(), [dead], counters=ctr, push_timeout_ms=120,
        )
        try:
            t0 = time.monotonic()
            for _ in range(16):  # well past the PAIR HWM (10)
                rnd.assign(0, "lite")
            elapsed = time.monotonic() - t0
            # 16 pushes, each bounded by ~120ms: generous ceiling that
            # still catches a single unbounded (10s default) send
            assert elapsed < 8.0, f"pushes wedged for {elapsed:.1f}s"
            assert ctr.get("scenario_push_failures") > 0
            snap = ctr.snapshot()
            assert snap["scenario_pushes"] \
                + snap["scenario_push_failures"] == 16
        finally:
            rnd.close()


# ---------------------------------------------------------------------------
# replay strata
# ---------------------------------------------------------------------------


def _fill(buf, n=64, stamp=True):
    for i in range(n):
        buf.append(
            {"obs": np.float32(i), "reward": np.float32(i % 7)},
            scenario=(("lite" if i % 2 == 0 else "rich")
                      if stamp else None),
        )


class TestReplayStrata:
    def test_stamps_never_perturb_the_draw_stream(self):
        """Scenario plane ON (stamped rows) vs OFF: identical appends
        must yield bit-identical sample streams — the stamps are pure
        bookkeeping (regression lock for the acceptance contract)."""
        a = ReplayBuffer(128, seed=3, counters=EventCounters())
        b = ReplayBuffer(128, seed=3, counters=EventCounters())
        _fill(a, stamp=False)
        _fill(b, stamp=True)
        for _ in range(8):
            _, ia, wa = a.sample(16)
            _, ib, wb = b.sample(16)
            np.testing.assert_array_equal(ia, ib)
            np.testing.assert_array_equal(wa, wb)
        assert b.counters.get("scenario_rows_stamped") == 64

    def test_uniform_mix_is_byte_identical_to_no_mix(self):
        """A uniform ``scenario_mix`` takes the exact scenario-less
        draw path (the no-op contract docs/scenarios.md pins)."""
        a = ReplayBuffer(128, seed=5, counters=EventCounters())
        b = ReplayBuffer(128, seed=5, counters=EventCounters())
        _fill(a), _fill(b)
        for _ in range(6):
            _, ia, wa = a.sample(16, scenario_mix=None)
            _, ib, wb = b.sample(
                16, scenario_mix={"lite": 0.5, "rich": 0.5}
            )
            np.testing.assert_array_equal(ia, ib)
            np.testing.assert_array_equal(wa, wb)
        assert b.counters.get("scenario_strata_draws") == 0

    def test_nonuniform_mix_shapes_the_draw(self):
        buf = ReplayBuffer(256, seed=1, counters=EventCounters())
        _fill(buf, n=128)
        _, idx, w = buf.sample(
            40, scenario_mix={"lite": 0.75, "rich": 0.25}
        )
        lite = buf._scenario_ids["lite"]
        picked = buf._scenario[idx]
        assert (picked == lite).sum() == 30  # exact apportionment
        assert w.max() == pytest.approx(1.0)
        assert buf.counters.get("scenario_strata_draws") == 1
        # a mix naming only scenarios with no rows falls back safely
        _, idx2, _ = buf.sample(8, scenario_mix={"ghost": 1.0})
        assert idx2.shape == (8,)
        # an equal-weight PARTIAL mix is NOT the identity: pinning one
        # scenario alone restricts the draw to its stratum
        _, idx3, _ = buf.sample(8, scenario_mix={"rich": 1.0})
        rich = buf._scenario_ids["rich"]
        assert (buf._scenario[idx3] == rich).all()

    def test_scenario_stats_and_stats_surface(self):
        buf = ReplayBuffer(64, seed=0, counters=EventCounters())
        _fill(buf, n=32)
        buf.append({"obs": np.float32(0), "reward": np.float32(0)})
        st = buf.scenario_stats()
        assert st["lite"]["rows"] == 16 and st["rich"]["rows"] == 16
        assert st["lite"]["eligible"] == 16
        assert st["lite"]["priority_mass"] > 0
        assert st["_unlabelled"]["rows"] == 1
        assert buf.stats()["scenarios"]["rich"]["rows"] == 16

    def test_unhealthy_rows_excluded_from_strata_eligibility(self):
        buf = ReplayBuffer(32, seed=0, counters=EventCounters())
        buf.append({"obs": np.float32(1)}, scenario="lite")
        buf.append({"obs": np.float32(2)}, scenario="lite",
                   healthy=False)
        st = buf.scenario_stats()
        assert st["lite"]["rows"] == 2
        assert st["lite"]["eligible"] == 1

    def test_strata_draw_honors_drawable_mask_override(self):
        """The strata path must respect subclass eligibility narrowing
        (``_drawable_mask_locked`` — ShardedReplay excludes
        quarantined-shard/journaled rows there): a stratified draw
        must never select rows the base draw could not gather."""

        class HalfDead(ReplayBuffer):
            def _drawable_mask_locked(self):
                # emulate a dead shard owning the first half of the ring
                mask = self._valid.copy()
                mask[: self.capacity // 2] = False
                return mask

        buf = HalfDead(64, seed=2, counters=EventCounters())
        _fill(buf, n=64)
        _, idx, _ = buf.sample(
            16, scenario_mix={"lite": 0.7, "rich": 0.3}
        )
        assert (idx >= 32).all(), idx
        # the uniform-identity probe uses the same mask: a full-span
        # uniform mix over only-live rows still short-circuits
        _, idx2, _ = buf.sample(
            16, scenario_mix={"lite": 0.5, "rich": 0.5}
        )
        assert idx2.shape == (16,)

    def test_save_restore_preserves_stamps_and_stream(self, tmp_path):
        buf = ReplayBuffer(64, seed=11, counters=EventCounters())
        _fill(buf, n=48)
        path = str(tmp_path / "ck.npz")
        buf.save(path)
        back = ReplayBuffer.restore(path, counters=EventCounters())
        np.testing.assert_array_equal(back._scenario, buf._scenario)
        assert back._scenario_names == buf._scenario_names
        assert back.scenario_stats() == buf.scenario_stats()
        # the restored buffer continues the exact draw stream, strata
        # included
        for mix in (None, {"lite": 0.8, "rich": 0.2}):
            _, i1, w1 = buf.sample(12, scenario_mix=mix)
            _, i2, w2 = back.sample(12, scenario_mix=mix)
            np.testing.assert_array_equal(i1, i2)
            np.testing.assert_array_equal(w1, w2)

    def test_btr_prefill_bit_identical_with_stamps(self, tmp_path):
        """The ``healthy``-key in-band pattern extended to
        ``scenario``: a buffer prefilled from a ``.btr`` recording of
        stamped transitions matches direct appends bit-for-bit —
        stored bytes AND stamps AND the draw stream."""
        from blendjax.btt.file import FileRecorder

        rng = np.random.default_rng(2)
        transitions = [
            {"obs": rng.standard_normal(3).astype(np.float32),
             "reward": np.float32(i)}
            for i in range(40)
        ]
        scen = ["lite" if i % 3 else "rich" for i in range(40)]
        path = str(tmp_path / "run_00.btr")
        rec = FileRecorder(path, max_messages=100)
        with rec:
            for tr, s in zip(transitions, scen):
                rec.save(transition_to_message(
                    tr, healthy=True, scenario=s
                ))
        direct = ReplayBuffer(64, seed=4, counters=EventCounters())
        for tr, s in zip(transitions, scen):
            direct.append(dict(tr), scenario=s)
        pre = ReplayBuffer(64, seed=4, counters=EventCounters())
        assert prefill_from_btr(pre, path) == 40
        np.testing.assert_array_equal(pre._scenario, direct._scenario)
        assert pre._scenario_names == direct._scenario_names
        for key, col in direct.store.state_arrays().items():
            np.testing.assert_array_equal(
                pre.store.state_arrays()[key], col, err_msg=key
            )
        for _ in range(4):
            _, i1, _ = direct.sample(8)
            _, i2, _ = pre.sample(8)
            np.testing.assert_array_equal(i1, i2)


# ---------------------------------------------------------------------------
# heterogeneous fan-in
# ---------------------------------------------------------------------------


class TestHeteroFanIn:
    def _seg(self, fanin, fid, t, n, d, fill=1.0):
        lists = (
            [np.full((n, d), fill, np.float32) for _ in range(t)],
            [np.zeros((n,), np.int32) for _ in range(t)],
            [np.full((n,), fill, np.float32) for _ in range(t)],
            [np.zeros((n,), bool) for _ in range(t)],
        )
        ev = threading.Event()
        assert fanin.put_segment(fid, lists, ev)
        return fanin.queues[fid].get_nowait()

    def test_mixed_obs_shapes_assemble_per_group(self):
        from blendjax.parallel import SegmentFanIn

        fanin = SegmentFanIn([2, 2], mesh=None)
        segs = {
            0: self._seg(fanin, 0, 4, 2, 3, fill=1.0),   # obs dim 3
            1: self._seg(fanin, 1, 4, 2, 5, fill=2.0),   # obs dim 5
        }
        batches = fanin.assemble_groups(segs)
        assert len(batches) == 2
        b0, b1 = batches
        # group 0 carries fleet 0's rows live, fleet 1's zero-masked
        np.testing.assert_array_equal(b0.data["mask"], [1, 1, 0, 0])
        np.testing.assert_array_equal(b1.data["mask"], [0, 0, 1, 1])
        assert b0.data["obs"].shape == (4, 4, 3)
        assert b1.data["obs"].shape == (4, 4, 5)
        assert (b0.data["obs"][:2] == 1.0).all()
        assert (b0.data["obs"][2:] == 0.0).all()
        assert (b1.data["obs"][2:] == 2.0).all()
        b0.recycle(), b1.recycle()
        # homogeneous segments keep the single-group (legacy) path
        segs = {
            0: self._seg(fanin, 0, 4, 2, 3),
            1: self._seg(fanin, 1, 4, 2, 3),
        }
        batches = fanin.assemble_groups(segs)
        assert len(batches) == 1
        np.testing.assert_array_equal(
            batches[0].data["mask"], [1, 1, 1, 1]
        )
        batches[0].recycle()

    def test_collect_min_ready_returns_without_slow_fleets(self):
        from blendjax.parallel import SegmentFanIn

        fanin = SegmentFanIn([1, 1], mesh=None)
        self._put = self._seg  # reuse builder but leave seg enqueued
        lists = (
            [np.zeros((1, 2), np.float32)] * 3,
            [np.zeros((1,), np.int32)] * 3,
            [np.zeros((1,), np.float32)] * 3,
            [np.zeros((1,), bool)] * 3,
        )
        ev = threading.Event()
        fanin.put_segment(0, lists, ev)  # only fleet 0 produced
        t0 = time.monotonic()
        segs = fanin.collect(
            lambda f: True, ev, min_ready=1,
            deadline=time.monotonic() + 10,
        )
        assert list(segs) == [0]  # returned without fleet 1
        assert time.monotonic() - t0 < 5.0
        fanin.recycle_segments(segs)


# ---------------------------------------------------------------------------
# serve tier: gateway records + mix bench schema
# ---------------------------------------------------------------------------


class TestServeScenarios:
    def test_gateway_per_scenario_records(self):
        from blendjax.serve.client import ServeClient
        from blendjax.serve.gateway import start_gateway_thread
        from blendjax.serve.server import ServerFleet

        ctr = EventCounters()
        with ServerFleet(1, model="linear", obs_dim=4, slots=8,
                         seed=0) as fleet:
            gw = start_gateway_thread(fleet.addresses, counters=ctr)
            try:
                c = ServeClient(gw.address, timeoutms=10000)
                obs = np.zeros(4, np.float32)
                c.reset(scenario="easy")
                for _ in range(5):
                    c.step(obs)  # steps inherit the lease's label
                c.close_episode()
                c.reset(scenario="hard")
                c.step(obs)
                c.close_episode()
                c.reset()  # unlabelled traffic stays unrecorded
                c.step(obs)
                c.close_episode()
                stats = c.stats()
                c.close()
                sc = gw.gateway.scenario_stats()
                assert sc["easy"]["requests"] == 7  # reset+5 steps+close
                assert sc["hard"]["requests"] == 3
                assert sc["easy"]["errors"] == 0
                assert sc["easy"]["p99_ms"] >= sc["easy"]["p50_ms"] > 0
                assert set(sc) == {"easy", "hard"}
                # the records ride the stats/telemetry replies too,
                # next to the per-version ones
                assert stats["scenarios"]["easy"]["requests"] == 7
                assert "weights" in stats
                assert ctr.get("scenario_serve_requests") == 10
            finally:
                gw.close()

    def test_request_profile_apportionment(self):
        from benchmarks.serve_benchmark import (
            RequestProfile,
            assign_profiles,
            parse_mix,
        )

        ps = parse_mix("a:3:16:0,b:1:4:500", obs_dim=6)
        assert [p.scenario for p in ps] == ["a", "b"]
        assert ps[0].episode_len == 16 and ps[1].think_us == 500
        assigned = assign_profiles(ps, 4)
        assert [p.scenario for p in assigned] == ["a", "a", "a", "b"]
        # a bare profile fans out to every client (the legacy arms)
        one = RequestProfile(6, 32)
        assert assign_profiles(one, 3) == [one] * 3
        with pytest.raises(ValueError):
            parse_mix(":", obs_dim=6)

    def test_serve_mix_bench_emits_locked_schema(self):
        from benchmarks._common import SERVE_MIX_KEYS
        from benchmarks.serve_benchmark import measure_mix

        rec = measure_mix(seconds=1.2, clients=4, model="linear",
                          rounds=1)
        missing = [k for k in SERVE_MIX_KEYS if k not in rec]
        assert not missing, missing
        assert rec["serve_mix_p99_ms"] > 0
        assert rec["serve_mix_qps"] > 0
        assert set(rec["per_scenario"]) == {"steady", "bursty", "slow"}
        for lab, r in rec["per_scenario"].items():
            assert r["p99_ms"] >= r["p50_ms"], lab


# ---------------------------------------------------------------------------
# scenario bench schema (tiny fleet)
# ---------------------------------------------------------------------------


def test_scenario_bench_emits_locked_schema(fake_blender):
    from benchmarks._common import SCENARIO_BENCH_KEYS
    from benchmarks.scenario_benchmark import measure

    rec = measure(seconds=4.0, instances=1, clients=3, pairs=1,
                  slow_us=2500, serve_rounds=1)
    missing = [k for k in SCENARIO_BENCH_KEYS if k not in rec]
    assert not missing, missing
    assert rec["scenario_hetero_x"] > 0
    assert rec["per_scenario_steps"].get("lite", 0) > 0
    assert rec["serve_mix"]["serve_mix_p99_ms"] == \
        rec["serve_mix_p99_ms"]


def test_bench_headline_carries_scenario_metrics():
    sys.path.insert(0, REPO)
    import bench

    out = bench.assemble(
        {"host_stream": {"items_per_sec": 1.0}},
        scenario_bench={
            "phase": "scenario_bench",
            "scenarios": ["lite", "rich"],
            "scenario_hetero_x": 6.3,
            "serve_mix_p99_ms": 2.9,
            "pair_ratios": [6.2, 6.3],
        },
    )
    assert out["scenario_bench"]["scenario_hetero_x"] == 6.3
    line = bench.headline(out)
    assert line["scenario_hetero_x"] == 6.3
    assert line["serve_mix_p99_ms"] == 2.9
    # ... and bench_compare extracts + bounds them
    from scripts.bench_compare import (
        DEFAULT_CEILINGS,
        DEFAULT_FLOORS,
        compare,
    )
    metrics = {}
    from scripts.bench_compare import _flatten

    _flatten(out, metrics)
    assert metrics["scenario_hetero_x"] == 6.3
    assert metrics["serve_mix_p99_ms"] == 2.9
    assert "scenario_hetero_x" in DEFAULT_FLOORS
    assert "serve_mix_p99_ms" in DEFAULT_CEILINGS
    rows, regressions = compare(
        {"scenario_hetero_x": 6.3, "serve_mix_p99_ms": 2.9},
        {"scenario_hetero_x": 3.0, "serve_mix_p99_ms": 9.0},
        DEFAULT_FLOORS,
    )
    assert regressions == 2  # both directions enforced


# ---------------------------------------------------------------------------
# the acceptance run + chaos
# ---------------------------------------------------------------------------


class TestScenarioTraining:
    def test_three_fleet_two_scenario_run_with_curriculum_shift(
        self, fake_blender
    ):
        """THE acceptance scenario (ISSUE-14): 3 fleets, 2 scenarios at
        different physics rates, training completes with per-scenario
        replay strata populated, the curriculum demonstrably
        reweighting the mix (the pinned shift reassigns every fleet),
        and zero learner stalls attributable to the slow scenario (the
        update budget completes under a wall-clock bound far below the
        slow scene's all-barrier rate)."""
        from blendjax.models.actor_learner import ActorLearner
        from blendjax.parallel import FleetSet

        cat = two_scenarios(fast_us=0, slow_us=3000)
        values = np.array([0.0, 1.0], np.float64)
        ctr = EventCounters()
        with FleetSet(
            "", ENV_SCRIPT, num_fleets=3, envs_per_fleet=1,
            start_port=25600, timeoutms=30000, horizon=1_000_000,
            ctrl=True,
            fleet_env_kwargs=[
                cat.get("lite").env_kwargs(),
                cat.get("lite").env_kwargs(),
                cat.get("rich").env_kwargs(),
            ],
        ) as fs:
            assert len(fs.ctrl_addresses) == 3
            rnd = DomainRandomizer(cat, fs.ctrl_addresses,
                                   counters=ctr)
            cur = CurriculumScheduler(cat, policy="uniform",
                                      interval=4, counters=ctr)
            replay = ReplayBuffer(4096, seed=0,
                                  counters=EventCounters())
            al = ActorLearner(
                fs, obs_dim=1, num_actions=2, rollout_len=8, seed=1,
                replay=replay, scenarios=rnd, curriculum=cur,
                fanin_min_ready=1,
                action_map=lambda a: list(values[np.asarray(a)]),
            )
            # phase 1: uniform curriculum bootstraps the assignment
            # (lite, lite, rich by catalog-order apportionment)
            t0 = time.monotonic()
            stats1 = al.run(num_updates=16, seconds=60)
            assert stats1["updates"] == 16
            assert stats1["scenario_assignments"] == \
                ["lite", "lite", "rich"]
            # both scenarios contributed env steps AND replay strata
            assert stats1["env_steps_by_scenario"]["lite"] > 0
            assert stats1["env_steps_by_scenario"]["rich"] > 0
            strata = replay.scenario_stats()
            assert strata["lite"]["rows"] > 0
            assert strata["rich"]["rows"] > 0
            assert strata["lite"]["eligible"] > 0
            # phase 2: pin the mix to the rich scenario — the shift
            # must reassign every fleet through the randomizer
            cur.pin({"rich": 1.0})
            stats2 = al.run(num_updates=12, seconds=60)
            elapsed = time.monotonic() - t0
            assert stats2["updates"] == 12
            assert stats2["scenario_assignments"] == \
                ["rich", "rich", "rich"]
            assert stats2["updates_by_scenario"].get("rich", 0) > 0
            assert ctr.get("scenario_mix_changes") >= 1
            assert ctr.get("scenario_pushes") >= 2  # the 2 shifted fleets
            # no learner stall: 28 updates of 8-step rollouts against
            # a 3 ms/frame scene would take >> this bound if every
            # update barriered on the rich fleet
            assert elapsed < 90, f"learner stalled: {elapsed:.1f}s"
            # stats() is live and hub-probe shaped
            live = al.stats()
            assert "env_steps_by_scenario" in live
            assert "scenario_mix" in live
            rnd.close()

    @pytest.mark.chaos
    def test_sigkill_producer_mid_push_reassigns_on_respawn(
        self, fake_blender
    ):
        """Chaos satellite: SIGKILL a producer mid-randomization-push.
        The duplex send must not wedge the pushing thread; the
        quarantined env's scenario is re-pushed on respawn
        (``scenario_reassignments``) and the per-scenario counters
        reconcile with the total step count."""
        from blendjax.btt.chaos import kill_instance
        from blendjax.btt.envpool import EnvPool
        from blendjax.btt.faults import FaultPolicy
        from blendjax.btt.launcher import BlenderLauncher
        from blendjax.btt.supervise import FleetSupervisor

        cat = two_scenarios(fast_us=0, slow_us=500)
        ctr = EventCounters()
        policy = FaultPolicy(max_retries=1, backoff_base=0.05,
                             deadline_s=2.0, circuit_threshold=0,
                             seed=7)
        with BlenderLauncher(
            scene="", script=ENV_SCRIPT, num_instances=2,
            named_sockets=["GYM", "CTRL"], start_port=25900,
            background=True,
            instance_args=[
                ["--horizon", "1000000", "--scenario", "lite"],
            ] * 2,
        ) as bl:
            pool = EnvPool(bl.launch_info.addresses["GYM"],
                           timeoutms=10000, fault_policy=policy,
                           counters=ctr)
            rnd = DomainRandomizer(
                cat, [bl.launch_info.addresses["CTRL"]],
                counters=ctr, push_timeout_ms=150,
            )
            rnd._assigned[0] = "lite"
            with FleetSupervisor(bl, pool=pool, interval=0.2,
                                 restart=True, counters=ctr) as sup:
                pool.reset()
                steps = {"lite": 0, "rich": 0, None: 0}
                for _ in range(8):
                    _, _, _, infos = pool.step([0.5, 0.5])
                    for inf in infos:
                        steps[inf.get("scenario")] += 1
                # kill env 0's producer, then keep pushing INTO the
                # corpse: every push must return bounded
                kill_instance(bl, 0)
                t0 = time.monotonic()
                for _ in range(12):
                    rnd.assign(0, "rich")
                push_elapsed = time.monotonic() - t0
                assert push_elapsed < 6.0, \
                    f"pushes wedged {push_elapsed:.1f}s"
                assert sup.await_deaths(1, timeout=30)
                assert sup.await_healthy(timeout=30)
                # drive steps until the respawned env is re-admitted
                # and re-pushed: its scenario must follow it back
                reassigned = False
                deadline = time.monotonic() + 30
                while time.monotonic() < deadline:
                    _, _, _, infos = pool.step([0.5, 0.5])
                    for i, inf in enumerate(infos):
                        sid = inf.get("scenario")
                        steps[sid] = steps.get(sid, 0) + 1
                        if inf.get("readmitted"):
                            rnd.reassign(0, i)
                        rnd.note_info(0, inf)
                    if infos[0].get("scenario") == "rich":
                        reassigned = True
                        break
                assert reassigned, "scenario never followed the respawn"
                assert ctr.get("scenario_reassignments") >= 1
                # counters reconcile: every surfaced transition is
                # attributed (labelled or the quarantine synthetics)
                assert sum(steps.values()) > 0
                total = sum(v for v in steps.values())
                labelled = steps.get("lite", 0) + steps.get("rich", 0)
                assert labelled + steps.get(None, 0) == total
            pool.close()
            rnd.close()
