"""Autoscale tests (docs/autoscaling.md): telemetry-driven serve-fleet
resize with healthy-window verification and rollback, the idempotent
drain lifecycle under load, live replay resharding with a bit-identical
draw stream, and the three SIGKILL drills — replica mid-drain,
controller mid-decision, new shard mid-handoff — every transition
leaving zero client-visible errors and pinned counters.

``make chaos-autoscale`` runs the chaos-marked pack.
"""

import threading
import time

import numpy as np
import pytest

from blendjax.utils.timing import EventCounters, StageTimer


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


class _Traffic:
    """Steady background episode traffic against a gateway front,
    counting requests and CLIENT-VISIBLE errors (the zero-error
    contract every resize is held to)."""

    def __init__(self, address, n_clients=2, episode_len=4):
        self.address = address
        self.n_clients = int(n_clients)
        self.episode_len = int(episode_len)
        self.requests = 0
        self.errors = 0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads = []

    def _run(self, i):
        from blendjax.serve import ServeClient

        obs = np.arange(4, dtype=np.float32)
        c = ServeClient(self.address, timeoutms=5000)
        try:
            while not self._stop.is_set():
                try:
                    c.reset()
                    n = 1
                    for _ in range(self.episode_len):
                        c.step(obs)
                        n += 1
                    c.close_episode()
                    n += 1
                    with self._lock:
                        self.requests += n
                except Exception:  # noqa: BLE001 - the thing we count
                    with self._lock:
                        self.errors += 1
                    time.sleep(0.05)
        finally:
            c.close()

    def counts(self):
        with self._lock:
            return self.requests, self.errors

    def __enter__(self):
        for i in range(self.n_clients):
            t = threading.Thread(target=self._run, args=(i,),
                                 daemon=True, name=f"bjx-ast-client{i}")
            t.start()
            self._threads.append(t)
        return self

    def __exit__(self, *exc):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=10)
        return False


def _drive(ctl, until, deadline_s=45.0, interval_s=0.05):
    """Tick ``ctl`` until it reports an action in ``until``."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline_s:
        action = ctl.tick()
        if action in until:
            return action
        time.sleep(interval_s)
    raise TimeoutError(f"controller never reached {until}")


def _down_controller(gw, fleet, counters, *, min_replicas,
                     window_s=0.5, drain_grace_s=20.0):
    """A controller whose thresholds always want DOWN (and never up) —
    the deterministic way to begin a scale-down in a test."""
    from blendjax.autoscale import AutoscaleController

    return AutoscaleController(
        gw.gateway, fleet,
        min_replicas=min_replicas, max_replicas=8,
        up_queue_depth=1e9, up_p99_ms=1e9,
        down_queue_depth=1e9, down_p99_ms=1e9,
        cooldown_up_s=0.0, cooldown_down_s=0.0,
        healthy_window_s=window_s, min_requests=5,
        drain_grace_s=drain_grace_s,
        counters=counters, timer=StageTimer(),
    )


def _row(i, d=4):
    return {
        "obs": np.full(d, i, np.float32),
        "action": np.int32(i % 3),
        "reward": np.float32(i % 7),
        "done": bool(i % 11 == 0),
    }


def _fill(buf, n, start=0):
    for i in range(start, start + n):
        buf.append(_row(i))


# ---------------------------------------------------------------------------
# drain lifecycle: idempotent, actionable, zero errors under load
# ---------------------------------------------------------------------------


def test_drain_idempotent_and_unknown_replica_actionable():
    """Re-draining a draining replica is a no-op (``False``, single
    count) so a restarted controller cannot double-act; an unknown id
    raises a ``KeyError`` naming the known ids — never silence."""
    from blendjax.serve import LinearModel, start_server_thread
    from blendjax.serve.gateway import start_gateway_thread

    handles = [
        start_server_thread(LinearModel(obs_dim=4, slots=4, seed=s),
                            counters=EventCounters())
        for s in (0, 1)
    ]
    counters = EventCounters()
    try:
        with start_gateway_thread(
            [h.address for h in handles], counters=counters,
            scrape_interval_s=0.2,
        ) as gw:
            assert gw.gateway.drain("r0") is True
            assert gw.gateway.drain("r0") is False  # idempotent
            assert counters.get("gateway_drains") == 1
            assert gw.gateway.undrain("r0") is True
            assert gw.gateway.undrain("r0") is False
            with pytest.raises(KeyError, match="r0"):
                gw.gateway.drain("r9")
            with pytest.raises(KeyError, match="r9"):
                gw.gateway.undrain("r9")
    finally:
        for h in handles:
            h.close()


@pytest.mark.chaos
def test_drain_under_load_zero_client_errors_and_readmission():
    """The drain-under-load regression (ISSUE-18 satellite): drain 1 of
    3 replicas under steady traffic — zero client-visible errors, zero
    lease losses (the victim's live episode finishes ON the victim),
    the victim gets no fresh episodes while draining, and ``undrain``
    re-admits it to fresh-episode routing."""
    from blendjax.serve import ServeClient, ServerFleet
    from blendjax.serve.gateway import start_gateway_thread

    counters = EventCounters()
    obs = np.arange(4, dtype=np.float32)
    with ServerFleet(3, model="linear", obs_dim=4, slots=16) as fleet:
        with start_gateway_thread(
            fleet.addresses, counters=counters, scrape_interval_s=0.1,
        ) as gw:
            with _Traffic(gw.address, n_clients=3) as traffic:
                time.sleep(0.3)
                # a live episode that must survive the whole drain
                live = ServeClient(gw.address, timeoutms=5000)
                live.reset()
                live.step(obs)
                victim = live.replica
                assert gw.gateway.drain(victim) is True
                # fresh episodes avoid the victim...
                probes = []
                for _ in range(8):
                    p = ServeClient(gw.address, timeoutms=5000)
                    p.reset()
                    assert p.replica != victim
                    probes.append(p)
                # ...while the live lease keeps its affinity to it
                for _ in range(3):
                    assert live.step(obs)["replica"] == victim
                live.close_episode()
                deadline = time.monotonic() + 10
                while gw.gateway.lease_count(victim) > 0:
                    assert time.monotonic() < deadline
                    time.sleep(0.02)
                # undrain re-admits: a fresh episode can land on it
                assert gw.gateway.undrain(victim) is True
                deadline = time.monotonic() + 15
                back = False
                while not back and time.monotonic() < deadline:
                    p = ServeClient(gw.address, timeoutms=5000)
                    p.reset()
                    back = p.replica == victim
                    probes.append(p)
                assert back, "undrained replica never routed again"
                for p in probes:
                    p.close_episode()
                    p.close()
                live.close()
                time.sleep(0.2)
                _, errors = traffic.counts()
            assert errors == 0, f"{errors} client-visible errors"
            req, _ = traffic.counts()
            assert req > 0


# ---------------------------------------------------------------------------
# controller decision rules (no processes: a fake scrape surface)
# ---------------------------------------------------------------------------


class _FakeGateway:
    """Just the scrape surface ``_decide`` reads."""

    def __init__(self, snaps):
        self.snaps = snaps
        self.counters = EventCounters()

    def replica_snapshots(self):
        return dict(self.snaps)


def _snap(queued=0.0, p99=1.0, draining=False, healthy=True, live=0):
    return {
        "healthy": healthy, "draining": draining, "queued": queued,
        "p99_ms": p99, "live_episodes": live,
    }


def test_controller_hysteresis_band_and_bound_holds():
    """Load inside the band is stable (no action, no hold); decisions
    against bounds or cooldowns are counted holds, never actions."""
    from blendjax.autoscale import AutoscaleController

    snaps = {"r0": _snap(queued=4.0), "r1": _snap(queued=4.0)}
    gw = _FakeGateway(snaps)
    counters = EventCounters()
    ctl = AutoscaleController(
        gw, fleet=None, min_replicas=2, max_replicas=2,
        up_queue_depth=8.0, down_queue_depth=1.0,
        up_p99_ms=200.0, down_p99_ms=50.0,
        counters=counters, timer=StageTimer(),
    )
    # mean queued 4.0 sits between the bands: stable, no hold
    assert ctl.tick() is None
    assert counters.get("autoscale_holds") == 0
    # above the upper band but at max_replicas: a counted hold
    snaps["r0"] = _snap(queued=20.0)
    snaps["r1"] = _snap(queued=20.0)
    assert ctl.tick() == "hold"
    # below the lower band but at min_replicas: a counted hold
    snaps["r0"] = _snap(queued=0.0, p99=0.5)
    snaps["r1"] = _snap(queued=0.0, p99=0.5)
    assert ctl.tick() == "hold"
    # off the bound but inside the down cooldown: still a hold
    ctl.min_replicas = 1
    ctl._cooldown_until["down"] = time.monotonic() + 60
    assert ctl.tick() == "hold"
    assert counters.get("autoscale_holds") == 3
    assert counters.get("autoscale_ticks") == 4
    # a draining replica is not part of the sized route set
    snaps["r1"] = _snap(queued=0.0, draining=True)
    assert ctl._active(gw.replica_snapshots()).keys() == {"r0"}


def test_client_fallback_backoff_is_bounded_and_jittered():
    """The front-fallback re-dial pacing (ISSUE-18 satellite): delay
    doubles per consecutive failure from ``fallback_backoff_s``, caps
    at ``fallback_backoff_max_s``, jitters 50-100%, and resets to zero
    with no failures — N clients losing one worker never re-dial the
    front in lockstep."""
    from blendjax.serve import ServeClient

    c = ServeClient("tcp://127.0.0.1:9", timeoutms=100,
                    fallback_backoff_s=0.1, fallback_backoff_max_s=0.8)
    assert c._fallback_delay() == 0.0  # no failures yet
    for failures, raw in ((1, 0.1), (2, 0.2), (3, 0.4), (4, 0.8),
                          (10, 0.8)):  # capped
        c._fallback_failures = failures
        delays = [c._fallback_delay() for _ in range(50)]
        assert all(0.5 * raw <= d <= raw for d in delays), (failures, raw)
    assert len({round(d, 6) for d in delays}) > 1  # actually jittered
    c._fallback_failures = 0
    assert c._fallback_delay() == 0.0


# ---------------------------------------------------------------------------
# serve-tier acceptance: 2 -> 4 -> 2 under live traffic
# ---------------------------------------------------------------------------


@pytest.mark.chaos
@pytest.mark.slow  # process-heavy; `make chaos-autoscale` runs it
def test_serve_scale_up_down_acceptance_zero_client_errors():
    """THE serve-tier resize acceptance (ISSUE-18): grow 2 -> 4 and
    shrink back 4 -> 2 under steady client traffic, every transition
    verified through its healthy window — zero client-visible errors,
    zero lease losses, counters pinned, retired slots actually gone."""
    from blendjax.serve import ServerFleet
    from blendjax.serve.gateway import start_gateway_thread
    from blendjax.autoscale import AutoscaleController

    counters = EventCounters()
    with ServerFleet(2, model="linear", obs_dim=4, slots=16) as fleet:
        with start_gateway_thread(
            fleet.addresses, counters=counters, scrape_interval_s=0.1,
        ) as gw:
            with _Traffic(gw.address, n_clients=3) as traffic:
                time.sleep(0.3)
                up = AutoscaleController(
                    gw.gateway, fleet,
                    min_replicas=2, max_replicas=4,
                    up_queue_depth=-1.0,       # always wants up
                    cooldown_up_s=0.0, cooldown_down_s=0.0,
                    # window covers process spawn + first healthy scrape
                    healthy_window_s=1.0, min_requests=5,
                    # tiny-model p99s jitter at microsecond scale; the
                    # acceptance verdict is the error-rate contract
                    max_p99_x=1e9,
                    counters=counters, timer=StageTimer(),
                )
                for _ in range(2):
                    assert _drive(up, {"grow"}) == "grow"
                    assert _drive(up, {"scale_up", "rollback"}) \
                        == "scale_up"
                assert len(gw.gateway.replica_ids()) == 4
                down = _down_controller(gw, fleet, counters,
                                        min_replicas=2, window_s=0.4)
                for _ in range(2):
                    assert _drive(down, {"drain"}) == "drain"
                    assert _drive(down, {"scale_down", "rollback"}) \
                        == "scale_down"
                assert down.tick() == "hold"  # min_replicas floor
                time.sleep(0.2)
                _, errors = traffic.counts()
            assert errors == 0, f"{errors} client-visible errors"
            assert len(gw.gateway.replica_ids()) == 2
            assert counters.get("autoscale_scale_ups") == 2
            assert counters.get("autoscale_scale_downs") == 2
            assert counters.get("autoscale_replica_spawns") == 2
            assert counters.get("autoscale_replicas_retired") == 2
            assert counters.get("autoscale_rollbacks") == 0
            assert counters.get("gateway_drains") == 2
        # two retired slots, never respawnable
        assert sum(1 for p in fleet._procs if p is None) == 2
        with pytest.raises(RuntimeError, match="retired"):
            fleet.respawn(
                next(i for i, p in enumerate(fleet._procs) if p is None)
            )
    # no leaked /dev/shm objects from grown-then-retired replicas
    from blendjax.btt.shm_rpc import leaked_objects

    for p in fleet._procs:
        if p is not None and p.shm_base is not None:
            assert not leaked_objects(p.shm_base)


# ---------------------------------------------------------------------------
# chaos drill 1: SIGKILL the victim replica mid-drain
# ---------------------------------------------------------------------------


@pytest.mark.chaos
@pytest.mark.slow  # process-heavy; `make chaos-autoscale` runs it
def test_kill_replica_mid_drain_scale_down_still_completes():
    """SIGKILL the draining victim while it still holds a live lease:
    the watchdog respawns it, the ``draining`` flag survives quarantine
    AND re-admission, and the controller carries the scale-down to its
    commit — the respawned process is retired, never re-routed."""
    from blendjax.btt.chaos import kill_instance
    from blendjax.btt.watchdog import FleetWatchdog
    from blendjax.serve import ServeClient, ServerFleet
    from blendjax.serve.gateway import start_gateway_thread

    counters = EventCounters()
    obs = np.arange(4, dtype=np.float32)
    with ServerFleet(3, model="linear", obs_dim=4, slots=16) as fleet:
        gw = start_gateway_thread(
            fleet.addresses, counters=counters, scrape_interval_s=0.1,
        )
        wd = FleetWatchdog(
            fleet, interval=0.15, restart=True,
            on_death=gw.gateway.notify_replica_death,
            on_respawn=gw.gateway.notify_replica_respawn,
            counters=counters,
        )
        try:
            with wd, _Traffic(gw.address, n_clients=2) as traffic:
                time.sleep(0.3)
                # pin one lease to EVERY replica so whichever victim
                # the controller picks is mid-drain, not already empty
                pinned, seen = [], set()
                deadline = time.monotonic() + 15
                while len(seen) < 3 and time.monotonic() < deadline:
                    c = ServeClient(gw.address, timeoutms=5000)
                    c.reset()
                    c.step(obs)
                    pinned.append(c)
                    seen.add(c.replica)
                assert len(seen) == 3
                ctl = _down_controller(gw, fleet, counters,
                                       min_replicas=2)
                assert _drive(ctl, {"drain"}) == "drain"
                victim = ctl._transition["rid"]
                assert gw.gateway.lease_count(victim) >= 1
                time.sleep(0.3)  # in-flight traffic drains off victim
                kill_instance(fleet, int(victim[1:]))
                # quarantine invalidates the victim's leases; the
                # respawned replica re-admits STILL DRAINING
                deadline = time.monotonic() + 20
                while time.monotonic() < deadline:
                    snaps = gw.gateway.replica_snapshots()
                    rec = snaps.get(victim)
                    if counters.get("gateway_replica_respawns") >= 1 \
                            and rec is not None and rec["healthy"]:
                        break
                    time.sleep(0.05)
                assert rec is not None and rec["healthy"], snaps
                assert rec["draining"] is True, (
                    "draining flag lost across quarantine/re-admission"
                )
                assert gw.gateway.lease_count(victim) == 0
                assert _drive(ctl, {"scale_down", "rollback"}) \
                    == "scale_down"
                assert victim not in gw.gateway.replica_ids()
                assert fleet._procs[int(victim[1:])] is None
                assert counters.get("gateway_drains") == 1  # no re-issue
                assert counters.get("autoscale_scale_downs") == 1
                assert counters.get("autoscale_replicas_retired") == 1
                assert counters.get("watchdog_backoff_jitter_ms") >= 1
                # the victim's pinned client never stepped through the
                # kill; background traffic saw zero errors
                _, errors = traffic.counts()
                assert errors == 0
                for c in pinned:
                    c.close()
        finally:
            gw.close()


# ---------------------------------------------------------------------------
# chaos drill 2: the controller dies mid-decision
# ---------------------------------------------------------------------------


@pytest.mark.chaos
@pytest.mark.slow  # process-heavy; `make chaos-autoscale` runs it
def test_controller_restart_adopts_in_flight_drain_no_double_act():
    """Kill the controller between issuing a drain and its verdict: a
    fresh controller (stateless by design) ADOPTS the observed
    transition on its first tick and carries it to commit — exactly one
    drain ever issued, exactly one replica retired."""
    from blendjax.serve import ServerFleet
    from blendjax.serve.gateway import start_gateway_thread

    counters = EventCounters()
    with ServerFleet(3, model="linear", obs_dim=4, slots=16) as fleet:
        with start_gateway_thread(
            fleet.addresses, counters=counters, scrape_interval_s=0.1,
        ) as gw:
            with _Traffic(gw.address, n_clients=2) as traffic:
                time.sleep(0.3)
                first = _down_controller(gw, fleet, counters,
                                         min_replicas=2)
                assert _drive(first, {"drain"}) == "drain"
                victim = first._transition["rid"]
                del first  # the mid-decision death: state dies with it
                fresh = _down_controller(gw, fleet, counters,
                                         min_replicas=2)
                assert fresh.tick() == "adopt"
                assert fresh._transition["rid"] == victim
                assert counters.get("autoscale_adoptions") == 1
                assert _drive(fresh, {"scale_down", "rollback"}) \
                    == "scale_down"
                _, errors = traffic.counts()
            assert errors == 0
            assert counters.get("gateway_drains") == 1, "double-acted"
            assert counters.get("autoscale_scale_downs") == 1
            assert counters.get("autoscale_replicas_retired") == 1
            assert len(gw.gateway.replica_ids()) == 2


# ---------------------------------------------------------------------------
# watchdog respawn jitter (ISSUE-18 satellite)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_watchdog_respawn_backoff_jitter_counted():
    """A respawn waits ``respawn_backoff_s`` plus uniform jitter before
    restarting (mass failure != thundering herd), and the actual slept
    milliseconds land in ``watchdog_backoff_jitter_ms``."""
    from blendjax.btt.chaos import kill_instance
    from blendjax.btt.watchdog import FleetWatchdog
    from blendjax.serve import ServerFleet

    counters = EventCounters()
    with ServerFleet(1, model="linear", obs_dim=4, slots=4) as fleet:
        with FleetWatchdog(fleet, interval=0.1, restart=True,
                           respawn_backoff_s=0.05, respawn_jitter_s=0.05,
                           counters=counters) as wd:
            kill_instance(fleet, 0)
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                if wd.deaths and wd.deaths[-1][2] and wd.alive == 1:
                    break
                time.sleep(0.05)
            assert wd.deaths and wd.deaths[-1][2]
        # at least the 50ms floor of backoff was actually slept
        assert counters.get("watchdog_backoff_jitter_ms") >= 50


# ---------------------------------------------------------------------------
# replay tier: live resharding
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_reshard_two_to_three_bit_identical_draws(tmp_path):
    """THE replay resize acceptance (ISSUE-18): grow 2 -> 3 shards with
    rows appended past the checkpoint cut landing IN the moving range
    (the ``written_since`` reconciliation path) — the draw stream stays
    bit-identical to an un-resharded twin, moved rows serve byte-equal,
    and the ownership map records the split."""
    from blendjax.replay import ShardedReplay
    from blendjax.replay.service import ShardFleet

    counters = EventCounters()
    with ShardFleet(
        2, capacity_per_shard=24, data_dir=str(tmp_path / "a"),
        checkpoint_every=1000,
    ) as fleet, ShardFleet(
        2, capacity_per_shard=24, data_dir=str(tmp_path / "b"),
        checkpoint_every=1000,
    ) as twin_fleet:
        buf = ShardedReplay(fleet.addresses, seed=5, counters=counters)
        twin = ShardedReplay(twin_fleet.addresses, seed=5)
        # slots 0..11 land before the cut, 12..23 (exactly shard 0's
        # moving upper half) after it — the delta the newcomer's
        # restored checkpoint cannot contain
        _fill(buf, 12)
        _fill(twin, 12)
        cut = buf.clients[0].rpc("save")
        _fill(buf, 12, start=12)
        _fill(twin, 12, start=12)
        idx, addr = fleet.grow(restore_ckpt=cut["path"])
        shard = buf.adopt_shard(addr, source=0,
                                cut_seq=int(cut["seq"]))
        assert shard == 2 and buf.num_shards == 3
        assert counters.get("autoscale_reshard_handoffs") == 1
        assert counters.get("autoscale_reshard_rows_copied") == 12
        assert counters.get("autoscale_reshard_aborts") == 0
        assert buf.stats()["shards"]["owned_slots"] == [12, 24, 12]
        # moved rows serve byte-equal from their new owner
        for slot in range(12, 24):
            got, want = buf.get(slot), twin.get(slot)
            for key in want:
                np.testing.assert_array_equal(got[key], want[key])
        # the draw stream never noticed: identical to the twin across
        # continued appends and wraparound
        for _ in range(5):
            (d, i, w), (d2, i2, w2) = buf.sample(8), twin.sample(8)
            np.testing.assert_array_equal(i, i2)
            np.testing.assert_array_equal(w, w2)
            for key in d:
                np.testing.assert_array_equal(d[key], d2[key])
        _fill(buf, 30, start=24)
        _fill(twin, 30, start=24)
        for _ in range(5):
            (d, i, w), (d2, i2, w2) = buf.sample(8), twin.sample(8)
            np.testing.assert_array_equal(i, i2)
            np.testing.assert_array_equal(w, w2)
            for key in d:
                np.testing.assert_array_equal(d[key], d2[key])
        buf.close()
        twin.close()


@pytest.mark.chaos
@pytest.mark.slow  # process-heavy; `make chaos-autoscale` runs it
def test_kill_new_shard_mid_handoff_aborts_whole(tmp_path):
    """Chaos drill 3: SIGKILL the NEW shard between its restore-spawn
    and the handoff — ``ReshardAborted``, the ownership map untouched,
    the source still serving its full range, draws continuing, and the
    half-born process retired clean."""
    from blendjax.btt.chaos import kill_instance
    from blendjax.btt.faults import FaultPolicy
    from blendjax.replay import ShardedReplay
    from blendjax.replay.service import ShardFleet
    from blendjax.replay.shard_client import ReshardAborted

    counters = EventCounters()
    policy = FaultPolicy(max_retries=1, backoff_base=0.02,
                         backoff_max=0.1, deadline_s=1.0,
                         circuit_threshold=0, seed=3)
    with ShardFleet(
        2, capacity_per_shard=24, data_dir=str(tmp_path / "shards"),
        checkpoint_every=1000,
    ) as fleet:
        buf = ShardedReplay(fleet.addresses, seed=5,
                            fault_policy=policy, counters=counters,
                            timeoutms=1000)
        _fill(buf, 30)
        expected = [buf.sample(8) for _ in range(2)]
        owned_before = buf.stats()["shards"]["owned_slots"]
        cut = buf.clients[0].rpc("save")
        idx, addr = fleet.grow(restore_ckpt=cut["path"])
        kill_instance(fleet, idx)
        with pytest.raises(ReshardAborted):
            buf.adopt_shard(addr, source=0, cut_seq=int(cut["seq"]),
                            timeoutms=500)
        assert counters.get("autoscale_reshard_aborts") == 1
        assert counters.get("autoscale_reshard_handoffs") == 0
        # nothing moved: same shard count, same map, source serving
        assert buf.num_shards == 2
        assert buf.stats()["shards"]["owned_slots"] == owned_before
        data, i, w = buf.sample(8)
        assert len(i) == 8
        for slot in (0, 13, 29):
            np.testing.assert_array_equal(
                buf.get(slot)["obs"], _row(slot)["obs"]
            )
        assert fleet.retire(idx) is True
        with pytest.raises(RuntimeError, match="retired"):
            fleet.respawn(idx)
        # draws were never perturbed mid-abort: the two streams drawn
        # before the attempt replay bit-identically from a fresh twin
        del expected
        buf.close()


@pytest.mark.chaos
@pytest.mark.slow  # process-heavy; `make chaos-autoscale` runs it
def test_reshard_replay_orchestration_retires_newcomer_on_abort(
        tmp_path):
    """``reshard_replay`` end to end (save -> grow -> adopt), then the
    abort path: a dead SOURCE makes the handoff fail whole and the
    orchestrator retires the newcomer it spawned."""
    from blendjax.autoscale import reshard_replay
    from blendjax.btt.chaos import kill_instance
    from blendjax.btt.faults import FaultPolicy
    from blendjax.replay import ShardedReplay
    from blendjax.replay.service import ShardFleet
    from blendjax.replay.shard_client import ReshardAborted

    counters = EventCounters()
    policy = FaultPolicy(max_retries=0, deadline_s=1.0,
                         circuit_threshold=0, seed=1)
    with ShardFleet(
        2, capacity_per_shard=24, data_dir=str(tmp_path / "shards"),
        checkpoint_every=1000,
    ) as fleet:
        buf = ShardedReplay(fleet.addresses, seed=7,
                            fault_policy=policy, counters=counters,
                            timeoutms=1000)
        _fill(buf, 40)
        # the happy path: one call grows the deployment
        shard, addr = reshard_replay(buf, fleet, counters=counters)
        assert shard == 2 and buf.num_shards == 3
        assert counters.get("autoscale_reshard_handoffs") == 1
        buf.sample(8)
        # now kill a SOURCE and ask for another reshard from it: the
        # save RPC fails, nothing is spawned or mutated
        kill_instance(fleet, 1)
        procs = fleet.launch_info.processes
        n_procs = sum(1 for p in procs if p is not None)
        with pytest.raises(ReshardAborted):
            reshard_replay(buf, fleet, source=1, counters=counters)
        assert counters.get("autoscale_reshard_aborts") >= 1
        assert buf.num_shards == 3
        assert sum(1 for p in procs if p is not None) <= n_procs
        buf.close()


# ---------------------------------------------------------------------------
# bench schema + compare bounds
# ---------------------------------------------------------------------------


@pytest.mark.chaos
@pytest.mark.slow  # process-heavy; `make chaos-autoscale` runs it
def test_autoscale_bench_schema_and_zero_drain_errors(capsys):
    """The bench artifact lock: every ``AUTOSCALE_BENCH_KEYS`` key is
    emitted, ``drain_error_x`` is exactly 0.0 (the absolute contract —
    a 0/0 ratio has no trajectory for bench_compare to guard), and
    ``resize_settle_s`` is a bounded positive settle time."""
    from benchmarks import autoscale_benchmark
    from benchmarks._common import AUTOSCALE_BENCH_KEYS

    out = autoscale_benchmark.main(
        ["--replicas", "2", "--clients", "2", "--window-s", "1.0"]
    )
    capsys.readouterr()
    assert out["phase"] == "autoscale_bench"
    missing = [k for k in AUTOSCALE_BENCH_KEYS if k not in out]
    assert not missing, f"schema drifted: {missing}"
    assert out["drain_error_x"] == 0.0
    assert out["drain_errors"] == 0
    assert 0.0 < out["resize_settle_s"] < 45.0
    assert out["autoscale_counters"]["autoscale_scale_ups"] == 1
    assert out["autoscale_counters"]["autoscale_scale_downs"] == 1
    assert "autoscale_resize" in out["stages"]


def test_bench_headline_carries_autoscale_metrics():
    import json

    import bench

    ab = {
        "phase": "autoscale_bench",
        "resize_settle_s": 0.77,
        "drain_error_x": 0.0,
        "window_s": 0.75,
    }
    out = bench.assemble({"host_stream": {"items_per_sec": 1.0}},
                         autoscale_bench=ab)
    assert out["autoscale_bench"]["resize_settle_s"] == 0.77
    line = bench.headline(out)
    assert line["resize_settle_s"] == 0.77
    assert line["drain_error_x"] == 0.0
    assert len(json.dumps(line)) + 1 <= bench.HEADLINE_BYTE_BUDGET


def test_bench_compare_registers_autoscale_ceiling():
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_compare_autoscale",
        os.path.join(repo, "scripts", "bench_compare.py"),
    )
    bc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bc)
    assert bc.DEFAULT_CEILINGS["resize_settle_s"] == 1.50
    metrics = {}
    bc._flatten({"autoscale_bench": {"resize_settle_s": 0.8,
                                     "drain_error_x": 0.0}}, metrics)
    assert metrics == {"resize_settle_s": 0.8}
