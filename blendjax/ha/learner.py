"""The supervised learner process: ``python -m blendjax.ha.learner``.

The launcher surface :class:`~blendjax.ha.supervisor.LearnerProcess`
spawns (and ``FleetWatchdog(restart=True)`` respawns).  Startup IS the
resume path:

1. find the latest complete manifest under ``--ckpt-dir``
   (:func:`blendjax.ha.checkpoint.latest_manifest` — damaged cuts are
   skipped, counted, warned);
2. rebuild the replay draw authority from the cut
   (:func:`~blendjax.ha.checkpoint.restore_replay`: the shards
   survived, so the restore reconciles the slots the dead incarnation
   appended past the cut out of the draw domain — the resumed actors
   rewrite them);
3. bind the weight bus at the SAME address with the default wall-clock
   ``version_base`` and republish the checkpointed params under a
   fresh HIGHER version id — subscribed serve replicas heal through
   their periodic re-sync and roll forward, clients observe a
   monotonic version stream with zero errors;
4. reconnect the producer fleet (the producers never died — a fresh
   :class:`~blendjax.btt.envpool.EnvPool` dials the same addresses)
   and train on, with the scenario assignment re-pushed and the update
   counter, curriculum and RNG-bearing replay state continuing from
   the cut.

A fresh directory (no manifest) starts training from scratch through
the exact same code path.  The checkpointer mirrors ``stats()`` to
``<ckpt-dir>/learner_stats.json`` every update — the supervisor's
postmortem source and the recovery benchmark's clock.

See docs/fault_tolerance.md "Learner failover".
"""

from __future__ import annotations

import argparse
import hashlib
import logging
import os
import signal
import threading

import numpy as np

logger = logging.getLogger("blendjax")


def build_parser():
    ap = argparse.ArgumentParser(
        description="Supervised blendjax learner (resumes from the "
                    "latest complete HA manifest at startup)."
    )
    ap.add_argument("--ckpt-dir", required=True)
    ap.add_argument("--envs", default="",
                    help="comma-separated producer GYM addresses (empty "
                         "= fleet-less: train off-policy from the "
                         "replay shards alone)")
    ap.add_argument("--replay-shards", default="",
                    help="comma-separated replay shard addresses")
    ap.add_argument("--shard-capacity", type=int, default=None)
    ap.add_argument("--weight-bus", default=None,
                    help="weight-bus BIND address (fixed port, so a "
                         "respawned learner re-binds where the "
                         "subscribers already dial)")
    ap.add_argument("--publish-every", type=int, default=1)
    ap.add_argument("--obs-dim", type=int, default=1)
    ap.add_argument("--num-actions", type=int, default=2)
    ap.add_argument("--rollout-len", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--replay-ratio", type=int, default=0)
    ap.add_argument("--replay-batch", type=int, default=32)
    ap.add_argument("--ckpt-every", type=int, default=2,
                    help="checkpoint cadence in completed updates")
    ap.add_argument("--ckpt-seconds", type=float, default=None)
    ap.add_argument("--updates", type=int, default=0,
                    help="stop once the (resumed) update counter "
                         "reaches this (0 = run until signalled)")
    ap.add_argument("--chunk-updates", type=int, default=4,
                    help="updates per run() chunk between stop checks")
    ap.add_argument("--offline-batch", type=int, default=32)
    ap.add_argument("--timeoutms", type=int, default=15000)
    ap.add_argument("--action-values", default=None,
                    help="comma-separated floats mapping the discrete "
                         "action index to the producers' action space")
    ap.add_argument("--probe-batch", type=int, default=0,
                    help="after a resume, draw one probe batch of this "
                         "size from the restored replay and record its "
                         "index digest in the stats mirror (evidence "
                         "that every acked row is still drawable)")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    logging.basicConfig(level=logging.INFO)

    from blendjax.ha.checkpoint import (
        TrainCheckpointer,
        latest_manifest,
        restore_replay,
    )
    from blendjax.utils.timing import fleet_counters

    counters = fleet_counters
    manifest = latest_manifest(args.ckpt_dir, counters=counters)

    shard_addrs = [a for a in args.replay_shards.split(",") if a]
    env_addrs = [a for a in args.envs.split(",") if a]

    replay = None
    if shard_addrs:
        from blendjax.replay.shard_client import ShardedReplay

        if manifest is not None and manifest.get("replay"):
            replay = restore_replay(
                manifest, shard_addrs, counters=counters,
                timeoutms=args.timeoutms,
            )
        else:
            replay = ShardedReplay(
                shard_addrs, seed=args.seed, counters=counters,
                timeoutms=args.timeoutms,
                shard_capacity=args.shard_capacity,
            )

    bus = None
    if args.weight_bus:
        from blendjax.weights.bus import WeightPublisher

        # default (wall-clock) version_base ON PURPOSE: a respawned
        # publisher must start above its predecessor so subscribers —
        # who never adopt backwards — roll forward (docs/weight_bus.md)
        bus = WeightPublisher(args.weight_bus,
                              counters=counters).start()

    pool = None
    if env_addrs:
        from blendjax.btt.envpool import EnvPool

        pool = EnvPool(env_addrs, timeoutms=args.timeoutms,
                       autoreset=True, counters=counters)

    ckptr = TrainCheckpointer(
        args.ckpt_dir, every_updates=args.ckpt_every,
        every_seconds=args.ckpt_seconds, counters=counters,
    )

    action_map = None
    if args.action_values:
        values = np.array(
            [float(v) for v in args.action_values.split(",")],
            np.float64,
        )
        action_map = lambda a: list(values[np.asarray(a)])  # noqa: E731

    from blendjax.models.actor_learner import ActorLearner

    learner = ActorLearner(
        pool, args.obs_dim, args.num_actions,
        rollout_len=args.rollout_len, seed=args.seed,
        action_map=action_map, replay=replay,
        replay_ratio=(args.replay_ratio if replay is not None else 0),
        replay_batch=args.replay_batch,
        weight_bus=bus, publish_every=args.publish_every,
        checkpointer=ckptr,
    )

    from blendjax.utils.device import device_info

    ckptr.stats_extra["pid"] = os.getpid()
    # where this learner computes, for a supervisor across the process
    # boundary (LearnerProcess.stats): platform, device_kind, count
    ckptr.stats_extra.update(device_info())
    resumed_from = None
    if manifest is not None:
        ckptr.restore(learner, manifest)  # republish included
        resumed_from = int(manifest["update"])
        ckptr.stats_extra["resumed_from"] = resumed_from
        if args.probe_batch and replay is not None:
            # the first post-resume draw, before any actor appends: a
            # successful stratified draw over the restored domain is
            # the "every acked row still drawable" witness, and its
            # digest is deterministic given the cut
            try:
                _, idx, _ = replay.sample(
                    args.probe_batch, timeout=0.0
                )
                ckptr.stats_extra["probe_digest"] = hashlib.sha1(
                    np.ascontiguousarray(idx, np.int64).tobytes()
                ).hexdigest()[:16]
            except TimeoutError:
                ckptr.stats_extra["probe_digest"] = "underfilled"
    elif bus is not None:
        # fresh start: put version 1 on the bus before the first
        # update so late-joining subscribers have a full sync target
        import jax

        learner.last_published_version = bus.publish(
            jax.device_get(learner.state.params), step=0
        )

    stop = threading.Event()

    def _term(signum, frame):
        stop.set()
        learner._stop.set()

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)

    # the ready barrier LearnerProcess.wait_ready polls for
    ckptr._write_stats(learner, force=True)
    logger.info(
        "HA learner ready (pid %d): resumed_from=%s updates=%d "
        "envs=%d shards=%d bus=%s", os.getpid(), resumed_from,
        learner._updates_done, len(env_addrs), len(shard_addrs),
        getattr(bus, "address", None),
    )

    try:
        while not stop.is_set():
            if args.updates and learner._updates_done >= args.updates:
                break
            chunk = args.chunk_updates
            if args.updates:
                chunk = min(
                    chunk, args.updates - learner._updates_done
                )
            if pool is not None:
                # seconds= bounds the chunk so a SIGTERM mid-chunk (the
                # single-fleet loop only checks update/deadline limits)
                # ends within one window instead of hanging
                learner.run(num_updates=chunk, seconds=10.0)
            else:
                learner.run_offline(num_updates=chunk,
                                    batch_size=args.offline_batch)
            ckptr._write_stats(learner, force=True)
    finally:
        ckptr.join(timeout=30)
        ckptr._write_stats(learner, force=True)
        if pool is not None:
            pool.close()
        if bus is not None:
            bus.close()
        if replay is not None and hasattr(replay, "close"):
            replay.close()


if __name__ == "__main__":
    main()
