"""RL step-rate benchmark — the reference's second headline number.

The reference reports ~2000 Hz physics-only stepping (no image transfer;
``Readme.md:95``).  This harness measures blendjax's REQ/REP RPC loop at
the same configuration: env instances running the real producer stack
(BaseEnv + RemoteControlledAgent + AnimationController, frame loop in
manual mode) with a scalar observation and no rendering, stepped from the
consumer via :class:`blendjax.btt.envpool.EnvPool` (pipelined RPCs).

Blender's physics tick is not part of the measurement in either number:
the reference's ~2000 Hz is dominated by the RPC round trip (its physics
cartpole sim costs ~nothing per frame), so the fake-Blender fleet speaks
the identical protocol through the identical stack.

``--pipeline-depth K`` switches the consumer loop to the async
``step_async``/``step_wait`` path (K requests in flight per env over
DEALER sockets — see docs/rl_stepping.md): producers integrate the next
frame while the consumer is still handling the previous replies, so the
per-step serialization tax (fan-out RTT + slowest physics, every step)
collapses to max(physics, consumer work).  ``--compare`` runs lock-step
then pipelined in one process and reports the ratio as
``rl_pipelined_x`` — the jax-free microbench behind ``make rlbench``.

``--sharded --mesh-devices N --fleets K`` runs the Sebulba sharded
configuration (docs/sharded_rl.md) against the single-device
actor/learner on N fake CPU devices (the MULTICHIP harness):
interleaved window pairs, median ratio reported as ``rl_sharded_x`` —
``make rlbench-sharded``.

Run: ``python benchmarks/rl_benchmark.py [--instances 4] [--seconds 10]``
Prints one JSON line: aggregate env-steps/sec and vs_baseline vs 2000 Hz.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

REFERENCE_HZ = 2000.0  # Readme.md:95, physics-only stepping


def _env_setup(args):
    """Shared fleet fixture config: fake-Blender fallback, env fixture
    script, per-env kwargs.  Returns ``(script, env_kwargs)``."""
    os.environ.setdefault(
        "BLENDJAX_BLENDER",
        os.path.join(
            os.path.dirname(HERE), "tests", "helpers", "fake_blender.py"
        ),
    )
    script = os.path.join(
        os.path.dirname(HERE), "tests", "blender", "env.blend.py"
    )
    return script, dict(
        horizon=1_000_000_000,  # episodes never end inside the window
        physics_us=args.physics_us,
    )


def launch_pool_for(args, pipeline_depth=1, port_salt=0):
    """One copy of the fleet setup for both configurations: fake-Blender
    fallback, env fixture script, and a randomized port base so
    back-to-back benchmark children can't collide on the launcher's
    default 11000 while lingering sockets drain."""
    from blendjax.btt.envpool import launch_env_pool

    script, env_kwargs = _env_setup(args)
    return launch_env_pool(
        scene="",
        script=script,
        num_instances=args.instances,
        background=True,
        timeoutms=30000,
        start_port=20000 + (os.getpid() * 37 + port_salt * 131) % 20000,
        pipeline_depth=pipeline_depth,
        **env_kwargs,
    )


def run(args):
    with launch_pool_for(args) as pool:
        pool.reset()
        actions = [0.5] * args.instances
        # warmup: first exchanges absorb connect + frame-loop spin-up
        for _ in range(32):
            pool.step(actions)
        t0 = time.perf_counter()
        n = 0
        while time.perf_counter() - t0 < args.seconds:
            pool.step(actions)
            n += 1
        dt = time.perf_counter() - t0
    steps_per_sec = n * args.instances / dt
    return {
        "metric": "rl_steps_per_sec_no_image",
        "value": round(steps_per_sec, 1),
        "unit": "env-steps/sec",
        "instances": args.instances,
        "per_env_hz": round(n / dt, 1),
        "vs_baseline": round(steps_per_sec / REFERENCE_HZ, 3),
        # the reference's ~2000 Hz rides a near-free cartpole sim; this
        # harness's env is free unless --physics-us adds a per-frame
        # busy-wait standing in for a solver tick
        "includes_physics": args.physics_us > 0,
        "physics_us": args.physics_us,
    }


def run_pipelined(args, port_salt=1):
    """Async pipelined configuration: ``--pipeline-depth`` requests in
    flight per env, collected ready-first (``min_ready=1``) and
    immediately resubmitted to exactly the envs that completed, so every
    producer's request queue stays non-empty and physics overlaps the
    consumer's reply handling — no barrier re-serializes on the
    straggler."""
    depth = args.pipeline_depth
    with launch_pool_for(args, pipeline_depth=depth,
                         port_salt=port_salt) as pool:
        pool.reset()
        n_envs = args.instances
        for _ in range(depth):
            pool.step_async([0.5] * n_envs)
        # warmup: first exchanges absorb connect + frame-loop spin-up
        warmed = 0
        while warmed < 32 * n_envs:
            idx, *_ = pool.step_wait(min_ready=1)
            pool.step_async([0.5] * len(idx), indices=list(idx))
            warmed += len(idx)
        t0 = time.perf_counter()
        n = 0
        while time.perf_counter() - t0 < args.seconds:
            idx, *_ = pool.step_wait(min_ready=1)
            pool.step_async([0.5] * len(idx), indices=list(idx))
            n += len(idx)
        dt = time.perf_counter() - t0
        pool.step_wait()  # drain the tail before teardown
    steps_per_sec = n / dt
    return {
        "metric": "rl_steps_per_sec_pipelined",
        "value": round(steps_per_sec, 1),
        "unit": "env-steps/sec",
        "instances": args.instances,
        "pipeline_depth": depth,
        "per_env_hz": round(steps_per_sec / args.instances, 1),
        "vs_baseline": round(steps_per_sec / REFERENCE_HZ, 3),
        "includes_physics": args.physics_us > 0,
        "physics_us": args.physics_us,
    }


def run_compare(args, pairs=5):
    """Lock-step vs pipelined on the SAME fleet, alternating measurement
    windows; one JSON line with the median paired ratio
    (``rl_pipelined_x``) — the acceptance microbench.

    Interleaving matters: shared/throttled CI boxes drift in absolute
    throughput by 2x within a minute, so back-to-back whole runs compare
    different machines.  Adjacent windows see the same conditions and
    their ratio cancels the drift; the median over ``pairs`` discards a
    window that caught a scheduling hiccup."""
    depth = args.pipeline_depth
    n_envs = args.instances
    # windows must dwarf the multi-second scheduler stalls seen on shared
    # CI hosts, or a single stall dominates one side of a pair
    window_s = max(args.seconds / pairs, 3.0)

    def lock_window(pool):
        t0 = time.perf_counter()
        n = 0
        while time.perf_counter() - t0 < window_s:
            pool.step([0.5] * n_envs)
            n += n_envs
        return n / (time.perf_counter() - t0)

    def pipe_window(pool):
        for _ in range(depth):
            pool.step_async([0.5] * n_envs)
        warmed = 0
        while warmed < 16 * n_envs:  # refill the producers' queues
            idx, *_ = pool.step_wait(min_ready=1)
            pool.step_async([0.5] * len(idx), indices=list(idx))
            warmed += len(idx)
        t0 = time.perf_counter()
        n = 0
        while time.perf_counter() - t0 < window_s:
            idx, *_ = pool.step_wait(min_ready=1)
            pool.step_async([0.5] * len(idx), indices=list(idx))
            n += len(idx)
        rate = n / (time.perf_counter() - t0)
        pool.step_wait()  # drain before handing the fleet back
        return rate

    locks, pipes, ratios = [], [], []
    with launch_pool_for(args, pipeline_depth=depth) as pool:
        pool.reset()
        for _ in range(32):  # warmup: connect + frame-loop spin-up
            pool.step([0.5] * n_envs)
        for _ in range(pairs):
            locks.append(lock_window(pool))
            pipes.append(pipe_window(pool))
            ratios.append(pipes[-1] / max(locks[-1], 1e-9))
    med = sorted(ratios)[len(ratios) // 2]
    return {
        "metric": "rl_pipelined_x",
        "value": round(med, 3),
        "unit": "x (pipelined / lock-step env-steps/sec, median of "
                f"{pairs} interleaved pairs)",
        "instances": args.instances,
        "pipeline_depth": depth,
        "physics_us": args.physics_us,
        "lockstep_steps_per_sec": round(sorted(locks)[len(locks) // 2], 1),
        "pipelined_steps_per_sec": round(sorted(pipes)[len(pipes) // 2], 1),
        "pair_ratios": [round(r, 3) for r in ratios],
    }


def run_podracer(args):
    """Overlapped actor/learner configuration (Sebulba, arXiv:2104.06272):
    env stepping + policy inference in an actor thread concurrent with
    jitted REINFORCE updates — RL throughput WITH learning, not just the
    RPC stack.  ``--pipeline-depth K`` additionally routes rollout
    collection through the pool's async path
    (``ActorLearner(pipeline=True)``, K requests in flight per env)."""
    import numpy as np

    from blendjax.models.actor_learner import ActorLearner

    values = np.array([0.0, 1.0], np.float64)
    depth = max(args.pipeline_depth, 1)
    pipelined = args.pipeline_depth >= 1
    with launch_pool_for(args, pipeline_depth=depth) as pool:
        al = ActorLearner(
            pool, obs_dim=1, num_actions=2, rollout_len=32, seed=0,
            action_map=lambda a: list(values[np.asarray(a)]),
            pipeline=pipelined,
        )
        al.run(num_updates=2)  # warmup: absorbs jit compiles
        stats = al.run(seconds=args.seconds)  # the measured window
    return {
        "metric": "rl_env_steps_per_sec_with_learning",
        "value": stats["env_steps_per_sec"],
        "unit": "env-steps/sec",
        "instances": args.instances,
        "updates_per_sec": stats["updates_per_sec"],
        "vs_baseline": round(stats["env_steps_per_sec"] / REFERENCE_HZ, 3),
        "includes_physics": args.physics_us > 0,
        "includes_learning": True,
        "pipeline_depth": depth,
        "pipelined": pipelined,
        "architecture": "sebulba (overlapped actor/learner)",
    }


def run_sharded_compare(args, pairs=3):
    """Sebulba sharded vs single-device actor/learner on live fleets,
    alternating measurement windows; one JSON line with the median
    paired ratio (``rl_sharded_x``) — the acceptance microbench for the
    sharded configuration (docs/sharded_rl.md).

    Single-device side: 1 fleet of ``--instances`` envs, one actor
    thread, plain ``jax.device_put`` learner (the old headline path,
    which cannot scale past one device).  Sharded side: ``--fleets``
    fleets of ``--instances`` envs each, one actor thread per fleet,
    global batches pre-sharded ``P('data')`` over a ``--mesh-devices``
    mesh.  Both fleets stay up for the whole run and windows interleave,
    so the ratio cancels host drift exactly like ``rl_pipelined_x``.
    """
    import jax
    import numpy as np

    from blendjax.models.actor_learner import ActorLearner
    from blendjax.parallel import FleetSet, make_mesh

    script, env_kwargs = _env_setup(args)
    base_port = 20000 + (os.getpid() * 37) % 18000
    mesh = make_mesh(
        {"data": args.mesh_devices}, jax.devices()[:args.mesh_devices]
    )
    values = np.array([0.0, 1.0], np.float64)

    def amap(a):
        return list(values[np.asarray(a)])

    window_s = max(args.seconds / pairs, 3.0)
    with FleetSet(
        "", script, 1, args.instances, start_port=base_port,
        timeoutms=30000, **env_kwargs,
    ) as single_fs, FleetSet(
        "", script, args.fleets, args.instances,
        start_port=base_port + 1000, timeoutms=30000, **env_kwargs,
    ) as shard_fs:
        al_single = ActorLearner(
            single_fs, obs_dim=1, num_actions=2, rollout_len=32, seed=0,
            action_map=amap,
        )
        al_shard = ActorLearner(
            shard_fs, obs_dim=1, num_actions=2, rollout_len=32, seed=0,
            mesh=mesh, action_map=amap,
        )
        al_single.run(num_updates=2)  # warmup: absorbs jit compiles
        al_shard.run(num_updates=2)
        singles, shardeds, ratios = [], [], []
        for _ in range(pairs):
            singles.append(
                al_single.run(seconds=window_s)["env_steps_per_sec"]
            )
            shardeds.append(
                al_shard.run(seconds=window_s)["env_steps_per_sec"]
            )
            ratios.append(shardeds[-1] / max(singles[-1], 1e-9))
        health = shard_fs.health()
    med = sorted(ratios)[len(ratios) // 2]
    return {
        "metric": "rl_sharded_x",
        "value": round(med, 3),
        "unit": f"x (sharded {args.fleets}-fleet / single-device "
                f"env-steps/sec with learning, median of {pairs} "
                "interleaved pairs)",
        "mesh_devices": args.mesh_devices,
        "fleets": args.fleets,
        "instances_per_fleet": args.instances,
        "total_envs": args.fleets * args.instances,
        "physics_us": args.physics_us,
        "single_env_steps_per_sec": round(
            sorted(singles)[len(singles) // 2], 1
        ),
        "sharded_env_steps_per_sec": round(
            sorted(shardeds)[len(shardeds) // 2], 1
        ),
        "pair_ratios": [round(r, 3) for r in ratios],
        # multi-fleet observability rides in the artifact: aggregate
        # quarantine/death counters plus the per-fleet breakdown
        # (blendjax.btt.supervise.aggregate_health)
        "fleet_health": {
            "num_envs": health["num_envs"],
            "healthy_envs": health["healthy_envs"],
            "quarantines": health["quarantines"],
            "deaths": health["deaths"],
            "restarts": health["restarts"],
            "dead_fleets": health["dead_fleets"],
            "per_fleet": {
                str(fid): {
                    "healthy_envs": h.get("healthy_envs", 0),
                    "quarantines": h.get("quarantines", 0),
                }
                for fid, h in health["fleets"].items()
            },
        },
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--instances", type=int, default=4)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument(
        "--physics-us", type=int, default=0,
        help="busy-wait per env step, simulating physics solver cost",
    )
    ap.add_argument(
        "--pipeline-depth", type=int, default=0,
        help="async step_async/step_wait mode with this many requests "
             "in flight per env (0 = lock-step step())",
    )
    ap.add_argument(
        "--compare", action="store_true",
        help="run lock-step AND pipelined, report rl_pipelined_x "
             "(requires --pipeline-depth >= 1)",
    )
    ap.add_argument("--podracer", action="store_true",
                    help="overlapped actor/learner configuration")
    ap.add_argument(
        "--sharded", action="store_true",
        help="sharded vs single-device actor/learner comparison "
             "(rl_sharded_x) on a fake-device CPU mesh",
    )
    ap.add_argument(
        "--mesh-devices", type=int, default=8,
        help="data-axis size of the learner mesh in --sharded mode "
             "(forced as fake CPU devices before jax initializes)",
    )
    ap.add_argument(
        "--fleets", type=int, default=4,
        help="env fleets on the sharded side of --sharded mode, each "
             "with --instances envs",
    )
    args = ap.parse_args(argv)
    if args.sharded:
        # the mesh is virtual CPU devices (the MULTICHIP harness): force
        # the device count BEFORE jax initializes
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count"
                  f"={args.mesh_devices}"
            ).strip()
        import jax

        try:
            jax.config.update("jax_platforms", "cpu")
        except Exception:
            pass
        print(json.dumps(run_sharded_compare(args)))
    elif args.compare:
        if args.pipeline_depth < 1:
            args.pipeline_depth = 4
        print(json.dumps(run_compare(args)))
    elif args.podracer:
        # jax runs in this child on the CPU: the policy is tiny and the
        # subject is the RL stack.
        # Checked BEFORE the bare pipelined branch: --podracer
        # --pipeline-depth K is the PIPELINED podracer (the depth used
        # to be silently ignored here — and the dispatch below used to
        # shadow this branch entirely whenever a depth was given)
        import jax

        try:
            jax.config.update("jax_platforms", "cpu")
        except Exception:
            pass
        print(json.dumps(run_podracer(args)))
    elif args.pipeline_depth >= 1:
        print(json.dumps(run_pipelined(args)))
    else:
        print(json.dumps(run(args)))


if __name__ == "__main__":
    main()
