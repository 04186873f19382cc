"""Shared benchmark-harness helpers.

One copy of the budget tracker, producer-fleet handle, and producer
launcher used by both ``suite.py`` (jax-free parent) and
``suite_device.py`` (accelerator child).  The shm ring-name scheme lives
HERE and only here: ``bjx-suite-{tag}-{nonce}-{i}``, where ``nonce``
embeds the orchestrating process's pid so ``bench.py``'s leak sweep
(``/dev/shm/bjx-suite-*-{pid}-*``) finds every ring either child created.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

#: Result-schema keys every ``replay_benchmark.py`` JSON line carries
#: (phase ``replay_bench``); ``bench.py`` and the suite consumers key off
#: these, and ``tests/test_replay.py`` locks emission against this tuple
#: so the artifact schema cannot drift silently.
#: ``replay_sample_x`` is the headline: batched columnar sampling over
#: naive per-item collation at the acceptance batch size (32).
REPLAY_BENCH_KEYS = (
    "frame", "batch", "capacity",
    "replay_appends_per_sec",
    "replay_batches_per_sec",   # {"naive": .., "columnar": ..}
    "replay_samples_per_sec",   # same, in transitions/sec
    "replay_sample_x",
    "record_msgs_per_sec",      # {"unbuffered": .., "buffered": ..}
    "record_buffered_x",
    "stages",
)

#: Keys of the ``--sharded`` sub-record (``replay_bench["sharded"]``):
#: in-process vs replay-*service* sampling over interleaved windows.
#: ``replay_shard_x`` is service/in-process at the median pair (the wire
#: tax of the storage tier; the service arm rides the ``transport``
#: wire — ShmRPC by default since ISSUE-12); ``shm_rpc_x`` is the
#: shm-arm/tcp-arm ratio at the median pair (what the shared-memory
#: transport recovers over loopback ZMQ + pickle framing; None when
#: ShmRPC is unavailable); ``replay_degraded_x`` is degraded/healthy
#: service rate with one shard quarantined (the strata-renormalization
#: overhead a shard outage costs).
REPLAY_SHARD_KEYS = (
    "shards", "capacity", "batch", "transport",
    "replay_shard_batches_per_sec",  # {"inproc", "service",
    #                                   "service_tcp", "service_degraded"}
    "replay_shard_x",
    "shm_rpc_x",
    "replay_degraded_x",
)


#: Result-schema keys every ``serve_benchmark.py`` JSON line carries
#: (phase ``serve_bench``); ``bench.py`` keys off these and
#: ``tests/test_serve.py`` locks emission against this tuple.
#: ``serve_qps``/``serve_p99_ms`` are the headline pair (median batched
#: round; client-observed union p99); ``serve_int8_x`` is the quantized
#: server's QPS over the float one (None when ``--no-int8``).
SERVE_BENCH_KEYS = (
    "model", "clients", "slots", "obs_dim", "rounds", "window_s",
    "episode_len",
    "serve_qps", "serve_p50_ms", "serve_p99_ms",
    "serve_int8_x",
    # batched prefill admission (reset with a T-step prefix replayed in
    # ONE teacher-forced pass) vs T serial steps, median interleaved
    # pair; None for stateless served models
    "serve_prefill_x",
    "prefill",           # the sub-record (prefix_len/admissions/rates)
    "serve_qps_modes",   # {"batched": .., "int8": ..}
    "stages",
)

#: Result-schema keys every ``serve_benchmark.py --gateway`` JSON line
#: carries (phase ``gateway_bench``); ``bench.py`` keys off these and
#: ``tests/test_gateway.py`` locks emission against this tuple.
#: ``gateway_scale_x`` is the headline: aggregate QPS through the
#: gateway at N replicas over the SAME fleet with all but one replica
#: drained, at the median interleaved window pair;
#: ``gateway_qps``/``gateway_p99_ms`` are the N-replica aggregate rate
#: and client-observed union p99.  ``gateway_shard_x`` is the sharded
#: data plane's win (``--gateway-workers N``): N-worker partitioned
#: direct dial over the UNSHARDED single-address shape
#: (``set_active_workers(1)`` — same worker processes, same front,
#: but no direct-dial map: every message relays through the front's
#: one event loop, the monolithic deployment shape) at the median
#: same-round pair, measured over the shard phase's OWN gateway-bound
#: fleet (``shard_profile``: light per-row work, fat observations) so
#: the window exercises the data-plane hop rather than replica
#: sleep-compute; None in 1-worker mode.  The scale pair stays on the
#: replica-bound fleet, keeping ``gateway_qps``/``gateway_scale_x``
#: comparable with pre-shard artifacts.
#: ``client_procs`` records whether the window's bench clients ran as
#: processes (``--client-procs``, GIL isolation) so before/after
#: artifacts are comparable.
GATEWAY_BENCH_KEYS = (
    "replicas", "clients", "obs_dim", "work_us", "rounds", "window_s",
    "episode_len",
    "gateway_workers", "client_procs",
    "gateway_qps", "gateway_qps_1replica", "gateway_qps_1worker",
    "gateway_qps_nworker", "shard_profile",
    "gateway_p50_ms", "gateway_p99_ms",
    "gateway_scale_x", "gateway_shard_x",
    "pair_ratios", "shard_pair_ratios",
    "gateway_counters",
    "stages",            # gw_route / gw_forward / gw_reply summaries
)


#: Result-schema keys every ``weight_benchmark.py`` JSON line carries
#: (phase ``weight_bench``); ``bench.py`` keys off these and
#: ``tests/test_weights.py`` locks emission against this tuple.
#: ``weight_swap_ms`` is publish() -> first client-observed reply at
#: the new version, p99 over the window's publishes (p50 rides as
#: ``weight_swap_ms_p50``); ``weight_swap_qps_dip_x`` is aggregate QPS
#: in the buckets around each swap over the steady-state median (1.0 =
#: rollouts cost nothing).
WEIGHT_BENCH_KEYS = (
    "clients", "obs_dim", "publishes", "window_s", "snapshot_kb",
    "weight_swap_ms", "weight_swap_ms_p50", "weight_swap_qps_dip_x",
    "qps_steady", "swaps_observed", "swap_ms_all", "publish_ms_p50",
    "weight_counters",
    "stages",            # weight_publish / weight_assemble / weight_swap
)


#: Result-schema keys every ``serve_benchmark.py --scenario-mix`` JSON
#: line carries (phase ``serve_mix_bench``); locked by
#: ``tests/test_scenario.py``.  ``serve_mix_p99_ms`` is the headline:
#: the client-observed UNION p99 under a weighted, labelled
#: multi-scenario traffic mix (per-scenario shapes in ``mix``, the
#: per-label QPS/p50/p99 breakdown in ``per_scenario``) — the tail a
#: realistic workload observes, not one synthetic client shape.
SERVE_MIX_KEYS = (
    "model", "clients", "rounds", "window_s", "mix",
    "serve_mix_qps", "serve_mix_p50_ms", "serve_mix_p99_ms",
    "per_scenario",
    "stages",
)

#: Result-schema keys every ``scenario_benchmark.py`` JSON line carries
#: (phase ``scenario_bench``); ``bench.py`` keys off these and
#: ``tests/test_scenario.py`` locks emission against this tuple.
#: ``scenario_hetero_x`` is the headline: aggregate env-steps/sec of a
#: heterogeneous 2-scenario fleet (fast + slow physics rates) stepped
#: ready-first (``step_wait(min_ready=1)``) over the SAME fleet
#: stepped through the homogeneous lock-step batch path (every step
#: barriers on the slow scenario), median of interleaved window pairs.
#: The serve-tier half carries the ``serve_mix_*`` record under
#: ``serve_mix`` (see ``SERVE_MIX_KEYS``).
SCENARIO_BENCH_KEYS = (
    "scenarios", "instances", "rounds", "window_s",
    "hetero_steps_per_sec", "lockstep_steps_per_sec",
    "scenario_hetero_x",
    "pair_ratios",
    "per_scenario_steps",   # hetero-arm env steps per scenario label
    "scenario_counters",    # scenario_* counter snapshot of the run
    "serve_mix",            # the SERVE_MIX_KEYS sub-record (or None)
    "serve_mix_p99_ms",     # hoisted headline (None when mix skipped)
)


#: Result-schema keys every ``ha_benchmark.py`` JSON line carries
#: (phase ``ha_bench``); ``bench.py`` keys off these and
#: ``tests/test_ha.py`` locks emission against this tuple.
#: ``ckpt_overhead_x`` is update throughput with the async
#: TrainCheckpointer attached over checkpointing off (target ~1.0 —
#: the bounded-stall contract, floor 0.90); ``learner_recovery_s`` is
#: SIGKILL -> first completed post-respawn update of the supervised
#: learner process (lower-is-better, ceiling-guarded on the
#: trajectory).
HA_BENCH_KEYS = (
    "window_s", "rounds", "ckpt_every_s", "batch",
    "ckpt_on_updates_per_sec", "ckpt_off_updates_per_sec",
    "ckpt_overhead_x", "pair_ratios",
    "learner_recovery_s", "recovery",
    "ha_counters",
    "stages",            # ha_snapshot / ha_serialize summaries
)


#: Result-schema keys every ``autoscale_benchmark.py`` JSON line
#: carries (phase ``autoscale_bench``); ``bench.py`` keys off these and
#: ``tests/test_autoscale.py`` locks emission against this tuple.
#: ``resize_settle_s`` is the headline: autoscale decision (the
#: controller's ``grow``) -> fleet verified healthy at the new size
#: under steady client traffic, healthy window included (lower is
#: better, ceiling-guarded on the trajectory in bench_compare);
#: ``drain_error_x`` is client-observed error fraction across the
#: scale-DOWN transition (drain -> verify -> retire) — the
#: zero-client-visible-errors contract, MUST be 0.0;
#: ``drain_settle_s`` is the same decision-to-settle measure for the
#: scale-down.
AUTOSCALE_BENCH_KEYS = (
    "replicas", "clients", "obs_dim", "window_s",
    "resize_settle_s", "drain_settle_s",
    "drain_error_x", "drain_requests", "drain_errors",
    "autoscale_counters",
    "stages",            # autoscale_resize / autoscale_drain summaries
)

#: pipeline_benchmark.py emits exactly these (phase ``pipeline_bench``).
#: ``pipe_mpmd_x`` — median interleaved-window throughput ratio of the
#: N-stage MPMD arm over the 1-stage same-harness baseline (the
#: headline number; bench_compare floors it); ``pipe_stages`` is the
#: MPMD arm's stage-process count (the key "stages" means StageTimer
#: summaries suite-wide, so the count rides its own name).
PIPE_BENCH_KEYS = (
    "pipe_stages", "layers", "microbatches", "batch", "wire",
    "work_us", "rounds", "window_updates",
    "mpmd_updates_per_sec", "single_updates_per_sec",
    "pipe_mpmd_x", "pair_ratios",
    "pipe_counters",
    "stages",            # pipe_feed / pipe_finish driver summaries
)


def note(msg, who="suite"):
    print(f"[{who}] {msg}", file=sys.stderr, flush=True)


class Budget:
    def __init__(self, total_s, who="suite"):
        self.t0 = time.monotonic()
        self.total = total_s
        self.who = who

    def remaining(self):
        return self.total - (time.monotonic() - self.t0)

    def has(self, seconds, what):
        if self.remaining() >= seconds:
            return True
        note(
            f"skipping {what}: {self.remaining():.0f}s left < {seconds:.0f}s",
            self.who,
        )
        return False


class Producers:
    """Handle over a launched synthetic-producer fleet."""

    def __init__(self, addrs, procs, transport):
        self.addrs = addrs
        self.procs = procs
        self.transport = transport

    def close(self):
        for p in self.procs:
            p.terminate()
        for p in self.procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
        if self.transport == "shm":
            from blendjax.native import unlink_address

            for a in self.addrs:
                unlink_address(a)


def launch_fleet(n, extra, tag, *, transport, raw, ring_nonce, env, nice=10):
    """Spawn ``n`` ``stream_producer.py`` processes; returns Producers.

    Producers run at ``nice`` +10 by default: on a 1-core host they are
    pure contention for the consumer/transfer-pump whenever the ring has
    space, and backpressure (the blocking ring writer) keeps them fed
    regardless of priority — deprioritizing them shortens transfer tails
    without starving the stream.  The priority drop rides a ``nice -n``
    command prefix, not ``preexec_fn`` — the parents here run reader/
    feed threads, and ``preexec_fn`` is documented deadlock-prone in
    multithreaded processes (ADVICE r4)."""
    from benchmarks.benchmark import free_port

    addrs, procs = [], []
    for i in range(n):
        if transport == "shm":
            addr = f"shm://bjx-suite-{tag}-{ring_nonce}-{i}"
        else:
            addr = f"tcp://127.0.0.1:{free_port()}"
        cmd = [
            sys.executable,
            os.path.join(HERE, "stream_producer.py"),
            "--addr", addr, "--btid", str(i),
        ] + extra + (["--raw"] if raw else [])
        if nice:
            cmd = ["nice", "-n", str(nice)] + cmd
        procs.append(subprocess.Popen(cmd, env=env))
        addrs.append(addr)
    return Producers(addrs, procs, transport)
