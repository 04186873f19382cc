"""Driver benchmark: two JSON lines on stdout — the full artifact first,
then a compact headline summary LAST so a bounded tail capture of stdout
always carries the verdict.  ONLY on a TPU: unless the device child
reports ``platform: tpu`` (and every one of its phases ran to an end)
this program exits non-zero and prints no metric line, so a CPU number
can never be read as a chip number.
Both lines are valid driver lines (metric/value/unit/vs_baseline
present); consumers wanting the full evidence should take the FIRST
line, tail-limited consumers get the headline.

Orchestrates ``benchmarks/suite.py`` (a child process that measures the
end-to-end pipeline in progressive phases, emitting a JSON line per phase
the moment it completes) plus ``benchmarks/rl_benchmark.py`` (the
reference's second headline number), and assembles the driver's single
JSON line from whatever arrived.

Honest labeling (the reference's 0.012 s/image *includes* Blender
rendering; ours cannot — Blender does not run in this image — so the
streamed pixels come from synthetic producers speaking the real wire
protocol):

- ``includes_rendering``: always false here; ``vs_baseline`` therefore
  compares transport+train throughput against the reference's
  full-pipeline number and must be read with that asterisk.
- both configurations are reported side by side: ``stream_to_hbm`` (feed
  only) and ``stream_to_train`` (feed + detector step), plus the
  MXU-bound ``seqformer`` phase with train duty cycle and MFU — the
  BASELINE.md north-star measurements.

The child emits per-phase lines immediately, so a deadline kill still
yields every completed phase.  The JAX persistent compilation cache goes
where ``blendjax.btt.launcher.place_compile_cache`` puts it
(``$JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/``).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
# driver kills around 540+ ($BJX_BENCH_BUDGET overrides for quick local
# runs)
TOTAL_BUDGET_S = float(os.environ.get("BJX_BENCH_BUDGET", 520))
RL_BUDGET_S = 90
REF_SEC_PER_IMAGE = 0.012  # reference 4-instance number, rendering included


def run_child_collect_json(cmd, env, deadline_s, must_succeed=False):
    """Run a child, reading stdout live; return parsed JSON lines.

    On deadline the child's process group is killed — lines already
    received are kept (the whole point of progressive emission).  With
    ``must_succeed`` a child that exits non-zero (or is killed at the
    deadline) ends this program non-zero too."""
    lines = []
    proc = subprocess.Popen(
        cmd,
        stdout=subprocess.PIPE,
        stderr=None,  # inherit: suite diagnostics must reach driver logs
        text=True,
        cwd=HERE,
        env=env,
        start_new_session=True,
    )

    def reader():
        for line in proc.stdout:
            line = line.strip()
            if line.startswith("{"):
                try:
                    lines.append(json.loads(line))
                except json.JSONDecodeError:
                    pass

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    try:
        proc.wait(timeout=deadline_s)
    except subprocess.TimeoutExpired:
        sys.stderr.write(f"child {cmd[1]} hit {deadline_s:.0f}s deadline\n")
        # TERM first: suite.py's handler kills its device-child sessions
        # (they are NOT in our child's process group) and sweeps its rings
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except OSError:
            proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except OSError:
                proc.kill()
            proc.wait(timeout=10)
        _sweep_shm(proc.pid)  # killed producers never unlink their rings
    t.join(timeout=5)
    if must_succeed and proc.returncode != 0:
        sys.exit(f"bench: child {cmd[1]} exited {proc.returncode}; "
                 "no metric line")
    return lines


def _sweep_shm(child_pid):
    """Remove shm rings leaked by THIS run's SIGKILLed suite child (the
    producers' unlink path never runs under killpg); names embed the suite
    child's pid, so the sweep can't touch a concurrently running suite."""
    import glob

    for path in glob.glob(f"/dev/shm/bjx-suite-*-{child_pid}-*"):
        try:
            os.unlink(path)
        except OSError:
            pass


def feed_bound_phase(seconds=3.0):
    """Measure the feed ceiling (batch assembly with a trivial train
    step), legacy collate vs arena-pooled scatter — jax-free, in-process.
    See benchmarks/feed_bound.py."""
    from benchmarks.feed_bound import measure

    return measure(seconds=seconds)


def replay_bench_phase(seconds=5.0):
    """Measure the replay subsystem (benchmarks/replay_benchmark.py):
    ring append rate, batched columnar vs naive per-item sampling
    (``replay_sample_x``), the FileRecorder buffered-write win, AND the
    sharded replay-service comparison (in-process vs service windows ->
    ``replay_shard_x``, plus the degraded-mode sampling overhead with a
    shard quarantined -> ``replay_degraded_x``) — jax-free, in-process,
    same rationale as the feed-bound phase."""
    from benchmarks.replay_benchmark import measure

    return measure(seconds=seconds, sharded=2)


def main():
    sys.path.insert(0, HERE)
    from blendjax.btt.launcher import child_env
    from blendjax.native import native_available

    if not native_available():
        sys.exit("bench: the native shm ring is not built "
                 "(make -C blendjax/native); no metric line")
    env = child_env()  # PYTHONPATH + the one compile-cache policy

    t_start = time.monotonic()
    # feed-bound mode first: cheap (~20 s), jax-free, and measures the
    # batch-assembly ceiling no other mode observes in isolation
    feed_bound = None
    try:
        feed_bound = feed_bound_phase()
    except Exception as e:  # noqa: BLE001 - the suite phases still run
        sys.stderr.write(f"feed_bound phase failed: {type(e).__name__}: {e}\n")
    # replay-path ceiling rides along under the same jax-free budget: the
    # off-policy workload's sampling rate (and its columnar speedup) is a
    # first-class headline next to the feed's
    replay_bench = None
    try:
        replay_bench = replay_bench_phase()
    except Exception as e:  # noqa: BLE001 - the suite phases still run
        sys.stderr.write(
            f"replay_bench phase failed: {type(e).__name__}: {e}\n"
        )
    cores = os.cpu_count() or 1
    instances = 4 if cores >= 4 else 1
    workers = 4 if cores >= 4 else 1
    suite_budget = max(60.0, TOTAL_BUDGET_S - RL_BUDGET_S - 30)
    cmd = [
        sys.executable,
        os.path.join(HERE, "benchmarks", "suite.py"),
        "--budget", str(suite_budget),
        "--instances", str(instances),
        "--workers", str(workers),
        "--batch", "8",
        "--prefetch", "12",
    ]
    cmd += ["--raw", "--transport", "shm"]
    phases = {
        p.get("phase"): p
        for p in run_child_collect_json(cmd, env, suite_budget + 30,
                                        must_succeed=True)
    }
    init = phases.get("device_init") or {}
    if init.get("platform") != "tpu":
        sys.exit(f"bench: the device child reported platform "
                 f"{init.get('platform')!r}, not 'tpu'; no metric line")

    rl = None
    rl_physics = None
    # every configuration below is a host-plane measurement (RL stepping,
    # gateway, weight bus, scenarios, failover, autoscale, MPMD over a
    # sleep stand-in): their children stay off the chip
    rl_env = dict(env)
    rl_env["JAX_PLATFORMS"] = "cpu"
    remaining = TOTAL_BUDGET_S - (time.monotonic() - t_start) - 20
    if remaining > 30:
        rl_lines = run_child_collect_json(
            [
                sys.executable,
                os.path.join(HERE, "benchmarks", "rl_benchmark.py"),
                "--instances", str(instances),
                "--seconds", "8",
            ],
            rl_env,
            min(RL_BUDGET_S, remaining),
        )
        rl = rl_lines[-1] if rl_lines else None
    # second configuration: 250 us busy-wait per step stands in for a
    # physics solver tick (the reference's ~2000 Hz cartpole spends
    # <500 us/step on everything incl. RPC), so the RL claim also has a
    # with-physics-cost number
    remaining = TOTAL_BUDGET_S - (time.monotonic() - t_start) - 20
    if rl and remaining > 25:
        rl_lines = run_child_collect_json(
            [
                sys.executable,
                os.path.join(HERE, "benchmarks", "rl_benchmark.py"),
                "--instances", str(instances),
                "--seconds", "5",
                "--physics-us", "250",
            ],
            rl_env,
            min(45, remaining),
        )
        rl_physics = rl_lines[-1] if rl_lines else None
    # third configuration: the async pipelined path at the same 250 us
    # physics cost — the with-physics serialization tax is exactly what
    # step_async/step_wait hides.  --compare interleaves lock-step and
    # pipelined windows on ONE fleet and reports the median paired ratio
    # (rl_pipelined_x), which survives the 2x throughput drift of shared
    # CI hosts that back-to-back whole runs do not
    rl_pipelined = None
    remaining = TOTAL_BUDGET_S - (time.monotonic() - t_start) - 20
    if rl_physics and remaining > 45:
        rl_lines = run_child_collect_json(
            [
                sys.executable,
                os.path.join(HERE, "benchmarks", "rl_benchmark.py"),
                "--instances", str(instances),
                "--seconds", "15",
                "--physics-us", "250",
                "--compare", "--pipeline-depth", "4",
            ],
            rl_env,
            min(75, remaining),
        )
        rl_pipelined = rl_lines[-1] if rl_lines else None
    # fourth configuration: the Sebulba sharded actor-learner on the
    # 8-fake-device MULTICHIP harness (4 fleets feeding a P('data')-
    # sharded learner vs the single-fleet/single-device path) —
    # interleaved window pairs, median ratio rl_sharded_x.  8 ms physics
    # puts the fleet in the simulation-bound regime the sharded split
    # scales (see make rlbench-sharded); the child forces its own
    # virtual-device count before importing jax
    rl_sharded = None
    remaining = TOTAL_BUDGET_S - (time.monotonic() - t_start) - 20
    if remaining > 75:
        rl_lines = run_child_collect_json(
            [
                sys.executable,
                os.path.join(HERE, "benchmarks", "rl_benchmark.py"),
                "--instances", str(instances),
                "--seconds", "24",
                "--physics-us", "8000",
                "--sharded", "--mesh-devices", "8", "--fleets", "4",
            ],
            rl_env,
            min(120, remaining),
        )
        rl_sharded = rl_lines[-1] if rl_lines else None
    # fifth configuration: the policy-serving inference tier
    # (docs/serving.md) — 8 concurrent episode clients against one
    # continuously-batched seqformer world-model server, interleaved
    # against the int8 server: serve_qps + serve_p99_ms headline, the
    # serve_int8_x ratio.  CPU-pinned child (jax, loopback wire).
    serve_bench = None
    remaining = TOTAL_BUDGET_S - (time.monotonic() - t_start) - 20
    if remaining > 45:
        serve_lines = run_child_collect_json(
            [
                sys.executable,
                os.path.join(HERE, "benchmarks", "serve_benchmark.py"),
                "--seconds", "18",
                "--clients", "8",
            ],
            rl_env,
            min(90, remaining),
        )
        serve_bench = serve_lines[-1] if serve_lines else None
    # sixth configuration: the serve FLEET (docs/serving.md
    # "ServeGateway" + "The sharded gateway") — 3 replica processes
    # behind the SHARDED gateway (2 worker processes + front), with
    # interleaved 1-replica (drained) vs 3-replica windows
    # (gateway_scale_x, replica scale-out, replica-bound fleet) AND a
    # second phase of 1-worker (single-address forwarding) vs 2-worker
    # (partitioned direct dial) windows over its own gateway-bound
    # fleet (gateway_shard_x); bench clients ride their own processes
    # (--client-procs) so their GIL never throttles the data plane.
    # gateway_qps + gateway_p99_ms headline.  Jax-free (linear
    # replicas).
    gateway_bench = None
    remaining = TOTAL_BUDGET_S - (time.monotonic() - t_start) - 20
    if remaining > 40:
        gw_lines = run_child_collect_json(
            [
                sys.executable,
                os.path.join(HERE, "benchmarks", "serve_benchmark.py"),
                "--gateway", "--replicas", "3",
                "--gateway-workers", "2",
                "--client-procs", "4",
                "--seconds", "27",
                "--clients", "16",
            ],
            rl_env,
            min(150, remaining),
        )
        gateway_bench = gw_lines[-1] if gw_lines else None

    # seventh configuration: the WeightBus live-rollout cost
    # (docs/weight_bus.md) — a subscribed linear-model server under
    # live traffic while versioned snapshots publish and hot-swap:
    # weight_swap_ms (publish -> first serving reply at the new
    # version, p99) and weight_swap_qps_dip_x (QPS through the swap
    # over steady state).  Jax-free.
    weight_bench = None
    remaining = TOTAL_BUDGET_S - (time.monotonic() - t_start) - 20
    if remaining > 25:
        wb_lines = run_child_collect_json(
            [
                sys.executable,
                os.path.join(HERE, "benchmarks", "weight_benchmark.py"),
                "--seconds", "10",
                "--clients", "6",
            ],
            rl_env,
            min(60, remaining),
        )
        weight_bench = wb_lines[-1] if wb_lines else None

    # eighth configuration: the scenario plane (docs/scenarios.md) —
    # a 2-scenario heterogeneous fleet stepped ready-first vs the
    # lock-step homogeneous batch path (scenario_hetero_x), plus the
    # batched serve tier under a labelled multi-scenario traffic mix
    # (serve_mix_p99_ms).  Jax-free.
    scenario_bench = None
    remaining = TOTAL_BUDGET_S - (time.monotonic() - t_start) - 20
    if remaining > 30:
        sc_lines = run_child_collect_json(
            [
                sys.executable,
                os.path.join(HERE, "benchmarks", "scenario_benchmark.py"),
                "--seconds", "18",
                "--instances", "2",
                "--clients", "6",
            ],
            rl_env,
            min(75, remaining),
        )
        scenario_bench = sc_lines[-1] if sc_lines else None

    # ninth configuration: the learner-failover plane
    # (docs/fault_tolerance.md "Learner failover") — ckpt_overhead_x
    # (async TrainCheckpointer on vs off over interleaved run_offline
    # windows) and learner_recovery_s (supervised learner SIGKILL ->
    # first post-respawn completed update).
    ha_bench = None
    remaining = TOTAL_BUDGET_S - (time.monotonic() - t_start) - 20
    if remaining > 40:
        ha_lines = run_child_collect_json(
            [
                sys.executable,
                os.path.join(HERE, "benchmarks", "ha_benchmark.py"),
            ],
            rl_env,
            min(150, remaining),
        )
        ha_bench = ha_lines[-1] if ha_lines else None

    # tenth configuration: the autoscale plane (docs/autoscaling.md) —
    # resize_settle_s (scale-up decision -> fleet verified healthy at
    # the new size under steady traffic) and drain_error_x (client-
    # observed error fraction across a drain scale-down — must be 0).
    # Jax-free.
    autoscale_bench = None
    remaining = TOTAL_BUDGET_S - (time.monotonic() - t_start) - 20
    if remaining > 30:
        as_lines = run_child_collect_json(
            [
                sys.executable,
                os.path.join(HERE, "benchmarks", "autoscale_benchmark.py"),
            ],
            rl_env,
            min(90, remaining),
        )
        autoscale_bench = as_lines[-1] if as_lines else None

    # eleventh configuration: the MPMD pipeline-parallel learner
    # (docs/pipeline.md) — N stage processes with 1F1B microbatch
    # interleaving vs a 1-stage same-harness baseline, interleaved
    # windows, calibrated per-stage compute stand-in.
    pipeline_bench = None
    remaining = TOTAL_BUDGET_S - (time.monotonic() - t_start) - 20
    if remaining > 40:
        pb_lines = run_child_collect_json(
            [
                sys.executable,
                os.path.join(HERE, "benchmarks", "pipeline_benchmark.py"),
            ],
            rl_env,
            min(150, remaining),
        )
        pipeline_bench = pb_lines[-1] if pb_lines else None

    out = assemble(phases, rl, rl_physics, feed_bound=feed_bound, rl_pipelined=rl_pipelined,
                   replay_bench=replay_bench, rl_sharded=rl_sharded,
                   serve_bench=serve_bench, gateway_bench=gateway_bench,
                   weight_bench=weight_bench,
                   scenario_bench=scenario_bench, ha_bench=ha_bench,
                   autoscale_bench=autoscale_bench,
                   pipeline_bench=pipeline_bench)
    print(json.dumps(out), flush=True)
    # The full line can exceed a tail-capture window.  Emit a compact
    # summary LAST so the trailing bytes of stdout always carry the
    # verdict; it is itself a valid driver line
    # (metric/value/unit/vs_baseline present).
    print(json.dumps(headline(out)), flush=True)


#: keys the compact trailing line carries verbatim (driver-line fields
#: spelled out so the summary is itself a valid driver line), plus the
#: abbreviated evidence keys below; chosen so the last 400 bytes of
#: stdout always answer: what was measured, and on what device
HEADLINE_KEYS = (
    "metric", "value", "unit", "vs_baseline", "vs_baseline_comparable",
    "train_degraded", "device", "duty_cycle_invalid",
)
#: full-artifact key -> compact headline key (byte budget: the whole
#: line must fit a 400-byte tail capture; test_bench_assembly locks it)
HEADLINE_ABBREV = (
    ("train_duty_cycle", "duty"),
)
#: the headline byte ceiling (newline included) and the key GROUPS
#: dropped — in order, only while over the ceiling — to get under it.
#: 'attn' goes first: whenever the line is long enough to overflow (the
#: banked partial-record shapes), flash_over_full is present and
#: already witnesses that the flash kernel ran.  A value and the
#: honesty flag qualifying it are dropped TOGETHER (never the flag
#: alone — a tail reader must not see a number whose 'untrustworthy'
#: marker was trimmed).  Everything here is recoverable from the full
#: artifact line; driver fields, the kernel verdict ratios, and the
#: partial/degraded markers are never dropped.
HEADLINE_BYTE_BUDGET = 400
HEADLINE_TRIM_ORDER = (
    ("telemetry_overhead_x",),
    ("pipe_mpmd_x",),
    ("resize_settle_s", "drain_error_x"),
    ("ckpt_overhead_x", "learner_recovery_s"),
    ("scenario_hetero_x", "serve_mix_p99_ms"),
    ("weight_swap_ms", "weight_swap_qps_dip_x"),
    ("serve_int8_x",),
    ("serve_prefill_x",),
    ("shm_rpc_x",),
    ("replay_shard_x", "replay_degraded_x"),
    ("gateway_qps", "gateway_p99_ms"),
    ("rl_sharded_x",),
    ("replay_sample_x",),
    ("gateway_scale_x", "gateway_shard_x"),
    ("serve_qps", "serve_p99_ms"),
    ("feed_arena_x",),
    ("rl_pipelined_x",),
    ("attn",),
    ("duty", "duty_cycle_invalid", "seq_duty", "seq_duty_invalid"),
)


def headline(out):
    """Compact summary of an assembled artifact (printed after it)."""
    line = {"headline": True}
    for k in HEADLINE_KEYS:
        if k in out:
            line[k] = out[k]
    for k, short in HEADLINE_ABBREV:
        if k in out:
            line[short] = out[k]
    fb = out.get("feed_bound")
    if fb and fb.get("arena_over_legacy") is not None:
        # arena assembly speedup over legacy collate at the feed ceiling
        line["feed_arena_x"] = fb["arena_over_legacy"]
    if fb and fb.get("telemetry_overhead_x") is not None:
        # telemetry-plane sanity: feed throughput with hub+histograms
        # enabled over disabled (floor 0.95 — see docs/observability.md)
        line["telemetry_overhead_x"] = fb["telemetry_overhead_x"]
    rb = out.get("replay_bench")
    if rb and rb.get("replay_sample_x") is not None:
        # columnar batched replay sampling speedup over naive per-item
        # collation (batch 32) — the off-policy workload's feed ceiling
        line["replay_sample_x"] = rb["replay_sample_x"]
    shard = (rb or {}).get("sharded")
    if shard and shard.get("replay_shard_x") is not None:
        # replay-service sampling rate over in-process (the wire tax of
        # the sharded storage tier — the service arm rides ShmRPC by
        # default since ISSUE-12), with the degraded-mode overhead
        # (one shard quarantined, strata renormalized) alongside
        line["replay_shard_x"] = shard["replay_shard_x"]
        if shard.get("shm_rpc_x") is not None:
            # the shared-memory transport over loopback ZMQ at the
            # median interleaved pair (docs/transport.md)
            line["shm_rpc_x"] = shard["shm_rpc_x"]
        if shard.get("replay_degraded_x") is not None:
            line["replay_degraded_x"] = shard["replay_degraded_x"]
    if out.get("rl_pipelined_x") is not None:
        # async pipelined EnvPool speedup over lock-step at physics 250us
        line["rl_pipelined_x"] = out["rl_pipelined_x"]
    if out.get("rl_sharded_x") is not None:
        # Sebulba sharded actor-learner speedup over single-device at
        # 4 fleets / 8 fake devices (simulation-bound, physics 8 ms)
        line["rl_sharded_x"] = out["rl_sharded_x"]
    sb = out.get("serve_bench")
    if sb and sb.get("serve_qps") is not None:
        # the policy-serving tier headline: batched QPS + client-
        # observed p99 at 8 concurrent episodes, with the
        # int8-over-float ratio
        line["serve_qps"] = sb["serve_qps"]
        if sb.get("serve_p99_ms") is not None:
            line["serve_p99_ms"] = sb["serve_p99_ms"]
        if sb.get("serve_int8_x") is not None:
            line["serve_int8_x"] = sb["serve_int8_x"]
        if sb.get("serve_prefill_x") is not None:
            # batched prefill admission over T serial decode steps
            line["serve_prefill_x"] = sb["serve_prefill_x"]
    gb = out.get("gateway_bench")
    if gb and gb.get("gateway_qps") is not None:
        # the serve-FLEET headline: aggregate QPS through the gateway
        # at 3 replicas, client-observed union p99, and the scale-out
        # ratio vs the same fleet with all but one replica drained
        line["gateway_qps"] = gb["gateway_qps"]
        if gb.get("gateway_p99_ms") is not None:
            line["gateway_p99_ms"] = gb["gateway_p99_ms"]
        if gb.get("gateway_scale_x") is not None:
            line["gateway_scale_x"] = gb["gateway_scale_x"]
        if gb.get("gateway_shard_x") is not None:
            # the sharded data plane's win: N gateway workers over one
            line["gateway_shard_x"] = gb["gateway_shard_x"]
    wb = out.get("weight_bench")
    if wb and wb.get("weight_swap_ms") is not None:
        # the live-rollout headline: publish -> first serving reply at
        # the new version (p99) and the QPS dip through the swap
        line["weight_swap_ms"] = wb["weight_swap_ms"]
        if wb.get("weight_swap_qps_dip_x") is not None:
            line["weight_swap_qps_dip_x"] = wb["weight_swap_qps_dip_x"]
    sc = out.get("scenario_bench")
    if sc and sc.get("scenario_hetero_x") is not None:
        # the scenario-plane headline: heterogeneous-fleet throughput
        # over the lock-step homogeneous batch path, and the serve
        # tier's union p99 under a labelled multi-scenario traffic mix
        line["scenario_hetero_x"] = sc["scenario_hetero_x"]
        if sc.get("serve_mix_p99_ms") is not None:
            line["serve_mix_p99_ms"] = sc["serve_mix_p99_ms"]
    ha = out.get("ha_bench")
    if ha:
        # the learner-failover headline: async-checkpointing overhead
        # (~1.0 = the update loop pays only the bounded barrier) and
        # the SIGKILL -> first-post-respawn-update outage
        if ha.get("ckpt_overhead_x") is not None:
            line["ckpt_overhead_x"] = ha["ckpt_overhead_x"]
        if ha.get("learner_recovery_s") is not None:
            line["learner_recovery_s"] = ha["learner_recovery_s"]
    asb = out.get("autoscale_bench")
    if asb:
        # the autoscale headline: scale-up decision -> verified-healthy
        # settle, and the zero-client-visible-errors drain contract
        if asb.get("resize_settle_s") is not None:
            line["resize_settle_s"] = asb["resize_settle_s"]
        if asb.get("drain_error_x") is not None:
            line["drain_error_x"] = asb["drain_error_x"]
    pb = out.get("pipeline_bench")
    if pb and pb.get("pipe_mpmd_x") is not None:
        # the MPMD pipeline headline: N stage processes' 1F1B schedule
        # over the 1-stage same-harness baseline (floor 1.5 at 3 stages)
        line["pipe_mpmd_x"] = pb["pipe_mpmd_x"]
    seq = out.get("seqformer")
    if seq:
        if "attn" in seq:
            line["attn"] = seq["attn"]
        if "flash_over_full" in seq:
            line["flash_over_full"] = seq["flash_over_full"]
        if seq.get("stream_pending") or seq.get("window_skipped"):
            # banked confirm-first record survived a mid-stream kill, or
            # the budget expired before the streaming window: the step
            # verdict is real, the stream window never ran
            line["seq_partial"] = True
        if seq.get("train_duty_cycle") is not None:
            line["seq_duty"] = seq["train_duty_cycle"]
            if seq.get("duty_cycle_invalid"):
                line["seq_duty_invalid"] = True
    moe = out.get("moe_compare")
    if moe and "topk_over_dense_mixture" in moe:
        line["topk_over_dense"] = moe["topk_over_dense_mixture"]
        if moe.get("partial"):
            # banked record survived a kill during mlp/topk_alt: the
            # ratio is real, the optional variants never ran
            line["moe_partial"] = True
    # bare-kernel fallbacks: surface only when the stronger train-step
    # ratio is absent (short window banked the micro verdict alone)
    ka = out.get("kernel_attn")
    if ka and "flash_over_full" not in line:
        if "flash_over_full_kernel" in ka:
            line["flash_over_full_kernel"] = ka["flash_over_full_kernel"]
        elif "flash_step_ms" in ka and ka.get("flash_compiled"):
            # flash ran compiled on this device even if the full-attn
            # comparison never landed
            line["flash_kernel_ran"] = True
    km = out.get("kernel_moe")
    if km and "topk_over_dense" not in line \
            and "topk_over_dense_kernel" in km:
        line["topk_over_dense_kernel"] = km["topk_over_dense_kernel"]
    for group in HEADLINE_TRIM_ORDER:
        if len(json.dumps(line)) + 1 <= HEADLINE_BYTE_BUDGET:
            break
        for k in group:
            line.pop(k, None)
    return line


def assemble(phases, rl=None, rl_physics=None, feed_bound=None, rl_pipelined=None, replay_bench=None,
             rl_sharded=None, serve_bench=None, gateway_bench=None,
             weight_bench=None, scenario_bench=None, ha_bench=None,
             autoscale_bench=None, pipeline_bench=None):
    """Assemble the driver's single JSON object from whatever phase lines
    arrived.  Pure, so the carry-through of stages/windows evidence is
    unit-testable (tests/test_bench_assembly.py)."""
    extras = {"includes_rendering": False}
    if serve_bench and serve_bench.get("phase") == "serve_bench":
        # the inference-tier ceiling: continuous-batched QPS/p99 + the
        # int8 ratio, stage percentiles included — see
        # benchmarks/serve_benchmark.py
        extras["serve_bench"] = {
            k: serve_bench[k]
            for k in (
                "model", "clients", "slots", "rounds", "window_s",
                "serve_qps", "serve_p50_ms", "serve_p99_ms",
                "serve_int8_x", "serve_prefill_x",
                "prefill", "serve_qps_modes", "stages",
            )
            if k in serve_bench
        }
    if gateway_bench and gateway_bench.get("phase") == "gateway_bench":
        # the serve-fleet scale-out record: N replicas behind the
        # gateway vs the same fleet drained to one — see
        # benchmarks/serve_benchmark.py --gateway
        extras["gateway_bench"] = {
            k: gateway_bench[k]
            for k in (
                "replicas", "clients", "work_us", "rounds", "window_s",
                "gateway_workers", "client_procs",
                "gateway_qps", "gateway_qps_1replica",
                "gateway_qps_1worker", "gateway_qps_nworker",
                "shard_profile",
                "gateway_p50_ms", "gateway_p99_ms", "gateway_scale_x",
                "gateway_shard_x", "pair_ratios", "shard_pair_ratios",
                "gateway_counters", "stages",
            )
            if k in gateway_bench
        }
    if scenario_bench and scenario_bench.get("phase") == "scenario_bench":
        # the scenario-plane record: heterogeneous-fleet ready-first
        # vs lock-step, plus the labelled serve traffic mix — see
        # benchmarks/scenario_benchmark.py
        extras["scenario_bench"] = {
            k: scenario_bench[k]
            for k in (
                "scenarios", "instances", "rounds", "window_s",
                "physics_us", "lockstep_steps_per_sec",
                "hetero_steps_per_sec", "scenario_hetero_x",
                "pair_ratios", "per_scenario_steps",
                "scenario_counters", "serve_mix", "serve_mix_p99_ms",
            )
            if k in scenario_bench
        }
    if ha_bench and ha_bench.get("phase") == "ha_bench":
        # the learner-failover record: async-checkpointing overhead
        # pairs + the SIGKILL recovery drill — see
        # benchmarks/ha_benchmark.py
        extras["ha_bench"] = {
            k: ha_bench[k]
            for k in (
                "window_s", "rounds", "ckpt_every_s",
                "ckpt_on_updates_per_sec", "ckpt_off_updates_per_sec",
                "ckpt_overhead_x", "pair_ratios",
                "learner_recovery_s", "recovery", "ha_counters",
                "stages",
            )
            if k in ha_bench
        }
    if autoscale_bench \
            and autoscale_bench.get("phase") == "autoscale_bench":
        # the autoscale record: decision-to-settle for a verified
        # scale-up and the drain scale-down's client-visible error
        # ledger — see benchmarks/autoscale_benchmark.py
        extras["autoscale_bench"] = {
            k: autoscale_bench[k]
            for k in (
                "replicas", "clients", "window_s",
                "resize_settle_s", "drain_settle_s",
                "drain_error_x", "drain_requests", "drain_errors",
                "autoscale_counters", "stages",
            )
            if k in autoscale_bench
        }
    if pipeline_bench \
            and pipeline_bench.get("phase") == "pipeline_bench":
        # the MPMD pipeline record: N-stage 1F1B over the 1-stage
        # same-harness baseline in interleaved windows — see
        # benchmarks/pipeline_benchmark.py
        extras["pipeline_bench"] = {
            k: pipeline_bench[k]
            for k in (
                "pipe_stages", "layers", "microbatches", "batch",
                "wire", "work_us", "rounds", "window_updates",
                "mpmd_updates_per_sec", "single_updates_per_sec",
                "pipe_mpmd_x", "pair_ratios", "pipe_counters",
                "stages",
            )
            if k in pipeline_bench
        }
    if weight_bench and weight_bench.get("phase") == "weight_bench":
        # the live-rollout cost record: publish -> first-serving-reply
        # swap latency and the QPS dip through the swap — see
        # benchmarks/weight_benchmark.py
        extras["weight_bench"] = {
            k: weight_bench[k]
            for k in (
                "clients", "publishes", "window_s", "snapshot_kb",
                "weight_swap_ms", "weight_swap_ms_p50",
                "weight_swap_qps_dip_x", "qps_steady",
                "swaps_observed", "swap_ms_all", "publish_ms_p50",
                "weight_counters", "stages",
            )
            if k in weight_bench
        }
    if feed_bound:
        # the feed ceiling, legacy vs arena assembly (trivial train step,
        # jax-free) — including the arena stage timings (arena_wait /
        # scatter / recycle), so the copy-elimination win is measurable
        # in the artifact rather than asserted
        extras["feed_bound"] = feed_bound
    if replay_bench:
        # the replay-path ceiling: ring append rate, columnar-vs-naive
        # sampling (replay_sample_x), and the FileRecorder buffered-write
        # before/after (record_buffered_x) — see benchmarks/replay_benchmark.py
        extras["replay_bench"] = replay_bench

    hbm = phases.get("stream_to_hbm")
    train = phases.get("stream_to_train")
    seq = phases.get("seqformer_train")
    moe = phases.get("moe_compare")
    host = phases.get("host_stream")
    init = phases.get("device_init")
    if init:
        extras["device_init_s"] = init.get("seconds")
        extras["device"] = init.get("platform")
        extras["device_kind"] = init.get("device_kind")
    put_strat = phases.get("put_strategy")
    if put_strat:
        # winner AND loser ship together (VERDICT r4 next #6): the feed's
        # transfer granularity choice is evidence, not a hidden default
        extras["put_strategy"] = {
            k: put_strat[k]
            for k in ("winner", "chunked_over_whole", "chunks",
                      "whole_s", "chunked_s", "batch_mb")
            if k in put_strat
        }
    if moe:
        extras["moe_compare"] = {
            k: moe[k]
            for k in ("mlp", "dense", "topk", "topk_alt",
                      "topk_over_dense_mixture",
                      "consistent_dense_ge_mlp", "experts", "top_k",
                      "moe_dispatch", "partial")
            if k in moe
        }
    # bare-kernel verdicts (suite phase_kernel_microverdicts): the
    # cheapest on-chip witnesses of flash<=full / topk<=dense — kept
    # alongside (never instead of) the train-step-level ratios, which
    # supersede them in the headline
    kflash = phases.get("kernel_flash")
    kff = phases.get("kernel_flash_vs_full")
    kwin = phases.get("kernel_flash_windowed")
    if kflash or kff or kwin:
        ka = {}
        if kflash:
            ka["flash_step_ms"] = round(
                kflash["step_stats"]["step_s"] * 1e3, 3
            )
            ka["flash_compiled"] = kflash.get("compiled")
        if kff:
            for k in ("flash_step_ms", "full_step_ms",
                      "flash_over_full_kernel"):
                if k in kff:
                    ka[k] = kff[k]
        if kwin:
            for k in ("window", "windowed_step_ms",
                      "windowed_over_flash"):
                if k in kwin:
                    ka[k] = kwin[k]
        extras["kernel_attn"] = ka
    kint8 = phases.get("int8_infer")
    if kint8:
        extras["int8_infer"] = {
            k: kint8[k]
            for k in ("bf16_step_ms", "int8_step_ms", "int8_over_bf16")
            if k in kint8
        }
    ktopk = phases.get("kernel_topk")
    ktd = phases.get("kernel_topk_vs_dense")
    if ktopk or ktd:
        km = {}
        if ktopk:
            km["topk_step_ms"] = round(
                ktopk["step_stats"]["step_s"] * 1e3, 3
            )
        if ktd:
            for k in ("topk_step_ms", "dense_step_ms",
                      "topk_over_dense_kernel"):
                if k in ktd:
                    km[k] = ktd[k]
        extras["kernel_moe"] = km
    if host:
        extras["host_stream_images_per_sec"] = host["items_per_sec"]
    if hbm:
        extras["stream_to_hbm_images_per_sec"] = hbm["items_per_sec"]
        extras["stream_to_hbm_windows"] = hbm.get("items_per_sec_windows")
        extras["stream_to_hbm_stages"] = hbm.get("stages")
    gateoff = phases.get("stream_to_hbm_gateoff")
    if (gateoff and hbm
            and gateoff.get("platform") == hbm.get("platform")):
        extras["stream_to_hbm_gateoff_images_per_sec"] = gateoff[
            "items_per_sec"
        ]
        if "items_per_sec_windows" in gateoff:
            extras["stream_to_hbm_gateoff_windows"] = gateoff[
                "items_per_sec_windows"
            ]
    if train:
        extras["train_duty_cycle"] = train.get("train_duty_cycle")
        if train.get("duty_cycle_invalid"):
            extras["duty_cycle_invalid"] = True
        extras["detector_step_ms"] = round(train["step_s"] * 1e3, 3)
        extras["stream_to_train_windows"] = train.get(
            "items_per_sec_windows"
        )
        extras["stream_to_train_stages"] = train.get("stages")
        extras["detector_step_stats"] = train.get("step_stats")
        for k in ("step_flops_analytic", "step_flops_xla", "mfu",
                  "mfu_invalid"):
            if k in train:
                extras[f"detector_{k}"] = train[k]
    if seq:
        extras["seqformer"] = {
            k: seq[k]
            for k in (
                "tokens_per_sec",
                "train_duty_cycle",
                "duty_cycle_invalid",
                "attn",
                "full_attn_step_s",
                "flash_over_full",
                "mfu",
                "mfu_invalid",
                "step_s",
                "step_stats",
                "device_kind",
                "model_flops_per_sec",
                "step_flops_analytic",
                "step_flops_xla",
                "items_per_sec_windows",
                "stages",
                "window_skipped",
                "stream_pending",
                "batches",
            )
            if k in seq
        }
    if rl:
        extras["rl_steps_per_sec"] = rl.get("value")
        extras["rl_vs_baseline"] = rl.get("vs_baseline")
        extras["rl_includes_physics"] = rl.get("includes_physics", False)
    if rl_physics:
        extras["rl_steps_per_sec_physics250us"] = rl_physics.get("value")
        extras["rl_vs_baseline_physics250us"] = rl_physics.get("vs_baseline")
    if rl_pipelined:
        extras["rl_pipeline_depth"] = rl_pipelined.get("pipeline_depth")
        if rl_pipelined.get("metric") == "rl_pipelined_x":
            # --compare line: the ratio IS the value (median of
            # interleaved lock-step/pipelined window pairs on one fleet —
            # the serialization tax the async path recovered), with both
            # absolute medians alongside
            extras["rl_pipelined_x"] = rl_pipelined.get("value")
            extras["rl_steps_per_sec_pipelined"] = rl_pipelined.get(
                "pipelined_steps_per_sec"
            )
        else:
            # single-mode pipelined line: ratio against the lock-step
            # phase (two separate runs; drift-prone, kept for compat)
            extras["rl_steps_per_sec_pipelined"] = rl_pipelined.get("value")
            base = (rl_physics or {}).get("value")
            if rl_pipelined.get("value") and base:
                extras["rl_pipelined_x"] = round(
                    rl_pipelined["value"] / base, 3
                )
    if rl_sharded and rl_sharded.get("metric") == "rl_sharded_x":
        # the Sebulba sharded actor-learner ratio (4 fleets feeding a
        # P('data')-sharded learner over the 8-fake-device MULTICHIP
        # harness vs single fleet/device; interleaved window pairs,
        # simulation-bound physics — see docs/sharded_rl.md), with both
        # absolute medians and the multi-fleet health aggregate
        extras["rl_sharded_x"] = rl_sharded.get("value")
        extras["rl_sharded_config"] = {
            k: rl_sharded[k]
            for k in ("mesh_devices", "fleets", "instances_per_fleet",
                      "total_envs", "physics_us", "pair_ratios",
                      "single_env_steps_per_sec",
                      "sharded_env_steps_per_sec")
            if k in rl_sharded
        }
        if "fleet_health" in rl_sharded:
            extras["rl_sharded_fleet_health"] = rl_sharded["fleet_health"]

    def dims(p):
        # --config small runs shrunken frames, and the wire carries RGB
        # by default since round 5 (RGBA before): name the metric by
        # what was actually measured, channels included — a 25%-lighter
        # payload must never ride under a pre-r5 metric name
        return (f"cube{p.get('width', 640)}x{p.get('height', 480)}"
                f"x{p.get('channels', 4)}")

    def full_res(p):
        return (p.get("width", 640), p.get("height", 480)) == (640, 480)

    if train:
        ips = train["items_per_sec"]
        # a shrunken-frame run is NOT comparable to the reference's
        # 640x480 number: keep it, but degraded
        metric = f"{dims(train)}_images_per_sec_stream_to_train"
        degraded = not full_res(train)
        if "channels" in train:
            extras["wire_channels"] = train["channels"]
    elif hbm:
        ips = hbm["items_per_sec"]
        metric, degraded = f"{dims(hbm)}_images_per_sec_stream_to_hbm", True
    elif host:
        ips = host["items_per_sec"]
        metric, degraded = "cube640x480x3_images_per_sec_host_stream_only", True
    else:
        raise ValueError("no stream phase arrived: nothing to report")

    out = {
        "metric": metric,
        "value": round(ips, 2),
        "unit": "images/sec",
        "vs_baseline": round(ips * REF_SEC_PER_IMAGE, 3),
        "train_degraded": degraded,
    }
    if not metric.startswith("cube640x480"):
        # reference's 0.012 s/image is 640x480; shrunken-frame throughput
        # must not be read as a baseline multiple
        out["vs_baseline_comparable"] = False
    out.update(extras)
    return out


if __name__ == "__main__":
    main()
