# blendjax developer entry points.
#
# `make blender-tests` is the one-command real-Blender acceptance run
# (VERDICT r2 task #6): on any machine with a Blender binary it needs no
# edits — discovery walks $PATH (override with $BLENDJAX_REAL_BLENDER);
# headless hosts get a GL context via scripts/blender_headless.sh.

PYTHON ?= python
# tier1 uses pipefail/PIPESTATUS (bash); everything else is sh-safe too
SHELL := /bin/bash

.PHONY: test tier1 chaos chaos-replay chaos-learner chaos-autoscale \
	chaos-pipeline blender-tests \
	chip-smoke bench rlbench rlbench-sharded replaybench shmbench \
	servebench gatewaybench weightbench scenariobench habench \
	autoscalebench pipebench multichip dryrun benchdiff obsdemo

test:
	# CPU-only: tests/conftest.py forces JAX_PLATFORMS=cpu and the
	# 8-device virtual mesh for pytest and every child it spawns
	$(PYTHON) -m pytest tests/ -q

# The ROADMAP tier-1 verify command, verbatim: CPU-forced, non-slow
# subset with the driver's DOTS_PASSED accounting.  This is the gate a
# PR must keep no worse than the seed.
tier1:
	set -o pipefail; rm -f /tmp/_t1.log; \
	timeout -k 10 870 env JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/ -q \
		-m 'not slow' --continue-on-collection-errors \
		-p no:cacheprovider -p no:xdist -p no:randomly 2>&1 \
		| tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; \
	echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log \
		| tr -cd . | wc -c); \
	exit $$rc

# The chaos pack (tests/test_chaos.py + FaultPolicy units): deterministic
# fault injection — proxy stall/drop/garble, producer SIGKILL, supervised
# restart-and-resync.  Includes the `slow` soak cycles that tier-1 skips.
# See docs/fault_tolerance.md.
# BJX_POSTMORTEM_DIR: every supervised producer/shard death during the
# chaos run dumps a flight-recorder postmortem JSON there (naming the
# quarantined target and the fault events around it) — the chaos
# failure is diagnosable from artifacts, not just exit codes.  See
# docs/observability.md.
chaos:
	env JAX_PLATFORMS=cpu \
		BJX_POSTMORTEM_DIR=obs_artifacts \
		$(PYTHON) -m pytest tests/ -m chaos -q -rs

# The replay-service shard chaos pack (tests/test_replay_service.py):
# SIGKILL a shard process mid-training -> degraded sampling with strata
# renormalized over live shards -> supervised respawn -> checkpoint +
# .btr spill-tail restore -> re-admission with the draw stream
# continuing bit-identically.  Subset of `make chaos` (same marker),
# runnable alone for storage-tier work.  See docs/replay.md.
chaos-replay:
	env JAX_PLATFORMS=cpu \
		BJX_POSTMORTEM_DIR=obs_artifacts \
		$(PYTHON) -m pytest tests/test_replay_service.py -m chaos -q -rs

# The learner-failover chaos pack (tests/test_ha.py): SIGKILL the
# supervised learner process mid-training (live fake-Blender fleet +
# sharded replay + a subscribed serve replica) -> watchdog respawn ->
# resume from the latest complete manifest with the replay draw
# authority reconciled to the cut, weight-bus versions strictly
# monotonic across the respawn, and zero serve-client errors.  Includes
# the `slow`-marked full acceptance that tier-1 skips.  See
# docs/fault_tolerance.md "Learner failover".
chaos-learner:
	env JAX_PLATFORMS=cpu \
		BJX_POSTMORTEM_DIR=obs_artifacts \
		$(PYTHON) -m pytest tests/test_ha.py -m chaos -q -rs

# The autoscale chaos pack (tests/test_autoscale.py): the three SIGKILL
# drills every live resize must survive — a serve replica killed
# MID-DRAIN (watchdog respawn, drain flag survives quarantine, the
# scale-down still completes), the controller killed MID-DECISION (a
# fresh controller adopts the observed in-flight drain instead of
# double-acting), and the NEW replay shard killed MID-HANDOFF (the
# handoff aborts whole, the ownership map untouched, the source keeps
# serving).  Subset of `make chaos` (same marker).  See
# docs/autoscaling.md.
chaos-autoscale:
	env JAX_PLATFORMS=cpu \
		BJX_POSTMORTEM_DIR=obs_artifacts \
		$(PYTHON) -m pytest tests/test_autoscale.py -m chaos -q -rs

# The MPMD pipeline chaos pack (tests/test_mpmd.py): SIGKILL one stage
# process mid-training -> FleetWatchdog respawn -> the stage restores
# its params from the per-stage checkpoint cut, the driver reconciles
# every stage to the lowest applied update and replays the in-flight
# one — same-mid resends deduped by the reply cache, so no microbatch
# is lost or applied twice and the final params match an uninterrupted
# run exactly.  Subset of `make chaos` (same marker).  See
# docs/pipeline.md.
chaos-pipeline:
	env JAX_PLATFORMS=cpu \
		BJX_POSTMORTEM_DIR=obs_artifacts \
		$(PYTHON) -m pytest tests/test_mpmd.py -m chaos -q -rs

# Real-Blender acceptance subset (camera goldens, producer streaming,
# cartpole physics).  Skips cleanly when no Blender is discoverable.
# On a headless host (e.g. a TPU-VM) route Blender through the virtual
# display wrapper so Eevee gets a GL context:
#   make blender-tests BLENDER_WRAPPER=1
blender-tests:
ifdef BLENDER_WRAPPER
	BLENDJAX_BLENDER=$(CURDIR)/scripts/blender_headless.sh \
		$(PYTHON) -m pytest tests/ -m blender -q -rs
else
	$(PYTHON) -m pytest tests/ -m blender -q -rs
endif

# The one way onto the chip: kernels, train (+ fence), serve and mesh
# legs of the SeqFormer main path at full width, one process per chip.
# Exits non-zero without a TPU (the CPU rehearsal is
# tests/test_smoke_chip.py).  From the sandbox: chiprun -- python chip_smoke.py
chip-smoke:
	$(PYTHON) chip_smoke.py

bench:
	$(PYTHON) bench.py

# Jax-free RL stepping microbench: lock-step vs async pipelined EnvPool
# (fake-Blender fleet speaking the real wire protocol, 250 us/frame
# physics stand-in).  One JSON line with rl_pipelined_x — the
# serialization tax recovered by step_async/step_wait.  See
# docs/rl_stepping.md.
rlbench:
	$(PYTHON) benchmarks/rl_benchmark.py \
		--instances 4 --seconds 15 --physics-us 250 \
		--compare --pipeline-depth 4

# Sebulba sharded actor-learner microbench (docs/sharded_rl.md): 4
# env fleets feeding a learner sharded over 8 fake CPU devices vs the
# single-fleet/single-device configuration, interleaved window pairs,
# median ratio as rl_sharded_x (floor 1.5).  The 8 ms physics stand-in
# puts the fleet in the simulation-bound regime the sharded split is
# for (a realistic Blender scene tick; the near-zero-physics protocol
# tax is rlbench's subject) — on a 2-core CI box lighter physics
# saturates the cores with producer work and measures oversubscription
# instead of the architecture.
rlbench-sharded:
	env JAX_PLATFORMS=cpu \
		$(PYTHON) benchmarks/rl_benchmark.py \
		--sharded --mesh-devices 8 --fleets 4 --instances 4 \
		--seconds 24 --physics-us 8000

# The sharding/multihost tier on the 8-fake-device MULTICHIP harness —
# the reproducible local entry point behind the MULTICHIP_r0x.json
# artifacts (before this target only `dryrun` set the virtual-device
# flag).  Runs the mesh/sharding/multihost/sharded-RL test files, then
# the __graft_entry__ multi-parallelism dry run.
multichip:
	env JAX_PLATFORMS=cpu \
		XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		$(PYTHON) -m pytest tests/test_sharding.py tests/test_multihost.py \
		tests/test_actor_learner_sharded.py tests/test_prefetch.py \
		tests/test_pipeline.py -q -rs
	env JAX_PLATFORMS=cpu \
		XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		$(PYTHON) __graft_entry__.py

# Jax-free replay-path microbench: appends/sec into the columnar ring,
# batched columnar vs naive per-item sampling (replay_sample_x, floor
# 2.0 at batch 32), the FileRecorder buffered-vs-unbuffered write
# comparison, and (--sharded) the replay-service windows — in-process
# vs ShardedReplay over 2 in-process shard servers in interleaved
# windows (replay_shard_x = the storage tier's wire tax) plus the
# degraded-mode sampling overhead with one shard quarantined
# (replay_degraded_x).  One JSON line; see docs/replay.md.
replaybench:
	$(PYTHON) benchmarks/replay_benchmark.py \
		--batch 32 --seconds 6 --sharded

# ShmRPC transport microbench (docs/transport.md): the replay-service
# windows with BOTH wires interleaved over the same shard servers —
# replay_shard_x from the shm arm (the storage tier's wire tax after
# the shared-memory transport) and shm_rpc_x (shm over loopback ZMQ at
# the median pair; floor trajectory-guarded in bench_compare).  Longer
# windows than replaybench: this is the transport's dedicated entry
# point.
shmbench:
	$(PYTHON) benchmarks/replay_benchmark.py \
		--batch 32 --seconds 10 --sharded --transport shm

# Policy-serving microbench (docs/serving.md): 8 concurrent episode
# clients against one continuously-batched seqformer world-model
# server (KV-cache slot pool, per-row positions) vs the int8-quantized
# server, in interleaved order-rotated rounds.  One JSON line with the
# serving headline: serve_qps, serve_p99_ms (client-observed union
# p99), serve_int8_x.
servebench:
	env JAX_PLATFORMS=cpu \
		$(PYTHON) benchmarks/serve_benchmark.py \
		--seconds 18 --clients 8

# Serve-fleet scale-out microbench (docs/serving.md "ServeGateway"): 3
# linear-model replica processes (sleep-based --work-us per-row compute
# stand-in, so replica compute is what scales) behind one ServeGateway,
# 16 clients, interleaved 1-replica (others DRAINED) vs 3-replica
# windows.  One JSON line with gateway_qps, gateway_p99_ms
# (client-observed union p99) and gateway_scale_x (aggregate QPS at 3
# replicas over 1 at the median pair; ~2.2 on the 2-core CI box — the
# gap to 3.0 is the box's 2 cores carrying 16 GIL-bound bench clients
# plus the single-threaded gateway hop).
gatewaybench:
	env JAX_PLATFORMS=cpu \
		$(PYTHON) benchmarks/serve_benchmark.py \
		--gateway --replicas 3 --gateway-workers 2 \
		--client-procs 4 --seconds 27 --clients 16

# WeightBus live-rollout microbench (docs/weight_bus.md): 6 concurrent
# episode clients against one subscribed linear-model server while an
# in-process publisher pushes a fresh 256 KiB versioned snapshot every
# ~800 ms.  One JSON line with weight_swap_ms (publish -> first
# client-observed reply at the new version, p99 over the window's
# swaps; ceiling-guarded in bench_compare) and weight_swap_qps_dip_x
# (QPS through the swap over steady state; floor 0.80).  Jax-free.
weightbench:
	$(PYTHON) benchmarks/weight_benchmark.py \
		--seconds 10 --clients 6

# Scenario-plane microbench (docs/scenarios.md): a 2-scenario
# fake-Blender fleet at very different physics rates (lite 200 us vs
# rich 4 ms), lock-step homogeneous batching vs ready-first
# step_wait(min_ready=1) over the SAME fleet in interleaved window
# pairs -> scenario_hetero_x (the throughput the slow scenario no
# longer steals); then the batched serve tier under a weighted
# labelled traffic mix -> serve_mix_p99_ms (the union tail a realistic
# multi-scenario workload observes).  Jax-free; both numbers carried
# in the bench headline with bench_compare bounds.
scenariobench:
	$(PYTHON) benchmarks/scenario_benchmark.py \
		--seconds 20 --instances 2 --clients 6

# Learner-failover microbench (docs/fault_tolerance.md "Learner
# failover"): ckpt_overhead_x (off-policy update throughput with the
# async TrainCheckpointer on vs off, interleaved window pairs — target
# ~1.0, floor 0.90) and learner_recovery_s (SIGKILL of the supervised
# learner process on a live fake-Blender fleet -> first completed
# post-respawn update, watchdog + respawn + jax import + manifest
# restore + first jitted update included).  One JSON line, both carried
# in the bench.py headline with bench_compare bounds.
habench:
	env JAX_PLATFORMS=cpu \
		$(PYTHON) benchmarks/ha_benchmark.py

# Autoscale microbench (docs/autoscaling.md): resize_settle_s (the
# controller's scale-up decision -> fleet verified healthy at the new
# size under steady client traffic, healthy window included — lower is
# better, bench_compare ceiling) and drain_error_x (client-observed
# error fraction across the drain -> verify -> retire scale-down —
# MUST be 0.0).  One JSON line, both carried in the bench.py headline.
autoscalebench:
	env JAX_PLATFORMS=cpu \
		$(PYTHON) benchmarks/autoscale_benchmark.py

# MPMD pipeline microbench (docs/pipeline.md): N-stage stage-process
# pipeline vs a 1-stage same-harness baseline in interleaved windows;
# the `pipe_mpmd_x` throughput ratio is carried into the bench headline
# (bench_compare floors it).
pipebench:
	env JAX_PLATFORMS=cpu \
		$(PYTHON) benchmarks/pipeline_benchmark.py

# Bench-trajectory guardrail (docs/observability.md): diff two bench
# artifacts with per-metric regression floors; non-zero exit on any
# metric below its floor.  Accepts raw bench.py stdout, headline lines,
# and the driver capture wrappers.
#   make benchdiff OLD=BENCH_old.json NEW=BENCH_new.json
OLD ?= BENCH_old.json
NEW ?= BENCH_new.json
benchdiff:
	$(PYTHON) scripts/bench_compare.py $(OLD) $(NEW)

# Telemetry-plane demo (docs/observability.md): a short fake-Blender
# pipeline with tracing on, emitting into obs_artifacts/ —
#   trace.perfetto.json  one merged Chrome/Perfetto timeline with
#                        producer- and consumer-side spans of the same
#                        correlation ids across >= 3 pids,
#   scrape.json/.prom    a TelemetryHub scrape (zero-filled canonical
#                        counters+stages, latency percentiles) in both
#                        exposition formats, pulled over the ZMQ REP
#                        scrape socket,
#   postmortem-*.json    a forced flight-recorder dump naming a
#                        quarantined target.
obsdemo:
	env JAX_PLATFORMS=cpu \
		$(PYTHON) scripts/obs_demo.py --out obs_artifacts

dryrun:
	env JAX_PLATFORMS=cpu \
		XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		$(PYTHON) __graft_entry__.py
