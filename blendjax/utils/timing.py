"""Lightweight per-stage timing — the observability the reference lacks
(SURVEY.md §5: "The TPU build should add lightweight stage timestamps
(render / serialize / recv / device_put) since the north-star metric is TPU
duty-cycle").

Usage::

    timer = StageTimer()
    with timer.stage("recv"):
        msg = sock.recv()
    ...
    timer.summary()   # {'recv': {'count': n, 'total_s': t, 'mean_ms': m,
                      #           'p50_ms': ..., 'p90_ms': ..., 'p99_ms': ...,
                      #           'max_ms': ...}, ...}
    timer.duty_cycle("step")   # fraction of wall time inside 'step'

Every ``add`` also lands in a fixed-memory log-bucketed latency
histogram (:class:`blendjax.obs.histogram.LatencyHistogram`), so the
summary carries per-stage p50/p90/p99/max — the percentile surface the
telemetry plane (docs/observability.md) scrapes and merges across
processes.  ``histograms=False`` opts out.

Every ``stage`` block is also a span on the profiler's clock: in a
process that has imported jax it opens a
``jax.profiler.TraceAnnotation`` of the same name, so under
``jax.profiler.start_trace`` the loader workers, the prefetch thread,
the train loop and the server's tick phases lie in the profiler's trace
beside the device's operations.  :func:`span` is the same for places
that have no timer.  This module never imports jax: in a jax-free
process (producers, replay shards) both are plain no-ops.
"""

from __future__ import annotations

import math
import sys
import threading
import time
from collections import defaultdict
from contextlib import contextmanager

from blendjax.obs import histogram as _histogram
from blendjax.obs.histogram import LatencyHistogram

# hot-path constants for the inlined histogram update in StageTimer.add
_hist_frexp = math.frexp
_HIST_TOP = _histogram.NBUCKETS - 1
_HIST_SUBBITS = _histogram.SUBBITS

#: Canonical feed-pipeline stage names (see docs/feed_pipeline.md).
#: ``recv``/``collate``/``device_put`` cover the legacy path; the
#: arena-pooled assembly adds ``arena_wait`` (blocked acquiring a free
#: batch arena — i.e. trainer backpressure), ``scatter`` (wire frame ->
#: batch-buffer copy) and ``recycle`` (arena returned after the device
#: transfer completes).  StageTimer itself accepts any name; this tuple
#: is the shared vocabulary bench.py and the suite report under.
FEED_STAGES = (
    "recv", "collate", "arena_wait", "scatter", "recycle", "device_put",
)

#: Canonical fault/health event names (see docs/fault_tolerance.md).
#: ``EventCounters`` accepts any name; this tuple is the shared vocabulary
#: the fault layer increments and ``FleetSupervisor.health()`` reports —
#: every name is present (zero) in a health snapshot even before its first
#: event, so dashboards and tests need no existence checks.
FLEET_EVENTS = (
    "deaths", "restarts", "retries", "timeouts", "failures", "quarantines",
    "readmissions", "circuit_opens", "circuit_rejections",
    "stream_timeouts", "stream_ring_vanished", "transfer_gate_backstops",
    # async env pipeline (EnvPool.step_async/step_wait):
    # ``ready_waits`` — step_wait calls that actually blocked for a reply;
    # ``stale_replies`` — replies with no matching in-flight request
    # (duplicate delivery, or orphaned by a quarantine drain);
    # ``inflight_discards`` — in-flight requests consumed without
    # surfacing a real transition (quarantine drain, post-``done`` frames,
    # pipeline flush); a reply lost ahead of an out-of-order match is NOT
    # discarded — it is re-sent and answered from the producer reply cache
    "ready_waits", "stale_replies", "inflight_discards",
    # record path: ``record_drops`` — messages FileRecorder refused because
    # its fixed capacity was reached (the recording is truncated, not the
    # stream; see btt/file.py)
    "record_drops",
    # watchdog respawn pacing: ``watchdog_backoff_jitter_ms`` — total
    # milliseconds of per-member randomized delay FleetWatchdog inserted
    # before respawns, so N members killed together do not relaunch in
    # lockstep and stampede the gateway's re-admission scrape (see
    # docs/fault_tolerance.md; the jitter itself is `respawn_jitter_s`)
    "watchdog_backoff_jitter_ms",
)

#: Canonical experience-replay event names (see docs/replay.md).  Same
#: contract as ``FLEET_EVENTS``: any ``EventCounters`` instance accepts
#: them, and ``FleetSupervisor.health()`` zero-fills every name so
#: dashboards need no existence checks.
#: ``replay_appends`` — transitions accepted into the ring;
#: ``replay_overwrites`` — appends that evicted a live transition (ring
#: wraparound: the buffer is at capacity and recycling oldest-first);
#: ``replay_excluded`` — appends flagged unhealthy (synthetic
#: degraded-mode transitions: stored for inspection, never sampled);
#: ``replay_samples`` — batches drawn;
#: ``replay_sample_waits`` — sample calls that blocked on an
#: underfilled buffer (learner outpacing the actor);
#: ``replay_priority_updates`` — update_priorities calls applied.
#: ``replay_sample_skips`` — off-policy learner tail draws skipped
#: because the buffer (or its live shards) could not serve the batch;
#: sharded replay service (docs/replay.md "Sharded replay service"):
#: ``replay_shard_quarantined`` — a shard stopped answering RPCs (or its
#: process died) and was isolated; sampling renormalizes strata over the
#: live shards and continues degraded;
#: ``replay_shard_readmissions`` — a shard passed the re-admission
#: handshake (restored checkpoint + ``.btr`` tail verified, journal
#: flushed) and rejoined the draw domain;
#: ``replay_shard_journal`` — appends owned by a quarantined shard held
#: client-side (flushed on re-admission, never lost);
#: ``replay_shard_lost`` — rows a restarted shard could not account for
#: (it restored an older state than the client acked); their slots are
#: invalidated instead of serving wrong rows;
#: per-request wire-bytes accounting (docs/transport.md): a shard
#: counts every RPC payload byte it moves, split by wire —
#: ``replay_wire_bytes`` over the ZMQ socket, ``replay_shm_bytes``
#: through the ShmRPC rings — so the shm-vs-tcp byte saving is
#: observable in a telemetry scrape, not just inferred from latency.
REPLAY_EVENTS = (
    "replay_appends", "replay_overwrites", "replay_excluded",
    "replay_samples", "replay_sample_waits", "replay_priority_updates",
    "replay_sample_skips",
    "replay_shard_quarantined", "replay_shard_readmissions",
    "replay_shard_journal", "replay_shard_lost",
    "replay_wire_bytes", "replay_shm_bytes",
)

#: Canonical policy-serving event names (see docs/serving.md).  Same
#: contract as ``FLEET_EVENTS``: any ``EventCounters`` accepts them and
#: the TelemetryHub zero-fills every name in every scrape.
#: ``serve_requests`` — requests admitted (any command);
#: ``serve_replies`` — replies sent (errors included);
#: ``serve_batches`` — batched compute ticks executed;
#: ``serve_batch_pad`` — padding rows added to reach a bucket size
#: (wasted compute rows, the bucket/recompile tradeoff's price);
#: ``serve_cache_hits`` — retried requests answered from the reply
#: cache (exactly-once: no second decode for the same correlation id);
#: ``serve_dup_inflight`` — duplicates of a still-queued request
#: dropped at admission (the original's reply answers both);
#: ``serve_resets`` — episodes admitted (slot allocations);
#: ``serve_closes`` — episodes closed by their client;
#: ``serve_evictions`` — idle slots reclaimed by the allocator;
#: ``serve_slot_denied`` — resets refused because no slot was free;
#: ``serve_errors`` — requests that errored: answered with an error
#: reply, or (batched mode only) dropped because their frames were
#: undecodable — the one case with no reply, healed by the client's
#: retry;
#: ``serve_prefills`` — episodes admitted WITH a T-step observation
#: prefix replayed in one teacher-forced batched pass (docs/serving.md
#: "Batched prefill admission") instead of T serial decode steps;
#: per-request wire-bytes accounting (docs/transport.md): the server
#: counts every request/reply payload byte it moves, split by wire —
#: ``serve_wire_bytes`` over the ZMQ socket, ``serve_shm_bytes``
#: through the ShmRPC rings;
#: two running quantities in microseconds, the one record of two
#: phases of the server's single thread (the same intervals are the
#: ``serve.prefill`` and ``serve.idle`` spans of a profiler trace):
#: ``serve_prefill_us`` — the thread's time in prefills: a prefill's
#: dispatch where its reset is admitted plus whatever the thread waited
#: where its reply is fetched (over ``serve_prefills``: one prefill;
#: over the wall: the share in which the thread could do nothing else);
#: ``serve_idle_us`` — spent polling with nothing queued (over
#: ``serve_batches``: the clients' turnaround per tick);
#: ``serve_pool_rebuilds`` — times a donated step or prefill failed
#: after it had taken the slot pool: the pool was rebuilt empty and the
#: model's leases dropped (docs/serving.md "KV-cache slot pool"); 0 in a
#: healthy server.
#: ``serve_moe_assignments`` / ``serve_moe_assignments_held`` /
#: ``serve_moe_experts_hit`` — a routed (held-share) model's decode
#: ticks, real rows only, summed over its expert layers: (token, expert)
#: assignments the router made, those whose expert this rank holds, and
#: distinct held experts that got a token (whose weights a tick must
#: read); they come over with the reply's fetch.
#: ``serve_ticks_overlapped`` — ticks launched (dispatched) while an
#: older tick's reply was still to be fetched: over ``serve_batches``,
#: how often the server's admission and replies ran beside the device
#: and not between its ticks (0 for a lone client or a model that
#: computes on the host);
#: ``serve_fetch_wait_us`` — microseconds the server's thread was
#: blocked fetching a launched entry's reply (waiting for the device):
#: a tick's or, since PR 40, a prefill's (so a launched prefill's wait
#: is in both this and ``serve_prefill_us``);
#: ``serve_prefills_overlapped`` — prefills (of ``serve_prefills``)
#: that did not have the device's queue to themselves: something
#: launched was still unfetched when the prefill was dispatched, or
#: something was dispatched behind it before its reply was fetched (0
#: for a lone client or a model that computes on the host).
#: ``serve_ctx_positions`` / ``serve_rows_stepped`` /
#: ``serve_window_positions`` / ``serve_state_resets`` — a served model
#: of mixed layer kinds (``HYBRID_EVENTS`` in blendjax/serve/server.py):
#: over a tick's real rows, the positions live in each row's full-length
#: K/V (the one being written included), the rows, and the positions
#: live in one window ring (they come over with the reply's fetch); and
#: the rows whose recurrent state a reset zeroed.
#: ``serve_state_bytes`` — bytes of recurrent state and convolution tails
#: that the real rows of such a model's decode ticks read and wrote.
#: The phase clock (``PolicyServer``'s thread, docs/serving.md "The phase
#: clock"): the serve loop's wall time cut into exclusive phases, each a
#: running quantity in microseconds, which together add up to the
#: thread's wall time: ``serve_idle_us`` and ``serve_fetch_wait_us``
#: above, and ``serve_poll_us`` (the admission window blocked in
#: ``poll``), ``serve_slice_us`` (the window sleeping out a 1 ms slice
#: after something arrived), ``serve_admit_us`` (draining both wires and
#: admitting, less a prefill's dispatch), ``serve_prefill_dispatch_us``
#: (a prefill's dispatch), ``serve_assemble_us`` (a tick's assembly),
#: ``serve_dispatch_us`` (a tick's dispatch), ``serve_reply_us``
#: (answering a tick's rows, or a reset at its retire),
#: ``serve_weights_us`` (polling the WeightBus and adopting a snapshot)
#: and ``serve_loop_us`` (the loop's own code between them).
#: ``serve_drained_us`` — the thread's time with nothing launched and
#: unfetched (the device's queue certainly empty: a lower bound of its
#: idle time); ``serve_drained_wait_us`` — the part of it spent in
#: ``serve_idle_us`` or ``serve_poll_us``, waiting on the clients.
#: ``serve_wire_in_us`` / ``serve_wire_in_n`` — a request's send stamp
#: (``wire.SENT_US_KEY``) to its admission, over the requests that
#: carried one; ``serve_client_turn_us`` / ``serve_client_turn_n`` — the
#: previous reply's send stamp to this request's send, over the requests
#: that carried both (``wire.REPLY_SENT_US_KEY``).  A retry answered
#: from the cache, or dropped as a duplicate in flight, counts in none.
SERVE_EVENTS = (
    "serve_requests", "serve_replies", "serve_batches",
    "serve_batch_pad", "serve_cache_hits", "serve_dup_inflight",
    "serve_resets", "serve_closes", "serve_evictions",
    "serve_slot_denied", "serve_errors", "serve_prefills",
    "serve_wire_bytes", "serve_shm_bytes",
    "serve_prefill_us", "serve_idle_us", "serve_pool_rebuilds",
    "serve_moe_assignments", "serve_moe_assignments_held",
    "serve_moe_experts_hit",
    "serve_ticks_overlapped", "serve_fetch_wait_us",
    "serve_prefills_overlapped",
    "serve_ctx_positions", "serve_rows_stepped", "serve_window_positions",
    "serve_state_resets", "serve_state_bytes",
    "serve_poll_us", "serve_slice_us", "serve_admit_us",
    "serve_prefill_dispatch_us", "serve_assemble_us", "serve_dispatch_us",
    "serve_reply_us", "serve_weights_us", "serve_loop_us",
    "serve_drained_us", "serve_drained_wait_us",
    "serve_wire_in_us", "serve_wire_in_n",
    "serve_client_turn_us", "serve_client_turn_n",
)

#: Canonical serve-gateway event names (see docs/serving.md
#: "ServeGateway").  Same contract as ``FLEET_EVENTS``: any
#: ``EventCounters`` accepts them and the TelemetryHub zero-fills every
#: name in every scrape.  The gateway's per-request counters carry the
#: ``gateway_`` prefix INSTEAD of reusing the ``serve_*`` vocabulary,
#: so a hub that registers the gateway AND its replicas (the documented
#: setup) folds distinct names — one client request must not read as
#: two ``serve_requests`` in the merged scrape.
#: ``gateway_requests`` — client requests admitted at the front (any
#: command);
#: ``gateway_replies`` — replies sent to clients (forwarded replica
#: replies AND gateway-local answers, errors included);
#: ``gateway_errors`` — requests the gateway errored or dropped
#: (unknown command, no healthy replica, undecodable frames);
#: ``gateway_cache_hits`` — retries answered from the gateway's
#: mutating-reply cache (exactly-once: the fleet never sees them);
#: ``gateway_dup_inflight`` — retries of a still-in-flight forward
#: re-sent to the SAME replica (whose dedupe keeps them exactly-once);
#: ``gateway_routed`` — requests forwarded to a replica (any command);
#: ``gateway_affinity_hits`` — step/close requests routed by a live
#: episode lease to the replica that owns its KV-cache row;
#: ``gateway_rebalances`` — fresh-episode routes where the load ranking
#: (queue depth + SERVE_STAGES p99 from the cached telemetry scrape)
#: overrode plain rotation;
#: ``gateway_replica_quarantined`` — a replica stopped answering (scrape
#: timeout, or the watchdog reported its death) and was isolated: its
#: leases are invalidated and fresh episodes avoid it;
#: ``gateway_replica_respawns`` — a quarantined replica answered a
#: scrape again (watchdog respawn landed) and rejoined the route set;
#: ``gateway_stale_lease_redirects`` — step/close requests whose lease
#: pointed at a dead/forgotten episode, answered with the actionable
#: stale-lease error (the client ``reset()``s onto a healthy replica);
#: ``gateway_drains`` — replicas put into drain (no fresh episodes,
#: live ones finish).
#: The sharded data plane (front/worker/control split, docs/serving.md)
#: adds:
#: ``gateway_worker_deaths`` — gateway worker processes the watchdog
#: reported dead (SIGKILL, crash);
#: ``gateway_worker_respawns`` — worker processes relaunched by the
#: watchdog and re-admitted by the control plane;
#: ``gateway_lease_rehash`` — lease-owned requests the front answered
#: for a dead worker with the actionable stale-lease error (the
#: client's ``reset()`` re-hashes onto a live worker);
#: ``gateway_snapshot_applies`` — versioned control-state snapshots a
#: worker adopted (replica health/drain/canary verdicts published by
#: the control plane; stale versions are ignored, not counted);
#: ``gateway_snapshot_publishes`` — snapshot versions the control plane
#: published to its workers (one count per version, not per worker);
#: ``gateway_front_relays`` — client requests the front relayed to a
#: worker on its behalf (rendezvous, proxied clients); direct-dialed
#: steady-state traffic never lands here.
GATEWAY_EVENTS = (
    "gateway_requests", "gateway_replies", "gateway_errors",
    "gateway_cache_hits", "gateway_dup_inflight",
    "gateway_routed", "gateway_affinity_hits", "gateway_rebalances",
    "gateway_replica_quarantined", "gateway_replica_respawns",
    "gateway_stale_lease_redirects", "gateway_drains",
    "gateway_worker_deaths", "gateway_worker_respawns",
    "gateway_lease_rehash", "gateway_snapshot_applies",
    "gateway_snapshot_publishes", "gateway_front_relays",
)

#: Canonical weight-bus event names (see docs/weight_bus.md).  Same
#: contract as ``FLEET_EVENTS``: any ``EventCounters`` accepts them and
#: the TelemetryHub zero-fills every name in every scrape.
#: ``weight_published`` — versioned snapshots streamed by a publisher
#: (rollback republishes included);
#: ``weight_publish_bytes`` — snapshot payload bytes streamed (summed
#: over subscribers; deltas ship only changed leaves);
#: ``weight_syncs`` — full-snapshot catch-ups served to late joiners /
#: re-syncing subscribers;
#: ``weight_adopted`` — complete, digest-verified snapshots hot-swapped
#: into a serving model between ticks;
#: ``weight_torn_discarded`` — partial snapshot streams discarded
#: (publisher died mid-stream, a superseding begin, a sequence gap, an
#: undecodable frame) — the server keeps serving the last good version;
#: ``weight_digest_rejected`` — completed streams rejected on checksum
#: mismatch (whole-stream or per-leaf), never half-applied;
#: ``weight_apply_failed`` — verified snapshots the model refused
#: (structure/shape mismatch); the last good version keeps serving;
#: ``weight_canary_starts`` — canary windows opened on a gateway;
#: ``weight_canary_routes`` — fresh episodes deliberately routed to the
#: canary version's replicas;
#: ``weight_canary_promotions`` — canary versions promoted to stable;
#: ``weight_canary_rollbacks`` — canary versions rolled back (fresh
#: traffic stops routing to them);
#: ``weight_rollback_publishes`` — rollback republishes: a prior
#: version's weights re-published under a fresh higher version id.
WEIGHT_EVENTS = (
    "weight_published", "weight_publish_bytes", "weight_syncs",
    "weight_adopted", "weight_torn_discarded", "weight_digest_rejected",
    "weight_apply_failed",
    "weight_canary_starts", "weight_canary_routes",
    "weight_canary_promotions", "weight_canary_rollbacks",
    "weight_rollback_publishes",
)

#: Canonical scenario-plane event names (see docs/scenarios.md).  Same
#: contract as ``FLEET_EVENTS``: any ``EventCounters`` accepts them and
#: the TelemetryHub zero-fills every name in every scrape.
#: ``scenario_samples`` — concrete parameter dicts sampled from a
#: :class:`~blendjax.scenario.ScenarioSpec` (seeded draws over its
#: randomization ranges);
#: ``scenario_pushes`` — parameter pushes sent into running producers
#: over the duplex control plane (the densityopt pattern, live
#: domain randomization);
#: ``scenario_push_failures`` — pushes that could not be delivered
#: (send timeout into a dead/stalled producer; the bounded-timeout
#: send is what keeps a SIGKILLed producer from wedging the
#: randomizer — the failed push is counted, never blocked on);
#: ``scenario_applies`` — pushed scenarios CONFIRMED applied: the
#: first transition stamped with the newly-pushed scenario id
#: observed back on the data plane (push is fire-and-forget; this is
#: the round-trip acknowledgement);
#: ``scenario_reassignments`` — scenarios re-pushed to a respawned /
#: re-admitted env over a fresh control channel (a quarantined env's
#: scenario must survive its producer's death);
#: ``scenario_curriculum_updates`` — curriculum reweight passes
#: executed (interval-gated);
#: ``scenario_mix_changes`` — reweight passes that actually CHANGED
#: the fleet's scenario mix (what a curriculum-shift test pins);
#: ``scenario_rows_stamped`` — replay rows appended carrying a
#: scenario id (the ``healthy``-key in-band pattern extended to
#: ``scenario``);
#: ``scenario_strata_draws`` — sampled batches drawn under a
#: NON-uniform scenario mix (per-scenario strata shaping the draw; a
#: uniform mix never counts here — it is byte-identical to the
#: scenario-less draw stream by contract);
#: ``scenario_serve_requests`` — scenario-labelled serve replies
#: recorded by a :class:`~blendjax.serve.gateway.ServeGateway` into
#: its per-scenario request/latency records.
SCENARIO_EVENTS = (
    "scenario_samples", "scenario_pushes", "scenario_push_failures",
    "scenario_applies", "scenario_reassignments",
    "scenario_curriculum_updates", "scenario_mix_changes",
    "scenario_rows_stamped", "scenario_strata_draws",
    "scenario_serve_requests",
)

#: Canonical learner-failover (HA) event names (see
#: docs/fault_tolerance.md "Learner failover").  Same contract as
#: ``FLEET_EVENTS``: any ``EventCounters`` accepts them and the
#: TelemetryHub zero-fills every name in every scrape.
#: ``ha_ckpt_saves`` — coordinated train-state checkpoints committed
#: (manifest written: TrainState + counters + curriculum + replay cut
#: + bus version form one consistent cut);
#: ``ha_ckpt_bytes`` — bytes serialized into committed checkpoints;
#: ``ha_ckpt_skipped`` — due checkpoints skipped because the previous
#: background serialization was still in flight (the bounded-stall
#: contract: the update loop never queues up checkpoint work);
#: ``ha_ckpt_failures`` — checkpoint attempts that failed (counted and
#: logged; never raised into the update loop);
#: ``ha_ckpt_evicted`` — old checkpoints removed by retention;
#: ``ha_restores`` — successful restores from a manifest;
#: ``ha_restore_fallbacks`` — restores that fell back to an OLDER
#: step/manifest because the latest failed to load (torn/truncated
#: file after a host crash) — counted and warned, never silent;
#: ``ha_learner_deaths`` — supervised learner-process deaths;
#: ``ha_learner_respawns`` — successful supervised learner respawns;
#: ``ha_resume_publishes`` — checkpointed params republished on the
#: weight bus at resume under a fresh higher version id (the serve
#: tier rolls forward across the respawn).
HA_EVENTS = (
    "ha_ckpt_saves", "ha_ckpt_bytes", "ha_ckpt_skipped",
    "ha_ckpt_failures", "ha_ckpt_evicted",
    "ha_restores", "ha_restore_fallbacks",
    "ha_learner_deaths", "ha_learner_respawns", "ha_resume_publishes",
)

#: Canonical autoscale control-plane event names (see
#: docs/autoscaling.md).  Same contract as ``FLEET_EVENTS``: any
#: ``EventCounters`` accepts them and the TelemetryHub zero-fills every
#: name in every scrape.
#: ``autoscale_ticks`` — controller decision passes executed;
#: ``autoscale_holds`` — decision passes that wanted to act but were
#: suppressed by a per-direction cooldown, the hysteresis band, the
#: min/max fleet bounds, or a transition already in flight (the
#: single-transition-at-a-time rule);
#: ``autoscale_scale_ups`` — serve scale-ups COMMITTED: a new replica
#: spawned, admitted at the gateway, and survived its post-action
#: healthy window;
#: ``autoscale_scale_downs`` — serve scale-downs committed: a replica
#: drained to zero leases, the shrunk fleet survived the healthy
#: window, and the process was retired and its ``/dev/shm`` swept;
#: ``autoscale_rollbacks`` — transitions ROLLED BACK by the verifier
#: (error-rate or p99 regression in the healthy window): the draining
#: replica was re-admitted, or the freshly-added replica was drained
#: back out — capacity returns to the pre-decision state;
#: ``autoscale_drain_timeouts`` — scale-downs abandoned because live
#: leases did not finish or idle out inside the bounded drain grace
#: window (the victim is undrained; counted under rollbacks too);
#: ``autoscale_replica_spawns`` — replica processes spawned by the
#: controller (before verification — a rolled-back spawn still counts);
#: ``autoscale_replicas_retired`` — replica processes retired (drained,
#: verified, terminated, shm swept);
#: ``autoscale_adoptions`` — in-flight transitions a (re)started
#: controller ADOPTED from observed fleet state instead of acting anew
#: (a replica already draining, an un-verified extra replica): the
#: idempotence witness for the SIGKILL-the-controller drill;
#: ``autoscale_reshard_handoffs`` — replay shard handoffs COMMITTED
#: (source checkpoint restored by the new shard, ``written_since``
#: reconciled, client slot-range map cut over);
#: ``autoscale_reshard_aborts`` — handoffs aborted whole (new shard
#: died / checkpoint or seq mismatch / reconcile overflow): the client
#: map is untouched and the source shard keeps serving its range;
#: ``autoscale_reshard_rows_copied`` — rows copied source→new shard
#: during handoffs (checkpoint restore is not counted; this is the
#: ``written_since`` reconcile traffic).
AUTOSCALE_EVENTS = (
    "autoscale_ticks", "autoscale_holds",
    "autoscale_scale_ups", "autoscale_scale_downs",
    "autoscale_rollbacks", "autoscale_drain_timeouts",
    "autoscale_replica_spawns", "autoscale_replicas_retired",
    "autoscale_adoptions",
    "autoscale_reshard_handoffs", "autoscale_reshard_aborts",
    "autoscale_reshard_rows_copied",
)

#: Canonical autoscale stage names (see docs/autoscaling.md):
#: ``autoscale_tick`` (one decision pass: scrape-derived load fold +
#: rule evaluation), ``autoscale_resize`` (decision → fleet healthy at
#: the new size, the whole transition including drain/verify — the
#: ``resize_settle_s`` bench metric is this stage's observation),
#: ``autoscale_drain`` (drain issued → victim's live leases at zero),
#: ``autoscale_handoff`` (shard handoff: source checkpoint → client
#: map cutover).
AUTOSCALE_STAGES = (
    "autoscale_tick", "autoscale_resize", "autoscale_drain",
    "autoscale_handoff",
)

#: Canonical learner-failover stage names (see docs/fault_tolerance.md
#: "Learner failover"): ``ha_snapshot`` (the synchronous barrier on the
#: update loop — host-gather of the TrainState plus the coordinated
#: replay cut; the only stall the checkpointer charges training),
#: ``ha_serialize`` (background thread: npz writes + fsync + manifest
#: commit + retention), ``ha_restore`` (manifest load + train-state /
#: replay / curriculum restore at learner startup).
HA_STAGES = (
    "ha_snapshot", "ha_serialize", "ha_restore",
)

#: Canonical scenario-plane stage names (see docs/scenarios.md):
#: ``scenario_sample`` (one seeded spec sample — param-dict build),
#: ``scenario_push`` (one duplex send of a sampled param push into a
#: producer, bounded by the push timeout), ``scenario_reweight`` (one
#: curriculum reweight pass: strata scrape fold + mix decision).
SCENARIO_STAGES = (
    "scenario_sample", "scenario_push", "scenario_reweight",
)

#: Canonical weight-bus stage names (see docs/weight_bus.md):
#: ``weight_publish`` (snapshot + digest + chunk + stream, publisher
#: side), ``weight_assemble`` (chunk ingest + digest verification per
#: completed snapshot, subscriber side — compute only, not wall wait),
#: ``weight_swap`` (the between-ticks hot-swap: pytree rebuild +
#: ``model.apply_weights``).
WEIGHT_STAGES = (
    "weight_publish", "weight_assemble", "weight_swap",
)

#: Canonical serve-gateway stage names (see docs/serving.md), the
#: :class:`StageTimer` vocabulary :class:`~blendjax.serve.gateway.
#: ServeGateway` reports under: ``gw_route`` (request decode + routing
#: decision), ``gw_forward`` (re-encode + send to the chosen replica),
#: ``gw_reply`` (replica reply receive + forward back to the client).
#: Prefixed ``gw_`` so the hub's union stage namespace cannot alias the
#: server-side ``reply`` stage.
GATEWAY_STAGES = (
    "gw_route", "gw_forward", "gw_reply",
)

#: Canonical policy-serving stage names (see docs/serving.md), the
#: :class:`StageTimer` vocabulary the serve benchmark and
#: ``PolicyServer`` report under: ``queue_wait`` (request admission to
#: batch dequeue — the continuous-batching latency price), and the tick
#: processing: ``batch_assemble`` (drain + pad-to-bucket + host-side
#: array build), ``compute`` (the host's time inside the model call
#: for one tick: its dispatch plus the fetch of its reply, not what ran
#: between the two), ``reply`` (per-client scatter of the batch's
#: replies).
SERVE_STAGES = (
    "queue_wait", "batch_assemble", "compute", "reply",
)

#: Canonical replay-path stage names (see docs/replay.md), the
#: :class:`StageTimer` vocabulary the replay benchmark and
#: ``ReplayBuffer`` report under: ``replay_append`` (row scatter into the
#: ring columns), ``sample_wait`` (blocked on an underfilled buffer),
#: ``sample_gather`` (index draw + columnar gather into the batch),
#: ``priority_update`` (sum-tree refresh after a learner step).
#: The sharded service adds ``shard_append`` (one append RPC to a shard,
#: wire + remote write + spill flush) and ``shard_gather`` (one gather
#: RPC: wire + remote columnar read + client-side scatter).
REPLAY_STAGES = (
    "replay_append", "sample_wait", "sample_gather", "priority_update",
    "shard_append", "shard_gather",
)

#: Canonical MPMD-pipeline event names (see docs/pipeline.md).  Same
#: contract as ``FLEET_EVENTS``: any ``EventCounters`` accepts them and
#: the TelemetryHub zero-fills every name in every scrape.  The driver
#: and each stage process count into their own sinks; the hub merge is
#: the fleet view.
#: ``pipe_updates`` — pipeline updates committed (stage side: SGD
#: applied at the update boundary; driver side: full
#: begin→feed→finish→commit rounds completed);
#: ``pipe_microbatches`` — microbatch records processed (stage side:
#: backward passes completed; driver side: microbatches fed);
#: ``pipe_feed_parks`` — feed stalls: the bounded in-flight window was
#: full, so the driver parked instead of allocating — the bubble
#: schedule acting as backpressure on the arena feed;
#: ``pipe_resends`` — in-flight activation/grad/target records re-sent
#: under the SAME correlation id after a missed ack (peer death or shm
#: demotion; the receiver's reply cache + ``(update, mb)`` dedup make
#: the resend exactly-once);
#: ``pipe_dup_records`` — duplicate records absorbed by that dedup (a
#: resent record whose original did land);
#: ``pipe_restarts`` — update attempts the driver abandoned and
#: replayed after reconciling a changed fleet (a stage died
#: mid-update);
#: ``pipe_rollbacks`` — stage-side param rollbacks to an earlier
#: committed boundary (checkpoint restore or rebuild-from-seed);
#: ``pipe_driver_rollbacks`` — rollback commands the driver issued
#: while reconciling stages to the lowest common applied update;
#: ``pipe_stage_respawns`` — stage incarnation changes the driver
#: observed at hello (the watchdog respawned a killed stage);
#: ``pipe_ckpt_restores`` — stage param restores from the per-stage
#: checkpoint cut (at process start or rollback);
#: ``pipe_wire_bytes`` — payload bytes through a stage server's wire
#: paths (both transports, both directions it counts).
PIPE_EVENTS = (
    "pipe_updates", "pipe_microbatches", "pipe_feed_parks",
    "pipe_resends", "pipe_dup_records",
    "pipe_restarts", "pipe_rollbacks", "pipe_driver_rollbacks",
    "pipe_stage_respawns", "pipe_ckpt_restores", "pipe_wire_bytes",
)

#: Canonical MPMD-pipeline stage names (see docs/pipeline.md), the
#: :class:`StageTimer` vocabulary the stage processes and the pipeline
#: driver report under: ``pipe_fwd`` (one microbatch forward through a
#: stage's owned layers), ``pipe_bwd`` (one microbatch backward — on
#: the last stage this is the fused forward+loss+backward unit),
#: ``pipe_apply`` (the SGD apply at an update commit), ``pipe_feed``
#: (driver: pushing one microbatch pair into the pipeline, parks
#: included), ``pipe_finish`` (driver: the grads-ready poll barrier
#: after the last microbatch — the visible tail of the 1F1B bubble).
PIPE_STAGES = (
    "pipe_fwd", "pipe_bwd", "pipe_apply", "pipe_feed", "pipe_finish",
)


class EventCounters:
    """Thread-safe named event counters — the numeric half of fleet
    observability (stage *times* live in :class:`StageTimer`; discrete
    *events* — retries, deaths, quarantines — live here).

    A process-wide default instance (:data:`fleet_counters`) is shared by
    the fault layer so counters aggregate across components without
    plumbing; pass a fresh instance for isolated accounting (tests,
    per-fleet supervisors).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._counts = defaultdict(int)

    def incr(self, name, n=1):
        with self._lock:
            self._counts[name] += n

    def incr_many(self, pairs):
        """``incr`` of each ``(name, n)`` in ``pairs``, under one lock."""
        with self._lock:
            for name, n in pairs:
                self._counts[name] += n

    def get(self, name):
        with self._lock:
            return self._counts.get(name, 0)

    def snapshot(self):
        """Copy of all counters as a plain dict."""
        with self._lock:
            return dict(self._counts)

    def reset(self):
        with self._lock:
            self._counts.clear()


#: Process-wide default counter registry (fault layer, TransferGate
#: backstop, stream timeouts).  Component constructors take a
#: ``counters=`` override for isolated accounting.
fleet_counters = EventCounters()


class _NullSpan:
    """What :func:`span` hands out where jax is not loaded."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args):
        pass


_NULL_SPAN = _NullSpan()


def span(name, **args):
    """A host span named ``name`` in the profiler's trace: a
    ``jax.profiler.TraceAnnotation`` (``args`` become the event's
    stats; ``set_metadata(**more)`` adds to them inside the block) when
    this process has imported jax, a no-op otherwise.  With no profiler
    session running the annotation costs one object and two C calls."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return _NULL_SPAN
    return profiler.TraceAnnotation(name, **args)


class StageTimer:
    """Accumulates wall-clock time per named stage (thread-safe: stages are
    recorded from loader workers and the prefetch thread concurrently).

    With ``histograms=True`` (the default) every :meth:`add` also lands
    in a fixed-memory log-bucketed
    :class:`~blendjax.obs.histogram.LatencyHistogram`, so
    :meth:`summary` reports p50/p90/p99/max per stage alongside the
    means — the percentile surface ``health()``, the TelemetryHub and
    the bench artifacts read.  ``histograms=False`` opts out (the knob
    the ``telemetry_overhead_x`` bench compares against).
    """

    def __init__(self, histograms=True):
        self._lock = threading.Lock()
        self._histograms = bool(histograms)
        self.reset()

    def reset(self):
        with self._lock:
            self._total = defaultdict(float)
            self._count = defaultdict(int)
            self._hist = {}
            self._start = time.perf_counter()

    @contextmanager
    def stage(self, name):
        """Time the block under ``name``, and lay it into the profiler's
        trace as a :func:`span` of the same name."""
        with span(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.add(name, time.perf_counter() - t0)

    def add(self, name, seconds, _frexp=_hist_frexp,
            _top=_HIST_TOP, _sub=_HIST_SUBBITS):
        with self._lock:
            self._total[name] += seconds
            self._count[name] += 1
            if self._histograms:
                h = self._hist.get(name)
                if h is None:
                    h = self._hist[name] = LatencyHistogram()
                # LatencyHistogram.add inlined AND thinned: this is the
                # feed/RL hot path, priced by telemetry_overhead_x
                # (floor 0.95).  The histogram's n/sum_s are NOT
                # maintained here — inside a StageTimer they duplicate
                # _count/_total exactly, so _sync_hist_locked derives
                # them at read time instead of paying two more
                # attribute RMWs per event
                us = seconds * 1e6
                if us < 1.0:
                    idx = 0
                else:
                    m, e = _frexp(us)
                    idx = ((e - 1) << _sub) + int((m + m - 1.0) *
                                                  (1 << _sub)) + 1
                    if idx > _top:
                        idx = _top
                h.counts[idx] += 1
                if seconds > h.max_s:
                    h.max_s = seconds

    def add_bulk(self, name, total_seconds, count):
        """Accumulate ``count`` pre-aggregated intervals in one locked
        update — for hot loops (e.g. the arena feed path at ~100 us per
        batch) where a per-interval :meth:`add` would itself be a
        measurable stage.  Histogram entries land at the aggregate's
        MEAN (per-interval spread is already lost) — percentiles for a
        stage fed only through here degenerate to that mean."""
        if count <= 0:
            return
        with self._lock:
            self._total[name] += total_seconds
            self._count[name] += count
            if self._histograms:
                h = self._hist.get(name)
                if h is None:
                    h = self._hist[name] = LatencyHistogram()
                h.add_many(total_seconds / count, count)

    @property
    def wall_s(self):
        return time.perf_counter() - self._start

    def total_s(self, name):
        with self._lock:
            return self._total.get(name, 0.0)

    def count(self, name):
        with self._lock:
            return self._count.get(name, 0)

    def mean_ms(self, name):
        with self._lock:
            c = self._count.get(name, 0)
            return (self._total[name] / c) * 1e3 if c else 0.0

    def duty_cycle(self, name):
        """Fraction of wall time since reset spent inside ``name``."""
        wall = self.wall_s
        with self._lock:
            return self._total.get(name, 0.0) / wall if wall > 0 else 0.0

    def _sync_hist_locked(self, name):
        """The stage's histogram with ``n``/``sum_s`` derived from
        ``_count``/``_total`` (the hot-path :meth:`add` skips those two
        RMWs — inside a StageTimer they are exact duplicates)."""
        h = self._hist.get(name)
        if h is not None:
            h.n = self._count[name]
            h.sum_s = self._total[name]
        return h

    def percentiles(self, name):
        """``{"p50_ms","p90_ms","p99_ms","max_ms"}`` for a stage (zeros
        when unrecorded or histograms are off)."""
        with self._lock:
            h = self._sync_hist_locked(name)
            if h is None:
                return {"p50_ms": 0.0, "p90_ms": 0.0, "p99_ms": 0.0,
                        "max_ms": 0.0}
            return h.percentiles()

    def summary(self):
        with self._lock:
            out = {}
            for name, total in self._total.items():
                rec = {
                    "count": self._count[name],
                    "total_s": round(total, 6),
                    "mean_ms": round((total / self._count[name]) * 1e3, 3)
                    if self._count[name]
                    else 0.0,
                }
                h = self._sync_hist_locked(name)
                if h is not None:
                    rec.update(h.percentiles())
                out[name] = rec
            return out

    def snapshot(self):
        """Mergeable per-stage state for the
        :class:`~blendjax.obs.hub.TelemetryHub`: ``{stage: {"count",
        "total_s", "hist"}}`` with the histograms COPIED (the hub merges
        destructively across components)."""
        with self._lock:
            return {
                name: {
                    "count": self._count[name],
                    "total_s": total,
                    "hist": (
                        self._sync_hist_locked(name).copy()
                        if name in self._hist else None
                    ),
                }
                for name, total in self._total.items()
            }

    def snapshot_serialized(self):
        """:meth:`snapshot` with histograms serialized sparse
        (``to_dict``) — the JSON-able ``stages`` shape a remote
        ``telemetry`` RPC ships and ``TelemetryHub`` remotes merge.
        One implementation for every wire-serving process (replay
        shards, policy servers)."""
        return {
            name: {
                "count": rec["count"],
                "total_s": rec["total_s"],
                "hist": (
                    rec["hist"].to_dict()
                    if rec["hist"] is not None else None
                ),
            }
            for name, rec in self.snapshot().items()
        }
