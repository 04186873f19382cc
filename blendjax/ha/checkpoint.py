"""TrainCheckpointer: one consistent cut of the whole learner-side state.

The learner owns five kinds of state that must agree for a resume the
rest of the system cannot distinguish from no crash:

1. the **TrainState** (params + optimizer state) — serialized through
   the existing :class:`blendjax.utils.checkpoint.CheckpointManager`
   (fsync + atomic rename since ISSUE-15, so a host crash never leaves
   a complete-looking truncated file);
2. the **update counter / seed / last published weight-bus version** —
   small scalars riding inline in the manifest
   (:meth:`blendjax.models.actor_learner.ActorLearner.checkpoint_state`);
3. the **curriculum** (:meth:`blendjax.scenario.CurriculumScheduler.
   state_dict`) and the per-fleet **scenario assignments**;
4. the **replay draw authority** — :meth:`ShardedReplay.save` already
   snapshots the client AND every live shard under one lock; it is
   called inside the same barrier as the TrainState host-gather, so the
   checkpoint's replay cursor and the learner step form one cut;
5. the **manifest** — a JSON file written (fsynced) LAST, naming the
   component files of the cut.  A checkpoint exists iff its manifest
   does; a crash mid-checkpoint leaves the previous manifest intact.

Checkpoints are taken **asynchronously off the update loop**: the
synchronous barrier (measured as ``ha_snapshot``) host-gathers the
TrainState the same way ``_publish_params`` does and takes the replay
cut; the npz serialization, manifest commit and retention run in a
background thread (``ha_serialize``).  A checkpoint that comes due
while the previous serialization is still in flight is SKIPPED and
counted (``ha_ckpt_skipped``) — the update loop never queues up
checkpoint work, which is the bounded-stall contract the
``ckpt_overhead_x`` benchmark prices at ~1.0.

See docs/fault_tolerance.md "Learner failover".
"""

from __future__ import annotations

import glob
import json
import logging
import os
import threading
import time

import numpy as np

from blendjax.obs.flight import flight_recorder
from blendjax.utils.checkpoint import CheckpointManager, _replace_durable
from blendjax.utils.timing import StageTimer, fleet_counters

logger = logging.getLogger("blendjax")

#: Manifest format tag — the commit record of one consistent cut.
MANIFEST_FORMAT = "blendjax.ha.manifest/1"


def _write_json_durable(path, doc):
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
    _replace_durable(tmp, path)


def _manifest_paths(directory):
    return sorted(glob.glob(os.path.join(directory, "manifest_*.json")))


def _verify_npz(path):
    """Integrity probe of a component npz: the zip central directory
    lives at the END of the file, so a torn write usually fails to
    open — and every member is read through so a truncated member
    behind an intact directory is caught HERE, at manifest selection
    (where falling back is cheap), not inside the strict restore."""
    with np.load(path) as data:
        if not data.files:
            raise ValueError(f"{path}: empty checkpoint archive")
        for key in data.files:
            data[key]


def latest_manifest(directory, counters=None):
    """The newest COMPLETE manifest under ``directory`` (or None).

    Complete = the manifest parses, carries the format tag, and every
    component file it names exists and passes the integrity probe.  A
    damaged newer manifest (host crash mid-commit, torn component) is
    counted (``ha_restore_fallbacks``) and warned, and the previous one
    is offered instead — never silent, never a half-cut."""
    for path in reversed(_manifest_paths(directory)):
        try:
            with open(path) as f:
                man = json.load(f)
            if man.get("format") != MANIFEST_FORMAT:
                raise ValueError(f"format {man.get('format')!r}")
            for key in ("train", "replay"):
                rel = man.get(key)
                if rel is None:
                    continue
                _verify_npz(os.path.join(directory, rel))
        except Exception as exc:  # noqa: BLE001 - fall back, loudly
            if counters is not None:
                counters.incr("ha_restore_fallbacks")
            logger.warning(
                "HA manifest %s is damaged (%s: %s); falling back to "
                "the previous one", path, type(exc).__name__, exc,
            )
            continue
        man["_path"] = path
        man["_directory"] = os.path.abspath(directory)
        return man
    return None


def restore_replay(manifest, shards=None, *, counters=None, timer=None,
                   fault_policy=None, timeoutms=5000, reconcile=True,
                   context=None):
    """Rebuild the replay buffer a manifest's cut describes.

    A ``sharded`` cut needs the shard endpoints (the same deployment,
    still running — the learner died, its storage tier did not) and
    restores with ``reconcile=True`` by default: shards legitimately
    sit AHEAD of the cut by whatever the dead learner appended after
    it, and exactly those slots leave the draw domain until the
    resumed actors rewrite them (docs/fault_tolerance.md).  A ``local``
    cut restores the in-process :class:`~blendjax.replay.ReplayBuffer`
    wholesale."""
    rel = manifest.get("replay")
    if rel is None:
        return None
    path = os.path.join(manifest["_directory"], rel)
    if manifest.get("replay_kind") == "sharded":
        if not shards:
            raise ValueError(
                "manifest describes a sharded replay cut; pass the "
                "shard endpoints to restore it"
            )
        from blendjax.replay.shard_client import ShardedReplay

        return ShardedReplay.restore(
            path, shards, counters=counters, timer=timer,
            fault_policy=fault_policy, timeoutms=timeoutms,
            context=context, reconcile=reconcile,
        )
    from blendjax.replay.buffer import ReplayBuffer

    return ReplayBuffer.restore(path, counters=counters, timer=timer)


class TrainCheckpointer:
    """Coordinated, atomic, versioned learner checkpoints (module doc).

    Params
    ------
    directory: str
        Checkpoint root.  Layout: ``train/step_<N>.npz`` (TrainState,
        via :class:`CheckpointManager`), ``replay_<N>.npz`` (the replay
        cut, when a buffer is attached), ``manifest_<N>.json`` (the
        commit record), ``learner_stats.json`` (the live stats mirror
        the supervisor's postmortem and the recovery benchmark read).
    every_updates: int
        Checkpoint cadence in completed learner updates.
    every_seconds: float | None
        Additional wall-clock cadence (whichever fires first).
    max_to_keep: int
        Retention depth, in complete cuts.
    stall_budget_s: float
        Budget for the synchronous barrier (host-gather + replay cut);
        exceeding it warns (debounced) — the knob is observability, the
        enforcement is the measured ``ha_snapshot`` stage and the
        ``ckpt_overhead_x`` benchmark floor.
    stats_path: str | None | "auto"
        Where :meth:`maybe_checkpoint` mirrors ``learner.stats()`` (an
        atomic small JSON, throttled): ``"auto"`` puts it in
        ``directory``; None disables.
    counters / timer:
        ``HA_EVENTS`` sink / ``HA_STAGES`` timer (process-wide
        defaults when omitted).
    """

    def __init__(self, directory, *, every_updates=50, every_seconds=None,
                 max_to_keep=3, stall_budget_s=1.0, stats_path="auto",
                 counters=None, timer=None):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self.every_updates = max(1, int(every_updates))
        self.every_seconds = (
            None if every_seconds is None else float(every_seconds)
        )
        self.max_to_keep = max(1, int(max_to_keep))
        self.stall_budget_s = float(stall_budget_s)
        self.counters = counters if counters is not None else fleet_counters
        self.timer = timer if timer is not None else StageTimer()
        self.train_mgr = CheckpointManager(
            os.path.join(self.directory, "train"),
            max_to_keep=self.max_to_keep, counters=self.counters,
        )
        self.stats_path = (
            os.path.join(self.directory, "learner_stats.json")
            if stats_path == "auto" else stats_path
        )
        #: extra fields merged into every stats mirror (the learner
        #: child sets pid/resumed_from/probe info here once)
        self.stats_extra = {}
        self._lock = threading.Lock()
        self._inflight = None
        self._last_ckpt_update = 0
        self._last_ckpt_time = time.monotonic()
        self._last_stats_write = 0.0
        self._next_stall_warn = 0.0
        self._saves = 0
        self._skipped = 0
        self._failures = 0

    # -- cadence --------------------------------------------------------------

    def _due(self, updates):
        if updates - self._last_ckpt_update >= self.every_updates:
            return True
        return (
            self.every_seconds is not None
            and time.monotonic() - self._last_ckpt_time
            >= self.every_seconds
            and updates > self._last_ckpt_update
        )

    def maybe_checkpoint(self, learner):
        """The per-update hook (called by the learner thread once per
        completed update): mirrors the stats file (throttled) and takes
        a checkpoint when one is due and no serialization is already in
        flight.  Never raises into the update loop.  Returns the cut's
        update number when a checkpoint started, else None."""
        self._write_stats(learner)
        if not self._due(learner._updates_done):
            return None
        with self._lock:
            if self._inflight is not None and self._inflight.is_alive():
                self._skipped += 1
                self.counters.incr("ha_ckpt_skipped")
                return None
        return self._checkpoint(learner, block=False)

    def checkpoint(self, learner, block=True):
        """Force one coordinated checkpoint now.  ``block=True`` waits
        for the manifest commit (tests, clean shutdown); False matches
        :meth:`maybe_checkpoint`'s async behavior.  Returns the cut's
        update number, or None on failure (counted, logged)."""
        prev = self._inflight
        if prev is not None:
            prev.join()
        return self._checkpoint(learner, block=block)

    # -- the cut --------------------------------------------------------------

    def _checkpoint(self, learner, block):
        import jax

        t0 = time.perf_counter()
        try:
            # the synchronous barrier: host-gather the TrainState (the
            # _publish_params pattern — params AND optimizer state) and
            # take the replay cut under the buffer's own lock, so the
            # replay cursor and the learner step agree on one cut
            aux = learner.checkpoint_state()
            update = int(aux["updates"])
            host_state = jax.device_get(learner.state)
            replay_rel = replay_kind = None
            replay = learner.replay
            if replay is not None and hasattr(replay, "save"):
                replay_rel = f"replay_{update:08d}.npz"
                replay.save(os.path.join(self.directory, replay_rel))
                replay_kind = (
                    "sharded" if hasattr(replay, "num_shards")
                    else "local"
                )
        except Exception:  # noqa: BLE001 - training outlives checkpoints
            self._failures += 1
            self.counters.incr("ha_ckpt_failures")
            # advance the cadence cursors on FAILURE too (the serialize
            # path already does): the barrier is expensive — a host
            # gather plus a full-column checkpoint on every live shard
            # — and a persistent failure (ENOSPC is the canonical one)
            # must cost one attempt per cadence, not one per update
            self._last_ckpt_update = learner._updates_done
            self._last_ckpt_time = time.monotonic()
            logger.exception(
                "HA checkpoint barrier failed (training continues; the "
                "previous manifest keeps covering recovery; next "
                "attempt at the normal cadence)"
            )
            return None
        finally:
            dt = time.perf_counter() - t0
            self.timer.add("ha_snapshot", dt)
        if dt > self.stall_budget_s:
            now = time.monotonic()
            if now >= self._next_stall_warn:
                self._next_stall_warn = now + 10.0
                logger.warning(
                    "HA checkpoint barrier took %.3fs (> stall budget "
                    "%.3fs) at update %d — the replay cut or the host "
                    "gather is outgrowing the budget; raise "
                    "every_updates or the budget", dt,
                    self.stall_budget_s, update,
                )
        self._last_ckpt_update = update
        self._last_ckpt_time = time.monotonic()
        if block:
            self._serialize(update, host_state, aux, replay_rel,
                            replay_kind)
            return update
        t = threading.Thread(
            target=self._serialize,
            args=(update, host_state, aux, replay_rel, replay_kind),
            daemon=True, name=f"bjx-ha-ckpt-{update}",
        )
        with self._lock:
            self._inflight = t
        t.start()
        return update

    def _serialize(self, update, host_state, aux, replay_rel,
                   replay_kind):
        """The background half: TrainState npz (fsync + atomic rename),
        manifest commit, retention.  Failures are counted, never
        raised — the previous manifest stays the recovery point."""
        t0 = time.perf_counter()
        try:
            train_path = self.train_mgr.save(update, host_state)
            train_rel = os.path.relpath(train_path, self.directory)
            nbytes = os.path.getsize(train_path)
            if replay_rel is not None:
                nbytes += os.path.getsize(
                    os.path.join(self.directory, replay_rel)
                )
            manifest = {
                "format": MANIFEST_FORMAT,
                "update": update,
                "ts": time.time(),
                "train": train_rel,
                "replay": replay_rel,
                "replay_kind": replay_kind,
                "aux": aux,
            }
            _write_json_durable(
                os.path.join(self.directory,
                             f"manifest_{update:08d}.json"),
                manifest,
            )
            self._retain()
            self._saves += 1
            self.counters.incr("ha_ckpt_saves")
            self.counters.incr("ha_ckpt_bytes", int(nbytes))
        except Exception:  # noqa: BLE001 - see docstring
            self._failures += 1
            self.counters.incr("ha_ckpt_failures")
            logger.exception(
                "HA checkpoint serialization failed at update %d "
                "(training continues; the previous manifest keeps "
                "covering recovery)", update,
            )
        finally:
            self.timer.add("ha_serialize", time.perf_counter() - t0)

    def _retain(self):
        paths = _manifest_paths(self.directory)
        for path in paths[:max(0, len(paths) - self.max_to_keep)]:
            try:
                with open(path) as f:
                    man = json.load(f)
            except Exception:  # noqa: BLE001 - damaged manifest
                man = {}
            for key in ("replay",):
                rel = man.get(key)
                if rel:
                    try:
                        os.unlink(os.path.join(self.directory, rel))
                    except OSError:
                        pass
            try:
                os.unlink(path)
            except OSError:
                continue
            self.counters.incr("ha_ckpt_evicted")
        # train steps retire through the CheckpointManager's own
        # retention (same depth, pruned at each save)

    # -- restore --------------------------------------------------------------

    def latest_manifest(self):
        return latest_manifest(self.directory, counters=self.counters)

    def restore(self, learner, manifest=None, *, republish=True):
        """Resume ``learner`` from a manifest (default: the latest
        complete one; raises FileNotFoundError when none exists).

        Applies the TrainState (strictly the manifest's step — the cut
        is all-or-nothing; damaged cuts were already skipped by
        :func:`latest_manifest`), the update counter / curriculum /
        scenario assignments via :meth:`ActorLearner.
        load_checkpoint_state`, and — when the learner carries a weight
        bus and ``republish`` — publishes the restored params under a
        fresh HIGHER version id (``ha_resume_publishes``): the serve
        tier rolls forward across the respawn, subscribers heal through
        their periodic re-sync, and clients observe a monotonic version
        stream with zero errors.  Returns the manifest."""
        import jax

        if manifest is None:
            manifest = self.latest_manifest()
            if manifest is None:
                raise FileNotFoundError(
                    f"no complete HA manifest under {self.directory}"
                )
        t0 = time.perf_counter()
        state = self.train_mgr.restore(
            learner.state, step=int(manifest["update"])
        )
        learner.load_checkpoint_state(state, manifest.get("aux") or {})
        self._last_ckpt_update = int(manifest["update"])
        self._last_ckpt_time = time.monotonic()
        self.counters.incr("ha_restores")
        self.timer.add("ha_restore", time.perf_counter() - t0)
        flight_recorder.note(
            "learner_restored", target="learner",
            update=int(manifest["update"]),
            manifest=manifest.get("_path"),
        )
        if republish and learner.weight_bus is not None:
            v = learner.weight_bus.publish(
                jax.device_get(learner.state.params),
                step=learner._updates_done,
            )
            learner.last_published_version = v
            self.counters.incr("ha_resume_publishes")
            logger.info(
                "resume republish: checkpointed params (update %d) "
                "published as weight version %s — the serve tier rolls "
                "forward", learner._updates_done, v,
            )
        return manifest

    # -- observability --------------------------------------------------------

    def join(self, timeout=None):
        """Wait for an in-flight background serialization (tests /
        clean shutdown)."""
        t = self._inflight
        if t is not None:
            t.join(timeout)

    def _write_stats(self, learner, force=False):
        if self.stats_path is None:
            return
        now = time.monotonic()
        if not force and now - self._last_stats_write < 0.2:
            return
        self._last_stats_write = now
        try:
            doc = {
                "ts": time.time(),
                "pid": os.getpid(),
                "updates": learner._updates_done,
                "last_published_version": learner.last_published_version,
                "last_ckpt_update": self._last_ckpt_update,
            }
            try:
                doc["stats"] = learner.stats()
            except Exception:  # noqa: BLE001 - mirror must not cascade
                pass
            doc.update(self.stats_extra)
            tmp = f"{self.stats_path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(doc, f, default=repr)
            os.replace(tmp, self.stats_path)
        except Exception:  # noqa: BLE001 - mirror must not cascade
            logger.exception("HA stats mirror write failed")

    def stats(self):
        with self._lock:
            inflight = (
                self._inflight is not None and self._inflight.is_alive()
            )
        return {
            "directory": self.directory,
            "every_updates": self.every_updates,
            "every_seconds": self.every_seconds,
            "max_to_keep": self.max_to_keep,
            "saves": self._saves,
            "skipped": self._skipped,
            "failures": self._failures,
            "last_ckpt_update": self._last_ckpt_update,
            "manifests": len(_manifest_paths(self.directory)),
            "serialize_inflight": inflight,
        }
