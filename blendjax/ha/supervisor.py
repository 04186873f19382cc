"""Supervised learner respawn: the training run's own watchdog.

:class:`LearnerProcess` is the launcher-compatible surface (duck-typed
``launch_info`` + ``respawn(idx)``) wrapping one ``python -m
blendjax.ha.learner`` child, so :class:`~blendjax.btt.watchdog.
FleetWatchdog` supervises the learner exactly like Blender producers,
replay shards and serve replicas.  :class:`LearnerSupervisor` ties the
watchdog to the HA vocabulary: a death counts ``ha_learner_deaths`` and
dumps a flight-recorder postmortem naming the dead learner with its
last ``stats()`` digest attached (the mirror the
:class:`~blendjax.ha.checkpoint.TrainCheckpointer` keeps on disk — a
SIGKILLed process cannot be asked anything); a successful respawn
counts ``ha_learner_respawns``.  The RESUME itself is the child's
startup behavior (restore the latest complete manifest, republish the
checkpointed weights under a fresh higher version id) — the supervisor
only has to bring the process back.

See docs/fault_tolerance.md "Learner failover".
"""

from __future__ import annotations

import json
import logging
import os
import subprocess
import sys
import time

from blendjax.btt.watchdog import FleetWatchdog
from blendjax.obs.flight import default_postmortem_dir, flight_recorder
from blendjax.utils.timing import HA_EVENTS, fleet_counters

logger = logging.getLogger("blendjax")


class _LearnerLaunchInfo:
    """Duck-typed ``launch_info`` so :class:`~blendjax.btt.watchdog.
    FleetWatchdog` supervises the learner process exactly like every
    other tier's children."""

    def __init__(self, processes):
        self.processes = processes
        self.addresses = {}


class LearnerProcess:
    """One supervised learner *process* (``python -m blendjax.ha.
    learner``) with a launcher-compatible surface, so
    ``FleetWatchdog(restart=True)`` respawns it after a SIGKILL with
    its original command line.  The respawned child resumes from the
    latest complete manifest under ``ckpt_dir`` on its own.

    Params mirror the child's CLI (see :mod:`blendjax.ha.learner`);
    ``extra_args`` passes anything not spelled out here."""

    def __init__(self, *, ckpt_dir, env_addresses=(), replay_shards=(),
                 shard_capacity=None, weight_bus=None, publish_every=1,
                 obs_dim=1, num_actions=2, rollout_len=8, seed=0,
                 replay_ratio=0, replay_batch=32, ckpt_every=2,
                 ckpt_seconds=None, updates=0, chunk_updates=4,
                 action_values=None, probe_batch=0, timeoutms=15000,
                 python=None, ready_timeout=90.0, extra_args=()):
        self.ckpt_dir = os.path.abspath(ckpt_dir)
        os.makedirs(self.ckpt_dir, exist_ok=True)
        self.stats_path = os.path.join(self.ckpt_dir,
                                       "learner_stats.json")
        self.python = python or sys.executable
        self.ready_timeout = ready_timeout
        self._cmd = [
            self.python, "-m", "blendjax.ha.learner",
            "--ckpt-dir", self.ckpt_dir,
            "--obs-dim", str(obs_dim),
            "--num-actions", str(num_actions),
            "--rollout-len", str(rollout_len),
            "--seed", str(seed),
            "--ckpt-every", str(ckpt_every),
            "--chunk-updates", str(chunk_updates),
            "--timeoutms", str(timeoutms),
        ]
        if env_addresses:
            self._cmd += ["--envs", ",".join(env_addresses)]
        if replay_shards:
            self._cmd += ["--replay-shards", ",".join(replay_shards)]
            self._cmd += ["--replay-ratio", str(replay_ratio),
                          "--replay-batch", str(replay_batch)]
        if shard_capacity is not None:
            self._cmd += ["--shard-capacity", str(shard_capacity)]
        if weight_bus:
            self._cmd += ["--weight-bus", weight_bus,
                          "--publish-every", str(publish_every)]
        if ckpt_seconds is not None:
            self._cmd += ["--ckpt-seconds", str(ckpt_seconds)]
        if updates:
            self._cmd += ["--updates", str(updates)]
        if action_values is not None:
            self._cmd += [
                "--action-values",
                ",".join(str(float(v)) for v in action_values),
            ]
        if probe_batch:
            self._cmd += ["--probe-batch", str(probe_batch)]
        self._cmd += list(extra_args)
        self.launch_info = None

    def _spawn(self):
        from blendjax.btt.launcher import child_env

        # the learner takes whatever platform the caller's environment
        # names (its stats file reports which); child_env also places
        # the compile cache, so a respawn skips the cold compile
        return subprocess.Popen(self._cmd, env=child_env(),
                                start_new_session=True)

    def __enter__(self):
        self.launch_info = _LearnerLaunchInfo([self._spawn()])
        try:
            self.wait_ready(self.ready_timeout)
        except BaseException:
            self.close()
            raise
        return self

    def read_stats(self):
        """The child's latest stats mirror (None when absent or torn —
        the atomic-rename write makes torn reads rare, not impossible
        against a different filesystem)."""
        try:
            with open(self.stats_path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def wait_ready(self, timeout=90.0):
        """Block until the CURRENT child wrote a stats mirror (its
        ready barrier — after the jax import, the restore, and the
        resume republish)."""
        proc = self.launch_info.processes[0]
        deadline = time.monotonic() + timeout
        while True:
            stats = self.read_stats()
            if stats is not None and stats.get("pid") == proc.pid:
                return stats
            if proc.poll() is not None:
                raise RuntimeError(
                    f"learner process exited with {proc.returncode} "
                    "before becoming ready"
                )
            if time.monotonic() >= deadline:
                raise TimeoutError(
                    f"learner process not ready within {timeout:.1f}s"
                )
            time.sleep(0.05)

    def respawn(self, idx=0):
        """Relaunch with the original command line (the watchdog's
        contract); the child restores the latest complete manifest on
        its own."""
        proc = self._spawn()
        self.launch_info.processes[idx] = proc
        return proc

    def close(self):
        info = self.launch_info
        if info is None:
            return
        for p in info.processes:
            try:
                p.terminate()
            except Exception:  # noqa: BLE001
                pass
        for p in info.processes:
            try:
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001
                try:
                    p.kill()
                except Exception:  # noqa: BLE001
                    pass
        self.launch_info = None

    def __exit__(self, *exc):
        self.close()
        return False


class LearnerSupervisor:
    """Death detection + respawn + postmortem for the learner process.

    Params
    ------
    process: LearnerProcess
        Inside its context (``launch_info`` populated).
    interval: float
        Watchdog poll period, seconds.
    restart: bool
        Respawn the dead learner (off = detect/postmortem only).
    counters: EventCounters | None
        ``HA_EVENTS`` sink; process-wide default when omitted.
    postmortem_dir: str | None
        Postmortem destination (defaults to ``$BJX_POSTMORTEM_DIR``).
    on_death / on_respawn: callable | None
        Extra user hooks, invoked after the supervisor's own handling.
    """

    def __init__(self, process, *, interval=0.5, restart=True,
                 counters=None, postmortem_dir=None, on_death=None,
                 on_respawn=None):
        self.process = process
        self.counters = counters if counters is not None else fleet_counters
        self.postmortem_dir = (
            postmortem_dir if postmortem_dir is not None
            else default_postmortem_dir()
        )
        self.last_postmortem = None
        self._user_on_death = on_death
        self._user_on_respawn = on_respawn
        self.watchdog = FleetWatchdog(
            process, interval=interval, on_death=self._on_death,
            restart=restart, on_respawn=self._on_respawn,
        )

    # -- lifecycle ----------------------------------------------------------

    def start(self):
        self.watchdog.start()
        return self

    def stop(self):
        self.watchdog.stop()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- death -> postmortem -> respawn --------------------------------------

    def _on_death(self, idx, code):
        self.counters.incr("ha_learner_deaths")
        stats = self.process.read_stats() or {}
        flight_recorder.note(
            "learner_death", target="learner", exit_code=code,
            updates=stats.get("updates"),
            last_ckpt_update=stats.get("last_ckpt_update"),
        )
        logger.warning(
            "learner process died (exit %s) at update %s (last "
            "checkpoint cut: update %s); %s", code,
            stats.get("updates"), stats.get("last_ckpt_update"),
            "respawning" if self.watchdog.restart
            else "restart disabled",
        )
        if self.postmortem_dir is not None:
            # the dead learner cannot be asked anything — attach the
            # stats mirror the checkpointer kept on disk, so the
            # postmortem names the learner AND its last known state
            self.last_postmortem = flight_recorder.dump(
                directory=self.postmortem_dir,
                reason="death-learner",
                extra={
                    "target": "learner",
                    "exit_code": code,
                    "stats": stats,
                    "ckpt_dir": self.process.ckpt_dir,
                },
            )
        if self._user_on_death is not None:
            self._user_on_death(idx, code)

    def _on_respawn(self, idx, proc):
        self.counters.incr("ha_learner_respawns")
        flight_recorder.note(
            "learner_respawn", target="learner", pid=proc.pid,
        )
        if self._user_on_respawn is not None:
            self._user_on_respawn(idx, proc)

    # -- observability -------------------------------------------------------

    def _await(self, cond, timeout):
        deadline = time.monotonic() + timeout
        while not cond():
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.05)
        return True

    def await_deaths(self, n=1, timeout=30.0):
        return self._await(
            lambda: self.counters.get("ha_learner_deaths") >= n, timeout
        )

    def await_respawns(self, n=1, timeout=30.0):
        return self._await(
            lambda: self.counters.get("ha_learner_respawns") >= n,
            timeout,
        )

    def health(self):
        """Zero-filled ``HA_EVENTS`` + watchdog liveness + the child's
        latest stats mirror — the one-snapshot contract every other
        supervisor keeps, pointed at the learner."""
        h = dict.fromkeys(HA_EVENTS, 0)
        h.update(self.counters.snapshot())
        h["alive"] = self.watchdog.alive
        h["learner_stats"] = self.process.read_stats()
        return h
