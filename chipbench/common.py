"""What both drivers share: the checks' record, the device's own readings,
the count of compilations, child processes, and the short profiler window."""

from __future__ import annotations

import os
import shutil
import subprocess
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))

_COMPILE_EVENTS = (
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


class Checks:
    """Every number compared, beside its limit.  A check passes when its
    value is at most its limit (an exact comparison has the limit 0)."""

    def __init__(self, limits):
        self.limits = dict(limits)
        self.rows = []

    def add(self, name, value, limit=None):
        if limit is None:
            if name not in self.limits:
                raise KeyError(f"no limit on file for check {name!r}")
            limit = self.limits[name]
        self.rows.append({"name": name, "value": float(value),
                          "limit": float(limit)})

    @property
    def correct(self):
        return bool(self.rows) and all(
            r["value"] <= r["limit"] for r in self.rows)  # NaN fails


class CompileCounter:
    """Counts programs compiled or fetched from the persistent cache."""

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.n += 1


def device_record():
    """The device as JAX reports it."""
    import jax

    dev = jax.devices()
    return {"platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev)}


def memory_peak_bytes():
    """Peak bytes held on the fullest chip: the allocator's peak of buffers
    in use plus its peak reservation.  On the TPU a compiled program's
    temporaries are not buffers: they are reserved (``bytes_reserved``)
    while the program is loaded, and ``peak_bytes_in_use`` alone leaves them
    out (a 2.1 GB temporary read 3.7 MB in use, 2.1 GB reserved).  The two
    peaks need not coincide, so the sum is an upper reading.  0 where the
    backend keeps no such count, as the CPU does."""
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0)
                     + stats.get("peak_bytes_reserved", 0))
    return int(max(peaks))


def child_env():
    """The environment of a jax-free child: the program's own policy for its
    children (the checkout on the path, the compile cache placed), and the
    CPU named as the platform so that it can never take the chip."""
    from blendjax.btt.launcher import child_env as program_child_env

    env = program_child_env()
    env["JAX_PLATFORMS"] = "cpu"
    return env


def stop_children(procs, grace_s=5.0):
    """Terminate, wait, and kill what is left."""
    for p in procs:
        if p.poll() is None:
            p.terminate()
    for p in procs:
        try:
            p.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()


class TraceWindow:
    """A few seconds of the steady window under ``jax.profiler``, started a
    little into it (``delay_s`` after it is armed) from a timer thread, written under the checkout and
    removed once reduced."""

    def __init__(self, enabled, seconds, delay_s=0.0):
        self.enabled = enabled
        self.after_s = delay_s + min(2.0, seconds / 4)
        self.length_s = min(3.0, seconds / 2)
        self.dir = os.path.join(HERE, ".trace")
        self._thread = None

    def arm(self):
        if self.enabled:
            shutil.rmtree(self.dir, ignore_errors=True)
            self._thread = threading.Thread(target=self._run, daemon=True)
            self._thread.start()

    def _run(self):
        import jax

        time.sleep(self.after_s)
        jax.profiler.start_trace(self.dir)
        time.sleep(self.length_s)
        jax.profiler.stop_trace()

    def reduce(self):
        """The traced window's record, or None when tracing was off."""
        if not self.enabled:
            return None
        from chipbench import trace_reduce

        self._thread.join()
        try:
            return trace_reduce.reduce_trace_dir(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
