"""Test configuration.

Forces JAX onto an 8-device virtual CPU mesh *before* jax is imported
anywhere, so multi-chip sharding paths (dp/tp meshes, prefetch shardings)
are exercised without TPU hardware.  Real-Blender tests hide behind the
``blender`` marker.
"""

import os
import sys

# Child processes (fake Blender fleet, producer subprocesses) resolve
# `python3` via their shebang/PATH; make sure they find the interpreter
# running pytest (which has the deps) rather than a bare system python.
import shutil

_bindir = os.path.dirname(os.path.abspath(sys.executable))
_resolved = shutil.which("python3")
if _resolved is None or os.path.dirname(os.path.abspath(_resolved)) != _bindir:
    os.environ["PATH"] = _bindir + os.pathsep + os.environ.get("PATH", "")

# Force, don't setdefault: unit tests run on the 8-device virtual CPU
# mesh whatever the caller's shell says, and every child a test spawns
# (fake Blender fleet, producers, serve/learner/stage processes) inherits
# this environment — launchers no longer pick a platform themselves.
# The one way onto a real chip is ``python chip_smoke.py``.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402,F401  (after env setup, before any test imports it)

sys.path.insert(0, os.path.dirname(__file__))  # tests/helpers importable


import atexit  # noqa: E402
import glob as _glob  # noqa: E402


@atexit.register
def _cleanup_test_shm_rings():
    """Remove shm rings leaked by aborted/short-read tests (rings are only
    auto-unlinked when a reader drains them to EOF), and ShmRPC objects
    whose base embeds this pid (abandoned in-process servers — crash
    stand-ins that never ran close())."""
    for p in _glob.glob(f"/dev/shm/bjx-test-*-{os.getpid()}"):
        try:
            os.unlink(p)
        except OSError:
            pass
    for p in _glob.glob(f"/dev/shm/bjxrpc-*-{os.getpid():x}-*"):
        try:
            os.unlink(p)
        except OSError:
            pass
