"""Where this process's jax runs.

Every process that holds jax reports these three fields on the surface it
already has (the policy server's ``hello``/``telemetry`` replies, the
learner's stats file, each ``chip_smoke.py`` leg), so a caller across a
process boundary can tell a chip run from a CPU run — launchers no
longer pick the platform, the caller's environment does.
"""

from __future__ import annotations


def device_info():
    """``{"platform", "device_kind", "device_count"}`` as jax reports
    them.  Initializes the backend (and so takes the chip) if nothing
    in the process has yet."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }
