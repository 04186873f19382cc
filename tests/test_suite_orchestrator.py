"""Benchmark-orchestrator regression tests: the jax-free parent runs its
host phase, then ONE device child on the caller's platform, and exits with
that child's exit code — no second child, no stand-in phases.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUITE = os.path.join(REPO, "benchmarks", "suite.py")


def _run_suite(extra_env, args, timeout=240):
    env = os.environ.copy()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (REPO, env.get("PYTHONPATH", "")) if p
    )
    env.update(extra_env)
    out = subprocess.run(
        [
            sys.executable, SUITE,
            "--instances", "1", "--workers", "1", "--batch", "4",
            "--width", "64", "--height", "64",
            "--host-seconds", "2", "--hbm-seconds", "2",
            "--train-seconds", "3",
            "--skip-seqformer", "--skip-moe",
        ] + args,
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    phases = {}
    for line in out.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            obj = json.loads(line)
            phases[obj.get("phase")] = obj
    return phases


def test_healthy_backend_runs_device_phases():
    """CPU backend up instantly: boot + host_stream + device phases,
    exit code 0 (asserted in _run_suite)."""
    phases = _run_suite(
        {"JAX_PLATFORMS": "cpu"}, ["--budget", "120"], timeout=200
    )
    assert "boot" in phases
    assert phases["host_stream"]["items_per_sec"] > 0
    assert phases["device_init"]["platform"] == "cpu"
    assert "stream_to_hbm" in phases
    # streams carry the multi-window distribution + honest fence label
    assert phases["stream_to_hbm"]["fence"] == "value_fetch"
    assert phases["stream_to_hbm"]["items_per_sec_windows"]["n"] >= 1
    assert not any(name.endswith("_cpu") for name in phases)
