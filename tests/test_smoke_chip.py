"""The CPU rehearsal of ``chip_smoke.py``: the same leg functions the chip
runs, at ``TINY`` sizes on the virtual CPU mesh with the Pallas
interpreter — control flow, checks and process plumbing are proven here
before chip time is spent.  Nothing in this file measures anything.
"""

import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _common(result, leg):
    assert result["leg"] == leg
    assert result["platform"] == "cpu" and result["device_count"] == 8
    assert result["jax"] and result["device_kind"]
    # the interpreter lowers to plain HLO: no Mosaic custom call off-TPU
    assert result["mosaic"] is False
    assert result["cold_compile_s"] >= 0


def test_kernels_leg_matches_references():
    result = chip_smoke.leg_kernels(chip_smoke.TINY)
    _common(result, "kernels")
    flash = [c for name, c in result["cases"].items()
             if not name.startswith("decode_")]
    assert len(flash) == len(chip_smoke.TINY["kernel_cases"])
    for case in flash:
        assert set(case) == {"out", "dq", "dk", "dv"}
    assert sum(name.startswith("decode_") for name in result["cases"]) == 2


def test_train_leg_streams_trains_and_checks_the_fence():
    result = chip_smoke.leg_train(chip_smoke.TINY)
    _common(result, "train")
    assert result["arena"] is True
    assert result["checksums_matched"] == chip_smoke.TINY["train_steps"]
    assert result["loss_rel_diff"] <= chip_smoke.LOSS_TOL
    # no peak on file for the CPU: the fence check reports, never judges
    assert "block_until_ready_fences" not in result["fence"]
    with pytest.raises(chip_smoke.SmokeFailure, match="no peak on file"):
        chip_smoke.leg_train(chip_smoke.TINY, require_peak=True)


def test_serve_leg_answers_and_matches_serial_decode():
    result = chip_smoke.leg_serve(chip_smoke.TINY, require_platform="cpu")
    _common(result, "serve")
    assert result["server_hello"]["platform"] == "cpu"
    assert result["server_hello"]["device_count"] == 8
    assert result["rpc_retries"] == 0
    assert result["wide_batches"] < result["wide_steps"]
    assert result["pred_err_vs_serial_decode"] <= 1e-4
    # a server that reports another platform than the caller demands
    # fails the leg (what stops a CPU server passing for a chip one)
    with pytest.raises(chip_smoke.SmokeFailure, match="platform"):
        chip_smoke.leg_serve(chip_smoke.TINY, require_platform="tpu")


def test_mesh_leg_shards_over_four_devices():
    result = chip_smoke.leg_mesh(chip_smoke.TINY)
    _common(result, "mesh")
    assert len(result["mesh_devices"]) == 4
    assert set(result["impls"]) == {"ring_flash", "ulysses_flash"}


def test_program_refuses_to_run_off_tpu():
    """``JAX_PLATFORMS=cpu python chip_smoke.py`` fails fast, before any
    leg, and prints no result line."""
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=60,
    )
    assert out.returncode != 0
    assert time.monotonic() - t0 < 10
    assert out.stdout == ""
    assert "TPU only" in out.stderr


def test_no_launcher_pins_the_platform():
    """Launchers stopped deciding the platform: no file under blendjax/
    defaults ``JAX_PLATFORMS`` for a child."""
    offenders = []
    for root, _, files in os.walk(os.path.join(REPO, "blendjax")):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path) as fp:
                    if 'setdefault("JAX_PLATFORMS"' in fp.read():
                        offenders.append(os.path.relpath(path, REPO))
    assert offenders == []


def test_compile_cache_is_placed_from_outside(tmp_path):
    """One policy: a caller's JAX_COMPILATION_CACHE_DIR is left alone,
    and an unset one becomes the fixed ``<checkout>/.jax_cache``."""
    from blendjax.btt.launcher import place_compile_cache

    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    assert place_compile_cache(env)["JAX_COMPILATION_CACHE_DIR"] == str(
        tmp_path)
    assert place_compile_cache({})["JAX_COMPILATION_CACHE_DIR"] == \
        os.path.join(REPO, ".jax_cache")
