"""The reader of the gated delta rule's decode kernel (PR 41,
``readers/kernel_gdn.py``): the bytes it counts a row stepped at the
published widths, a hand-worked reading, None where the program has no
``gdn_update`` row, the needle against the program's own ``pallas_call``
name, and the metric's entry resolved in its one cell.  CPU only:
``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q``.
"""

from __future__ import annotations

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from chipbench import run  # noqa: E402
from chipbench.readers import kernel_gdn  # noqa: E402

CELL = "olmohybrid7b.serve_closed64"
NAME = "kernel.gdn_update_hbm_pct"
CTX = types.SimpleNamespace(
    config=run.resolve_cell(CELL).config,
    peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def _obs(device_ops, rows=30_000, window_s=30.0, traced_s=3.0):
    return {"window_s": window_s, "events": {"serve_rows_stepped": rows},
            "trace": {"window_s": traced_s, "busy_s": 2.0,
                      "device_ops": device_ops}}


def test_a_row_stepped_moves_nine_matrix_states_in_and_out():
    # 30 heads of dv 192 x dk 96, float32, nine linear layers, twice
    assert kernel_gdn.state_bytes_per_row(CTX.config) == \
        552_960 * 4 * 2 * 9 == 39_813_120


def test_the_reading_hand_worked():
    obs = _obs([["fusion", 1.1], ["gdn_update_tpu_custom_call_", 0.2]])
    need = 30_000 / 30.0 * 3.0 * 39_813_120
    got = kernel_gdn.gdn_update_hbm_pct(obs, CTX)
    assert got == pytest.approx(100.0 * need / 0.2 / 819e9)
    assert 0 < got < 100


@pytest.mark.parametrize("obs", [
    _obs([["fusion", 1.3], ["multiply_reduce_fusion", 0.217],
          ["multiply_add_fusion", 0.163]]),   # the parent's step
    _obs([]),
    {"window_s": 30.0, "events": {"serve_rows_stepped": 1}, "trace": None},
    _obs([["gdn_update_tpu_custom_call_", 0.2]], rows=0),
    _obs([["gdn_update_tpu_custom_call_", 0.2]], window_s=0.0),
    _obs([["gdn_update_tpu_custom_call_", 0.2]], traced_s=0.0),
], ids=["no_kernel_row", "no_rows", "untraced", "no_steps", "no_window",
        "no_traced_extent"])
def test_none_without_a_gdn_update_row_or_its_counts(obs):
    assert kernel_gdn.gdn_update_hbm_pct(obs, CTX) is None


def test_the_needle_is_the_programs_kernel_name():
    import jax
    import jax.numpy as jnp

    from blendjax.ops.gdn_update import gdn_update

    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    f32 = jax.ShapeDtypeStruct
    walk(jax.make_jaxpr(gdn_update)(
        f32((5, 3, 10, 192, 96), jnp.float32), f32((2,), jnp.int32),
        f32((2, 30, 96), jnp.float32), f32((2, 30, 96), jnp.float32),
        f32((2, 30, 192), jnp.float32), f32((2, 30), jnp.float32),
        f32((2, 30), jnp.float32)).jaxpr)
    assert names and all(kernel_gdn.GDN_UPDATE in n for n in names)


def test_the_entry_resolves_in_its_one_cell():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = {m["name"]: m for m in bench["per_layer"]}[NAME]
    assert entry["workloads"] == [CELL] and entry["layer"] == "kernels"
    assert entry["unit"] == "%" and entry["source"] == "device_trace"
    serve = {m["name"]: m for m in bench["end_to_end"]}[entry["moves"]]
    assert CELL in serve["workloads"]
    for cell in bench["workloads"]:
        names = {m["name"] for m in run.resolve_cell(cell["name"]).per_layer}
        assert (NAME in names) == (cell["name"] == CELL)
    spec = json.load(open(os.path.join(ROOT, "chipbench", "metrics",
                                       NAME + ".json")))
    assert run._resolve(spec["reader"]) is kernel_gdn.gdn_update_hbm_pct
