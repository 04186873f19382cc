"""CPU rehearsal of the hybrid serve cell at a tiny size: the driver the
chip runs (``drivers/serve_tokens_hybrid.py``) with its real load generator
as a child, the control and both faults, and the arithmetic of
``flops_phi4flash.py``.  Run with
``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q``.
"""

from __future__ import annotations

import json
import os
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from chipbench import flops_phi4flash, reference_phi4flash, run  # noqa: E402

CELL = "phi4miniflash.reason_closed64"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TINY = {
    "hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 4,
    "intermediate_size": 128, "num_hidden_layers": 8, "mb_per_layer": 2,
    "sliding_window": 8, "layer_norm_eps": 1e-5, "vocab_size": 96,
    "mamba_d_state": 4, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_dt_rank": 4,
    "param_dtype": "float32", "compute_dtype": "float32",
    "cache_dtype": "float32", "matmul_precision": "highest",
    "control_quant": "int8", "slots": 6, "length": 64,
    "server": {"max_batch": 4, "tick_ms": 2.0, "buckets": [1, 2, 4]}}
# the CPU runs float32 throughout: the program sits at rounding from the
# reference, the int8 control and both faults far above
LIMITS = {"logit_gap_p50": 1e-4, "logit_gap_rms": 1e-4,
          "logit_gap_max": 1e-3, "lse_gap_max": 1e-4}


def _ctx(seed=2**31 + 7, **over):
    workload = {
        "driver": "chipbench.drivers.serve_tokens_hybrid:run",
        "check": {"sample_episodes": 3, "limits": LIMITS},
        "traffic": {"clients": 3, "prefix_lengths": [6, 16], "steps_min": 8,
                    "steps_max": 24, "step_grid": 8, "ramp_s": 0.3,
                    "rpc_timeout_ms": 60000}}
    ctx = types.SimpleNamespace(
        cell={"name": "tiny.hybrid", "chips": 1}, workload=workload,
        config=dict(TINY), peaks=PEAKS, seed=seed, seconds=1.5, trace=False,
        control=False, fault=None, t_start=time.monotonic())
    for k, v in over.items():
        setattr(ctx, k, v)
    return ctx


def _drive(ctx):
    return run._resolve(ctx.workload["driver"])(ctx)


def _failed(obs):
    return {r["name"] for r in obs["checks"].rows
            if not r["value"] <= r["limit"]}


@pytest.mark.parametrize("trace", [False, True])
def test_driver_end_to_end_prints_the_contracts_keys(trace):
    ctx = _ctx(trace=trace)
    obs = _drive(ctx)
    assert obs["checks"].correct, obs["checks"].rows
    assert obs["failed"] == 0 and obs["attempted"] > 0
    assert obs["compiles_in_window"] == 0
    assert obs["notes"]["episodes_checked"] == 3
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    line = json.loads(json.dumps(run.result_line(
        run.resolve_cell(CELL), obs, ctx, device)))
    assert line["correct"] is True
    names = set(line["metrics"])
    if trace:
        assert {"serve.hybrid_decode_hbm_pct", "serve.hybrid_decode_mfu_pct",
                "serve.cache_live_pct", "serve.compute_ms",
                "serve.batch_rows_mean"} <= names
    else:
        assert names == {"serve_tokens_per_s", "setup_s"}
    for name, m in line["metrics"].items():
        assert m["unit"] and m["value"] >= 0, name
    for name in names & {"serve.hybrid_decode_hbm_pct", "serve_tokens_per_s",
                         "serve.cache_live_pct"}:
        assert line["metrics"][name]["value"] > 0
    # the counters hang together: every stepped row is live at 1 .. length
    # positions, at most the window of them in a ring, and every episode's
    # reset zeroed a state
    events = obs["events"]
    rows = events["serve_rows_stepped"]
    assert rows <= events["serve_ctx_positions"] <= 64 * rows
    assert rows <= events["serve_window_positions"] <= 8 * rows
    # (a reset's count rides the next tick's drain, so the window's edges
    # may hold one a client apart)
    assert events["serve_state_resets"] > 0
    assert abs(events["serve_state_resets"] - events["serve_resets"]) <= 3
    assert rows <= 4 * events["serve_batches"]


@pytest.mark.parametrize("fault,fails", [
    ("answer_altered", "logit_gap_max"),
    ("state_not_reset", "logit_gap_p50"),
])
def test_a_broken_timed_path_is_not_correct(fault, fails):
    obs = _drive(_ctx(fault=fault))
    assert not obs["checks"].correct
    assert fails in _failed(obs), obs["checks"].rows


def test_the_control_in_lower_precision_is_not_correct():
    obs = _drive(_ctx(control=True))
    assert not obs["checks"].correct
    assert "logit_gap_p50" in _failed(obs), obs["checks"].rows


def test_readers_find_nothing_in_a_program_without_the_counters():
    from chipbench.readers import serve_phi4flash

    obs = {"events": {"serve_batches": 10}, "replies_in_window": 100,
           "sum_pos_in_window": 1000, "window_s": 1.0}
    ctx = _ctx()
    for reader in (serve_phi4flash.hybrid_decode_hbm_pct,
                   serve_phi4flash.hybrid_decode_mfu_pct,
                   serve_phi4flash.cache_live_pct):
        assert reader(obs, ctx) is None
        assert reader({}, ctx) is None


def test_the_cell_resolves_and_its_config_keeps_every_published_key():
    r = run.resolve_cell(CELL)
    cfg = r.config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(line) for line in open(catalog)
                   if json.loads(line)["name"] == "Phi-4-mini-flash-reasoning")
        assert {k for k, v in row["config"].items()
                if cfg.get(k, "?") != v} == set()
        assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == [] and len(cfg["assumed"]) >= 5
    assert (cfg["slots"], cfg["length"]) == (128, 2048)
    assert cfg["model"] == {**cfg["model"], "d_model": 2560, "n_heads": 40,
                            "n_layers": 32, "obs_dim": 1, "reply_width": 17}
    t = r.workload["traffic"]
    assert (t["clients"], t["prefix_lengths"]) == (64, [128, 256, 512])
    assert (t["steps_min"], t["steps_max"], t["step_grid"]) == (384, 768, 96)
    assert {m["name"] for m in r.end_to_end} == {"serve_tokens_per_s",
                                                  "setup_s"}
    assert {m["moves"] for m in r.per_layer} == {"serve_tokens_per_s"}
    assert {"serve.hybrid_decode_hbm_pct", "serve.hybrid_decode_mfu_pct",
            "serve.cache_live_pct", "device.idle_pct.serve"} <= {
        m["name"] for m in r.per_layer}
    assert set(r.workload["check"]["why"]) == set(
        r.workload["check"]["limits"])


def test_parameters_and_slot_bytes_against_the_issues_arithmetic():
    cfg = run.resolve_cell(CELL).config
    w = flops_phi4flash.weight_counts(cfg)
    assert flops_phi4flash.layer_counts(cfg) == {
        "ssm": 9, "window": 8, "full": 1, "gmu": 7, "cross": 7}
    kinds = reference_phi4flash.layer_kinds(cfg)
    assert {k: kinds.count(k) for k in set(kinds)} \
        == flops_phi4flash.layer_counts(cfg)
    assert w["mlp"] / 1e6 == pytest.approx(78.64, rel=1e-3)
    assert w["ssm"] / 1e6 == pytest.approx(41.24, rel=1e-3)
    assert w["attention"] / 1e6 == pytest.approx(19.66, rel=1e-3)
    assert w["gmu"] / 1e6 == pytest.approx(26.21, rel=1e-3)
    assert w["cross"] / 1e6 == pytest.approx(13.11, rel=1e-3)
    assert w["embed"] / 1e6 == pytest.approx(512.2, rel=1e-3)
    n = flops_phi4flash.param_count(cfg)
    assert n / 1e9 == pytest.approx(3.852, rel=1e-3)
    assert 2 * n / 1e9 == pytest.approx(7.70, rel=1e-3)
    # what make_params makes is what is counted
    made = sum(int(np.prod(shape))
               for _, shape, _, _ in reference_phi4flash.leaf_shapes(cfg))
    assert made == n
    slot = flops_phi4flash.slot_bytes(cfg, cfg["length"])
    assert slot == {"rings": 20_971_520, "full": 10_485_760,
                    "state": 3_225_600}
    assert sum(slot.values()) / 1e6 == pytest.approx(34.7, rel=1e-3)
    assert 129 * sum(slot.values()) / 1e9 == pytest.approx(4.47, rel=1e-3)


def test_decode_flops_and_bytes_against_hand_worked_values():
    cfg = run.resolve_cell(CELL).config
    n = flops_phi4flash.param_count(cfg)
    # one tick of 32 rows, each at 1000 live positions (512 in a ring):
    # every parameter, 32 embedding rows and states (read and written),
    # the full K/V's rows eight times and eight rings, 5120 B a position
    state = 9 * (16 * 5120 * 4 + 3 * 5120 * 2)
    want = (2 * n + 32 * (2560 * 2 + 2 * state)
            + 5120 * (8 * 32_000 + 8 * 32 * 512))
    assert flops_phi4flash.decode_bytes(cfg, 1, 32, 32_000, 32 * 512) == want
    assert 9.5e9 < want < 10.5e9
    # per live position and attention layer: 20 query pairs x 2 maps x
    # (64 + 128) multiply-adds
    vectors = (65 * 2 * 2560 + 9 * (4 * 5120 + 3 * 5120 + 16 * 5120)
               + 16 * (4 * 64 + 128))
    want = (2.0 * (n - vectors) + 9 * 6.0 * 5120 * 16
            + 20 * 2 * 2 * 192 * (8 * 1000 + 8 * 512))
    assert flops_phi4flash.decode_flops(cfg, 1, 1000, 512) == want
