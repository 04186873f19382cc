"""CPU rehearsal of the sliding-window, softmax-routed serve cell at a tiny
size: the driver the chip runs (``drivers/serve_tokens_mellum2.py``) with
its real load generator as a child, the control and the four faults, the
readers, the arithmetic of ``flops_mellum2.py`` against the numbers the
configuration's sizing rests on, and the configuration's keys against the
published ones.  Run with ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests
-q``.
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

from chipbench import flops_mellum2, reference_mellum2, run  # noqa: E402

CELL = "mellum2.code_closed64"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TINY = {
    "hidden_size": 48, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "num_hidden_layers": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "sliding_window": 8, "moe_intermediate_size": 24, "num_experts": 8,
    "num_experts_held": 4, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "vocab_size": 96,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 10000, "factor": 4,
            "original_max_position_embeddings": 64, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2},
        "sliding_attention": {"rope_type": "default", "rope_theta": 1000}},
    "param_dtype": "float32", "compute_dtype": "float32",
    "cache_dtype": "float32", "matmul_precision": "highest",
    "control_quant": "int8", "slots": 6, "length": 64,
    "server": {"max_batch": 4, "tick_ms": 2.0, "buckets": [1, 2, 4]}}
# the CPU runs float32 throughout: the program sits at rounding from the
# reference, the int8 control and the four faults far above
LIMITS = {"logit_gap_p50": 1e-4, "logit_gap_rms": 1e-3,
          "logit_gap_max": 1e-2, "lse_gap_max": 1e-3}


def _ctx(seed=2**31 + 7, **over):
    workload = {
        "driver": "chipbench.drivers.serve_tokens_mellum2:run",
        "check": {"sample_episodes": 3, "limits": LIMITS},
        # every prefix longer than the window: the prefill's ring wraps
        "traffic": {"clients": 3, "prefix_lengths": [12, 20], "steps_min": 8,
                    "steps_max": 24, "step_grid": 8, "ramp_s": 0.3,
                    "rpc_timeout_ms": 60000}}
    ctx = types.SimpleNamespace(
        cell={"name": "tiny.mellum2", "chips": 1}, workload=workload,
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


NEW = {"serve.moe_swa_decode_hbm_pct", "serve.moe_swa_decode_mfu_pct",
       "serve.swa_kv_bytes_pct"}


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
        assert NEW | {"serve.compute_ms", "serve.batch_rows_mean"} <= names
        assert "serve.moe_tokens_per_expert" not in names
        for name in NEW:
            assert 0 < line["metrics"][name]["value"] < 100, name
    else:
        assert names == {"serve_tokens_per_s", "setup_s"}
    for name, m in line["metrics"].items():
        assert m["unit"] and m["value"] >= 0, name
    # the counters hang together: both sets from one step; every stepped
    # row is past the window (prefixes 12 and 20, window 8), so each ring
    # holds the window; no recurrent state, nothing zeroed
    events = obs["events"]
    rows = events["serve_rows_stepped"]
    assert events["serve_window_positions"] == 8 * rows
    assert 12 * rows < events["serve_ctx_positions"] <= 44 * rows
    assert events["serve_moe_assignments"] == 4 * 2 * rows
    assert 0 < events["serve_moe_assignments_held"] \
        < events["serve_moe_assignments"]
    assert events.get("serve_state_bytes", 0) == 0
    assert events.get("serve_state_resets", 0) == 0
    assert rows <= 4 * events["serve_batches"]
    # the driver leaves the sibling driver, and the collector, as it found
    # them
    import gc

    from chipbench.drivers import serve_tokens_hybrid, serve_tokens_mellum2
    assert serve_tokens_hybrid.build_model is not \
        serve_tokens_mellum2.build_model
    assert serve_tokens_hybrid._tenant_every_slot is \
        serve_tokens_mellum2._TENANT_EVERY_SLOT
    assert gc.get_freeze_count() == 0


def test_the_set_ups_heap_is_frozen_once_every_slot_has_a_tenant():
    """The window starts with what set-up built out of the collector's
    reach: every slot admitted first, then the heap frozen."""
    import gc

    from chipbench.drivers import serve_tokens_mellum2

    class Pool:
        slots = 3

        def __init__(self):
            self.admitted = []

        def prefill_rows(self, idx, prefix):
            self.admitted.append((int(idx[0]), prefix.shape))
            assert gc.get_freeze_count() == 0

    pool = Pool()
    try:
        serve_tokens_mellum2._tenant_every_slot_then_freeze(pool, 12, 96, 7)
        assert pool.admitted == [(s, (12, 1)) for s in range(3)]
        assert gc.get_freeze_count() > 0
    finally:
        gc.unfreeze()


@pytest.mark.parametrize("fault,fails", [
    ("answer_altered", "logit_gap_max"),
    ("renorm_left_out", "logit_gap_p50"),
    ("window_left_out_in_prefill", "logit_gap_p50"),
    ("rope_kinds_swapped", "logit_gap_p50"),
])
def test_a_broken_timed_path_is_not_correct(fault, fails):
    obs = _drive(_ctx(fault=fault))
    assert not obs["checks"].correct
    assert fails in _failed(obs), obs["checks"].rows


def test_the_control_in_lower_precision_is_not_correct():
    obs = _drive(_ctx(control=True))
    assert not obs["checks"].correct
    assert {"logit_gap_p50", "logit_gap_rms"} <= _failed(obs), \
        obs["checks"].rows


def test_a_program_that_cannot_describe_the_model_exits_cleanly(
        monkeypatch):
    from blendjax.models import seqformer
    from chipbench.drivers import serve_tokens_mellum2

    monkeypatch.setattr(seqformer, "_LAYER_TYPES", {
        "linear_attention": "gdn", "full_attention": "full"})
    with pytest.raises(SystemExit, match="sliding-window"):
        serve_tokens_mellum2.run(_ctx())


def test_readers_on_a_hand_made_window_and_without_the_counters():
    from chipbench.readers import serve_mellum2

    cfg = run.resolve_cell(CELL).config
    ctx = _ctx(config=cfg)
    events = {"serve_batches": 1000, "serve_rows_stepped": 32_000,
              "serve_moe_assignments": 28 * 8 * 32_000,
              "serve_moe_assignments_held": 28 * 2 * 32_000,
              "serve_moe_experts_hit": 1000 * 28 * 15,
              "serve_window_positions": 32_000 * 1024,
              "serve_ctx_positions": 32_000 * 1740}
    obs = {"events": events, "window_s": 30.0}
    need = flops_mellum2.decode_bytes(cfg, 1000, 32_000, 1000 * 28 * 15,
                                      32_000 * 1024, 32_000 * 1740)
    assert serve_mellum2.moe_swa_decode_hbm_pct(obs, ctx) == pytest.approx(
        100 * need / 30 / 819e9)
    kv = flops_mellum2.kv_bytes(cfg, 32_000 * 1024, 32_000 * 1740)
    assert serve_mellum2.swa_kv_bytes_pct(obs, ctx) == pytest.approx(
        100 * kv / need)
    assert 15 < serve_mellum2.swa_kv_bytes_pct(obs, ctx) < 35
    assert serve_mellum2.moe_swa_decode_mfu_pct(obs, ctx) == pytest.approx(
        100 * flops_mellum2.decode_flops(cfg, 32_000, 2.0, 32_000 * 1024,
                                         32_000 * 1740) / 30 / 197e12)
    # a program without the counters: nothing to read, and no exception
    old = {"events": {"serve_batches": 10, "serve_rows_stepped": 100,
                      "serve_ctx_positions": 1000}, "window_s": 1.0}
    for reader in (serve_mellum2.moe_swa_decode_hbm_pct,
                   serve_mellum2.moe_swa_decode_mfu_pct,
                   serve_mellum2.swa_kv_bytes_pct):
        assert reader(old, ctx) is None
        assert reader({}, ctx) is None


def test_the_cell_resolves_and_its_config_keeps_every_published_width():
    r = run.resolve_cell(CELL)
    cfg = r.config
    assert cfg["reduced"] == ["num_experts_held", "vocab_size"]
    assert cfg["published"] == {"num_experts": 64, "vocab_size": 98304}
    assert (cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["num_experts_held"], cfg["vocab_size"]) == (64, 8, 16, 24576)
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 28
    assert flops_mellum2.layer_counts(cfg) == {"window": 21, "full": 7}
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["sliding_window"],
            cfg["moe_intermediate_size"]) == (2304, 128, 1024, 896)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"]) == (32, 4)
    assert cfg["source"].startswith(
        "https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct")
    assert len(cfg["assumed"]) >= 4 and cfg["deployment"]
    assert (cfg["slots"], cfg["length"]) == (72, 2560)
    assert cfg["model"] == {**cfg["model"], "d_model": 2304, "n_heads": 32,
                            "n_layers": 28, "obs_dim": 1, "reply_width": 17}
    t = r.workload["traffic"]
    assert (t["clients"], t["prefix_lengths"]) == (64, [1024, 1536, 2048])
    assert (t["steps_min"], t["steps_max"], t["step_grid"]) == (256, 512, 64)
    assert max(t["prefix_lengths"]) + t["steps_max"] == cfg["length"]
    assert min(t["prefix_lengths"]) >= cfg["sliding_window"]
    assert {m["name"] for m in r.end_to_end} == {"serve_tokens_per_s",
                                                  "setup_s"}
    assert {m["moves"] for m in r.per_layer} == {"serve_tokens_per_s"}
    names = {m["name"] for m in r.per_layer}
    assert NEW | {"device.idle_pct.serve", "serve.batch_pad_pct",
                  "serve.client_turn_ms"} <= names
    assert "serve.moe_tokens_per_expert" not in names
    assert len(names) == 17
    if "why" in r.workload["check"]:
        assert set(r.workload["check"]["why"]) == set(
            r.workload["check"]["limits"])


def test_parameters_and_slot_bytes_against_the_sizing():
    cfg = run.resolve_cell(CELL).config
    w = flops_mellum2.weight_counts(cfg)
    assert w["attention"] == 2 * 2304 * 4096 + 2 * 2304 * 512 + 2 * 128
    assert w["attention"] / 1e6 == pytest.approx(21.23, rel=1e-3)
    assert w["router"] / 1e6 == pytest.approx(0.147, rel=1e-2)
    assert w["expert"] / 1e6 == pytest.approx(6.193, rel=1e-3)
    layer = w["attention"] + w["router"] + 16 * w["expert"]
    assert layer / 1e6 == pytest.approx(120.5, rel=1e-3)
    assert 28 * layer / 1e9 == pytest.approx(3.373, rel=1e-3)
    assert (w["embed"] + w["head"]) / 1e6 == pytest.approx(113.2, rel=1e-3)
    n = flops_mellum2.param_count(cfg)
    assert n / 1e9 == pytest.approx(3.487, rel=1e-3)
    assert 2 * n / 1e9 == pytest.approx(6.97, rel=2e-3)
    # what make_params makes is what is counted
    made = sum(int(np.prod(shape))
               for _, shape, _, _ in reference_mellum2.leaf_shapes(cfg))
    assert made == n
    slot = flops_mellum2.slot_bytes(cfg, cfg["length"])
    assert slot == 21 * 1024 * 2048 + 7 * 2560 * 2048
    assert slot / 1e6 == pytest.approx(80.74, rel=1e-4)
    assert 73 * slot / 1e9 == pytest.approx(5.89, rel=2e-3)
    # the arguments: weights (the router in float32) and the pool, 75% of
    # the chip's 16 GiB
    args = 2 * n + 2 * 28 * w["router"] + 73 * slot
    assert args / 1e9 == pytest.approx(12.88, rel=2e-3)
    assert 0.74 < args / 2 ** 34 < 0.76


def test_decode_bytes_and_flops_of_a_tick_against_the_forecast():
    cfg = run.resolve_cell(CELL).config
    w = flops_mellum2.weight_counts(cfg)
    # a tick of 32 rows at ~1740 live positions of a full layer, the rings
    # full, about 15.75 of 16 held experts hit in each of 28 layers
    hit = 28 * 15.75
    kv = flops_mellum2.kv_bytes(cfg, 32 * 1024, 32 * 1740)
    assert kv == (21 * 32 * 1024 + 7 * 32 * 1740) * 2048
    assert kv / 1e9 == pytest.approx(2.21, rel=1e-2)
    experts = hit * w["expert"] * 2
    assert experts / 1e9 == pytest.approx(5.46, rel=1e-2)
    need = flops_mellum2.decode_bytes(cfg, 1, 32, hit, 32 * 1024, 32 * 1740)
    attention = 28 * w["attention"] * 2
    assert attention / 1e9 == pytest.approx(1.19, rel=1e-2)
    rest = need - kv - experts - attention
    assert 0.12e9 < rest < 0.14e9       # head, routers, norms, 32 rows
    assert need / 1e9 == pytest.approx(9.0, rel=1e-2)
    assert need / 819e9 * 1e3 == pytest.approx(11.0, rel=2e-2)  # ms
    flops = flops_mellum2.decode_flops(cfg, 32, 2.0, 32 * 1024, 32 * 1740)
    matrices = 28 * (w["attention"] - 256 + w["router"]) + w["head"]
    want = (32 * (2.0 * matrices + 28 * 2.0 * 2.0 * w["expert"])
            + (21 * 32 * 1024 + 7 * 32 * 1740) * 32 * 4.0 * 128)
    assert flops == want
