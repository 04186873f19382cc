"""CPU rehearsal of the linear-attention serve cell at a tiny size: the
driver the chip runs (``drivers/serve_tokens_linear.py``) with its real load
generator as a child, the control and the three faults, the readers and the
arithmetic of ``flops_olmohybrid.py``.  Run with
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

from chipbench import flops_olmohybrid, reference_olmohybrid, run  # noqa: E402

CELL = "olmohybrid7b.serve_closed64"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TINY = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 4,
    "num_attention_heads": 2, "num_key_value_heads": 2,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"],
    "linear_num_key_heads": 2, "linear_num_value_heads": 2,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rms_norm_eps": 1e-6, "vocab_size": 128, "tie_word_embeddings": False,
    "param_dtype": "float32", "compute_dtype": "float32",
    "cache_dtype": "float32", "matmul_precision": "highest",
    "control_quant": "int8", "slots": 6, "length": 64,
    "server": {"max_batch": 4, "tick_ms": 2.0, "buckets": [1, 2, 4]}}
# the CPU runs float32 throughout: the program sits at rounding from the
# reference (amplified by the norms after each sublayer), the int8 control
# and the three faults far above
LIMITS = {"logit_gap_p50": 1e-4, "logit_gap_rms": 1e-3,
          "logit_gap_max": 1e-2, "lse_gap_max": 1e-3}


def _ctx(seed=2**31 + 7, **over):
    workload = {
        "driver": "chipbench.drivers.serve_tokens_linear:run",
        "check": {"sample_episodes": 3, "limits": LIMITS},
        "traffic": {"clients": 3, "prefix_lengths": [6, 16], "steps_min": 8,
                    "steps_max": 24, "step_grid": 8, "ramp_s": 0.3,
                    "rpc_timeout_ms": 60000}}
    ctx = types.SimpleNamespace(
        cell={"name": "tiny.linear", "chips": 1}, workload=workload,
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
        assert {"serve.gdn_decode_hbm_pct", "serve.gdn_decode_mfu_pct",
                "serve.gdn_state_bytes_pct", "serve.compute_ms",
                "serve.batch_rows_mean"} <= names
    else:
        assert names == {"serve_tokens_per_s", "setup_s"}
    for name, m in line["metrics"].items():
        assert m["unit"] and m["value"] >= 0, name
    for name in names & {"serve.gdn_decode_hbm_pct", "serve_tokens_per_s",
                         "serve.gdn_state_bytes_pct"}:
        assert line["metrics"][name]["value"] > 0
    # the counters hang together: every stepped row is live at 1 .. length
    # positions, moves its state twice, and there is no window layer
    events = obs["events"]
    rows = events["serve_rows_stepped"]
    assert rows <= events["serve_ctx_positions"] <= 64 * rows
    assert events.get("serve_window_positions", 0) == 0
    assert events["serve_state_bytes"] == rows * 2 * \
        flops_olmohybrid.state_row_bytes(TINY, cache_bytes=4)
    assert events["serve_state_resets"] > 0
    assert rows <= 4 * events["serve_batches"]
    # the driver leaves the sibling driver and the program as it found them
    from blendjax.models import deltanet
    from chipbench.drivers import serve_tokens_hybrid, serve_tokens_linear
    assert serve_tokens_hybrid.build_model is not \
        serve_tokens_linear.build_model
    assert deltanet.mix_step.__module__ == "blendjax.models.deltanet"


@pytest.mark.parametrize("fault,fails", [
    ("answer_altered", "logit_gap_max"),
    ("state_not_reset", "logit_gap_p50"),
    ("decay_left_out", "logit_gap_p50"),
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


def test_readers_on_a_hand_made_window_and_without_the_counters():
    from chipbench.readers import serve_olmohybrid

    cfg = run.resolve_cell(CELL).config
    ctx = _ctx(config=cfg)
    row2 = 2 * flops_olmohybrid.state_row_bytes(cfg)
    obs = {"events": {"serve_batches": 1000, "serve_rows_stepped": 32_000,
                      "serve_ctx_positions": 32_000_000,
                      "serve_state_bytes": 32_000 * row2},
           "window_s": 30.0}
    need = flops_olmohybrid.decode_bytes(cfg, 1000, 32_000, 32_000_000,
                                         32_000 * row2)
    assert serve_olmohybrid.gdn_decode_hbm_pct(obs, ctx) == pytest.approx(
        100 * need / 30 / 819e9)
    assert serve_olmohybrid.gdn_state_bytes_pct(obs, ctx) == pytest.approx(
        100 * 32_000 * row2 / need)
    assert 10 < serve_olmohybrid.gdn_state_bytes_pct(obs, ctx) < 20
    assert serve_olmohybrid.gdn_decode_mfu_pct(obs, ctx) == pytest.approx(
        100 * flops_olmohybrid.decode_flops(cfg, 32_000, 32_000_000)
        / 30 / 197e12)
    # a program without the counters: nothing to read, and no exception
    old = {"events": {"serve_batches": 10, "serve_rows_stepped": 100,
                      "serve_ctx_positions": 1000}, "window_s": 1.0}
    for reader in (serve_olmohybrid.gdn_decode_hbm_pct,
                   serve_olmohybrid.gdn_decode_mfu_pct,
                   serve_olmohybrid.gdn_state_bytes_pct):
        assert reader(old, ctx) is None
        assert reader({}, ctx) is None


def test_the_cell_resolves_and_its_config_keeps_every_published_width():
    r = run.resolve_cell(CELL)
    cfg = r.config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(line) for line in open(catalog)
                   if json.loads(line)["name"] == "Olmo-Hybrid-7B")
        differs = {k for k, v in row["config"].items()
                   if cfg.get(k, "?") != v}
        assert differs == {"num_hidden_layers"}  # layer_types kept whole
        assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 12
    assert cfg["published"]["num_hidden_layers"] == 32
    assert len(cfg["assumed"]) >= 6 and cfg["deployment"]
    assert (cfg["slots"], cfg["length"]) == (72, 1536)
    assert cfg["state_dtype"] == "float32"
    assert cfg["model"] == {**cfg["model"], "d_model": 3840, "n_heads": 30,
                            "n_layers": 12, "obs_dim": 1, "reply_width": 17}
    t = r.workload["traffic"]
    assert (t["clients"], t["prefix_lengths"]) == (64, [256, 512, 1024])
    assert (t["steps_min"], t["steps_max"], t["step_grid"]) == (256, 512, 64)
    assert max(t["prefix_lengths"]) + t["steps_max"] <= cfg["length"]
    assert {m["name"] for m in r.end_to_end} == {"serve_tokens_per_s",
                                                  "setup_s"}
    assert {m["moves"] for m in r.per_layer} == {"serve_tokens_per_s"}
    assert {"serve.gdn_decode_hbm_pct", "serve.gdn_decode_mfu_pct",
            "serve.gdn_state_bytes_pct", "device.idle_pct.serve",
            "serve.batch_pad_pct"} <= {m["name"] for m in r.per_layer}
    assert set(r.workload["check"]["why"]) == set(
        r.workload["check"]["limits"])


def test_parameters_and_slot_bytes_against_the_issues_arithmetic():
    cfg = run.resolve_cell(CELL).config
    w = flops_olmohybrid.weight_counts(cfg)
    assert flops_olmohybrid.layer_counts(cfg) == {"linear": 9, "full": 3}
    # Wq, Wk 11.06 M each; Wv, Wg, Wo 22.12 M each; Wa, Wb 0.23 M; taps 46 k
    assert w["linear"] == (2 * 3840 * 2880 + 3 * 3840 * 5760 + 2 * 3840 * 30
                           + 4 * 11520 + 2 * 30 + 192)
    assert w["linear"] / 1e6 == pytest.approx(88.75, rel=1e-3)
    assert w["full"] / 1e6 == pytest.approx(58.99, rel=1e-3)
    assert w["mlp"] / 1e6 == pytest.approx(126.81, rel=1e-3)
    assert (w["embed"] + w["head"]) / 1e6 == pytest.approx(770.7, rel=1e-3)
    period = 3 * (w["linear"] + w["mlp"]) + w["full"] + w["mlp"]
    assert period / 1e6 == pytest.approx(832.5, rel=1e-3)
    n = flops_olmohybrid.param_count(cfg)
    assert 2 * n / 1e9 == pytest.approx(6.54, rel=2e-3)
    # what make_params makes is what is counted
    made = sum(int(np.prod(shape))
               for _, shape, _, _ in reference_olmohybrid.leaf_shapes(cfg))
    assert made == n
    slot = flops_olmohybrid.slot_bytes(cfg, cfg["length"])
    assert slot == {"state": 9 * 30 * 192 * 96 * 4,
                    "tails": 9 * 3 * 11520 * 2,
                    "kv": 3 * 1536 * 2 * 3840 * 2}
    assert slot["state"] / 9e6 == pytest.approx(2.21, rel=2e-3)
    assert sum(slot.values()) / 1e6 == pytest.approx(91.3, rel=1e-3)
    assert 73 * sum(slot.values()) / 1e9 == pytest.approx(6.67, rel=2e-3)


def test_decode_flops_and_bytes_against_hand_worked_values():
    cfg = run.resolve_cell(CELL).config
    w = flops_olmohybrid.weight_counts(cfg)
    streamed = flops_olmohybrid.layer_params(cfg) + w["head"]
    assert 2 * streamed / 1e9 == pytest.approx(5.77, rel=2e-3)
    # one tick of 32 rows, each at 700 live positions: the layers and the
    # head, 32 embedding rows, 32 rows' state and tails read and written,
    # three layers' live K/V at 2 x 3840 x 2 B a position
    state = 2 * 32 * (9 * (552960 * 4 + 3 * 11520 * 2))
    want = (2 * streamed + 32 * 3840 * 2 + state + 3 * 32 * 700 * 15360)
    assert flops_olmohybrid.decode_bytes(cfg, 1, 32, 32 * 700) == want
    assert flops_olmohybrid.decode_bytes(cfg, 1, 32, 32 * 700, state) == want
    assert state / 1e9 == pytest.approx(1.31, rel=1e-2)
    assert 7.5e9 < want < 8.5e9
    vectors = 25 * 3840 + 9 * (4 * 11520 + 60 + 192) + 3 * 2 * 3840
    want = (2.0 * (streamed - vectors) + 9 * 6.0 * 30 * 96 * 192
            + 3 * 700 * 30 * 4.0 * 128)
    assert flops_olmohybrid.decode_flops(cfg, 1, 700) == want
