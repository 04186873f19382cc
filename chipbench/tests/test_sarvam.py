"""CPU rehearsal of the token-model serve cell at a tiny size: the driver
the chip runs (``drivers/serve_tokens.py``) with its real load generator as
a child, the faults and the control, and the arithmetic of
``flops_sarvam.py``.  Run with
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

from chipbench import flops_sarvam, reference_sarvam, run  # noqa: E402
from chipbench.traffic import closed_loop_token_clients  # noqa: E402

CELL = "sarvam105b.serve_closed64"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TINY = {
    "hidden_size": 32, "num_attention_heads": 4, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "rope_theta": 10000, "rope_scaling": {
        "factor": 40, "beta_fast": 32, "beta_slow": 1, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096},
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "intermediate_size": 64, "moe_intermediate_size": 16, "num_experts": 16,
    "num_experts_held": 4, "held_first": 4, "num_experts_per_tok": 4,
    "routed_scaling_factor": 2.5, "num_shared_experts": 1, "vocab_size": 64,
    "param_dtype": "float32", "compute_dtype": "float32",
    "cache_dtype": "float32", "matmul_precision": "highest",
    "control_quant": "int8", "slots": 6, "length": 64,
    "server": {"max_batch": 4, "tick_ms": 2.0, "buckets": [1, 2, 4]}}
# the CPU runs float32 throughout: the program sits at rounding from the
# reference (1e-6), the int8 control and both faults far above
LIMITS = {"logit_gap_p50": 1e-4, "logit_gap_rms": 1e-4,
          "logit_gap_max": 1e-3, "lse_gap_max": 1e-4}


# what this cell's own readers find on a CPU run, whatever later PRs list
# beside them (a membership: an exact set broke with every new metric)
OWN_METRICS = {"serve.moe_mla_decode_mfu_pct", "serve.moe_mla_decode_hbm_pct",
               "serve.moe_tokens_per_expert", "serve.compute_ms",
               "serve.batch_rows_mean", "serve.batch_pad_pct"}
# three clients for 1.5 s need never find the queue empty, launch behind a
# tick in flight or miss a bucket: 0 is a reading of these three there (on
# the chip at 64 clients all three are above it)
MAY_READ_ZERO = {"serve.idle_wait_ms", "serve.tick_overlap_pct",
                 "serve.batch_pad_pct"}


def _ctx(seed=2**31 + 7, **over):
    workload = {
        "driver": "chipbench.drivers.serve_tokens:run",
        "check": {"sample_episodes": 3, "limits": LIMITS},
        "traffic": {"clients": 3, "prefix_lengths": [8, 16], "steps_min": 8,
                    "steps_max": 24, "step_grid": 8, "ramp_s": 0.3,
                    "rpc_timeout_ms": 60000}}
    ctx = types.SimpleNamespace(
        cell={"name": "tiny.tokens", "chips": 1}, workload=workload,
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
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert line["correct"] is True
    names = set(line["metrics"])
    if trace:
        assert names >= OWN_METRICS
        assert "device.idle_pct.serve" not in names  # no device plane here
        assert not any(n.startswith("serve.hybrid") for n in names)
    else:
        # the tails are not this cell's: at ~70 episodes a window they
        # spread by more than half their bounds (PERF.md section 2)
        assert names == {"serve_tokens_per_s", "setup_s"}
    for name, m in line["metrics"].items():
        assert m["unit"]
        assert m["value"] > 0 or (name in MAY_READ_ZERO
                                  and m["value"] == 0), name
    # a share of a peak cannot pass 100, and the counters hang together
    events = obs["events"]
    assert events["serve_moe_experts_hit"] \
        <= events["serve_moe_assignments_held"] \
        < events["serve_moe_assignments"]
    assert events["serve_moe_experts_hit"] <= 4 * 2 * events["serve_batches"]


@pytest.mark.parametrize("fault,fails", [
    ("answer_altered", "logit_gap_max"),
    ("shared_expert_left_out", "logit_gap_p50"),
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
    from chipbench.readers import serve_sarvam

    obs = {"events": {"serve_batches": 10}, "replies_in_window": 100,
           "sum_pos_in_window": 1000, "window_s": 1.0}
    ctx = _ctx()
    for reader in (serve_sarvam.moe_mla_decode_mfu_pct,
                   serve_sarvam.moe_mla_decode_hbm_pct,
                   serve_sarvam.moe_tokens_per_expert):
        assert reader(obs, ctx) is None
        assert reader({}, ctx) is None


def test_same_seed_same_ids_and_large_seeds():
    spec = {"clients": 64, "prefix_lengths": [256, 512, 1024],
            "steps_min": 256, "steps_max": 512, "step_grid": 64,
            "vocab_size": 65536}
    grid = closed_loop_token_clients.shape_grid(spec)
    assert len(grid) == 15 and (1024, 512) in grid and (256, 256) in grid
    seed = 2**31 + 12345
    a = closed_loop_token_clients.episode_plan(spec, seed, 3, 2)
    b = closed_loop_token_clients.episode_plan(spec, seed, 3, 2)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert a[0].dtype == np.int32 and a[0].shape[1] == 1
    assert 0 <= a[0].min() and a[1].max() < 65536
    c = closed_loop_token_clients.episode_plan(spec, seed + 1, 3, 2)
    assert not (a[0].shape == c[0].shape and np.array_equal(a[0], c[0]))
    # every client walks all fifteen shapes
    shapes = {tuple(len(x) for x in closed_loop_token_clients.episode_plan(
        spec, seed, 5, i)) for i in range(15)}
    assert shapes == set(grid)
    p = reference_sarvam.make_params(dict(TINY), seed)
    q = reference_sarvam.make_params(dict(TINY), seed)
    assert np.array_equal(np.asarray(p["head"]["w"], np.float32),
                          np.asarray(q["head"]["w"], np.float32))


def test_the_cell_resolves_and_its_config_keeps_the_published_widths():
    r = run.resolve_cell(CELL)
    cfg = r.config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(line) for line in open(catalog)
                   if json.loads(line)["name"] == "sarvam-105b")
        differs = {k for k, v in row["config"].items() if cfg.get(k, "?") != v}
        assert differs == {"num_hidden_layers", "vocab_size"}
        assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held",
                              "vocab_size"]
    assert cfg["num_experts"] == 128 and cfg["num_experts_held"] == 32
    assert cfg["published"] == {"num_hidden_layers": 32, "num_experts": 128,
                                "vocab_size": 262144}
    assert r.workload["traffic"]["clients"] == 64
    assert {m["name"] for m in r.end_to_end} == {"serve_tokens_per_s",
                                                  "setup_s"}
    per_layer = {m["name"] for m in r.per_layer}
    assert per_layer >= OWN_METRICS | {"device.idle_pct.serve"}
    assert {m["moves"] for m in r.per_layer} == {"serve_tokens_per_s"}
    assert not {"serve.decode_mfu_pct", "serve.decode_hbm_pct"} & per_layer


def test_the_buckets_hold_the_two_cohorts():
    """64 clients step as two cohorts of about 32 rows (one tick is kept in
    flight): a cohort of 33 must find a bucket short of 64."""
    srv = run.resolve_cell(CELL).config["server"]
    buckets = srv["buckets"]
    assert buckets == sorted(set(buckets)) and buckets[0] >= 1
    assert buckets[-1] == srv["max_batch"] == 64
    assert any(32 < b < 64 for b in buckets)
    assert 32 in buckets  # the even split pads nothing


def test_batch_pad_pct_hand_worked():
    from chipbench.readers import serve

    step_s = np.zeros(100)
    assert serve.batch_pad_pct({}, None) is None
    assert serve.batch_pad_pct(
        {"events": {"serve_batch_pad": 5}, "step_s": step_s}, None) is None
    assert serve.batch_pad_pct(
        {"events": {"serve_batches": 0}, "step_s": step_s}, None) is None
    assert serve.batch_pad_pct(
        {"events": {"serve_batches": 4}, "step_s": ()}, None) is None
    # 100 replies and 28 pad rows: 28 of 128 rows computed
    assert serve.batch_pad_pct(
        {"events": {"serve_batches": 4, "serve_batch_pad": 28},
         "step_s": step_s}, None) == pytest.approx(21.875)
    # a server that padded nothing counts nothing: 0 is a reading
    assert serve.batch_pad_pct(
        {"events": {"serve_batches": 4}, "step_s": step_s}, None) == 0.0


def test_weight_bytes_against_the_issues_arithmetic():
    cfg = run.resolve_cell(CELL).config
    w = flops_sarvam.weight_counts(cfg)
    # W_q 50.33 M + W_dkv 2.36 M + W_ukv 8.39 M + W_o 33.55 M
    assert w["attention"] == 50_331_648 + 2_359_296 + 8_388_608 + 33_554_432
    assert w["attention"] / 1e6 == pytest.approx(94.6, rel=1e-3)
    assert w["dense_mlp"] / 1e6 == pytest.approx(201.3, rel=1e-3)
    assert (w["attention"] + w["dense_mlp"]) / 1e6 == pytest.approx(
        295.9, rel=1e-3)
    assert w["expert"] / 1e6 == pytest.approx(25.17, rel=1e-3)
    outside = w["attention"] + w["shared"] + w["router"]
    assert outside / 1e6 == pytest.approx(120.3, rel=1e-3)
    assert 32 * w["expert"] / 1e6 == pytest.approx(805.3, rel=1e-3)
    assert (outside + 32 * w["expert"]) / 1e6 == pytest.approx(
        925.6, rel=1e-3)
    assert (w["embed"] + w["head"]) * 2 / 1e9 == pytest.approx(
        1.074, rel=1e-3)
    # one dense + four expert layers + the quarter vocabulary, bfloat16
    assert flops_sarvam.param_count(cfg) * 2 / 1e9 == pytest.approx(
        9.075, rel=1e-3)
    # and what make_params makes is what is counted
    made = sum(int(np.prod(shape))
               for _, shape, _, _ in reference_sarvam.leaf_shapes(cfg))
    assert made == flops_sarvam.param_count(cfg)
    # the latent pool: 129 rows x 2048 positions x 5 layers x 640 lanes
    assert 129 * 2048 * 5 * 640 * 2 == 1_690_828_800


def test_decode_flops_and_bytes_against_hand_worked_values():
    cfg = run.resolve_cell(CELL).config
    w = flops_sarvam.weight_counts(cfg)
    # one step at 1000 live positions, 2 routed experts here + the shared
    per_layer = 2 * (50_331_648 + 2_359_296 + 64 * 128 * 512
                     + 64 * 512 * 128 + 33_554_432)
    attn_pos = 2 * 64 * (512 + 64 + 512) * 1000
    experts = 3 * 2 * w["expert"] + 2 * 4096 * 128
    want = (5 * per_layer + 5 * attn_pos + 2 * w["dense_mlp"] + 4 * experts
            + 2 * w["head"])
    assert flops_sarvam.decode_flops(cfg, 1, 1000, 2.0) == want
    # a tick of 64 rows that hit 100 held experts over the four layers:
    # every other weight once, 100 experts, 64 embedding rows, 64000 live
    # latent rows of 576 x 2 B in each of 5 layers
    every = (5 * w["attention"] + w["dense_mlp"]
             + 4 * (w["shared"] + w["router"]) + w["head"] + w["norms"])
    want = 2 * every + 100 * 2 * w["expert"] + 64 * 4096 * 2 \
        + 64000 * 5 * 1152
    assert flops_sarvam.decode_bytes(cfg, 1, 64, 64000, 100) == want
    # all 128 held experts hit: the tick the issue reckons at ~8.5 GB
    full = flops_sarvam.decode_bytes(cfg, 1, 64, 64000, 128)
    assert 8.0e9 < full < 9.0e9
