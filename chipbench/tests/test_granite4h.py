"""CPU rehearsal of the Mamba-2 serve cell at a tiny size: the driver the
chip runs (``drivers/serve_tokens_granite4h.py``) with its real load
generator as a child, the control and the four faults, the readers, the
arithmetic of ``flops_granite4h.py`` against the numbers the
configuration's sizing rests on, and the configuration's keys against the
published ones.  Run with ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests
-q``.
"""

from __future__ import annotations

import gc
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

from chipbench import flops_granite4h, reference_granite4h, run  # noqa: E402

CELL = "granite4hmicro.serve_closed64"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TINY = {
    "model_type": "granitemoehybrid", "hidden_size": 64,
    "shared_intermediate_size": 128, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "layer_types": ["mamba", "attention", "mamba", "mamba"],
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_chunk_size": 8, "rms_norm_eps": 1e-5, "vocab_size": 128,
    "tie_word_embeddings": True, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 8,
    "attention_multiplier": 0.0625, "position_embedding_type": "nope",
    "rope_theta": 10000, "num_local_experts": 0,
    "param_dtype": "float32", "compute_dtype": "float32",
    "cache_dtype": "float32", "matmul_precision": "highest",
    "control_quant": "int8", "slots": 6, "length": 64,
    "server": {"max_batch": 4, "tick_ms": 2.0, "buckets": [1, 2, 4]}}
# the CPU runs float32 throughout: the program sits at rounding from the
# reference, the int8 control and the four faults far above
LIMITS = {"logit_gap_p50": 1e-4, "logit_gap_rms": 1e-3,
          "logit_gap_max": 1e-2, "lse_gap_max": 1e-3, "state_gap_max": 1e-3,
          "state_left_after_reset": 0.0, "state_resets_missing": 2}
# the published config.json's keys, which the configuration keeps as they
# are (``reduced`` is empty)
PUBLISHED = {
    "model_type": "granitemoehybrid", "hidden_size": 2048,
    "intermediate_size": 8192, "shared_intermediate_size": 8192,
    "num_hidden_layers": 40, "num_attention_heads": 32,
    "num_key_value_heads": 8, "mamba_n_heads": 64, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "attention_bias": False,
    "attention_multiplier": 0.015625, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 8,
    "num_local_experts": 0, "num_experts_per_tok": 0,
    "position_embedding_type": "nope", "rope_theta": 10000,
    "rope_scaling": None, "rms_norm_eps": 1e-5,
    "normalization_function": "rmsnorm", "hidden_act": "silu",
    "tie_word_embeddings": True, "vocab_size": 100352,
    "max_position_embeddings": 131072}
NEW = ("serve.ssd_decode_hbm_pct", "serve.ssd_decode_mfu_pct",
       "serve.ssd_state_bytes_pct")


def _ctx(seed=2**31 + 7, **over):
    workload = {
        "driver": "chipbench.drivers.serve_tokens_granite4h:run",
        "check": {"sample_episodes": 3, "limits": LIMITS},
        # prefixes under, and past, the chunk of 8
        "traffic": {"clients": 3, "prefix_lengths": [6, 20], "steps_min": 8,
                    "steps_max": 24, "step_grid": 8, "ramp_s": 0.3,
                    "rpc_timeout_ms": 60000}}
    ctx = types.SimpleNamespace(
        cell={"name": "tiny.granite4h", "chips": 1}, workload=workload,
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
        assert set(NEW) | {"serve.compute_ms", "serve.batch_rows_mean"} \
            <= names
        assert "kernel.gdn_update_hbm_pct" not in names
        for name in NEW:
            assert 0 < line["metrics"][name]["value"] < 100
    else:
        assert names == {"serve_tokens_per_s", "setup_s"}
    for name, m in line["metrics"].items():
        assert m["unit"] and m["value"] >= 0, name
    # the counters hang together: every stepped row is live at 1 .. length
    # positions, moves its state and tail twice, and there is no window
    events = obs["events"]
    rows = events["serve_rows_stepped"]
    assert rows <= events["serve_ctx_positions"] <= 64 * rows
    assert events.get("serve_window_positions", 0) == 0
    assert events["serve_state_bytes"] == rows * 2 * \
        flops_granite4h.state_row_bytes(TINY, cache_bytes=4)
    assert events["serve_state_resets"] > 0
    assert rows <= 4 * events["serve_batches"]
    # every reset the server answered zeroed its row's state
    missing = next(r for r in obs["checks"].rows
                   if r["name"] == "state_resets_missing")
    assert missing["value"] <= 0 and missing["limit"] == 2
    # the probe's slots hold the reference's states, and nothing once reset
    gaps = obs["notes"]["reply_gaps"]
    assert 0 < gaps["state_gap_max"] < 1e-4
    assert gaps["state_left_after_reset"] == 0
    # the driver leaves the sibling driver and the program as it found them
    from blendjax.models import mamba2
    from chipbench.drivers import serve_tokens_granite4h, serve_tokens_hybrid
    assert serve_tokens_hybrid.build_model is not \
        serve_tokens_granite4h.build_model
    assert mamba2.mix_step.__module__ == "blendjax.models.mamba2"
    # the set-up's heap was frozen for the window and is thawed after it
    assert serve_tokens_hybrid._tenant_every_slot is \
        serve_tokens_granite4h._TENANT_EVERY_SLOT
    assert gc.get_freeze_count() == 0


@pytest.mark.parametrize("fault,fails", [
    ("answer_altered", "logit_gap_max"),
    ("state_not_reset", "state_gap_max"),
    ("decay_left_out", "logit_gap_p50"),
    ("gate_norm_left_out", "logit_gap_p50"),
])
def test_a_broken_timed_path_is_not_correct(fault, fails):
    from blendjax.models import mamba2

    obs = _drive(_ctx(fault=fault))
    assert not obs["checks"].correct
    assert fails in _failed(obs), obs["checks"].rows
    # the fault is taken out again with the run
    assert mamba2.mix_step.__module__ == "blendjax.models.mamba2"
    assert mamba2.rms_norm.__module__ == "blendjax.models.layers"
    assert mamba2.decay.__module__ == "blendjax.models.mamba2"


def _rewind_keeping(keep):
    """``seqformer.rewind_rows`` that zeroes ``keep(cache, rows)``'s rows
    of the Mamba-2 leaves in place of ``rows``, or leaves ``keep``'s leaf."""
    from blendjax.models import seqformer

    real = seqformer.rewind_rows

    def rewind_rows(cache, rows):
        new = real(cache, rows)
        return keep(cache, new, rows)
    return rewind_rows


@pytest.mark.parametrize("how,fails", [
    # the tail is left behind: it reads between the reset and the prefill
    ("tail_kept", "state_left_after_reset"),
    # the row after each reset one is zeroed in its place
    ("next_row_zeroed", "state_gap_max"),
])
def test_a_reset_that_misses_a_leaf_or_a_row_is_not_correct(
        monkeypatch, how, fails):
    from blendjax.models import seqformer

    def tail_kept(cache, new, rows):
        return {**new, "ssd_tail": cache["ssd_tail"]}

    def next_row_zeroed(cache, new, rows):
        other = (rows + 1) % cache["pos"].shape[0]
        return {**new, "ssd_s": [
            leaf if leaf is None else leaf.at[other].set(0)
            for leaf in cache["ssd_s"]]}

    keep = {"tail_kept": tail_kept, "next_row_zeroed": next_row_zeroed}
    monkeypatch.setattr(seqformer, "rewind_rows", _rewind_keeping(keep[how]))
    obs = _drive(_ctx())
    assert not obs["checks"].correct
    assert fails in _failed(obs), obs["checks"].rows


def test_the_control_in_lower_precision_is_not_correct():
    obs = _drive(_ctx(control=True))
    assert not obs["checks"].correct
    assert {"logit_gap_p50", "logit_gap_rms"} <= _failed(obs), \
        obs["checks"].rows


def test_a_program_without_the_mixer_exits_at_once(monkeypatch):
    """The parent of this cell has no ``blendjax.models.mamba2``: the
    driver exits cleanly before it makes a weight."""
    import blendjax.models

    monkeypatch.delattr(blendjax.models, "mamba2", raising=False)
    monkeypatch.setitem(sys.modules, "blendjax.models.mamba2", None)
    with pytest.raises(SystemExit, match="no Mamba-2 model"):
        _drive(_ctx())


def test_readers_on_a_hand_made_window_and_without_the_counters():
    from chipbench.readers import serve_granite4h

    cfg = run.resolve_cell(CELL).config
    ctx = _ctx(config=cfg)
    row2 = 2 * flops_granite4h.state_row_bytes(cfg)
    obs = {"events": {"serve_batches": 1000, "serve_rows_stepped": 32_000,
                      "serve_ctx_positions": 32_000_000,
                      "serve_state_bytes": 32_000 * row2},
           "window_s": 30.0}
    need = flops_granite4h.decode_bytes(cfg, 1000, 32_000, 32_000_000,
                                        32_000 * row2)
    assert serve_granite4h.ssd_decode_hbm_pct(obs, ctx) == pytest.approx(
        100 * need / 30 / 819e9)
    assert serve_granite4h.ssd_state_bytes_pct(obs, ctx) == pytest.approx(
        100 * 32_000 * row2 / need)
    # ~32 rows a tick: the state is about two fifths of what a tick moves
    assert 35 < serve_granite4h.ssd_state_bytes_pct(obs, ctx) < 50
    assert serve_granite4h.ssd_decode_mfu_pct(obs, ctx) == pytest.approx(
        100 * flops_granite4h.decode_flops(cfg, 32_000, 32_000_000)
        / 30 / 197e12)
    # a program without the counters: nothing to read, and no exception
    old = {"events": {"serve_batches": 10, "serve_rows_stepped": 100,
                      "serve_ctx_positions": 1000}, "window_s": 1.0}
    for reader in (serve_granite4h.ssd_decode_hbm_pct,
                   serve_granite4h.ssd_decode_mfu_pct,
                   serve_granite4h.ssd_state_bytes_pct):
        assert reader(old, ctx) is None
        assert reader({}, ctx) is None


def test_the_cell_resolves_and_its_config_keeps_every_published_key():
    r = run.resolve_cell(CELL)
    cfg = r.config
    assert cfg["source"] == ("https://huggingface.co/ibm-granite/"
                             "granite-4.0-h-micro/blob/main/config.json")
    assert {k: cfg.get(k, "?") for k in PUBLISHED} == PUBLISHED
    assert [i for i, t in enumerate(cfg["layer_types"])
            if t != "mamba"] == [5, 15, 25, 35]
    assert cfg["reduced"] == [] and cfg["num_hidden_layers"] == 40
    assert len(cfg["assumed"]) >= 5 and cfg["deployment"].startswith(
        "the whole model on one v5e chip, as one replica")
    assert (cfg["slots"], cfg["length"]) == (72, 1536)
    assert cfg["state_dtype"] == "float32"
    assert cfg["model"] == {**cfg["model"], "d_model": 2048, "n_heads": 32,
                            "n_layers": 40, "obs_dim": 1, "reply_width": 17}
    assert cfg["server"] == {"max_batch": 64, "tick_ms": 2.0,
                             "buckets": [8, 16, 32, 40, 48, 64]}
    t = r.workload["traffic"]
    assert (t["clients"], t["prefix_lengths"]) == (64, [256, 512, 1024])
    assert (t["steps_min"], t["steps_max"], t["step_grid"]) == (256, 512, 64)
    assert max(t["prefix_lengths"]) + t["steps_max"] <= cfg["length"]
    assert {m["name"] for m in r.end_to_end} == {"serve_tokens_per_s",
                                                  "setup_s"}
    assert {m["moves"] for m in r.per_layer} == {"serve_tokens_per_s"}
    names = [m["name"] for m in r.per_layer]
    assert set(NEW) | {"device.idle_pct.serve", "serve.batch_pad_pct",
                       "serve.client_turn_ms"} <= set(names)
    assert "serve.moe_tokens_per_expert" not in names
    assert "kernel.gdn_update_hbm_pct" not in names
    assert len(names) == 14 + 3
    assert set(r.workload["check"]["why"]) == set(
        r.workload["check"]["limits"])
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert [m["name"] for m in bench["per_layer"][-3:]] == list(NEW)
    for m in bench["per_layer"][-3:]:
        assert (m["workloads"], m["source"], m["unit"]) == (
            [CELL], "program_counter", "%")


def test_parameters_and_slot_bytes_against_the_sizing():
    cfg = run.resolve_cell(CELL).config
    w = flops_granite4h.weight_counts(cfg)
    assert flops_granite4h.layer_counts(cfg) == {"mamba": 36, "attention": 4}
    # W_in 2048 x 8512, taps and bias over 4352, three vectors of 64, the
    # gated norm's 4096, W_out 4096 x 2048
    assert w["mamba"] == (2048 * 8512 + 5 * 4352 + 3 * 64 + 4096
                          + 4096 * 2048)
    assert w["mamba"] / 1e6 == pytest.approx(25.85, rel=1e-3)
    assert w["attention"] / 1e6 == pytest.approx(10.49, rel=1e-3)
    assert w["mlp"] / 1e6 == pytest.approx(50.33, rel=1e-3)
    assert w["embed"] / 1e6 == pytest.approx(205.5, rel=1e-3)
    assert w["head"] == 0  # tied
    n = flops_granite4h.param_count(cfg)
    assert n / 1e9 == pytest.approx(3.19, rel=2e-3)
    assert 2 * n / 1e9 == pytest.approx(6.38, rel=2e-3)
    # what make_params makes is what is counted
    made = sum(int(np.prod(shape))
               for _, shape, _, _ in reference_granite4h.leaf_shapes(cfg))
    assert made == n
    slot = flops_granite4h.slot_bytes(cfg, cfg["length"])
    assert slot == {"state": 36 * 64 * 64 * 128 * 4,
                    "tails": 36 * 3 * 4352 * 2,
                    "kv": 4 * 1536 * 2 * 8 * 64 * 2}
    assert flops_granite4h.state_row_bytes(cfg) == 36 * (2_097_152 + 26_112)
    assert sum(slot.values()) / 1e6 == pytest.approx(89.0, rel=1e-3)
    assert 73 * sum(slot.values()) / 1e9 == pytest.approx(6.50, rel=2e-3)
    assert (2 * n + 73 * sum(slot.values())) / 2 ** 30 / 16 \
        == pytest.approx(0.75, abs=0.01)


def test_decode_flops_and_bytes_against_hand_worked_values():
    cfg = run.resolve_cell(CELL).config
    w = flops_granite4h.weight_counts(cfg)
    streamed = flops_granite4h.layer_params(cfg) + w["embed"]
    assert 2 * streamed / 1e9 == pytest.approx(6.38, rel=2e-3)
    # one tick of 32 rows, each at 700 live positions: the layers and the
    # table, 32 embedding rows, 32 rows' state and tails read and written,
    # four layers' live K/V at 2 x 512 x 2 B a position
    state = 2 * 32 * 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    want = (2 * streamed + 32 * 2048 * 2 + state + 4 * 32 * 700 * 2048)
    assert flops_granite4h.decode_bytes(cfg, 1, 32, 32 * 700) == want
    assert flops_granite4h.decode_bytes(cfg, 1, 32, 32 * 700, state) == want
    assert state / 1e9 == pytest.approx(4.89, rel=1e-2)
    assert 11e9 < want < 12e9
    vectors = 81 * 2048 + 36 * (5 * 4352 + 3 * 64 + 4096)
    want = (2.0 * (streamed - vectors) + 36 * 5.0 * 64 * 64 * 128
            + 4 * 700 * 32 * 4.0 * 64)
    assert flops_granite4h.decode_flops(cfg, 1, 700) == want
