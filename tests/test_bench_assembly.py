"""Unit tests for bench.py's artifact assembly — the carry-through of
evidence (stages, window stats, kernel verdicts) from suite phase lines
into the driver's single JSON object (VERDICT r3 next #1/#5: the r03
driver line DROPPED the per-phase stage breakdowns)."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import assemble, headline  # noqa: E402


def _tpu_phases():
    return {
        "device_init": {"phase": "device_init", "seconds": 0.1,
                        "platform": "tpu", "device_kind": "TPU v5 lite"},
        "host_stream": {"phase": "host_stream", "items_per_sec": 1300.0},
        "stream_to_hbm": {
            "phase": "stream_to_hbm", "platform": "tpu",
            "items_per_sec": 10.4, "batches_per_sec": 1.3, "batches": 7,
            "elapsed_s": 5.4,
            "items_per_sec_windows": {"min": 9.8, "median": 10.4,
                                      "max": 11.0, "n": 3},
            "stages": {"device_put": {"count": 7}},
            "width": 640, "height": 480, "channels": 4,
        },
        "stream_to_train": {
            "phase": "stream_to_train", "platform": "tpu",
            "items_per_sec": 10.1, "batches_per_sec": 1.26, "batches": 7,
            "elapsed_s": 5.6, "step_s": 0.0021, "train_duty_cycle": 0.003,
            "items_per_sec_windows": {"min": 9.2, "median": 10.1,
                                      "max": 10.8, "n": 3},
            "stages": {"feed_wait": {"count": 7}},
            "step_stats": {"step_s": 0.0021, "dispatch_bound": True},
            "step_flops_analytic": 3.8e10, "mfu": 0.09,
            "width": 640, "height": 480, "channels": 4,
        },
        "seqformer_train": {
            "phase": "seqformer_train", "platform": "tpu", "attn": "flash",
            "items_per_sec": 180.0, "batches_per_sec": 22.5,
            "tokens_per_sec": 92160.0, "train_duty_cycle": 0.93,
            "step_s": 0.041, "mfu": 0.33,
            "items_per_sec_windows": {"min": 170, "median": 180,
                                      "max": 190, "n": 3},
            "stages": {"fence": {"count": 3}},
        },
        "moe_compare": {
            "phase": "moe_compare", "platform": "tpu", "experts": 8,
            "top_k": 2, "moe_dispatch": "sort",
            "mlp": {"step_s": 0.02}, "dense": {"step_s": 0.095},
            "topk": {"step_s": 0.04, "dispatch_fraction_measured": 0.98},
            "topk_over_dense_mixture": 0.42,
            "consistent_dense_ge_mlp": True,
        },
        "put_strategy": {
            "phase": "put_strategy", "platform": "tpu", "chunks": 4,
            "whole_s": {"min": 0.78, "median": 0.8, "max": 0.83, "n": 3},
            "chunked_s": {"min": 0.8, "median": 0.82, "max": 0.85, "n": 3},
            "chunked_over_whole": 1.025, "winner": "whole",
            "batch_mb": 9.83,
        },
    }


def test_tpu_evidence_carries_through():
    phases = _tpu_phases()
    phases["stream_to_hbm_gateoff"] = {
        "phase": "stream_to_hbm_gateoff", "platform": "tpu",
        "items_per_sec": 10.2, "transfer_gate": False,
    }
    out = assemble(phases, rl={"value": 9900.0, "vs_baseline": 4.95})
    assert out["stream_to_hbm_gateoff_images_per_sec"] == 10.2
    assert out["metric"] == "cube640x480x4_images_per_sec_stream_to_train"
    assert out["value"] == 10.1
    assert out["train_degraded"] is False
    # the r03 verdict's missing evidence, now mandatory:
    assert out["stream_to_train_stages"]["feed_wait"]["count"] == 7
    assert out["stream_to_train_windows"]["n"] == 3
    assert out["detector_step_stats"]["dispatch_bound"] is True
    assert out["seqformer"]["attn"] == "flash"
    assert out["moe_compare"]["topk_over_dense_mixture"] == 0.42
    assert out["rl_steps_per_sec"] == 9900.0
    # winner AND loser of the transfer-granularity probe ship together
    assert out["put_strategy"]["winner"] == "whole"
    assert out["put_strategy"]["chunked_over_whole"] == 1.025


def test_duty_cycle_invalid_carries_through():
    phases = _tpu_phases()
    phases["stream_to_train"]["train_duty_cycle"] = 1.31
    phases["stream_to_train"]["duty_cycle_invalid"] = True
    out = assemble(phases)
    assert out["train_duty_cycle"] == 1.31  # unclamped
    assert out["duty_cycle_invalid"] is True
    line = headline(out)
    assert line["duty_cycle_invalid"] is True


def test_headline_flags_invalid_seqformer_duty():
    phases = _tpu_phases()
    phases["seqformer_train"]["train_duty_cycle"] = 1.4
    phases["seqformer_train"]["duty_cycle_invalid"] = True
    line = headline(assemble(phases))
    assert line["seq_duty"] == 1.4
    assert line["seq_duty_invalid"] is True


def test_headline_carries_shm_rpc_x():
    """ISSUE-12: the shm-vs-tcp service ratio rides the headline next
    to replay_shard_x (whose service arm now rides the shm wire)."""
    rb = {
        "phase": "replay_bench", "replay_sample_x": 3.9,
        "sharded": {"shards": 2, "capacity": 2048, "batch": 32,
                    "transport": "shm",
                    "replay_shard_batches_per_sec": {},
                    "replay_shard_x": 0.37, "shm_rpc_x": 1.6,
                    "replay_degraded_x": 1.2},
    }
    out = assemble(_tpu_phases(), replay_bench=rb)
    line = headline(out)
    assert line["replay_shard_x"] == 0.37
    assert line["shm_rpc_x"] == 1.6
    assert line["replay_degraded_x"] == 1.2


def test_headline_tail_window_self_sufficient():
    """The compact line printed LAST must fit a 400-byte tail capture and
    carry the verdict even when the full line is truncated."""
    out = assemble(_tpu_phases(), rl={"value": 9900.0, "vs_baseline": 4.95})
    line = json.dumps(headline(out))
    assert len(line) + 1 <= 400, f"headline too long: {len(line)}B"
    # simulate the driver's tail capture over full + headline output
    stdout = json.dumps(out) + "\n" + line + "\n"
    tail = stdout[-400:]
    recovered = json.loads(tail[tail.index("\n") + 1:].strip())
    assert recovered["headline"] is True
    assert recovered["metric"] == "cube640x480x4_images_per_sec_stream_to_train"
    assert recovered["value"] == 10.1
    assert recovered["vs_baseline"] == out["vs_baseline"]
    assert recovered["device"] == "tpu"
    assert recovered["attn"] == "flash"
    assert recovered["topk_over_dense"] == 0.42


def test_kernel_microverdicts_carry_and_headline_fallback():
    """Bare-kernel verdict records (phase_kernel_microverdicts) ride the
    artifact; in the headline they surface ONLY when the stronger
    train-step ratio is absent — a window that banked nothing but the
    micro verdicts still reports them in the tail."""
    phases = _tpu_phases()
    phases["kernel_flash"] = {
        "phase": "kernel_flash", "platform": "tpu", "compiled": True,
        "step_stats": {"step_s": 0.012, "fence": "value_fetch"},
        "seq_len": 512, "heads": 8, "head_dim": 128, "batch": 2,
    }
    phases["kernel_flash_vs_full"] = {
        "phase": "kernel_flash_vs_full", "platform": "tpu",
        "flash_step_ms": 12.0, "full_step_ms": 19.0,
        "flash_over_full_kernel": 0.6316,
    }
    phases["kernel_flash_windowed"] = {
        "phase": "kernel_flash_windowed", "platform": "tpu",
        "window": 128, "windowed_step_ms": 4.1, "flash_step_ms": 12.0,
        "windowed_over_flash": 0.3417,
    }
    phases["kernel_topk_vs_dense"] = {
        "phase": "kernel_topk_vs_dense", "platform": "tpu",
        "topk_step_ms": 8.0, "dense_step_ms": 21.0,
        "topk_over_dense_kernel": 0.381,
    }
    out = assemble(phases, rl=None)
    assert out["kernel_attn"]["flash_over_full_kernel"] == 0.6316
    assert out["kernel_attn"]["flash_compiled"] is True
    assert out["kernel_attn"]["windowed_over_flash"] == 0.3417
    assert out["kernel_attn"]["window"] == 128
    assert out["kernel_moe"]["topk_over_dense_kernel"] == 0.381

    # train-step ratios present: the headline keeps the stronger claim
    out["seqformer"]["flash_over_full"] = 0.71
    line = headline(out)
    assert "flash_over_full_kernel" not in line
    assert "topk_over_dense_kernel" not in line  # moe ratio present

    # micro-only window: kernel ratios surface in the tail line
    out2 = assemble(
        {k: v for k, v in phases.items()
         if k not in ("seqformer_train", "moe_compare")},
        rl=None,
    )
    line2 = headline(out2)
    assert line2["flash_over_full_kernel"] == 0.6316
    assert line2["topk_over_dense_kernel"] == 0.381
    assert len(json.dumps(line2)) + 1 <= 400

    # flash ran compiled but the full-attn comparison never landed:
    # the witness alone still reaches the tail
    out3 = assemble(
        {k: v for k, v in phases.items()
         if k not in ("seqformer_train", "moe_compare",
                      "kernel_flash_vs_full", "kernel_topk_vs_dense")},
        rl=None,
    )
    line3 = headline(out3)
    assert line3["flash_kernel_ran"] is True


def test_banked_partial_records_disclose_truncation():
    """A confirm-first device child killed mid-stream leaves banked
    records (suite_device emits them before the wire-heavy windows); the
    truncation markers must survive assembly so the artifact cannot pass
    a truncated phase off as a complete one."""
    phases = _tpu_phases()
    seq = phases["seqformer_train"]
    for k in ("items_per_sec", "batches_per_sec", "tokens_per_sec",
              "train_duty_cycle", "items_per_sec_windows", "stages"):
        seq.pop(k)
    seq.update({"batches": 0, "stream_pending": True,
                "flash_over_full": 0.71})
    phases["moe_compare"].pop("mlp")
    phases["moe_compare"]["partial"] = True
    out = assemble(phases, rl=None)
    assert out["seqformer"]["stream_pending"] is True
    assert out["seqformer"]["batches"] == 0
    assert out["seqformer"]["flash_over_full"] == 0.71
    assert out["moe_compare"]["partial"] is True
    line = headline(out)
    assert line["seq_partial"] is True
    assert line["flash_over_full"] == 0.71
    assert line["topk_over_dense"] == 0.42
    assert line["moe_partial"] is True
    # the banked shape is the longest headline; it must still fit the
    # tail window, and the trim may only drop recoverable keys — the
    # verdict ratios and honesty flags survive
    s = json.dumps(line)
    assert len(s) + 1 <= 400, f"headline too long: {len(s)}B"
    for k in ("metric", "value", "vs_baseline",
              "flash_over_full", "seq_partial", "topk_over_dense",
              "moe_partial"):
        assert k in line, k


def test_rl_pipelined_compare_line_carries_through():
    """The --compare microbench line (rl_pipelined_x IS the value) must
    reach the extras and the headline; a single-mode pipelined line
    falls back to the drift-prone ratio against the lock-step phase."""
    phases = _tpu_phases()
    out = assemble(
        phases,
        rl={"value": 9900.0, "vs_baseline": 4.95},
        rl_physics={"value": 2872.0, "vs_baseline": 1.44},
        rl_pipelined={
            "metric": "rl_pipelined_x", "value": 2.18,
            "pipeline_depth": 4, "pipelined_steps_per_sec": 1246.8,
        },
    )
    assert out["rl_pipelined_x"] == 2.18
    assert out["rl_pipeline_depth"] == 4
    assert out["rl_steps_per_sec_pipelined"] == 1246.8
    assert headline(out)["rl_pipelined_x"] == 2.18

    out2 = assemble(
        phases,
        rl={"value": 9900.0, "vs_baseline": 4.95},
        rl_physics={"value": 2000.0, "vs_baseline": 1.0},
        rl_pipelined={"metric": "rl_steps_per_sec_pipelined",
                      "value": 5000.0, "pipeline_depth": 4},
    )
    assert out2["rl_steps_per_sec_pipelined"] == 5000.0
    assert out2["rl_pipelined_x"] == 2.5


def test_no_stream_phase_is_an_error():
    """bench.py prints no metric line without a stream phase: there is
    no host-only stand-in to fall back on."""
    import pytest

    with pytest.raises(ValueError):
        assemble({})
