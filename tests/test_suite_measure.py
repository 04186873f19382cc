"""Unit tests for the round-4 measurement core in benchmarks/suite_device.py:
differential-chain step timing and fence-based stream windows (the machinery
every artifact number now rests on)."""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks._common import Budget  # noqa: E402
from benchmarks.suite_device import (  # noqa: E402
    _measure_stream,
    _stats,
    flops_report,
    measure_step_time,
)
from blendjax.utils.timing import StageTimer  # noqa: E402


def _toy_step():
    @jax.jit
    def step(state, batch):
        w = state["w"] + 0.001 * jnp.sum(batch["x"])
        return {"w": w}, jnp.sum(w)

    return step, {"w": jnp.ones((8, 8))}


def test_measure_step_time_returns_positive_median_and_windows():
    step, state = _toy_step()
    batch = {"x": jnp.ones((4, 4))}
    stats, state2 = measure_step_time(step, state, batch, Budget(300),
                                      windows=2)
    assert stats["step_s"] > 0
    assert stats["fence"] == "value_fetch"
    assert stats["step_ms_windows"]["n"] >= 1
    assert stats["step_ms_windows"]["min"] <= stats["step_ms_windows"]["max"]
    assert stats["chain"][1] > stats["chain"][0]
    # state threaded through the chains, not discarded
    assert float(jnp.sum(state2["w"])) != float(jnp.sum(state["w"]))


class _FakeStream:
    """Minimal JaxStream stand-in: host batches + a StageTimer."""

    def __init__(self, n_batches, delay_s=0.0):
        self.timer = StageTimer()
        self._n = n_batches
        self._delay = delay_s

    def __iter__(self):
        def gen():
            for i in range(self._n):
                if self._delay:
                    time.sleep(self._delay)
                yield {"x": np.full((2, 3), i, np.float32)}

        g = gen()

        class _It:
            def __iter__(self):
                return self

            def __next__(self):
                return next(g)

            def close(self):
                g.close()

        return _It()


def test_measure_stream_hbm_windows_and_stages():
    # paced feed so three 0.15s windows cannot exhaust the stream
    stream = _FakeStream(n_batches=400, delay_s=0.002)
    res, _ = _measure_stream(
        stream, window_s=0.15, warmup_batches=2, batch_size=2,
        fence_every=4, windows=3, budget=Budget(120),
    )
    assert res["items_per_sec"] > 0
    assert res["items_per_sec_windows"]["n"] == 3
    assert res["fence"] == "value_fetch"
    # the loop's own stages were recorded for the median window
    assert "feed_wait" in res["stages"]
    assert "dispatch" in res["stages"]
    assert "fence" in res["stages"]


def test_measure_stream_train_duty_cycle_and_chain():
    step, state = _toy_step()
    # paced feed: the claimed step_s (1 ms) is a plausible fraction of
    # the 2 ms inter-batch delay, so duty lands in (0, 1]
    stream = _FakeStream(n_batches=400, delay_s=0.002)
    res, state2 = _measure_stream(
        stream, window_s=0.15, warmup_batches=2, batch_size=2,
        train_step=step, state=state, step_s=0.001,
        fence_every=4, windows=2, budget=Budget(120),
    )
    assert res["step_s"] == 0.001
    assert 0 < res["train_duty_cycle"] <= 1.02
    assert "duty_cycle_invalid" not in res
    assert float(jnp.sum(state2["w"])) != float(jnp.sum(state["w"]))


def test_measure_stream_duty_cycle_unclamped_and_flagged():
    """An impossible duty cycle (step_s x batches exceeding the window)
    must be reported unclamped and flagged, mirroring mfu_invalid —
    clamping to 1.0 was VERDICT r4 weak #3."""
    step, state = _toy_step()
    stream = _FakeStream(n_batches=400)
    res, _ = _measure_stream(
        stream, window_s=0.15, warmup_batches=2, batch_size=2,
        train_step=step, state=state, step_s=0.5,  # absurd claimed step
        fence_every=4, windows=1, budget=Budget(120),
    )
    assert res["train_duty_cycle"] > 1.02
    assert res["duty_cycle_invalid"] is True
    assert "duty_cycle_diagnostic" in res


def test_measure_stream_exhaustion_keeps_partial_window():
    stream = _FakeStream(n_batches=12)
    res, _ = _measure_stream(
        stream, window_s=30.0, warmup_batches=2, batch_size=2,
        fence_every=4, windows=3, budget=Budget(120),
    )
    assert res["batches"] == 10  # 12 - 2 warmup, one partial window
    assert res["items_per_sec_windows"]["n"] == 1


def test_flops_report_flags_impossible_mfu_without_clamping():
    peak = 100e12
    entry = flops_report({}, step_s=0.001, flops_xla=None,
                         flops_analytic=1e12, peak=peak)
    # 1e12 flops in 1 ms = 1e15/s = 10x peak: must flag, must NOT clamp
    assert entry["mfu"] == pytest.approx(10.0)
    assert entry["mfu_invalid"] is True
    ok = flops_report({}, step_s=1.0, flops_xla=2e12, flops_analytic=1e12,
                      peak=peak)
    assert ok["mfu"] == pytest.approx(0.01)
    assert "mfu_invalid" not in ok
    assert ok["flops_xla_over_analytic"] == pytest.approx(2.0)


def test_stats_min_median_max():
    s = _stats([3.0, 1.0, 2.0])
    assert (s["min"], s["median"], s["max"], s["n"]) == (1.0, 2.0, 3.0, 3)


def test_phase_kernel_microverdicts_banks_incrementally(capsys):
    """The bare-kernel verdict phase emits one record per measurement
    the moment it exists (kernel_flash -> kernel_flash_vs_full ->
    kernel_topk -> kernel_topk_vs_dense), each preceded by a progress
    heartbeat — a kill at any point keeps everything banked so far.  Tiny shapes; interpret-mode flash off-TPU."""
    import argparse
    import json

    from benchmarks.suite_device import phase_kernel_microverdicts

    args = argparse.Namespace(
        seq_len=33, n_heads=2, d_model=32, windows=1,
        moe_experts=4, moe_topk=2, moe_dispatch="sort",
        skip_seqformer=False, skip_moe=False,
    )
    tag = {"platform": "cpu", "config": "small"}
    phase_kernel_microverdicts(args, Budget(600), tag)
    lines = [json.loads(s) for s in
             capsys.readouterr().out.strip().splitlines()]
    by_phase = {}
    order = []
    for l in lines:
        by_phase[l["phase"]] = l
        order.append(l["phase"])

    # every measurement record banked, heartbeat before each compile
    for ph in ("kernel_flash", "kernel_flash_vs_full", "kernel_topk",
               "kernel_topk_vs_dense"):
        assert ph in by_phase, order
    assert order.count("progress") == 4
    assert order.index("kernel_flash") < order.index("kernel_topk")

    kf = by_phase["kernel_flash"]
    assert kf["compiled"] is False  # interpret mode off-TPU
    assert kf["step_stats"]["step_s"] > 0
    assert kf["step_stats"]["fence"] == "value_fetch"
    kff = by_phase["kernel_flash_vs_full"]
    assert kff["flash_over_full_kernel"] > 0
    assert kff["flash_step_ms"] > 0 and kff["full_step_ms"] > 0
    ktd = by_phase["kernel_topk_vs_dense"]
    assert ktd["topk_over_dense_kernel"] > 0
    assert ktd["experts"] == 4 and ktd["top_k"] == 2

    # the windowed-flash witness needs T >= 256: absent at this size
    assert "kernel_flash_windowed" not in by_phase

    # operator skip flags suppress the matching halves (and their input
    # tensors are then never built)
    args.skip_seqformer = True
    args.skip_moe = True
    phase_kernel_microverdicts(args, Budget(600), tag)
    assert capsys.readouterr().out == ""


def test_phase_kernel_microverdicts_windowed_witness(capsys):
    """At T >= 256 the phase also times the sliding-window kernel at
    W = T/4 and ships the windowed/flash ratio."""
    import argparse
    import json

    from benchmarks.suite_device import phase_kernel_microverdicts

    args = argparse.Namespace(
        seq_len=257, n_heads=2, d_model=32, windows=1,
        moe_experts=4, moe_topk=2, moe_dispatch="sort",
        skip_seqformer=False, skip_moe=True,
    )
    phase_kernel_microverdicts(
        args, Budget(900), {"platform": "cpu", "config": "small"}
    )
    lines = [json.loads(s) for s in
             capsys.readouterr().out.strip().splitlines()]
    rec = [l for l in lines if l["phase"] == "kernel_flash_windowed"]
    assert len(rec) == 1
    rec = rec[0]
    assert rec["window"] == 64
    assert rec["windowed_over_flash"] > 0
    assert rec["windowed_step_ms"] > 0


def test_apply_config_n_layers_default_and_override():
    """--n-layers defaults by config (8 big / 2 small); an explicit
    value always wins."""
    import argparse

    from benchmarks.suite_device import apply_config

    def ns(config, n_layers):
        return argparse.Namespace(
            config=config, n_layers=n_layers, seq_len=513, d_model=1024,
            n_heads=8, seq_instances=2, width=640, height=480,
        )

    assert apply_config(ns("big", None)).n_layers == 8
    assert apply_config(ns("small", None)).n_layers == 2
    assert apply_config(ns("small", 4)).n_layers == 4
    assert apply_config(ns("big", 2)).n_layers == 2


def test_peak_flops_raises_on_unknown_device():
    """A device that is not in the peak table is an error, not a
    default (here: the CPU backend)."""
    from benchmarks.suite_device import mfu_peak, peak_flops

    with pytest.raises(LookupError):
        peak_flops()
    assert mfu_peak({"platform": "cpu"}) is None


def test_phase_put_strategy_emits_winner_and_loser(capsys):
    """The transfer-granularity probe ships winner AND loser; gated to
    tpu-tagged runs (on loopback it measures dispatch, not a strategy).
    The tag is a label, so the phase body runs fine on the CPU backend."""
    import argparse
    import json

    from benchmarks.suite_device import phase_put_strategy

    args = argparse.Namespace(batch=4, height=16, width=16, channels=3)
    tag = {"platform": "cpu"}
    phase_put_strategy(args, Budget(120), tag)
    assert capsys.readouterr().out == ""  # cpu: no emission

    tag = {"platform": "tpu"}
    phase_put_strategy(args, Budget(120), tag)
    line = json.loads(capsys.readouterr().out.strip())
    assert line["phase"] == "put_strategy"
    assert line["winner"] in ("chunked", "whole")
    assert line["chunks"] == 4
    assert line["chunked_over_whole"] > 0
    assert {"min", "median", "max", "n"} <= set(line["whole_s"])
    assert line["fence"] == "value_fetch"


def test_phase_int8_infer_emits_ratio(capsys):
    """The int8-vs-bf16 inference exhibit: TPU-gated (tag-label gated —
    the body runs fine on the CPU backend), one record with both step
    times and the ratio."""
    import argparse
    import json

    from benchmarks.suite_device import phase_int8_infer

    args = argparse.Namespace(batch=2, height=32, width=32, windows=1)
    phase_int8_infer(args, Budget(300), {"platform": "cpu"})
    assert capsys.readouterr().out == ""  # cpu: no emission

    phase_int8_infer(args, Budget(300), {"platform": "tpu"})
    lines = [json.loads(s) for s in
             capsys.readouterr().out.strip().splitlines()]
    rec = [l for l in lines if l["phase"] == "int8_infer"]
    assert len(rec) == 1
    rec = rec[0]
    assert rec["int8_over_bf16"] > 0
    assert rec["bf16_step_ms"] > 0 and rec["int8_step_ms"] > 0
    assert any(l["phase"] == "progress" for l in lines)
