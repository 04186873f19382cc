"""The five per-layer metrics that read the program's own names and counters
(PR 26): each reader on a hand-made ``obs``, the names the kernel readers
match against the program's ``pallas_call``s, and every new entry of
``BENCHMARK.json`` resolved to its metric file and reader.  CPU only:
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

from chipbench import flops, run  # noqa: E402
from chipbench.readers import kernels, serve_phases  # noqa: E402

NEW = ("kernel.flash_fwd_mfu_pct", "kernel.flash_bwd_mfu_pct",
       "serve.prefill_ms", "serve.prefill_block_pct", "serve.idle_wait_ms")
MODEL = {"obs_dim": 32, "d_model": 1024, "n_heads": 8, "n_layers": 8,
         "d_ff": 4096, "max_len": 512}
CTX = types.SimpleNamespace(
    config={"model": MODEL, "seq_len": 512},
    peaks={"bf16_flops_per_s": 197e12})


def _train_obs(device_ops):
    # 13 steps of 64 x 512 tokens in 3 s; the trace covers 1.5 s of them
    return {"tokens": 13 * 64 * 512, "window_s": 3.0, "steps": 13,
            "trace": {"window_s": 1.5, "busy_s": 1.5,
                      "device_ops": device_ops}}


def test_attention_flops_are_the_forward_counts_second_term():
    whole = flops.forward_flops_per_token(MODEL, 512)
    assert kernels.attention_forward_flops_per_token(MODEL, 512) == \
        whole - 2.0 * flops.matmul_params(MODEL)
    # 2.75e11 a step of 64 x 512, as the issue reckons
    assert 64 * 512 * kernels.attention_forward_flops_per_token(
        MODEL, 512) == pytest.approx(2.749e11, rel=1e-3)


def test_kernel_readers_hand_worked():
    obs = _train_obs([["fusion", 0.6],
                      ["jvp_flash_fwd_[tpu_custom_call]", 0.25],
                      ["flash_bwd_dkv[tpu_custom_call]", 0.3],
                      ["flash_bwd_dq[tpu_custom_call]", 0.2]])
    tokens_traced = 13 * 64 * 512 / 3.0 * 1.5
    per_token = 8 * 2.0 * 512 * 1024
    assert kernels.flash_fwd_mfu_pct(obs, CTX) == pytest.approx(
        100 * tokens_traced * per_token / 0.25 / 197e12)
    assert kernels.flash_bwd_mfu_pct(obs, CTX) == pytest.approx(
        100 * 2 * tokens_traced * per_token / 0.5 / 197e12)
    assert 0 < kernels.flash_fwd_mfu_pct(obs, CTX) < 100


@pytest.mark.parametrize("reader", [kernels.flash_fwd_mfu_pct,
                                    kernels.flash_bwd_mfu_pct])
def test_kernel_readers_find_nothing_on_a_program_without_names(reader):
    parent_rows = [["fusion", 1.05],
                   ["transpose_jvp___[tpu_custom_call]", 0.885],
                   ["jvp__[tpu_custom_call]", 0.466]]
    assert reader(_train_obs(parent_rows), CTX) is None
    assert reader(_train_obs([]), CTX) is None
    assert reader({"tokens": 1, "window_s": 1.0, "trace": None}, CTX) is None
    named = [["flash_fwd[tpu_custom_call]", 0.2],
             ["flash_bwd_dq[tpu_custom_call]", 0.2]]
    assert reader(dict(_train_obs(named), tokens=0), CTX) is None
    assert reader(dict(_train_obs(named), window_s=0.0), CTX) is None


def _serve_obs(**events):
    return {"window_s": 30.0, "events": events}


def test_serve_phase_readers_hand_worked():
    obs = _serve_obs(serve_prefill_us=3_000_000, serve_prefills=120,
                     serve_idle_us=1_350_000, serve_batches=675)
    assert serve_phases.prefill_ms(obs, None) == pytest.approx(25.0)
    assert serve_phases.prefill_block_pct(obs, None) == pytest.approx(10.0)
    assert serve_phases.idle_wait_ms(obs, None) == pytest.approx(2.0)


@pytest.mark.parametrize("reader,counter,count", [
    (serve_phases.prefill_ms, "serve_prefill_us", "serve_prefills"),
    (serve_phases.idle_wait_ms, "serve_idle_us", "serve_batches"),
])
def test_serve_phase_readers_none_without_counter_or_count(
        reader, counter, count):
    # the parent's server counts neither phase: the key is not there
    assert reader(_serve_obs(**{count: 10}), None) is None
    assert reader(_serve_obs(**{counter: 5, count: 0}), None) is None
    assert reader(_serve_obs(**{counter: 5}), None) is None
    assert reader({"window_s": 30.0}, None) is None
    assert reader(_serve_obs(**{counter: 0, count: 10}), None) == 0.0


def test_prefill_block_none_without_counter_or_window():
    assert serve_phases.prefill_block_pct(_serve_obs(), None) is None
    assert serve_phases.prefill_block_pct(
        {"window_s": 0.0, "events": {"serve_prefill_us": 5}}, None) is None
    assert serve_phases.prefill_block_pct(
        _serve_obs(serve_prefill_us=0), None) == 0.0


def _pallas_names(fn, *args):
    import jax

    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


def test_readers_match_the_programs_kernel_names():
    import jax
    import jax.numpy as jnp

    from blendjax.ops.flash_attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, True, None, 32, 32, True).sum()

    x = jnp.ones((1, 64, 2, 32), jnp.float32)
    forward = _pallas_names(loss, x, x, x)
    both = _pallas_names(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
    backward = [n for n in both if n not in forward]
    assert forward and all(kernels.FLASH_FWD in n for n in forward)
    assert len(set(backward)) == 2  # dq and dkv, told apart
    assert all(kernels.FLASH_BWD in n for n in backward)
    # neither needle catches the other side's kernels
    assert not any(kernels.FLASH_BWD in n for n in forward)
    assert not any(kernels.FLASH_FWD in n for n in backward)


def test_new_entries_resolve_and_both_cells_still_do():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entries = {m["name"]: m for m in bench["per_layer"]}
    # a membership, not a position: later PRs append their own metrics
    assert set(NEW) <= set(entries)
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in NEW} | {"kernels"}
    seen = set()
    for cell in bench["workloads"]:
        resolved = run.resolve_cell(cell["name"])
        for m in resolved.per_layer:
            assert callable(run._resolve(m["reader"])), m["name"]
            if m["name"] in NEW:
                seen.add(m["name"])
                # the cell reports the end-to-end metric this one moves
                assert cell["name"] in end_to_end[m["moves"]]["workloads"]
    assert seen == set(NEW)
    for name in NEW:
        assert entries[name]["layer"] in layers
        assert entries[name]["unit"] in ("%", "ms")
