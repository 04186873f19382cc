"""A token model of sliding-window and full grouped-query attention side by
side in one slot pool, each kind with its own rotation, and softmax-routed
experts held 4 of 8, against the plain reference
(``chipbench/reference_mellum2.py``, which imports nothing of the program):
two periods of three window layers and one full layer, hidden 48, 4 query
and 2 K/V heads of 16 (not 48 / 4), window 8, top 2 of 8 experts, a YaRN
rotation on the full layers, vocabulary 96, seeded weights.

Tolerances.  Float32 against float32 agrees to 1e-5 through the eight
layers (the logits' standard deviation is about 1): ``ATOL`` is 1e-4, and a
window left out, a rotation swapped or the renormalisation dropped reads
above 0.05.  In bfloat16 the served logits lie a median 0.017 from the
float32 reference over the same weights; the reference at int8 lies a
median 0.057 away: ``BF16_P50`` 0.03 stands between.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blendjax.models import moe, seqformer
from blendjax.models.layers import rope_table
from blendjax.serve.server import HYBRID_EVENTS, MOE_EVENTS, SeqFormerModel
from chipbench import reference_mellum2 as ref

PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
TINY = dict(
    hidden_size=48, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, num_hidden_layers=8, layer_types=PERIOD * 2,
    sliding_window=8, moe_intermediate_size=24, num_experts=8,
    num_experts_held=4, num_experts_per_tok=2, norm_topk_prob=True,
    rms_norm_eps=1e-6, vocab_size=96,
    rope_parameters={
        "full_attention": dict(
            rope_type="yarn", rope_theta=10000, factor=4,
            original_max_position_embeddings=64, beta_fast=32, beta_slow=1,
            attention_factor=1.2),
        "sliding_attention": dict(rope_type="default", rope_theta=1000)})
ATOL = 1e-4
BF16_P50 = 0.03


def make(seed=0, dtype=jnp.float32, cfg=TINY):
    arrays = ref.make_params(cfg, seed, dtype)
    served = seqformer.describe_token_model(
        jax.tree.map(lambda x: x, arrays), cfg)
    return arrays, served


def ids_for(seed, n):
    return np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], n).astype(np.int32)


def serve(served, slots=3, length=64, dtype=jnp.float32):
    return SeqFormerModel(served, slots=slots, length=length,
                          compute_dtype=dtype, cache_dtype=dtype)


def gap(reply, want):
    """A reply row's top logits against ``want`` (vocab,) at its ids, and
    its logsumexp."""
    k = (len(reply) - 1) // 2
    at = reply[k:2 * k].astype(int)
    lse = np.log(np.sum(np.exp(want - want.max()))) + want.max()
    return max(np.abs(reply[:k] - want[at]).max(), abs(reply[-1] - lse))


def run_episode(model, slot, ids, t0):
    model.reset_rows(np.asarray([slot]))
    replies = [model.prefill_rows(np.asarray([slot]), ids[:t0, None])]
    for t in range(t0, len(ids)):
        replies.append(np.asarray(model.step_rows(
            np.asarray([slot]), ids[t:t + 1, None]))[0])
    return replies


def test_the_kinds_and_static_entries_are_the_configurations_own():
    assert seqformer.hybrid_layer_kinds(TINY) == (
        ["window"] * 3 + ["full"]) * 2
    assert ref.layer_kinds(TINY) == seqformer.hybrid_layer_kinds(TINY)
    _, served = make()
    window, full = served["blocks"][0]["attn"], served["blocks"][3]["attn"]
    assert window == seqformer.AttnSpec(8, 1000.0)
    assert full == seqformer.AttnSpec(None, 10000.0, (4.0, 32, 1, 64), 1.2)
    assert served["blocks"][5]["moe"]["route"] == moe.RouteSpec(
        top_k=2, first=0, score="softmax", renorm=True)
    assert served["blocks"][0]["q_norm"]["scale"].shape == (16,)
    assert seqformer._hybrid(served) and not seqformer._recurrent(served)
    # a model without a recurrent block keeps keys and values alone
    cache = seqformer.init_cache(served, 4, dtype=jnp.bfloat16, length=32,
                                 per_row=True)
    assert sorted(cache) == ["k", "pos", "v"]
    ring, whole = ((4, 8, 32), "bfloat16"), ((4, 32, 32), "bfloat16")
    assert [(t.shape, str(t.dtype)) for t in cache["k"]] == (
        [ring] * 3 + [whole]) * 2
    assert seqformer.state_row_bytes(cache) == 0
    with pytest.raises(ValueError, match="by the configuration"):
        seqformer.describe_token_model(
            {**make()[0], "blocks": [{"gdn": {}}] * 8}, TINY)


@pytest.mark.parametrize("kind", ["window", "full"])
def test_both_rotations_equal_the_references_tables(kind):
    """The program's float32 tables (``rope_table`` from the block's
    ``AttnSpec``) against the reference's, written out in float64 from the
    published keys: the default rotation and YaRN with its
    ``attention_factor``, at the tiny sizes and at the published ones."""
    import json
    import os

    _, served = make()
    spec = served["blocks"][0 if kind == "window" else 3]["attn"]
    pos = jnp.arange(200)
    cos, sin = rope_table(pos, 16, spec.theta, spec.yarn,
                          spec.attention_factor)
    want_cos, want_sin = ref.rope_tables(TINY, kind, 200)
    np.testing.assert_allclose(cos, want_cos, atol=2e-5)
    np.testing.assert_allclose(sin, want_sin, atol=2e-5)
    if kind == "full":
        assert float(jnp.abs(cos[0]).max()) == pytest.approx(1.2)
    path = os.path.join(os.path.dirname(ref.__file__), "configs",
                        "mellum2_ep4_serve_bf16.json")
    with open(path) as f:
        cfg = json.load(f)
    spec = seqformer._attn_spec(
        cfg, "sliding_attention" if kind == "window" else "full_attention",
        kind)
    assert spec.window == (1024 if kind == "window" else None)
    pos = jnp.arange(0, 2560, 7)
    cos, sin = rope_table(pos, 128, spec.theta, spec.yarn,
                          spec.attention_factor)
    want_cos, want_sin = ref.rope_tables(cfg, kind, 2560)
    # float32 angles at positions up to 2560 against float64
    np.testing.assert_allclose(cos, want_cos[::7], atol=3e-4)
    np.testing.assert_allclose(sin, want_sin[::7], atol=3e-4)
    if kind == "full":
        freqs, scale = ref.inv_freq(cfg["rope_parameters"]["full_attention"],
                                    128)
        plain = 500000.0 ** (-np.arange(0, 128, 2) / 128)
        # the ramp runs from pair 18 (kept) to pair 35 (divided by 16)
        np.testing.assert_allclose(freqs[:19], plain[:19])
        np.testing.assert_allclose(freqs[35:], plain[35:] / 16)
        assert plain[20] / 16 < freqs[20] < plain[20]
        assert scale == pytest.approx(1.2772588722239782)


@pytest.mark.parametrize("renorm", [True, False])
def test_the_softmax_router_with_and_without_renormalisation(renorm):
    kw, kx = jax.random.split(jax.random.PRNGKey(5))
    router = {"w": jax.random.normal(kw, (48, 8)) * 48 ** -0.5}
    x = jax.random.normal(kx, (50, 48))
    spec = moe.RouteSpec(top_k=2, score="softmax", renorm=renorm)
    sel, g = moe.route(router, x, spec)
    dense = np.zeros((50, 8))
    np.put_along_axis(dense, np.asarray(sel), np.asarray(g), axis=1)
    want = np.asarray(ref.route(router, x, 2, renorm))
    np.testing.assert_allclose(dense, want, atol=1e-6)
    if renorm:
        np.testing.assert_allclose(g.sum(-1), 1.0, rtol=1e-6)
    else:  # the top 2 of 8 softmax weights keep their share of 1
        assert float(g.sum(-1).max()) < 0.95
    with pytest.raises(ValueError, match="routing score"):
        moe.route(router, x, dataclasses.replace(spec, score="relu"))


def test_the_four_ranks_held_parts_add_up_to_the_uncut_layer():
    """The share test: 8 experts held 2 a rank (``first`` 0, 2, 4, 6); each
    rank routes over all 8 and computes its own experts' part; the four
    parts add up to the reference's whole layer (nothing else is computed
    alike on every rank: no shared expert)."""
    whole_cfg = dict(TINY, num_experts_held=8)
    arrays = ref.make_params(whole_cfg, 3, jnp.float32)
    p = arrays["blocks"][1]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(6), (40, 48))
    want = ref.moe(p, h, 2, True, 0)
    total = jnp.zeros_like(want)
    made = held = 0
    for first in (0, 2, 4, 6):
        part = {"router": p["router"],
                **{n: p[n][first:first + 2] for n in ("gate", "up", "down")},
                "route": moe.RouteSpec(top_k=2, first=first,
                                       score="softmax", renorm=True)}
        y, counts = moe.moe_apply_held(part, h, jnp.float32)
        total = total + y
        made, held = int(counts[0]), held + int(counts[1])
        # the reference given the same share computes the same part
        ref_part = {n: part[n] for n in ("router", "gate", "up", "down")}
        np.testing.assert_allclose(
            y, ref.moe(ref_part, h, 2, True, first), atol=1e-5)
    assert held == made == 40 * 2
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_lane_wide_rows_are_gathered_as_tiles_to_the_references_part():
    """At a width of whole 128-lane tiles (the published 2304 is 18) each
    assignment's row is gathered as tiles; the held part over many tokens
    (a long prefill's) is the reference's."""
    kr, kx, kw = jax.random.split(jax.random.PRNGKey(9), 3)
    d, f, n = 256, 32, 300
    p = {"router": {"w": jax.random.normal(kr, (d, 16)) * d ** -0.5},
         **moe.gated_mlp_init(kw, d, f, stack=(4,)),
         "route": moe.RouteSpec(top_k=8, first=4, score="softmax")}
    x = jax.random.normal(kx, (n, d))
    y, counts = moe.moe_apply_held(p, x, jnp.float32)
    ref_part = {name: p[name] for name in ("router", "gate", "up", "down")}
    np.testing.assert_allclose(y, ref.moe(ref_part, x, 8, True, 4),
                               atol=1e-5)
    assert int(counts[0]) == n * 8 and 0 < int(counts[1]) < n * 8


@pytest.mark.parametrize("n", [24, 40])
def test_forward_equals_reference_logits(n):
    arrays, served = make()
    ids = ids_for(1, n)
    got, _ = seqformer._forward(served, ids[None], compute_dtype=jnp.float32)
    np.testing.assert_allclose(got[0], ref.forward(arrays, TINY, ids),
                               atol=ATOL)


# under the window, at it, past it, and a multiple of 32 (the flash kernel
# under the window, interpreted here): the ring wraps in the prefill and
# in the steps after it
@pytest.mark.parametrize("t0", [5, 8, 13, 32])
def test_prefill_then_steps_through_the_pool_equal_the_full_forward(t0):
    arrays, served = make()
    model = serve(served, length=64)
    ids = ids_for(2, t0 + 12)
    want = np.asarray(ref.forward(arrays, TINY, ids))
    replies = run_episode(model, 1, ids, t0)
    gaps = [gap(r, want[t0 - 1 + i]) for i, r in enumerate(replies)]
    assert max(gaps) < ATOL, gaps
    events = model.drain_events()
    assert set(events) == set(MOE_EVENTS) | set(HYBRID_EVENTS[:3]) | {
        "serve_state_bytes"}
    assert events["serve_rows_stepped"] == 12
    assert events["serve_ctx_positions"] == sum(range(t0 + 1, t0 + 13))
    assert events["serve_window_positions"] == sum(
        min(p, 8) for p in range(t0 + 1, t0 + 13))
    assert events["serve_moe_assignments"] == 12 * 8 * 2
    # nothing recurrent: the reset zeroes nothing and moves nothing
    assert events["serve_state_bytes"] == 0
    assert "serve_state_resets" not in events


def test_two_rows_at_different_positions_step_in_one_padded_batch():
    arrays, served = make()
    model = serve(served)
    eps = [(0, ids_for(3, 30), 11), (2, ids_for(4, 14), 5)]
    wants = [np.asarray(ref.forward(arrays, TINY, ids)) for _, ids, _ in eps]
    for slot, ids, t0 in eps:
        model.reset_rows(np.asarray([slot]))
        model.prefill_rows(np.asarray([slot]), ids[:t0, None])
    idx = np.asarray([0, 2, model.pad_slot, model.pad_slot])
    for k in range(9):
        obs = np.zeros((4, 1), np.int32)
        for j, (_, ids, t0) in enumerate(eps):
            obs[j] = ids[t0 + k]
        replies = np.asarray(model.step_rows(idx, obs))
        for j, (_, _, t0) in enumerate(eps):
            assert gap(replies[j], wants[j][t0 + k]) < ATOL
    events = model.drain_events()
    assert events["serve_rows_stepped"] == 18  # pad rows not counted
    assert events["serve_moe_assignments"] == 18 * 8 * 2


def test_a_pad_rows_step_leaves_every_real_row_bit_equal():
    _, served = make()
    model = serve(served)
    run_episode(model, 1, ids_for(5, 19), 10)
    model.drain_events()
    before = jax.tree.map(np.array, model._cache)
    pad = np.full(4, model.pad_slot)
    np.asarray(model.step_rows(pad, np.full((4, 1), 7, np.int32)))
    after = jax.tree.map(np.array, model._cache)
    real = np.arange(model.slots)
    for name in ("k", "v"):
        for was, now in zip(before[name], after[name]):
            np.testing.assert_array_equal(was[real], now[real])
    np.testing.assert_array_equal(before["pos"][real], after["pos"][real])
    assert np.any(after["k"][0][model.pad_slot]
                  != before["k"][0][model.pad_slot])
    assert model.drain_events()["serve_rows_stepped"] == 0


@pytest.mark.parametrize("fault", [None, "window_dropped", "rope_swapped",
                                   "renorm_dropped"])
def test_a_reused_slot_answers_as_a_fresh_one_only_if_the_path_is_whole(
        fault):
    """The next tenant of a slot: the rewind moves ``pos`` alone and the
    last tenant's keys sit at positions that no query sees.  A window
    dropped from the sliding layers, their rotation swapped for the full
    layers' or the top-k weights left unrenormalised each read far off."""
    arrays, served = make()
    if fault == "window_dropped":
        served = jax.tree.map(lambda x: x, served)
        for blk in served["blocks"]:
            blk["attn"] = dataclasses.replace(blk["attn"], window=None)
    elif fault == "rope_swapped":
        full = served["blocks"][3]["attn"]
        for blk in served["blocks"]:
            if blk["attn"].window:
                blk["attn"] = dataclasses.replace(full, window=8)
    elif fault == "renorm_dropped":
        for blk in served["blocks"]:
            blk["moe"]["route"] = dataclasses.replace(
                blk["moe"]["route"], renorm=False)
    model = serve(served, length=64)
    run_episode(model, 1, ids_for(6, 30), 21)
    ids = ids_for(7, 26)
    want = np.asarray(ref.forward(arrays, TINY, ids))
    replies = run_episode(model, 1, ids, 14)
    worst = max(gap(r, want[13 + i]) for i, r in enumerate(replies))
    if fault:
        assert worst > 0.05
    else:
        assert worst < ATOL


def test_bfloat16_agrees_at_a_tolerance_that_an_int8_pass_fails():
    arrays, served = make(dtype=jnp.bfloat16)
    model = serve(served, length=160, dtype=jnp.bfloat16)
    ids = ids_for(11, 140)
    want = np.asarray(ref.forward(arrays, TINY, ids))
    low = np.asarray(ref.forward(arrays, TINY, ids, quant="int8"))
    replies = np.stack(run_episode(model, 0, ids, 64))
    at = replies[:, 8:16].astype(int)
    pos = 63 + np.arange(len(replies))
    served_gap = np.abs(replies[:, :8] - np.take_along_axis(
        want[pos], at, axis=1))
    int8_gap = np.abs(np.take_along_axis(low[pos], at, axis=1)
                      - np.take_along_axis(want[pos], at, axis=1))
    assert np.median(served_gap) < BF16_P50 < np.median(int8_gap), (
        np.median(served_gap), np.median(int8_gap))


def test_a_window_argument_is_refused():
    _, served = make()
    with pytest.raises(ValueError, match="windows from its description"):
        SeqFormerModel(served, slots=2, length=16, window=4)
