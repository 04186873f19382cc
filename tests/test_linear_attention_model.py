"""A token model of gated delta-rule linear attention (a float32 matrix
state a head, three convolution tails) beside full softmax attention with a
q/k norm, norms after the sublayers, against the plain reference
(``chipbench/reference_olmohybrid.py``, which imports nothing of the
program): two periods of three linear layers and one full layer, hidden 64,
2 heads of ``dk`` 8 / ``dv`` 16, vocabulary 128, seeded weights.

Tolerances.  Float32 against float32 at one layer agrees to 1e-5 (the
chunked form against the position-by-position recurrence, a step against
the sequence form).  Through the eight layers the rounding of one is
amplified by the norms after every sublayer (a sublayer's input is the
un-normed residual stream, and the gated norm over ``dv`` divides by the
size of a read that may be small), and the logits (standard deviation 1)
agree to 5e-4 .. 2.1e-3 at 150 positions: ``ATOL`` is 5e-3, and a state
left behind or a decay dropped reads above 0.05.  In bfloat16 the served
logits lie a median 0.06 from the float32 reference over the same weights;
the reference at int8 lies a median 0.38 away: ``BF16_P50`` 0.15 stands
between.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blendjax.models import deltanet, seqformer
from blendjax.serve.server import HYBRID_EVENTS, SeqFormerModel
from chipbench import reference_olmohybrid as ref

PERIOD = ["linear_attention"] * 3 + ["full_attention"]
TINY = dict(
    hidden_size=64, intermediate_size=128, num_hidden_layers=8,
    num_attention_heads=2, num_key_value_heads=2, layer_types=PERIOD * 2,
    linear_num_key_heads=2, linear_num_value_heads=2, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rms_norm_eps=1e-6, vocab_size=128,
    tie_word_embeddings=False)
ATOL = 5e-3
BF16_P50 = 0.15


def make(seed=0, dtype=jnp.float32):
    arrays = ref.make_params(TINY, seed, dtype)
    served = seqformer.describe_token_model(
        jax.tree.map(lambda x: x, arrays), TINY)
    return arrays, served


def ids_for(seed, n):
    return np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], n).astype(np.int32)


def serve(served, slots=3, length=64, dtype=jnp.float32):
    return SeqFormerModel(served, slots=slots, length=length,
                          compute_dtype=dtype, cache_dtype=dtype)


def logits_at(reply, want):
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


def rule_inputs(seed, b, t, h=2, dk=8, dv=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (b, t, h, dk))
    k = jax.random.normal(ks[1], (b, t, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -1.5 * jax.random.uniform(ks[3], (b, t, h))
    beta = 2.0 * jax.random.uniform(ks[4], (b, t, h))
    return q, k, v, g, beta, jax.random.normal(ks[5], (b, h, dv, dk))


def test_the_kinds_are_the_configurations_own_list_on_both_sides():
    assert seqformer.hybrid_layer_kinds(TINY) == ["gdn"] * 3 + ["full"] \
        + ["gdn"] * 3 + ["full"]
    assert ref.layer_kinds(TINY) == [
        {"gdn": "linear", "full": "full"}[k]
        for k in seqformer.hybrid_layer_kinds(TINY)]
    # a cut in depth keeps the list's head
    assert seqformer.hybrid_layer_kinds(
        dict(TINY, num_hidden_layers=5)) == ["gdn"] * 3 + ["full", "gdn"]
    with pytest.raises(ValueError, match="layer_types"):
        seqformer.hybrid_layer_kinds(dict(TINY, num_hidden_layers=9))


def test_init_has_the_layout_the_reference_reads():
    served = seqformer.init_linear_hybrid_model(jax.random.PRNGKey(0), TINY)
    arrays = ref.make_params(TINY, 0, jnp.float32)
    leaves = lambda t: sorted(  # noqa: E731
        (jax.tree_util.keystr(k), v.shape, str(v.dtype))
        for k, v in jax.tree_util.tree_leaves_with_path(t))
    assert leaves(served) == leaves(arrays)
    assert served["blocks"][0]["gdn"]["spec"] == deltanet.GdnSpec(True, 1e-6)
    with pytest.raises(ValueError, match="by the configuration"):
        seqformer.describe_token_model(
            {**arrays, "blocks": arrays["blocks"][::-1]}, TINY)


# one ragged chunk, whole chunks, whole chunks and a ragged one
@pytest.mark.parametrize("t", [5, 64, 128, 150])
def test_the_chunked_form_equals_the_recurrence_from_a_nonzero_state(t):
    q, k, v, g, beta, s0 = rule_inputs(t, 2, t)
    o, s = deltanet.chunked_rule(q, k, v, g, beta, s0)
    for b in range(2):
        o_ref, s_ref = ref.delta_rule(q[b], k[b], v[b], jnp.exp(g[b]),
                                      beta[b], s0[b])
        np.testing.assert_allclose(o[b], o_ref, atol=1e-5)
        np.testing.assert_allclose(s[b], s_ref, atol=1e-5)


def test_the_one_step_update_equals_the_sequence_forms_next_position():
    _, served = make()
    p = served["blocks"][0]["gdn"]
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 70, 64))
    zeros = [jnp.zeros((2, *shape)) for shape in deltanet.state_shapes(p)]
    out, *state = deltanet.mix_sequence(p, x, *zeros, jnp.float32)
    _, *state69 = deltanet.mix_sequence(p, x[:, :69], *zeros, jnp.float32)
    out1, *state1 = deltanet.mix_step(p, x[:, 69], *state69, jnp.float32)
    np.testing.assert_allclose(out1, out[:, 69], atol=1e-5)
    np.testing.assert_allclose(state1[0], state[0], atol=1e-5)
    for got, want in zip(state1[1:], state[1:]):  # the tails
        np.testing.assert_allclose(got, want, atol=1e-5)


def test_beta_passes_one_and_the_state_stays_bounded_over_2048_steps():
    """``linear_allow_neg_eigval``: ``beta`` in (0, 2), so ``I - beta k
    k^T`` has an eigenvalue in (-1, 1) and no step can grow the state
    beyond what it writes."""
    _, served = make()
    p = served["blocks"][0]["gdn"]
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(9), (1, 2048, 64))
    _, beta = deltanet.gates(p, x, jnp.float32)
    assert 1.5 < float(beta.max()) < 2.0 and float(beta.min()) > 0.0
    halved = {**p, "spec": deltanet.GdnSpec(False)}
    assert float(deltanet.gates(halved, x, jnp.float32)[1].max()) < 1.0
    state = [jnp.zeros((1, *shape)) for shape in deltanet.state_shapes(p)]
    step = jax.jit(lambda x_t, *s: deltanet.mix_step(p, x_t, *s,
                                                     jnp.float32))
    worst = 0.0
    for t in range(2048):
        _, *state = step(x[:, t], *state)
        if t % 64 == 63:
            worst = max(worst, float(jnp.abs(state[0]).max()))
    assert np.isfinite(worst) and worst < 50.0
    # the same 2048 positions in chunks end in the same state
    zeros = [jnp.zeros((1, *shape)) for shape in deltanet.state_shapes(p)]
    _, s_chunks, *_ = deltanet.mix_sequence(p, x, *zeros, jnp.float32)
    np.testing.assert_allclose(state[0], s_chunks, atol=1e-4)


@pytest.mark.parametrize("n", [24, 150])
def test_forward_equals_reference_logits(n):
    arrays, served = make()
    ids = ids_for(1, n)
    got, _ = seqformer._forward(served, ids[None], compute_dtype=jnp.float32)
    np.testing.assert_allclose(got[0], ref.forward(arrays, TINY, ids),
                               atol=ATOL)


def test_the_pool_holds_a_matrix_state_three_tails_and_full_kv():
    _, served = make()
    cache = seqformer.init_cache(served, 4, dtype=jnp.bfloat16, length=32,
                                 per_row=True)
    assert sorted(cache) == ["gdn_s", "gdn_tail_k", "gdn_tail_q",
                             "gdn_tail_v", "k", "pos", "v"]
    shapes = {name: [None if t is None else (t.shape, str(t.dtype))
                     for t in cache[name]] for name in cache if name != "pos"}
    full = ((4, 32, 64), "bfloat16")
    assert shapes["k"] == shapes["v"] == [None] * 3 + [full] + [None] * 3 \
        + [full]
    lin = [True] * 3 + [False] + [True] * 3 + [False]
    assert shapes["gdn_s"] == [((4, 2, 16, 8), "float32") if on else None
                               for on in lin]
    assert shapes["gdn_tail_q"] == shapes["gdn_tail_k"] == [
        ((4, 48), "bfloat16") if on else None for on in lin]
    assert shapes["gdn_tail_v"] == [((4, 96), "bfloat16") if on else None
                                    for on in lin]
    assert seqformer.state_row_bytes(cache) == 6 * (
        2 * 16 * 8 * 4 + 3 * (16 + 16 + 32) * 2)
    # at the published sizes the 30 heads go in three pieces of ten, each
    # under the gather's slice limit
    assert seqformer._state_leaf(0, (30, 192, 96)) == (3, 10, 192, 96)
    assert seqformer._state_leaf(0, (16, 5120)) == (16, 5120)
    assert seqformer._state_leaf(1, (3, 2880)) == (8640,)


# a ragged chunk, whole chunks (and the flash kernel under the full
# layers, interpreted here), whole chunks and a ragged one
@pytest.mark.parametrize("t0", [5, 64, 77])
def test_prefill_then_steps_through_the_pool_equal_the_full_forward(t0):
    arrays, served = make()
    model = serve(served, length=96)
    ids = ids_for(2, t0 + 12)
    want = np.asarray(ref.forward(arrays, TINY, ids))
    replies = run_episode(model, 1, ids, t0)
    gaps = [logits_at(r, want[t0 - 1 + i]) for i, r in enumerate(replies)]
    assert max(gaps) < ATOL, gaps
    events = model.drain_events()
    assert events[HYBRID_EVENTS[1]] == 12          # rows stepped
    assert events[HYBRID_EVENTS[0]] == sum(range(t0 + 1, t0 + 13))
    assert events[HYBRID_EVENTS[2]] == 0           # no window layer
    assert events[HYBRID_EVENTS[3]] == 1           # the reset's zeroing
    assert events[HYBRID_EVENTS[4]] == 12 * 2 * seqformer.state_row_bytes(
        model._cache)


def test_two_rows_at_different_positions_step_in_one_padded_batch():
    arrays, served = make()
    model = serve(served)
    eps = [(0, ids_for(3, 20), 11), (2, ids_for(4, 14), 5)]
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
            assert logits_at(replies[j], wants[j][t0 + k]) < ATOL
    assert model.drain_events()[HYBRID_EVENTS[1]] == 18  # pad rows not counted


def test_a_pad_rows_step_leaves_every_real_rows_state_bit_equal():
    _, served = make()
    model = serve(served)
    run_episode(model, 1, ids_for(5, 9), 6)
    before = jax.tree.map(np.array, model._cache)
    pad = np.full(4, model.pad_slot)
    np.asarray(model.step_rows(pad, np.full((4, 1), 7, np.int32)))
    after = jax.tree.map(np.array, model._cache)
    real = np.arange(model.slots)
    for name in before:
        if name == "pos":
            continue
        for was, now in zip(before[name], after[name]):
            if was is not None:
                np.testing.assert_array_equal(was[real], now[real])
    np.testing.assert_array_equal(before["pos"][real], after["pos"][real])
    assert np.any(after["gdn_s"][0][model.pad_slot]
                  != before["gdn_s"][0][model.pad_slot])


@pytest.mark.parametrize("fault", [None, "state_left", "decay_dropped"])
def test_a_reused_slot_answers_as_a_fresh_one_only_if_the_path_is_whole(
        fault, monkeypatch):
    """The next tenant of a slot: its prefill goes on from the row's
    state and tails, which the rewind zeroes.  With the rewind moving
    ``pos`` alone (the benchmark's ``state_not_reset``) the answers carry
    the last tenant's state; with the decay dropped where the step is made
    (``decay_left_out``) they drift from the first step on."""
    arrays, served = make()
    if fault == "decay_dropped":
        real = deltanet.gates
        monkeypatch.setattr(deltanet, "gates", lambda p, x, dtype: (
            jnp.zeros_like(real(p, x, dtype)[0]), real(p, x, dtype)[1])
            if x.ndim == 2 else real(p, x, dtype))
    model = serve(served)
    if fault == "state_left":
        model._rewind = jax.jit(
            lambda cache, rows: {**cache,
                                 "pos": cache["pos"].at[rows].set(0)},
            donate_argnums=(0,))
    run_episode(model, 1, ids_for(6, 17), 9)
    ids = ids_for(7, 15)
    want = np.asarray(ref.forward(arrays, TINY, ids))
    replies = run_episode(model, 1, ids, 6)
    worst = max(logits_at(r, want[5 + i]) for i, r in enumerate(replies))
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
    rms = lambda g: float(np.sqrt(np.mean(g * g)))  # noqa: E731
    assert rms(served_gap) < 0.5 * rms(int8_gap)


def test_a_window_argument_is_refused_and_rollout_does_not_sample():
    _, served = make()
    with pytest.raises(ValueError, match="windows from its description"):
        SeqFormerModel(served, slots=2, length=16, window=4)
    with pytest.raises(ValueError, match="token model"):
        seqformer.rollout(served, jnp.zeros((1, 4, 1)), 2)
