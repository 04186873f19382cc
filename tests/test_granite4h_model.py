"""A token model of Mamba-2 layers (a float32 ``(P, N)`` state a head, one
group of B and C for all heads, a scalar decay a head) beside grouped-query
attention without a position encoding, norms before the sublayers and the
four muP multipliers, against the plain reference
(``chipbench/reference_granite4h.py``, which imports nothing of the
program): two periods of a Mamba-2 layer and an attention layer, hidden 64,
8 Mamba-2 heads of 16 (``d_inner`` 128, not the hidden size), a state of
16, a chunk of 8, 4 query heads of 16 over 2 K/V heads, vocabulary 128,
tied, seeded weights.

Tolerances.  Float32 against float32 agrees to about 3e-6 on logits of
standard deviation ~1 (the chunked form against the position-by-position
recurrence, a step against the sequence form; the norms before the
sublayers amplify nothing): ``ATOL`` is 1e-4.  At four layers the residual
stream is mostly the embedding (its rows times 12 against each sublayer's
output times 0.22), so what a fault moves moves the logits little, yet far
above rounding: a state left behind reads 0.007, the decay dropped 0.014,
the gated norm dropped 0.050, the attention scale read as ``1 / sqrt(Dh)``
0.024, and the other multipliers 0.38 and more; each is held above ten
times ``ATOL``.  For the same reason bfloat16 and int8 lie close: the
served logits a median 0.0029 from the float32 reference over the same
weights, the reference at int8 0.0095: ``BF16_P50`` 0.005 stands between.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blendjax.models import mamba2, seqformer
from blendjax.serve.server import HYBRID_EVENTS, SeqFormerModel
from chipbench import reference_granite4h as ref

TINY = dict(
    model_type="granitemoehybrid", hidden_size=64,
    shared_intermediate_size=128, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2,
    layer_types=["mamba", "attention"] * 2, mamba_n_heads=8,
    mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
    mamba_n_groups=1, mamba_chunk_size=8, rms_norm_eps=1e-5,
    vocab_size=128, tie_word_embeddings=True, embedding_multiplier=12,
    residual_multiplier=0.22, logits_scaling=8,
    attention_multiplier=0.0625, position_embedding_type="nope",
    rope_theta=10000, num_local_experts=0)
ATOL = 1e-4
BF16_P50 = 0.005


def make(seed=0, dtype=jnp.float32, config=TINY):
    arrays = ref.make_params(TINY, seed, dtype)
    served = seqformer.describe_token_model(
        jax.tree.map(lambda x: x, arrays), config)
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


def scan_inputs(seed, b, t, h=8, p=16, n=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)) - 2.0)
    a = -jax.random.uniform(ks[2], (h,), minval=1.0, maxval=16.0)
    bm = jax.random.normal(ks[3], (b, t, n))
    cm = jax.random.normal(ks[4], (b, t, n))
    return x, dt, a, bm, cm, jax.random.normal(ks[5], (b, h, p, n))


def test_the_kinds_are_the_configurations_own_list_on_both_sides():
    assert seqformer.hybrid_layer_kinds(TINY) == ["ssd", "full"] * 2
    assert ref.layer_kinds(TINY) == ["mamba", "attention"] * 2
    published = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert seqformer.hybrid_layer_kinds(dict(
        TINY, layer_types=published, num_hidden_layers=10)) == \
        ["ssd"] * 5 + ["full"] + ["ssd"] * 4


def test_init_has_the_layout_the_reference_reads():
    served = seqformer.init_ssd_hybrid_model(jax.random.PRNGKey(0), TINY)
    arrays = ref.make_params(TINY, 0, jnp.float32)
    leaves = lambda t: sorted(  # noqa: E731
        (jax.tree_util.keystr(k), v.shape, str(v.dtype))
        for k, v in jax.tree_util.tree_leaves_with_path(t))
    assert leaves(served) == leaves(arrays)
    blocks = served["blocks"]
    assert blocks[0]["ssd"]["spec"] == mamba2.SsdSpec(8, 1e-5)
    assert blocks[1]["attn"] == seqformer.AttnSpec(scale=0.0625)
    assert served["mup"] == blocks[0]["mup"] == seqformer.MupSpec(
        12.0, 0.22, 8.0)
    assert seqformer.init_ssd_hybrid_model(
        jax.random.PRNGKey(0), dict(TINY, tie_word_embeddings=False)
    )["head"]["w"].shape == (64, 128)
    with pytest.raises(ValueError, match="by the configuration"):
        seqformer.describe_token_model(
            {**arrays, "blocks": arrays["blocks"][::-1]}, TINY)
    with pytest.raises(ValueError, match="one group"):
        seqformer.init_ssd_hybrid_model(jax.random.PRNGKey(0),
                                        dict(TINY, mamba_n_groups=2))


# under a chunk, one chunk, whole chunks, whole chunks and a ragged one
@pytest.mark.parametrize("t", [5, 8, 16, 21])
def test_the_chunked_form_equals_the_recurrence_from_a_nonzero_state(t):
    x, dt, a, bm, cm, s0 = scan_inputs(t, 2, t)
    y, s = mamba2.chunked_scan(x, dt, a, bm, cm, s0, 8)
    for b in range(2):
        y_ref, s_ref = ref.ssd_recurrence(x[b], dt[b], a, bm[b], cm[b], s0[b])
        np.testing.assert_allclose(y[b], y_ref, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(s[b], s_ref, atol=2e-5, rtol=2e-5)


def test_the_one_step_update_equals_the_sequence_forms_next_position():
    _, served = make()
    p = served["blocks"][0]["ssd"]
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 21, 64))
    zeros = [jnp.zeros((2, *shape)) for shape in mamba2.state_shapes(p)]
    out, *state = mamba2.mix_sequence(p, x, *zeros, jnp.float32)
    _, *state20 = mamba2.mix_sequence(p, x[:, :20], *zeros, jnp.float32)
    out1, *state1 = mamba2.mix_step(p, x[:, 20], *state20, jnp.float32)
    np.testing.assert_allclose(out1, out[:, 20], atol=1e-5)
    np.testing.assert_allclose(state1[0], state[0], atol=1e-5)
    np.testing.assert_allclose(state1[1], state[1], atol=1e-6)  # the tail


@pytest.mark.parametrize("n", [5, 8, 21])
def test_forward_equals_reference_logits(n):
    arrays, served = make()
    ids = ids_for(1, n)
    got, _ = seqformer._forward(served, ids[None], compute_dtype=jnp.float32)
    want = ref.forward(arrays, TINY, ids)
    assert float(jnp.std(want)) > 0.3
    np.testing.assert_allclose(got[0], want, atol=ATOL)


def test_the_pool_holds_a_matrix_state_a_tail_and_full_kv():
    _, served = make()
    cache = seqformer.init_cache(served, 4, dtype=jnp.bfloat16, length=32,
                                 per_row=True)
    assert sorted(cache) == ["k", "pos", "ssd_s", "ssd_tail", "v"]
    shapes = {name: [None if t is None else (t.shape, str(t.dtype))
                     for t in cache[name]] for name in cache if name != "pos"}
    full = ((4, 32, 32), "bfloat16")
    assert shapes["k"] == shapes["v"] == [None, full] * 2
    assert shapes["ssd_s"] == [((4, 8, 16, 16), "float32"), None] * 2
    assert shapes["ssd_tail"] == [((4, 3 * 160), "bfloat16"), None] * 2
    assert seqformer.state_row_bytes(cache) == 2 * (
        8 * 16 * 16 * 4 + 3 * 160 * 2)
    # at the published sizes the 64 heads go in two pieces of 32, each
    # under the gather's slice limit, and the tail lies flat
    assert seqformer._state_leaf(0, (64, 64, 128)) == (2, 32, 64, 128)
    assert seqformer._state_leaf(1, (3, 4352)) == (13056,)


# under a chunk, one chunk, past a chunk, and a prefill of whole chunks
# the flash kernel serves (interpreted here) at the scale of 1/16
@pytest.mark.parametrize("t0", [5, 8, 13, 32])
def test_prefill_then_steps_through_the_pool_equal_the_full_forward(t0):
    arrays, served = make()
    model = serve(served, length=64)
    ids = ids_for(2, t0 + 10)
    want = np.asarray(ref.forward(arrays, TINY, ids))
    replies = run_episode(model, 1, ids, t0)
    gaps = [logits_at(r, want[t0 - 1 + i]) for i, r in enumerate(replies)]
    assert max(gaps) < ATOL, gaps
    # the pool's state rows are the reference's after the same ids, which
    # the ids padding them to another length do not move
    padded = np.zeros(48, np.int32)
    padded[:len(ids)] = ids
    states = ref.ssd_states(arrays, TINY, jnp.asarray(padded), len(ids))
    pooled = [leaf[1].reshape(8, 16, 16) for leaf in model._cache["ssd_s"]
              if leaf is not None]
    assert len(pooled) == len(states) == 2
    for got, state in zip(pooled, states):
        np.testing.assert_allclose(got, state, atol=1e-5, rtol=1e-4)
    events = model.drain_events()
    assert events[HYBRID_EVENTS[1]] == 10          # rows stepped
    assert events[HYBRID_EVENTS[0]] == sum(range(t0 + 1, t0 + 11))
    assert events[HYBRID_EVENTS[2]] == 0           # no window layer
    assert events[HYBRID_EVENTS[3]] == 1           # the reset's zeroing
    assert events[HYBRID_EVENTS[4]] == 10 * 2 * seqformer.state_row_bytes(
        model._cache) == 10 * 2 * 2 * (8 * 16 * 16 + 3 * 160) * 4


def test_two_rows_at_different_positions_step_in_one_padded_batch():
    arrays, served = make()
    model = serve(served)
    eps = [(0, ids_for(3, 25), 11), (2, ids_for(4, 14), 5)]
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
    assert np.any(after["ssd_s"][0][model.pad_slot]
                  != before["ssd_s"][0][model.pad_slot])


@pytest.mark.parametrize("fault", [None, "state_left", "decay_dropped",
                                   "gate_norm_dropped"])
def test_a_reused_slot_answers_as_a_fresh_one_only_if_the_path_is_whole(
        fault, monkeypatch):
    """The next tenant of a slot: its prefill goes on from the row's
    state and tail, which the rewind zeroes.  With the rewind moving
    ``pos`` alone (the benchmark's ``state_not_reset``) the answers carry
    the last tenant's state; with the decay or the gated norm dropped
    where the step is made (``decay_left_out``, ``gate_norm_left_out``)
    they drift from the first step on."""
    arrays, served = make()
    if fault in ("decay_dropped", "gate_norm_dropped"):
        name, stand_in = {
            "decay_dropped": ("decay", lambda p, dt: jnp.ones_like(dt)),
            "gate_norm_dropped": ("rms_norm", lambda scale, y, eps: y),
        }[fault]
        real_step, real = mamba2.mix_step, getattr(mamba2, name)

        def mix_step(*args, **kwargs):  # the sequence form keeps it
            setattr(mamba2, name, stand_in)
            try:
                return real_step(*args, **kwargs)
            finally:
                setattr(mamba2, name, real)
        monkeypatch.setattr(mamba2, "mix_step", mix_step)
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
        assert worst > 10 * ATOL
    else:
        assert worst < ATOL


def _with(config, **over):
    return {**{k: v for k, v in config.items() if k not in over},
            **{k: v for k, v in over.items() if v is not None}}


# each multiplier dropped (read as 1, or as 1 / sqrt(Dh) for the scale)
@pytest.mark.parametrize("dropped", [
    None, "embedding_multiplier", "residual_multiplier", "logits_scaling",
    "attention_multiplier"])
def test_each_mup_multiplier_is_read_where_the_reference_reads_it(dropped):
    config = TINY if dropped is None else _with(TINY, **{dropped: None})
    arrays, served = make(config=config)
    model = serve(served)
    ids = ids_for(9, 20)
    want = np.asarray(ref.forward(arrays, TINY, ids))
    replies = run_episode(model, 0, ids, 12)
    worst = max(logits_at(r, want[11 + i]) for i, r in enumerate(replies))
    if dropped:
        assert worst > 10 * ATOL, worst
    else:
        assert worst < ATOL


def test_nope_is_no_rotation_whatever_the_rope_keys_say():
    """``position_embedding_type: nope``: the attention layers rotate
    nothing, though the configuration names a ``rope_theta`` (and here a
    ``rope_parameters`` too); the same layers told ``rope`` rotate, and
    lose the reference."""
    roped = dict(TINY, rope_parameters={"rope_theta": 10000.0})
    arrays, served = make(config=roped)
    assert served["blocks"][1]["attn"] == seqformer.AttnSpec(scale=0.0625)
    _, rotated = make(config=dict(roped, position_embedding_type="rope"))
    assert rotated["blocks"][1]["attn"].theta == 10000.0
    ids = ids_for(10, 24)
    want = np.asarray(ref.forward(arrays, TINY, ids))
    for model, close in ((served, True), (rotated, False)):
        got, _ = seqformer._forward(model, ids[None],
                                    compute_dtype=jnp.float32)
        gap = float(np.abs(np.asarray(got[0]) - want).max())
        assert (gap < ATOL) == close, gap


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
