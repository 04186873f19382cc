"""A token model of mixed layer kinds (state-space, window attention, one
full attention whose keys and values the cross layers read, gated memory
units; differential attention throughout) against the plain reference
(``chipbench/reference_phi4flash.py``, which imports nothing of the
program): a 64-wide cut with the 32-layer kind rule shortened to 8 layers,
window 8, seeded float32 weights.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blendjax.models import mamba, seqformer
from blendjax.serve.server import HYBRID_EVENTS, SeqFormerModel
from chipbench import reference_phi4flash as ref

TINY = dict(
    hidden_size=64, num_attention_heads=8, num_key_value_heads=4,
    intermediate_size=128, num_hidden_layers=8, mb_per_layer=2,
    sliding_window=8, layer_norm_eps=1e-5, vocab_size=96, mamba_d_state=4,
    mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=4)
WINDOW = TINY["sliding_window"]
ATOL = 1e-4  # float32 against float32, logits of standard deviation 8


def make(seed=0):
    """Seeded float32 weights (the reference's generator) and the program's
    model over the same arrays."""
    arrays = ref.make_params(TINY, seed, jnp.float32)
    served = seqformer.describe_token_model(
        jax.tree.map(lambda x: x, arrays), TINY)
    return arrays, served


def ids_for(seed, n):
    return np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], n).astype(np.int32)


def serve(served, slots=3, length=32):
    return SeqFormerModel(served, slots=slots, length=length,
                          compute_dtype=jnp.float32)


def logits_at(reply, want):
    """A reply row's top logits against ``want`` (vocab,) at its ids, and
    its logsumexp."""
    k = (len(reply) - 1) // 2
    at = reply[k:2 * k].astype(int)
    lse = np.log(np.sum(np.exp(want - want.max()))) + want.max()
    return max(np.abs(reply[:k] - want[at]).max(), abs(reply[-1] - lse))


def run_episode(model, slot, ids, t0):
    """reset, prefill ``t0`` ids, then step the rest: the worst gap of any
    reply to the reference's full pass, position by position."""
    model.reset_rows(np.asarray([slot]))
    replies = [model.prefill_rows(np.asarray([slot]), ids[:t0, None])]
    for t in range(t0, len(ids)):
        replies.append(np.asarray(model.step_rows(
            np.asarray([slot]), ids[t:t + 1, None]))[0])
    return replies


def test_the_kind_rule_is_the_same_on_both_sides_at_the_published_depth():
    published = dict(TINY, num_hidden_layers=32)
    kinds = seqformer.hybrid_layer_kinds(published)
    assert kinds == ref.layer_kinds(published)
    assert [kinds.count(k) for k in ("ssm", "window", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert kinds[16:20] == ["ssm", "full", "gmu", "cross"]
    assert seqformer.hybrid_layer_kinds(TINY) == [
        "ssm", "window", "ssm", "window", "ssm", "full", "gmu", "cross"]


def test_init_hybrid_model_has_the_layout_the_reference_reads():
    served = seqformer.init_hybrid_model(jax.random.PRNGKey(0), TINY)
    arrays = ref.make_params(TINY, 0, jnp.float32)
    leaves = lambda t: sorted(  # noqa: E731
        (jax.tree_util.keystr(k), v.shape, str(v.dtype))
        for k, v in jax.tree_util.tree_leaves_with_path(t))
    assert leaves(served) == leaves(arrays)
    with pytest.raises(ValueError, match="by the configuration"):
        seqformer.describe_token_model(
            {**arrays, "blocks": arrays["blocks"][::-1]}, TINY)


@pytest.mark.parametrize("n", [24, 32])
def test_forward_equals_reference_logits(n):
    arrays, served = make()
    ids = ids_for(1, n)
    got, _ = seqformer._forward(served, ids[None], compute_dtype=jnp.float32)
    np.testing.assert_allclose(got[0], ref.forward(arrays, TINY, ids),
                               atol=ATOL)


def test_the_pool_holds_three_kinds_of_state():
    _, served = make()
    cache = seqformer.init_cache(served, 4, dtype=jnp.bfloat16, length=32,
                                 per_row=True)
    shapes = {name: [None if t is None else (t.shape, str(t.dtype))
                     for t in cache[name]] for name in cache if name != "pos"}
    ring, full = ((4, WINDOW, 32), "bfloat16"), ((4, 32, 32), "bfloat16")
    assert shapes["k"] == shapes["v"] == [
        None, ring, None, ring, None, full, None, None]
    h, tail = ((4, 4, 128), "float32"), ((4, 3 * 128), "bfloat16")
    assert shapes["ssm_h"] == [h, None, h, None, h, None, None, None]
    assert shapes["ssm_tail"] == [tail, None, tail, None, tail, None, None,
                                  None]


# shorter than, equal to and longer than the window; 32 runs the prefill's
# attention through the flash kernel (interpreted here)
@pytest.mark.parametrize("t0", [5, WINDOW, 13, 32])
def test_prefill_then_steps_through_the_pool_equal_the_full_forward(t0):
    arrays, served = make()
    model = serve(served, length=48)
    ids = ids_for(2, t0 + 12)
    want = np.asarray(ref.forward(arrays, TINY, ids))
    replies = run_episode(model, 1, ids, t0)
    gaps = [logits_at(r, want[t0 - 1 + i]) for i, r in enumerate(replies)]
    assert max(gaps) < ATOL, gaps
    events = model.drain_events()
    assert events[HYBRID_EVENTS[1]] == 12          # rows stepped
    assert events[HYBRID_EVENTS[0]] == sum(range(t0 + 1, t0 + 13))
    assert events[HYBRID_EVENTS[2]] == sum(
        min(p, WINDOW) for p in range(t0 + 1, t0 + 13))
    assert events[HYBRID_EVENTS[3]] == 1           # the reset's zeroing


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
    ids = ids_for(5, 9)
    run_episode(model, 1, ids, 6)
    before = jax.tree.map(np.array, model._cache)
    pad = np.full(4, model.pad_slot)
    np.asarray(model.step_rows(pad, np.full((4, 1), 7, np.int32)))
    after = jax.tree.map(np.array, model._cache)
    real = np.arange(model.slots)
    for name in ("ssm_h", "ssm_tail", "k", "v"):
        for was, now in zip(before[name], after[name]):
            if was is not None:
                np.testing.assert_array_equal(was[real], now[real])
    np.testing.assert_array_equal(before["pos"][real], after["pos"][real])
    assert np.any(after["ssm_h"][0][model.pad_slot]
                  != before["ssm_h"][0][model.pad_slot])


@pytest.mark.parametrize("state_left", [False, True])
def test_a_reused_slot_answers_as_a_fresh_one_only_if_its_state_was_zeroed(
        state_left):
    """The next tenant of a slot: its prefill scans on from the row's
    recurrent state, which the rewind zeroes.  With the rewind moving
    ``pos`` alone (the benchmark's ``state_not_reset`` fault) the answers
    are the previous tenant's state's."""
    arrays, served = make()
    model = serve(served)
    if state_left:
        model._rewind = jax.jit(
            lambda cache, rows: {**cache,
                                 "pos": cache["pos"].at[rows].set(0)},
            donate_argnums=(0,))
    run_episode(model, 1, ids_for(6, 17), 9)
    ids = ids_for(7, 15)
    want = np.asarray(ref.forward(arrays, TINY, ids))
    replies = run_episode(model, 1, ids, 6)
    worst = max(logits_at(r, want[5 + i]) for i, r in enumerate(replies))
    if state_left:
        assert worst > 0.1
    else:
        assert worst < ATOL


def test_the_one_step_update_equals_the_scans_next_position():
    _, served = make()
    p = served["blocks"][0]["ssm"]
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 70, 64))
    (h_shape, tail_shape) = mamba.state_shapes(p)
    zeros = (jnp.zeros((2, *h_shape)), jnp.zeros((2, *tail_shape)))
    out, y, h, tail = mamba.mix_sequence(p, x, *zeros, jnp.float32)
    # 69 positions scanned (a chunk and a ragged one), then one stepped
    _, _, h69, tail69 = mamba.mix_sequence(p, x[:, :69], *zeros, jnp.float32)
    out1, y1, h1, tail1 = mamba.mix_step(p, x[:, 69], h69, tail69,
                                         jnp.float32)
    np.testing.assert_allclose(out1, out[:, 69], atol=1e-5)
    np.testing.assert_allclose(y1, y[:, 69], atol=1e-5)
    np.testing.assert_allclose(h1, h, atol=1e-5)
    np.testing.assert_array_equal(tail1, tail)


def test_a_window_argument_is_refused_and_rollout_does_not_sample():
    _, served = make()
    with pytest.raises(ValueError, match="windows from its description"):
        SeqFormerModel(served, slots=2, length=16, window=4)
    cache = seqformer.init_cache(served, 1, length=16, per_row=True)
    with pytest.raises(ValueError, match="layer by layer"):
        seqformer.prefill(served, cache, jnp.zeros((1, 4), jnp.int32),
                          window=4)
    with pytest.raises(ValueError, match="token model"):
        seqformer.rollout(served, jnp.zeros((1, 4, 1)), 2)
