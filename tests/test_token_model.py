"""A token model with latent attention and a held share of sigmoid-routed
experts, against the plain reference (``chipbench/reference_sarvam.py``,
which imports nothing of the program): tiny widths, seeded float32 weights.
"""

import math
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blendjax.models import mla, moe, seqformer
from blendjax.models.layers import rope_table, yarn_inv_freq, yarn_mscale
from blendjax.serve.client import ServeClient
from blendjax.serve.server import (
    MOE_EVENTS,
    TOKEN_REPLY_TOP,
    PolicyServer,
    SeqFormerModel,
)
from blendjax.utils.timing import SERVE_EVENTS, EventCounters
from chipbench import reference_sarvam as ref

YARN = dict(factor=40, beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1,
            original_max_position_embeddings=4096)
TINY = dict(
    hidden_size=32, num_attention_heads=4, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, rope_theta=10000,
    rope_scaling=YARN, num_hidden_layers=3, first_k_dense_replace=1,
    intermediate_size=64, moe_intermediate_size=16, num_experts=16,
    num_experts_held=16, num_experts_per_tok=4, routed_scaling_factor=2.5,
    num_shared_experts=1, vocab_size=64)


def make(model=TINY, seed=0, first=0):
    """Seeded float32 weights (the reference's generator) and the program's
    model over the same arrays."""
    model = dict(model, held_first=first)
    arrays = ref.make_params(model, seed, jnp.float32)
    served = seqformer.describe_token_model(
        jax.tree.map(lambda x: x, arrays), model, first)
    return model, arrays, served


def ids_for(seed, n, vocab=TINY["vocab_size"]):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


@pytest.mark.parametrize("held", [(0, 16), (4, 8)])
def test_forward_equals_reference_logits(held):
    model, arrays, served = make(dict(TINY, num_experts_held=held[1]),
                                 first=held[0])
    ids = ids_for(1, 24)
    got, auxs = seqformer._forward(served, ids[None],
                                   compute_dtype=jnp.float32)
    want = ref.forward(arrays, model, ids)
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    assert [a["counts"][0] for a in auxs] == [24 * 4, 24 * 4]  # 2 expert layers


def test_init_token_model_has_the_layout_the_reference_reads():
    served = seqformer.init_token_model(jax.random.PRNGKey(0), TINY)
    arrays = ref.make_params(dict(TINY), 0, jnp.float32)
    leaves = lambda t: sorted(  # noqa: E731
        (jax.tree_util.keystr(k), v.shape)
        for k, v in jax.tree_util.tree_leaves_with_path(t))
    assert leaves(served) == leaves(arrays)


@pytest.mark.parametrize("bucket", [2, 4])
def test_prefill_then_steps_through_the_pool_equal_the_full_forward(bucket):
    """Expanded (prefill) against absorbed (decode): three episodes at
    different positions, stepped together in a padded bucket."""
    model, arrays, served = make(dict(TINY, num_experts_held=8), first=4)
    m = SeqFormerModel(served, slots=5, length=32,
                       compute_dtype=jnp.float32)
    lens, steps = [5, 9, 3], 6
    eps = [ids_for(10 + i, n + steps) for i, n in enumerate(lens)]
    slots = [3, 0, 4][:bucket if bucket < 3 else 3]
    eps, lens = eps[:len(slots)], lens[:len(slots)]
    replies = [[m.prefill_rows(np.asarray([s]), e[:n, None])]
               for s, e, n in zip(slots, eps, lens)]
    idx = np.full(bucket, m.pad_slot, np.int64)
    idx[:len(slots)] = slots
    for t in range(steps):
        obs = np.zeros((bucket, 1), np.int32)
        obs[:len(slots), 0] = [e[n + t] for e, n in zip(eps, lens)]
        out = m.step_rows(idx, obs)
        for r in range(len(slots)):
            replies[r].append(out[r])
    k = TOKEN_REPLY_TOP
    for e, n, rep in zip(eps, lens, replies):
        rep = np.stack(rep)
        assert rep.shape == (steps + 1, 2 * k + 1)
        logits = np.asarray(ref.forward(arrays, model, e))[n - 1:n + steps]
        order = np.argsort(-logits, axis=-1)[:, :k]
        np.testing.assert_array_equal(rep[:, k:2 * k].astype(int), order)
        np.testing.assert_allclose(
            rep[:, :k], np.take_along_axis(logits, order, -1), atol=2e-5)
        np.testing.assert_allclose(
            rep[:, -1], jax.nn.logsumexp(logits, axis=-1), atol=2e-5)
    # the counts are over the real rows only, whatever the padding
    events = m.drain_events()
    assert events["serve_moe_assignments"] == steps * len(slots) * 4 * 2
    assert 0 < events["serve_moe_experts_hit"] \
        <= events["serve_moe_assignments_held"] \
        < events["serve_moe_assignments"]
    assert m.drain_events() == {}


def test_rollout_refuses_a_token_model():
    """``rollout`` feeds predictions back as observations; logits are
    not ids, and it says so up front (it used to die on ``cache['k']``)."""
    _, _, served = make()
    with pytest.raises(ValueError, match="token model"):
        seqformer.rollout(served, ids_for(3, 6)[None, :, None], 2,
                          compute_dtype=jnp.float32)


def test_the_pool_is_one_latent_row_a_position():
    _, _, served = make()
    cache = seqformer.init_cache(served, 3, jnp.float32, length=8,
                                 per_row=True)
    assert set(cache) == {"kv", "pos"}
    # [c 16 | k_pe 4] padded to whole lanes
    assert [c.shape for c in cache["kv"]] == [(3, 8, 128)] * 3


def test_quarters_and_the_shared_expert_once_make_the_whole_layer():
    """The share ties to the model: the routed parts that the four ranks'
    shares give, plus the shared expert counted once, are the uncut
    reference layer."""
    whole_model, whole, _ = make()
    p = whole["blocks"][1]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(3), (40, 32))
    want = ref.moe(p, whole_model, h)
    total = 0.0
    for first in range(0, 16, 4):
        part = {"router": p["router"], "shared": p["shared"],
                "route": moe.RouteSpec(top_k=4, scale=2.5, first=first),
                **{n: p[n][first:first + 4] for n in ("gate", "up", "down")}}
        y, counts = moe.moe_apply_held(part, h, jnp.float32)
        ref_part = ref.moe(
            {k: v for k, v in part.items() if k != "route"},
            dict(whole_model, held_first=first), h)
        np.testing.assert_allclose(y, ref_part, atol=2e-5)
        total = total + y
        assert counts[0] == 40 * 4
    shared = ref.expert(p["shared"]["gate"], p["shared"]["up"],
                        p["shared"]["down"], h)
    np.testing.assert_allclose(total - 3 * shared, want, atol=5e-5)


@pytest.mark.parametrize("case", ["bias", "weights", "skew"])
def test_routing(case):
    spec = moe.RouteSpec(top_k=4, scale=2.5)
    kw, kx = jax.random.split(jax.random.PRNGKey(5))
    router = {"w": jax.random.normal(kw, (32, 16)) * 32 ** -0.5,
              "bias": jnp.zeros((16,))}
    x = jax.random.normal(kx, (50, 32))
    sel, g = moe.route(router, x, spec)
    if case == "weights":
        np.testing.assert_allclose(g.sum(-1), 2.5, rtol=1e-6)
        return
    if case == "bias":
        # a bias on expert 7 pulls it into every selection and leaves the
        # weight it gets (its own score's share) as it would be unbiased
        biased = dict(router, bias=router["bias"].at[7].set(10.0))
        sel_b, g_b = moe.route(biased, x, spec)
        assert (sel_b == 7).any(-1).all() and not (sel == 7).any(-1).all()
        s = jax.nn.sigmoid(x @ router["w"])
        picked = jnp.take_along_axis(s, sel_b, -1)
        np.testing.assert_allclose(
            g_b, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
        return
    # a skewed router: every token wants the same four experts, all held;
    # nothing is dropped (a capacity arena of 1.25 would drop most)
    skew = dict(router, bias=jnp.zeros((16,)).at[:4].set(10.0))
    p = moe.held_init(jax.random.PRNGKey(6), 32, 16, 16, 16, spec)
    p["router"] = skew
    y, counts = moe.moe_apply_held(p, x, jnp.float32)
    assert list(counts) == [200, 200, 4]
    want = ref.moe({k: v for k, v in p.items() if k != "route"},
                   dict(TINY, held_first=0), x)
    np.testing.assert_allclose(y, want, atol=2e-5)


@pytest.mark.parametrize("i,want", [
    (0, 1.0),                                   # below the ramp: plain
    (31, 10000 ** (-62 / 64) / 40),             # above it: over the factor
    (16, 10000 ** (-32 / 64) * (1 - 6 / 13 + 6 / 13 / 40)),  # on the ramp
])
def test_yarn_frequencies(i, want):
    # correction dimensions at 64 rope dims, base 1e4, 4096 positions:
    # beta_fast 32 -> floor(10.47) = 10, beta_slow 1 -> ceil(22.5) = 23
    freqs = yarn_inv_freq(64, 10000.0, 40, 32, 1, 4096)
    assert freqs[i] == pytest.approx(want, rel=1e-5)
    ref_freqs = ref.yarn_inv_freq(dict(qk_rope_head_dim=64, rope_theta=10000,
                                       rope_scaling=YARN))
    assert ref_freqs[i] == pytest.approx(want, rel=1e-9)


def test_yarn_scale():
    assert yarn_mscale(40, 1) == pytest.approx(1.3689, abs=5e-5)
    assert yarn_mscale(1, 1) == 1.0
    p = {"wq": jnp.zeros((8, 2, 192)),
         "spec": mla.MlaSpec(rope_dim=64, yarn=(40, 32, 1, 4096, 1, 1))}
    m = 0.1 * math.log(40) + 1
    assert mla.softmax_scale(p) == pytest.approx(192 ** -0.5 * m * m)
    cos, _ = rope_table(jnp.arange(3), 64, yarn=(40, 32, 1, 4096, 1, 1))
    assert float(cos[0, 0]) == 1.0  # m(mscale) / m(mscale_all_dim) = 1
    plain_cos, _ = rope_table(jnp.arange(3), 64)
    np.testing.assert_allclose(cos[:, :10], plain_cos[:, :10], rtol=1e-6)


def test_an_episode_through_the_policy_server():
    _, _, served = make(dict(TINY, num_experts_held=8), first=4)
    model = SeqFormerModel(served, slots=2, length=32,
                           compute_dtype=jnp.float32)
    counters = EventCounters()
    server = PolicyServer("tcp://127.0.0.1:*", model, max_batch=2,
                          tick_ms=1.0, counters=counters)
    stop = threading.Event()
    thread = threading.Thread(target=server.serve_forever, args=(stop,),
                              daemon=True)
    thread.start()
    client = ServeClient(server.address, timeoutms=30000)
    try:
        assert all(name in SERVE_EVENTS for name in MOE_EVENTS)
        ids = ids_for(20, 9)
        reply = client.reset(prefix=ids[:5, None], timeout_ms=30000)
        assert reply["pos"] == 5 and reply["pred"].shape == (17,)
        before = counters.snapshot()
        assert not any(before.get(name) for name in MOE_EVENTS)
        for t in range(4):
            out = client.step(ids[5 + t:6 + t], timeout_ms=30000)
            assert out["pred"].shape == (17,)
            assert out["pred"].dtype == np.float32
        after = counters.snapshot()
        assert after["serve_moe_assignments"] == 4 * 4 * 2
        assert 0 < after["serve_moe_experts_hit"] \
            <= after["serve_moe_assignments_held"] < 32
        assert client.close_episode(timeout_ms=30000) is True
    finally:
        client.close()
        stop.set()
        thread.join(timeout=30)
        server.close()
