"""The ``gdn_update`` kernel (``blendjax/ops/gdn_update.py``, interpreted
on this CPU) against the gated delta rule's one-position update written in
``jax.numpy`` here, as ``deltanet.mix_step`` computed it before the kernel:
on a pool of slots at tiny widths and at the published head shape (30
heads in three pieces of ten, ``dk`` 96, ``dv`` 192), with the rows out of
order and the pool's extra row repeated as a padded bucket repeats it.
Its variant without the delta term (``delta=False``) against the plain
Mamba-2 step the same way (granite-4.0-h-micro's 64 heads in two pieces of
32, ``P`` 64, ``N`` 128, one ``B`` and ``C`` for every head); and the
default path, Olmo's step, lowered to the StableHLO it lowered to before
the variant was added.

The stepped rows' new state and read agree within float32 rounding (only
the order of the sums over ``dk`` may differ); every row not stepped is
left bit-equal; the pad row is garbage either way and is not compared.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blendjax.models import deltanet, mamba2, seqformer
from blendjax.ops.gdn_update import gdn_update

SLOTS = 9  # the last is the pool's extra row, what a padded bucket repeats
#: heads, dk, dv: tiny, and the published Olmo-Hybrid-7B linear layer
WIDTHS = {"tiny": (2, 8, 16), "published": (30, 96, 192)}
#: stepped slots: in order, and out of order with the extra row repeated
ROWS = {"in_order": [0, 1, 2], "shuffled_padded": [5, 2, 7, 8, 8]}


def one_step(s, q, k, v, alpha, beta):
    """``(B, H, dv, dk)`` state, ``q, k`` (B, H, dk), ``v`` (B, H, dv),
    ``alpha, beta`` (B, H) -> (o, new state): the old ``mix_step``."""
    alpha = alpha[..., None]
    s_k = jnp.sum(s * k[:, :, None, :], -1)
    s_q = jnp.sum(s * q[:, :, None, :], -1)
    u = beta[..., None] * (v - alpha * s_k)
    new = alpha[..., None] * s + u[..., None] * k[:, :, None, :]
    return alpha * s_q + u * jnp.sum(k * q, -1, keepdims=True), new


def pool_of(heads, dk, dv, seed=0):
    """A pool leaf as ``init_cache`` lays it, filled with a state."""
    shape = (SLOTS, *seqformer._state_leaf(0, (heads, dv, dk)))
    return jax.random.normal(jax.random.PRNGKey(seed), shape)


def step_inputs(b, heads, dk, dv, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (b, heads, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, heads, dk)))
    v = jax.random.normal(ks[2], (b, heads, dv))
    alpha = jnp.exp(-1.5 * jax.random.uniform(ks[3], (b, heads)))
    beta = 2.0 * jax.random.uniform(ks[4], (b, heads))
    return q, k, v, alpha, beta


@pytest.mark.parametrize("rows", list(ROWS.values()), ids=list(ROWS))
@pytest.mark.parametrize("width", list(WIDTHS))
def test_the_kernel_steps_the_rows_where_they_lie(width, rows):
    heads, dk, dv = WIDTHS[width]
    pool = pool_of(heads, dk, dv)
    before = np.asarray(pool)
    rows = np.asarray(rows, np.int32)
    args = step_inputs(len(rows), heads, dk, dv)
    o, after = jax.jit(gdn_update)(pool, jnp.asarray(rows), *args)
    after = np.asarray(after)
    assert after.shape == before.shape and o.shape == (len(rows), heads, dv)
    want_o, want_s = one_step(before[rows].reshape(len(rows), heads, dv, dk),
                              *args)
    real = [i for i, r in enumerate(rows) if r != SLOTS - 1]
    for i in real:
        np.testing.assert_allclose(
            after[rows[i]].reshape(heads, dv, dk), want_s[i],
            rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(o[i], want_o[i], rtol=1e-5, atol=1e-6)
    for slot in set(range(SLOTS)) - set(rows.tolist()):
        np.testing.assert_array_equal(after[slot], before[slot])


@pytest.mark.parametrize("width", list(WIDTHS))
def test_mix_step_reads_and_writes_the_pool_as_the_gathered_step_did(width):
    """``deltanet.mix_step`` handed the pool's leaf and the stepped rows
    answers as the gathered step did: its output (through ``o``), the
    rows' new state in the pool and the three tails."""
    heads, dk, dv = WIDTHS[width]
    p = deltanet.init(jax.random.PRNGKey(2), 64, heads, dk, dv, 4)
    rows = np.asarray(ROWS["shuffled_padded"], np.int32)
    b = len(rows)
    x = jax.random.normal(jax.random.PRNGKey(3), (b, 64))
    _, *tail_shapes = deltanet.state_shapes(p)
    tails = [0.1 * jax.random.normal(jax.random.PRNGKey(4 + j), (b, *shape))
             for j, shape in enumerate(tail_shapes)]
    pool = pool_of(heads, dk, dv)
    before = np.asarray(pool)
    out, after, *new_tails = jax.jit(
        lambda pool, rows, x, *tails: deltanet.mix_step(
            p, x, pool, *tails, jnp.float32, rows=rows))(
        pool, jnp.asarray(rows), x, *tails)

    q, k, v, _ = deltanet._streams(p, x[:, None], tails, jnp.float32)
    g, beta = deltanet.gates(p, x, jnp.float32)
    want_o, want_s = one_step(
        before[rows].reshape(b, heads, dv, dk), q[:, 0], k[:, 0], v[:, 0],
        jnp.exp(g), beta)
    want_out = deltanet._gate_out(p, want_o, x, jnp.float32)
    real = [i for i, r in enumerate(rows) if r != SLOTS - 1]
    np.testing.assert_allclose(np.asarray(out)[real],
                               np.asarray(want_out)[real], rtol=1e-5,
                               atol=1e-6)
    after = np.asarray(after)
    for i in real:
        np.testing.assert_allclose(after[rows[i]].reshape(heads, dv, dk),
                                   want_s[i], rtol=1e-5, atol=1e-6)
    for got, want in zip(new_tails, deltanet._streams(
            p, x[:, None], tails, jnp.float32)[3]):
        np.testing.assert_array_equal(got, want)
    for slot in set(range(SLOTS)) - set(rows.tolist()):
        np.testing.assert_array_equal(after[slot], before[slot])


def test_without_rows_the_step_takes_the_leafs_first_rows():
    """``rows`` left out (``rollout``, a per-row cache without slots):
    the leaf's first B rows, the others as they were."""
    heads, dk, dv = WIDTHS["tiny"]
    p = deltanet.init(jax.random.PRNGKey(2), 64, heads, dk, dv, 4)
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 64))
    tails = [jnp.zeros((3, *shape)) for shape in deltanet.state_shapes(p)[1:]]
    pool = pool_of(heads, dk, dv)
    by_default = deltanet.mix_step(p, x, pool, *tails, jnp.float32)
    given = deltanet.mix_step(p, x, pool, *tails, jnp.float32,
                              rows=jnp.arange(3))
    for a, b in zip(by_default, given):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(np.asarray(by_default[1])[3:],
                                  np.asarray(pool)[3:])


#: heads, P, N of a Mamba-2 layer: tiny, and granite-4.0-h-micro's
SSD_WIDTHS = {"tiny": (4, 8, 16), "published": (64, 64, 128)}


def one_ssd_step(s, c, b, x, alpha, dt):
    """``(B, H, P, N)`` state, ``c, b`` (B, N) for every head, ``x`` (B, H,
    P), ``alpha, dt`` (B, H) -> (y, new state): the plain Mamba-2 step,
    ``S' = alpha S + dt x b^T``, ``y = S' c``."""
    new = (alpha[..., None, None] * s
           + (dt[..., None] * x)[..., None] * b[:, None, None, :])
    return jnp.sum(new * c[:, None, None, :], -1), new


def ssd_inputs(b, heads, p, n, seed=5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (b, heads)) - 2.0)
    alpha = jnp.exp(-dt * jax.random.uniform(ks[1], (heads,), minval=1.0,
                                             maxval=16.0))
    return (jax.random.normal(ks[2], (b, n)), jax.random.normal(ks[3], (b, n)),
            jax.random.normal(ks[4], (b, heads, p)), alpha, dt)


@pytest.mark.parametrize("rows", list(ROWS.values()), ids=list(ROWS))
@pytest.mark.parametrize("width", list(SSD_WIDTHS))
def test_without_the_delta_term_the_kernel_steps_mamba2_in_place(width,
                                                                 rows):
    heads, p, n = SSD_WIDTHS[width]
    pool = pool_of(heads, n, p)   # (S, ..., P, N): the value axis is P
    assert pool.shape[1:] == seqformer._state_leaf(0, (heads, p, n))
    before = np.asarray(pool)
    rows = np.asarray(rows, np.int32)
    c, b, x, alpha, dt = ssd_inputs(len(rows), heads, p, n)
    y, after = jax.jit(lambda *a: gdn_update(*a, delta=False))(
        pool, jnp.asarray(rows), c, b, x, alpha, dt)
    after = np.asarray(after)
    assert after.shape == before.shape and y.shape == (len(rows), heads, p)
    want_y, want_s = one_ssd_step(
        before[rows].reshape(len(rows), heads, p, n), c, b, x, alpha, dt)
    for i in [i for i, r in enumerate(rows) if r != SLOTS - 1]:
        np.testing.assert_allclose(after[rows[i]].reshape(heads, p, n),
                                   want_s[i], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(y[i], want_y[i], rtol=1e-5, atol=1e-4)
    for slot in set(range(SLOTS)) - set(rows.tolist()):
        np.testing.assert_array_equal(after[slot], before[slot])


def test_mamba2_mix_step_steps_the_pool_as_its_sequence_form_would():
    """``mamba2.mix_step`` handed the pool's leaf and shuffled, padded
    rows: each real row's output and new state are what one more position
    of ``mix_sequence`` gives from that row's state and tail."""
    heads, p, n = SSD_WIDTHS["tiny"]
    blk = mamba2.init(jax.random.PRNGKey(2), 32, heads, p, n, 4)
    blk["spec"] = mamba2.SsdSpec(4)
    rows = np.asarray(ROWS["shuffled_padded"], np.int32)
    b = len(rows)
    x = jax.random.normal(jax.random.PRNGKey(3), (b, 32))
    tail = 0.3 * jax.random.normal(jax.random.PRNGKey(4),
                                   (b, *mamba2.state_shapes(blk)[1]))
    pool = 0.1 * pool_of(heads, n, p)
    before = np.asarray(pool)
    out, after, new_tail = jax.jit(
        lambda pool, rows, x, tail: mamba2.mix_step(
            blk, x, pool, tail, jnp.float32, rows=rows))(
        pool, jnp.asarray(rows), x, tail)
    state = jnp.asarray(before[rows].reshape(b, heads, p, n))
    want_out, want_s, want_tail = mamba2.mix_sequence(
        blk, x[:, None], state, tail, jnp.float32)
    after = np.asarray(after)
    for i in [i for i, r in enumerate(rows) if r != SLOTS - 1]:
        np.testing.assert_allclose(out[i], want_out[i, 0], rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(after[rows[i]].reshape(heads, p, n),
                                   want_s[i], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(new_tail, want_tail, atol=1e-6)
    for slot in set(range(SLOTS)) - set(rows.tolist()):
        np.testing.assert_array_equal(after[slot], before[slot])


#: sha256 of Olmo's tiny step (``OLMO_TINY``, ``test_linear_attention_model``'s
#: model, bfloat16, a bucket of 4, three slots of 64 positions) as StableHLO
#: without debug information, as it lowered before ``delta=False`` was
#: added: the default path is the same program to the byte
OLMO_STEP_SHA256 = ("b021971192e8a3ad5c21f6108454f469"
                    "d5313499cd108ee5a22cec6a209c4041")
OLMO_TINY = dict(
    hidden_size=64, intermediate_size=128, num_hidden_layers=8,
    num_attention_heads=2, num_key_value_heads=2,
    layer_types=(["linear_attention"] * 3 + ["full_attention"]) * 2,
    linear_num_key_heads=2, linear_num_value_heads=2, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rms_norm_eps=1e-6, vocab_size=128,
    tie_word_embeddings=False)


def test_the_default_path_lowers_olmos_step_as_before():
    from blendjax.serve.server import SeqFormerModel
    from chipbench import reference_olmohybrid

    params = seqformer.describe_token_model(
        reference_olmohybrid.make_params(OLMO_TINY, 0, jnp.bfloat16),
        OLMO_TINY)
    model = SeqFormerModel(params, slots=3, length=64,
                           compute_dtype=jnp.bfloat16,
                           cache_dtype=jnp.bfloat16)
    text = model._step.lower(
        model.params, model._cache, jax.ShapeDtypeStruct((4,), jnp.int32),
        jax.ShapeDtypeStruct((4, 1), jnp.int32)).as_text(debug_info=False)
    assert hashlib.sha256(text.encode()).hexdigest() == OLMO_STEP_SHA256
