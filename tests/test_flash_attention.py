"""Pallas flash attention vs the reference einsum attention — forward
and gradient parity in interpret mode (same kernel code CI can run on
CPU), plus SeqFormer integration through the ``attn_fn`` seam."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blendjax.ops.flash_attention import flash_attention, make_flash_attention
from blendjax.parallel.ring_attention import full_attention


def _qkv(b=2, t=256, h=4, d=64, dtype=jnp.float32, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        jax.random.normal(k1, (b, t, h, d), dtype),
        jax.random.normal(k2, (b, t, h, d), dtype),
        jax.random.normal(k3, (b, t, h, d), dtype),
    )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("blocks", [(128, 128), (64, 128), (128, 64)])
def test_forward_matches_reference(causal, blocks):
    q, k, v = _qkv()
    bq, bkv = blocks
    out = flash_attention(q, k, v, causal, None, bq, bkv, True)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_bfloat16_io():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, True, None, 128, 128, True)
    assert out.dtype == jnp.bfloat16
    ref = full_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=True,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=2e-2, rtol=2e-2
    )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("blocks", [(64, 64), (32, 64), (64, 32)])
def test_gradients_match_reference(causal, blocks):
    q, k, v = _qkv(t=128, d=32)
    bq, bkv = blocks

    def loss_flash(q, k, v):
        return (
            flash_attention(q, k, v, causal, None, bq, bkv, True) ** 2
        ).sum()

    def loss_ref(q, k, v):
        return (full_attention(q, k, v, causal=causal) ** 2).sum()

    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5
        )


def test_gradients_explicit_scale_and_bf16():
    q, k, v = _qkv(t=128, d=32, dtype=jnp.bfloat16)

    def loss_flash(q, k, v):
        return (
            flash_attention(q, k, v, True, 0.25, 64, 64, True)
            .astype(jnp.float32) ** 2
        ).sum()

    def loss_ref(q, k, v):
        # f32-math baseline: the kernel computes in f32 internally, while
        # a bf16 einsum reference would carry its own rounding error
        return (
            full_attention(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), causal=True, scale=0.25,
            ) ** 2
        ).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
    )
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=5e-2, rtol=5e-2,
        )


def test_seqformer_attn_fn_integration():
    """The kernel slots into the SeqFormer through the attn_fn seam and
    reproduces the default-attention forward exactly."""
    from blendjax.models import seqformer

    params = seqformer.init(
        jax.random.PRNGKey(0), obs_dim=6, d_model=32, n_heads=2,
        n_layers=2, max_len=128,
    )
    obs = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 6), jnp.float32)
    default = seqformer.apply(params, obs, compute_dtype=jnp.float32)
    flash = seqformer.apply(
        params, obs, compute_dtype=jnp.float32,
        attn_fn=make_flash_attention(causal=True, block_q=64, block_kv=64,
                                     interpret=True),
    )
    np.testing.assert_allclose(
        np.asarray(flash), np.asarray(default), atol=2e-4, rtol=2e-4
    )


@pytest.mark.parametrize("window", [1, 5, 64, 96, 1000])
def test_sliding_window_forward_matches_reference(window):
    """window=W spans every regime: sub-block (1, 5), exactly one block
    (64), block-straddling (96), and wider-than-T (1000, == plain
    causal)."""
    q, k, v = _qkv(t=256, d=32)
    out = flash_attention(q, k, v, True, None, 64, 64, True, window)
    ref = full_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_sliding_window_wider_than_t_equals_plain_causal():
    q, k, v = _qkv(t=128, d=32)
    windowed = flash_attention(q, k, v, True, None, 64, 64, True, 1000)
    plain = flash_attention(q, k, v, True, None, 64, 64, True)
    np.testing.assert_allclose(np.asarray(windowed), np.asarray(plain))


@pytest.mark.parametrize("window", [5, 96])
def test_sliding_window_gradients_match_reference(window):
    q, k, v = _qkv(t=128, d=32)

    def loss_flash(q, k, v):
        return (
            flash_attention(q, k, v, True, None, 64, 32, True, window) ** 2
        ).sum()

    def loss_ref(q, k, v):
        return (full_attention(q, k, v, causal=True, window=window) ** 2).sum()

    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5
        )


def test_sliding_window_shrinks_grid():
    """The windowed grids really are O(W), not O(T): step counts drop
    below the full block count, and parity holds with the shrunk grids
    active in ALL THREE passes (incl. the end-of-sequence overshoot rows
    where a derived q index past the last real block must be dead, not
    double-counted)."""
    from blendjax.ops.flash_attention import (
        _kv_window_steps,
        _q_window_steps,
    )

    # t=384, blocks 64: 6 full blocks; W=96 needs only 4 steps
    assert _kv_window_steps(6, 64, 64, 96) == 4
    assert _q_window_steps(6, 64, 64, 96) == 4
    # W wider than T: clamped to the full grid
    assert _kv_window_steps(6, 64, 64, 10_000) == 6

    q, k, v = _qkv(b=1, t=384, h=2, d=16)

    def loss_flash(q, k, v):
        return (
            flash_attention(q, k, v, True, None, 64, 64, True, 96) ** 2
        ).sum()

    def loss_ref(q, k, v):
        return (full_attention(q, k, v, causal=True, window=96) ** 2).sum()

    out = flash_attention(q, k, v, True, None, 64, 64, True, 96)
    ref = full_attention(q, k, v, causal=True, window=96)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )
    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5
        )


def test_sliding_window_requires_causal():
    q, k, v = _qkv(t=64, d=16)
    with pytest.raises(ValueError, match="requires causal"):
        flash_attention(q, k, v, False, None, 64, 64, True, 8)
    with pytest.raises(ValueError, match="requires causal"):
        make_flash_attention(causal=False, window=8)
    with pytest.raises(ValueError, match="window requires causal"):
        full_attention(q, k, v, causal=False, window=8)


def test_make_flash_attention_window_closure():
    """The factory threads window through to the kernel (seqformer seam)."""
    q, k, v = _qkv(t=128, d=32)
    attn = make_flash_attention(causal=True, block_q=64, block_kv=64,
                                interpret=True, window=48)
    np.testing.assert_allclose(
        np.asarray(attn(q, k, v)),
        np.asarray(full_attention(q, k, v, causal=True, window=48)),
        atol=2e-5, rtol=2e-5,
    )


@pytest.mark.parametrize("h_kv", [1, 2, 4])
@pytest.mark.parametrize("causal", [False, True])
def test_gqa_forward_matches_reference(h_kv, causal):
    """Grouped-query attention (h_kv < h, incl. MQA at h_kv=1): the KV
    BlockSpec head mapping must agree with the broadcast reference."""
    q, _, _ = _qkv(t=128, d=16)
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    k = jax.random.normal(ks[0], (2, 128, h_kv, 16), jnp.float32)
    v = jax.random.normal(ks[1], (2, 128, h_kv, 16), jnp.float32)
    out = flash_attention(q, k, v, causal, None, 64, 32, True)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_gqa_gradients_match_reference_incl_window():
    """dK/dV under GQA group-sum onto the shared head (f32 partials),
    composed with sliding-window; shapes follow the kv head count."""
    q, _, _ = _qkv(t=128, d=16)
    ks = jax.random.split(jax.random.PRNGKey(10), 2)
    k = jax.random.normal(ks[0], (2, 128, 2, 16), jnp.float32)
    v = jax.random.normal(ks[1], (2, 128, 2, 16), jnp.float32)

    def loss_flash(q, k, v):
        return (
            flash_attention(q, k, v, True, None, 64, 32, True, 48) ** 2
        ).sum()

    def loss_ref(q, k, v):
        return (full_attention(q, k, v, causal=True, window=48) ** 2).sum()

    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    assert gf[1].shape == (2, 128, 2, 16)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4
        )


def test_gqa_rejects_indivisible_heads():
    q, _, _ = _qkv(t=64, d=16)  # 4 heads
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    k = jax.random.normal(ks[0], (2, 64, 3, 16), jnp.float32)
    with pytest.raises(ValueError, match="multiple of kv heads"):
        flash_attention(q, k, k, True, None, 64, 64, True)
    with pytest.raises(ValueError, match="multiple of kv heads"):
        full_attention(q, k, k, causal=True)


def test_make_flash_attention_auto_tiles_to_sequence():
    """block='auto' sizes the tile per call via flash_block_size, so the
    closure works at lengths a fixed 128 block would reject."""
    import numpy as np

    from blendjax.ops.flash_attention import (
        flash_block_size,
        make_flash_attention,
    )
    from blendjax.parallel.ring_attention import full_attention

    # the largest tile that divides the length and fits VMEM: a grid step
    # costs more than the causal work a big tile wastes (PERF.md, PR 29)
    assert flash_block_size(512) == 512  # the train cell: one step a head
    assert flash_block_size(4096) == 1024
    assert flash_block_size(768) == 256
    assert flash_block_size(160) == 32
    assert flash_block_size(20) == 20  # falls back to the length itself
    # a function of what the call sees: head size and dtype against
    # VMEM, and windowed calls stop at the largest tile measured for them
    assert flash_block_size(4096, 256, jnp.float32) == 512
    assert flash_block_size(4096, 128, jnp.float32) == 1024
    assert flash_block_size(4096, 1024, jnp.float32) == 256
    assert flash_block_size(4096, window=256) == 512

    attn = make_flash_attention(causal=True, block_q="auto",
                                block_kv="auto", interpret=True)
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 160, 2, 16),
                          jnp.float32)
    got = attn(q, q, q)
    want = full_attention(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)

    # ragged beyond a single tile: rejected, not silently O(T^2)
    bad = jax.random.normal(jax.random.PRNGKey(1), (1, 161, 2, 16),
                            jnp.float32)
    with pytest.raises(ValueError, match="pad to a 32-multiple"):
        attn(bad, bad, bad)


def _ref_out_and_grads(q, k, v, w):
    """float32 reference: out and the gradients of sum(out * w)."""
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]

    def loss(q, k, v):
        o = full_attention(q, k, v, causal=True)
        return jnp.sum(o * w), o

    (_, o), g = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(*f32)
    return (o, *g)


@pytest.mark.parametrize("blocks", ["auto", (128, 128)])
@pytest.mark.parametrize("dtype,out_tol,grad_tol", [
    (jnp.bfloat16, 2e-2, 5e-2), (jnp.float32, 2e-5, 5e-5),
])
def test_train_cell_shape_matches_reference(blocks, dtype, out_tol, grad_tol):
    """The train cell's per-head shape (T=512, d=128, causal) under the
    tiles the policy picks (one 512 tile a head: the single-block forward,
    the transposed dK/dV tiles, lse and delta as rows) and under explicit
    128 x 128 (the carried accumulators): bfloat16 inputs, whose blocks
    go to the products as bfloat16, within the bfloat16 tolerance of the
    float32 reference; float32 inputs within the float32 one."""
    q, k, v = _qkv(b=1, t=512, h=2, d=128, dtype=dtype, seed=3)
    w = jax.random.normal(jax.random.PRNGKey(4), q.shape, jnp.float32)
    bq, bkv = (blocks, blocks) if blocks == "auto" else blocks
    attn = make_flash_attention(causal=True, block_q=bq, block_kv=bkv,
                                interpret=True)

    def loss(q, k, v):
        o = attn(q, k, v)
        return jnp.sum(o.astype(jnp.float32) * w), o

    (_, o), g = jax.jit(
        jax.value_and_grad(loss, (0, 1, 2), has_aux=True))(q, k, v)
    assert o.dtype == dtype and all(x.dtype == dtype for x in g)
    ref = _ref_out_and_grads(q, k, v, w)
    for got, want, tol in zip((o, *g), ref, (out_tol,) + (grad_tol,) * 3):
        scale = float(np.abs(np.asarray(want)).max())
        np.testing.assert_allclose(
            np.asarray(got, np.float32) / scale, np.asarray(want) / scale,
            atol=tol, rtol=tol,
        )


def _dot_generals(jaxpr):
    """Every dot_general equation under ``jaxpr``, kernels' bodies too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _dot_generals(inner)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_products_take_the_inputs_dtype(dtype):
    """bfloat16 blocks reach every product of the three kernels as
    bfloat16 (p and ds are rounded to their partner's dtype,
    FlashAttention-2's convention), float32 blocks as float32 (what they
    lowered to before); every product accumulates in float32."""
    q, k, v = _qkv(b=1, t=128, h=1, d=128, dtype=dtype)

    def loss(q, k, v):
        return flash_attention(
            q, k, v, True, None, 64, 64, True).astype(jnp.float32).sum()

    dots = list(_dot_generals(
        jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v).jaxpr))
    assert len(dots) == 9  # forward 2, dQ pass 3, dK/dV pass 4
    for eqn in dots:
        assert [x.aval.dtype for x in eqn.invars] == [dtype, dtype]
        assert eqn.params["preferred_element_type"] == jnp.float32
