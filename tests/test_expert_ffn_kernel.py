"""The ``expert_ffn`` kernel (``blendjax/ops/expert_ffn.py``, interpreted on
this CPU) against the composition it replaced in ``moe_apply_held``: three
``jax.lax.ragged_dot`` calls, gate and up, ``silu`` and the product, then
down, kept here as the reference.

Widths whose intermediate size is an odd number of 128-lane columns (3, as
Mellum 2's 896 is 7) and an even one (4, as Sarvam's 2048 is 16), and one
that is not whole lanes (the tiny models' 24); runs of rows that are all
empty, empty between full ones, one row each, ~4 each (a decode tick's
share), one of hundreds over several row tiles (a prefill's), a row count
no tile divides, and rows past the runs' sum that hold NaN.  Only the runs'
rows are compared: the rest are the caller's to mask, and
``moe_apply_held`` masks them (its ``(y, counts)`` equal the
``ragged_dot`` path's).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blendjax.models import moe
from blendjax.ops import expert_ffn as expert_ffn_module
from blendjax.ops.expert_ffn import _vmem_bytes, expert_ffn, expert_ffn_tiles

#: (d, f, chunks): f of 3 and of 4 whole 128-lane columns, and under one
#: lane; each lane width also in chunks of 128 columns (as a wide expert's
#: are: Sarvam's 2048 in fours), where no wider chunk fits the budget
WIDTHS = {"odd_lanes": (256, 384, False), "even_lanes": (256, 512, False),
          "narrow": (48, 24, False), "odd_lanes_in_128s": (256, 384, True),
          "even_lanes_in_128s": (256, 512, True)}
#: (rows, run lengths): every case leaves rows past the runs' sum but one
RUNS = {
    "all_empty": (16, [0, 0, 0, 0]),
    "empty_between": (24, [5, 0, 7, 0, 3]),
    "one_row_each": (16, [1] * 8),
    "decode": (256, [4, 3, 5, 4, 0, 2, 6, 4, 3, 5, 4, 4, 2, 4, 4, 5]),
    "prefill": (512, [0, 300, 12, 0, 151]),
    "exactly_full": (64, [20, 20, 24]),
    "rows_unaligned": (13, [3, 0, 6]),   # padded to 16 and cut back
}
#: tolerance on the runs' rows, over the reference's largest magnitude
TOL = {"float32": 1e-5, "bfloat16": 2e-2}


def ragged_ffn(xs, sizes, gate, up, down):
    """The composition ``moe_apply_held`` made before the kernel."""
    h = jax.nn.silu(jax.lax.ragged_dot(xs, gate, sizes)) \
        * jax.lax.ragged_dot(xs, up, sizes)
    return jax.lax.ragged_dot(h, down, sizes)


def experts(e, d, f, dtype, seed=0):
    kg, ku, kd = jax.random.split(jax.random.PRNGKey(seed), 3)
    return ((jax.random.normal(kg, (e, d, f)) * d ** -0.5).astype(dtype),
            (jax.random.normal(ku, (e, d, f)) * d ** -0.5).astype(dtype),
            (jax.random.normal(kd, (e, f, d)) * f ** -0.5).astype(dtype))


@pytest.mark.parametrize("dtype", list(TOL))
@pytest.mark.parametrize("runs", list(RUNS))
@pytest.mark.parametrize("width", list(WIDTHS))
def test_the_kernel_equals_the_ragged_dot_composition(width, runs, dtype,
                                                      monkeypatch):
    d, f, chunked = WIDTHS[width]
    if chunked:
        monkeypatch.setattr(expert_ffn_module, "_VMEM_BUDGET", 0)
        assert expert_ffn_tiles(d, f, RUNS[runs][0], dtype)[1] == 128
    rows, sizes = RUNS[runs]
    used = sum(sizes)
    xs = jax.random.normal(jax.random.PRNGKey(1), (rows, d))
    xs = xs.at[used:].set(jnp.nan).astype(dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    weights = experts(len(sizes), d, f, dtype)
    got = np.asarray(jax.jit(expert_ffn)(xs, sizes, *weights)
                     .astype(jnp.float32))
    assert got.shape == (rows, d)
    # the reference in float32 from the same values: the kernel keeps
    # silu and the product in float32 and rounds h to the weights' dtype
    want = np.asarray(ragged_ffn(
        xs.astype(jnp.float32), sizes,
        *(w.astype(jnp.float32) for w in weights)))
    if not used:
        return
    scale = np.abs(want[:used]).max()
    assert np.isfinite(got[:used]).all()
    np.testing.assert_allclose(got[:used], want[:used],
                               atol=TOL[dtype] * scale, rtol=TOL[dtype])


@pytest.mark.parametrize("rows", [64, 128, 256, 320, 384, 512, 2048, 8192,
                                  12288, 16384, 24, 1001])
@pytest.mark.parametrize("d,f", [(2304, 896), (4096, 2048), (48, 24)])
def test_the_tiles_follow_the_shapes(d, f, rows):
    """Mellum 2's and Sarvam's experts at every bucket's and prefill's
    rows (top 8), the tiny models', and rows no power of two divides: a
    row tile of whole sublanes up to the MXU's 128 that divides the rows
    (padded to 8), a column chunk of whole lanes that divides ``f``, and
    a VMEM limit over the step's estimate and under the chip's 128 MiB."""
    tm, tf, limit = expert_ffn_tiles(d, f, rows, jnp.bfloat16)
    assert tm % 8 == 0 and tm <= 128 and (-(-rows // 8) * 8) % tm == 0
    assert f % tf == 0 and (tf % 128 == 0 or tf == f)
    assert _vmem_bytes(d, tf, tm, 2) < limit < 100 * 2 ** 20
    if (d, f) == (2304, 896):
        assert tf == 896  # a Mellum expert's three matrices in one step


def held_layer(d, f, held, first, n_routed, score):
    kr, kw = jax.random.split(jax.random.PRNGKey(4))
    spec = moe.RouteSpec(top_k=4, first=first, score=score, renorm=True)
    return {"router": {"w": jax.random.normal(kr, (d, n_routed)) * d ** -0.5,
                       "bias": jnp.zeros((n_routed,))},
            **moe.gated_mlp_init(kw, d, f, stack=(held,)), "route": spec}


@pytest.mark.parametrize("dtype", list(TOL))
@pytest.mark.parametrize("valid", [False, True])
@pytest.mark.parametrize("d,f,score", [(256, 384, "softmax"),
                                       (48, 24, "sigmoid")])
def test_moe_apply_held_equals_the_ragged_dot_path(d, f, score, valid,
                                                    dtype, monkeypatch):
    p = held_layer(d, f, held=4, first=2, n_routed=16, score=score)
    x = jax.random.normal(jax.random.PRNGKey(5), (40, d))
    mask = (jnp.arange(40) < 33) if valid else None
    y, counts = moe.moe_apply_held(p, x, dtype, valid=mask)
    monkeypatch.setattr(moe, "expert_ffn", ragged_ffn)
    want, want_counts = moe.moe_apply_held(p, x, dtype, valid=mask)
    np.testing.assert_array_equal(counts, want_counts)
    want = np.asarray(want.astype(jnp.float32))
    np.testing.assert_allclose(
        np.asarray(y.astype(jnp.float32)), want,
        atol=TOL[dtype] * np.abs(want).max(), rtol=TOL[dtype])
