"""The held experts' gated feed-forward, one Pallas kernel for all three
products.

A held-share routed layer (:func:`blendjax.models.moe.moe_apply_held`)
sorts its assignment rows by expert, so that expert ``e``'s rows are the
contiguous run ``xs[offsets[e]:offsets[e + 1]]`` (``sizes`` the runs'
lengths, rows past their sum whatever they are), and computes for every
run::

    down_e(silu(x gate_e) * (x up_e))

:func:`expert_ffn` does that in one ``pallas_call``.  Its grid runs over
*visits* (an expert and one row tile of ``tm`` rows that its run touches)
and, inside a visit, over chunks of ``tf`` of the ``f`` intermediate
columns.  A step reads one chunk of the expert's ``gate`` and ``up``
columns and the matching rows of ``down`` (the block index comes from the
visit's expert, a scalar-prefetch argument), computes ``silu(x Wg) *
(x Wu)`` in VMEM with float32 accumulation, and adds ``h Wd`` into a
float32 accumulator; the visit's last chunk writes the rows of the tile
that belong to its expert into the output block, which stays in VMEM while
the next visit works on the same tile and is written back once.  Experts
with no row are not visited, so their weights are never read; an expert
whose run lies in one tile is read once.

Why it is not ``jax.lax.ragged_dot``: the TPU compiler lowers each of its
three products to a grouped kernel of its own whose weight blocks, at a
narrow expert (2304 x 896), are 256 x 128 (64 KB): a step's fixed cost then
outweighs its copy, and the weights streamed at ~15-20% of the chip's HBM
peak whatever the rows (PERF.md section 6).  Here a step moves one whole
chunk of an expert's three matrices in blocks of megabytes, double-buffered,
and the three products of a row tile never leave VMEM.

The tiles follow from the shapes alone (:func:`expert_ffn_tiles`): no
model name, no option.  The arithmetic is the composition's: the inputs'
dtype on the MXU, float32 accumulation, ``silu`` and the product in
float32, ``h`` rounded to the weights' dtype before ``down``.  Interpret
mode follows the one rule in
:func:`blendjax.ops.flash_attention.resolve_interpret`.
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the module (the package re-exports its function under the same name),
# read at each call: one rule steers every kernel, and a test that steers it
# steers this one
_RULE = importlib.import_module("blendjax.ops.flash_attention")

_LANES = 128
_MXU = 128            # a row tile under the MXU's width costs a full pass
_VMEM_BUDGET = 40 * 2 ** 20


def _row_tile(rows):
    """The largest of 128, 64 ... 8 that divides ``rows`` (one tile when
    ``rows`` is smaller); None where none does."""
    if rows <= _MXU and rows % 8 == 0:
        return rows
    tile = _MXU
    while tile >= 8:
        if rows % tile == 0:
            return tile
        tile //= 2
    return None


def _vmem_bytes(d, tf, tm, itemsize):
    """VMEM one grid step holds: the three weight blocks, the row tile in
    and the output tile, each double-buffered by the pipeline; the float32
    accumulator; the float32 temporaries of the two column products, ``h``
    and the step's part of the output."""
    return (2 * 3 * d * tf * itemsize + 2 * 2 * tm * d * itemsize
            + 4 * tm * d + 4 * (3 * tm * tf + tm * d))


def expert_ffn_tiles(d, f, rows, dtype):
    """``(tm, tf, vmem_limit_bytes)`` for ``rows`` sorted rows of width
    ``d`` through experts of ``f`` intermediate columns in ``dtype``: the
    row tile the largest power of two up to the MXU's 128 that divides the
    rows (the rows are padded to a multiple of 8 where none does), the
    column chunk the largest run of whole 128-lane columns dividing ``f``
    (or ``f`` itself where it is not whole lanes) whose step fits the VMEM
    budget, and the limit that step's estimate with a quarter's room."""
    itemsize = jnp.dtype(dtype).itemsize
    tm = _row_tile(rows) or _row_tile(-(-rows // 8) * 8)
    if f % _LANES:
        chunks = [f]
    else:
        chunks = [c * _LANES for c in range(f // _LANES, 0, -1)
                  if (f // _LANES) % c == 0]
    fits = [c for c in chunks
            if _vmem_bytes(d, c, tm, itemsize) <= _VMEM_BUDGET]
    tf = fits[0] if fits else chunks[-1]
    need = _vmem_bytes(d, tf, tm, itemsize)
    return tm, tf, max(32 * 2 ** 20, need + need // 4)


def _visits(sizes, rows, tm):
    """The grid's visits, expert by expert and, inside an expert's run,
    tile by tile (so a tile's visits are consecutive): ``offsets`` (E + 1,)
    where each run starts, each visit's expert and row tile, padded to the
    static bound ``rows / tm + E - 1`` with the last expert and tile, and
    the number of real visits."""
    e = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    count = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    seen = jnp.cumsum(count)
    bound = rows // tm + e - 1
    v = jnp.arange(bound, dtype=jnp.int32)
    group = jnp.minimum(jnp.searchsorted(seen, v, side="right"), e - 1)
    tile = first[group] + v - (seen - count)[group]
    tile = jnp.clip(tile, 0, rows // tm - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets.astype(jnp.int32), group.astype(jnp.int32),
            tile.astype(jnp.int32), seen[-1].astype(jnp.int32))


def _kernel(offsets_ref, group_ref, tile_ref, x_ref, gate_ref, up_ref,
            down_ref, out_ref, acc_ref):
    v, j = pl.program_id(0), pl.program_id(1)
    x = x_ref[...]
    a = jnp.dot(x, gate_ref[...], preferred_element_type=jnp.float32)
    b = jnp.dot(x, up_ref[...], preferred_element_type=jnp.float32)
    h = (a * jax.nn.sigmoid(a) * b).astype(down_ref.dtype)
    part = jnp.dot(h, down_ref[...], preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = part

    @pl.when(j > 0)
    def _():
        acc_ref[...] += part

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        # the rows of this tile that are the visit's expert's; the rest
        # keep what an earlier visit of the tile wrote (or nothing)
        g = group_ref[v]
        tm = acc_ref.shape[0]
        row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        out_ref[...] = jnp.where(mine, acc_ref[...].astype(out_ref.dtype),
                                 out_ref[...])


def expert_ffn(xs, sizes, gate, up, down, interpret=None):
    """``down_e(silu(x gate_e) * (x up_e))`` for the rows of each expert's
    run: ``xs`` (m, d) sorted by expert, ``sizes`` (E,) int32 run lengths
    summing to at most m, ``gate`` and ``up`` (E, d, f), ``down`` (E, f,
    d), one dtype.  Returns (m, d) in ``xs``' dtype; rows past the runs'
    sum are left as they fall (the caller masks them)."""
    m, d = xs.shape
    tiles = expert_ffn_tiles(d, gate.shape[2], m, xs.dtype)
    return _expert_ffn(xs, sizes, gate, up, down, tiles,
                       _RULE.resolve_interpret(interpret))


# jitted with the tiles and the mode as static arguments: a model's layers
# call it with the same shapes, and one traced and lowered kernel serves
# them all (lowering it again in every layer cost seconds of set-up)
@functools.partial(jax.jit, static_argnums=(5, 6))
def _expert_ffn(xs, sizes, gate, up, down, tiles, interpret):
    m, d = xs.shape
    e, _, f = gate.shape
    tm, tf, vmem = tiles
    rows = -(-m // tm) * tm
    if rows != m:
        xs = jnp.pad(xs, ((0, rows - m), (0, 0)))
    offsets, group, tile, n_visits = _visits(sizes.astype(jnp.int32), rows,
                                             tm)

    def by_tile(v, j, offsets, group, tile):
        return tile[v], 0

    itemsize = xs.dtype.itemsize
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n_visits, f // tf),
            in_specs=[
                pl.BlockSpec((tm, d), by_tile),
                pl.BlockSpec((None, d, tf),
                             lambda v, j, o, g, t: (g[v], 0, j)),
                pl.BlockSpec((None, d, tf),
                             lambda v, j, o, g, t: (g[v], 0, j)),
                pl.BlockSpec((None, tf, d),
                             lambda v, j, o, g, t: (g[v], j, 0)),
            ],
            out_specs=pl.BlockSpec((tm, d), by_tile),
            scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((rows, d), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        cost_estimate=pl.CostEstimate(
            flops=6 * m * d * f, transcendentals=m * f,
            bytes_accessed=(3 * e * d * f + 2 * m * d) * itemsize),
        interpret=interpret,
        name="expert_ffn",
    )(offsets, group, tile, xs, gate, up, down)
    return out[:m] if rows != m else out
