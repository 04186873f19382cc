"""Pallas TPU flash attention: block-wise online-softmax attention that
never materializes the (T, T) score matrix.

The SeqFormer's single-device attention (`full_attention`,
``blendjax/parallel/ring_attention.py``) builds (B, H, T, T) scores —
O(T^2) HBM traffic and memory, the classic long-context wall.  This
kernel streams K/V blocks through VMEM, keeping the running max/sum and
the output accumulator on-chip (the FlashAttention recurrence), so HBM
traffic is O(T*D) and the MXU sees back-to-back (block_q, D) x
(D, block_kv) and (block_q, block_kv) x (block_kv, D) matmuls.

Grid layout: ``(B*H, T/block_q, T/block_kv)`` with the KV dimension
innermost — TPU grid steps run sequentially per core, so the f32
accumulator/max/sum scratch carries across KV steps and is written to
the output on the last one; the two outer axes are declared ``parallel``
to the compiler.  Where one KV block is the whole sequence (the train
shape, T=512) the forward is a plain softmax with nothing carried.

Differentiation is fully fused too (``custom_vjp``): the forward also
emits the per-row logsumexp, and the backward runs two block-wise
kernels — a dQ pass (KV innermost, dQ accumulator carried) and a dK/dV
pass (Q innermost, its tiles transposed so p^T and ds^T are matmul
operands as they stand) — recomputing probabilities from the saved
logsumexp (FlashAttention-2 recurrence, with ``D = rowsum(dO * O)`` as
the softmax-jacobian correction).  No (T, T) matrix exists in either
direction; gradient parity vs the einsum reference is tested to ~5e-5.

What a grid step hands the chip (PR 29; the numbers are in PERF.md):

* **Operands follow the inputs' dtype, accumulation is float32.**
  bfloat16 q/k/v/dO blocks go to the MXU as bfloat16, p and ds are
  rounded to their partner's dtype before p@v, p^T@dO, ds@k, ds^T@q;
  float32 inputs keep float32 products.  Softmax state (m, l, lse,
  delta, exp, the mask) is float32 always.  (On a v5e Mosaic's default
  float32 product is already one bfloat16 pass, so this changed neither
  a time nor a result there; it halves the blocks' VMEM and registers.)
* **Tiles as large as divide the sequence and fit VMEM**
  (:func:`flash_block_size`): a grid step costs ~0.5-1 us whatever it
  computes, so 128-tiles were step-bound: x5.6 at T=512, x6.9 at T=4096.
* **lse and delta travel as lane-dense rows** (:func:`_row_blocks`), not
  as (T, 1) columns whose every number is a DMA row of its own: at 512
  tiles the columns alone were 0.8 ms of a 1.3 ms backward call.

Interpret mode runs the same kernel on CPU for CI (parity against
``full_attention`` is tested both causal and not).  ONE rule picks it,
:func:`resolve_interpret`: ``interpret=None`` (every default) compiles
through Mosaic on a TPU backend and interprets elsewhere; an explicit
bool wins.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
_LANES = 128


def resolve_interpret(interpret=None):
    """THE rule for Pallas interpret mode, shared by every kernel the
    package ships and every parallel scheme that wraps one: ``None``
    compiles on a TPU backend and interprets elsewhere (the CPU mesh CI
    runs on); an explicit bool wins (``tests/test_tpu_lowering.py``
    forces the compiled lowering when exporting for TPU from a CPU
    host)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _default_scale(scale, d):
    return scale if scale is not None else 1.0 / (d ** 0.5)


_TILES = (1024, 512, 256, 128, 64, 32)
_WINDOW_TILE_CAP = 512
_VMEM_BUDGET = 24 * 2 ** 20


def _tile_vmem_bytes(block, head_dim, itemsize):
    """VMEM one grid step of the dK/dV pass (the largest live set of the
    three) holds at a square ``block`` tile: q, k, v, dO in and dK, dV
    out, each double-buffered by the pipeline; two float32 accumulators;
    three float32 (block, block) score-sized temporaries."""
    io = 2 * 6 * block * head_dim * itemsize
    acc = 2 * block * head_dim * 4
    return io + acc + 3 * block * block * 4


def flash_block_size(seq_len, head_dim=128, dtype=jnp.bfloat16, window=None):
    """THE tile-selection policy (``block_q == block_kv``, all three
    kernels), shared by ``block='auto'``, the ring/ulysses parallel paths
    and user code: the LARGEST tile of 1024 ... 32 that divides
    ``seq_len`` and fits VMEM, or ``seq_len`` itself where none divides
    (legal on TPU via the 'equal to the array dim' tiling clause).

    Why the largest: a grid step costs ~0.5-1 us whatever it computes (its
    DMAs, its ``pl.when``s, the scratch read-modify-write), so on a v5e
    small tiles are step-bound, and the causal work a big tile wastes above
    the diagonal costs less than the steps it saves.  Measured, bfloat16,
    d=128, one call of fwd + dq + dkv (PERF.md, PR 29): 64 x 8 heads at
    T=512, 12.0 ms at 128 tiles, 5.8 at 256, 2.1 at 512 (one step per
    head); 8 x 8 heads at T=4096, 76 ms at 128, 15.2 at 512, 11.1 at 1024.
    The same order held for forward, dQ and dK/dV alone and for every
    non-square pair tried, so one number serves all three.  Under a
    sliding ``window`` the shrunk grids keep their O(T*W) step count at any
    tile; 512 beat 256 and 128 at W=256 and W=1024, larger was not
    measured, so windowed calls stop at 512.

    Against VMEM: ``_tile_vmem_bytes`` under ``_VMEM_BUDGET``, set from
    what the v5e compiler accepts (1024 tiles compile up to d=256 float32
    and are refused at d=512, where 512 tiles compile;
    ``tests/test_tpu_compile.py`` compiles the policy's choices)."""
    itemsize = jnp.dtype(dtype).itemsize
    cap = _TILES[0] if window is None else _WINDOW_TILE_CAP
    for block in _TILES:
        if (seq_len % block == 0 and block <= cap and _tile_vmem_bytes(
                block, head_dim, itemsize) <= _VMEM_BUDGET):
            return block
    return seq_len


def _block_live(causal, qi, kj, block_q, block_kv, window=None,
                q_offset=0):
    """False for blocks whose probabilities are exactly zero, so compute
    is skipped: strictly above the causal diagonal (roughly halves the
    FLOPs at long context), and — under a sliding ``window`` — strictly
    below it (every key older than ``window`` positions).  The windowed
    grids are also *shrunk* (see ``_kv_window_steps``): ``kj``/``qi``
    may then be derived block indices that run past the array, and the
    two predicates below also correctly kill those overshoot steps (a
    too-large ``kj`` fails the causal bound when ``q_offset == 0``; a
    too-large ``qi`` fails the window bound) — EXCEPT a kv overshoot
    under a nonzero ``q_offset``, where rows sit above every real
    column and the caller's kernels add an explicit range guard.

    ``q_offset`` (static) is the q rows' global position minus the kv
    cols': the ring variants run this kernel on (my queries x an
    EARLIER shard's KV), where the pair's offset is a static multiple
    of the shard length."""
    if not causal:
        return True
    live = kj * block_kv <= qi * block_q + q_offset + (block_q - 1)
    if window is not None:
        # kv block's newest col must be within `window` of the q block's
        # oldest row: max_col >= min_row - (window - 1).  qi/kj are traced
        # program ids, so combine with logical_and, not `and`
        live = jnp.logical_and(
            live,
            kj * block_kv + (block_kv - 1)
            >= qi * block_q + q_offset - (window - 1),
        )
    return live


def _kv_window_steps(num_kv, block_q, block_kv, window):
    """Grid steps needed along KV for one q block under a sliding window:
    the visible span is ``block_q + window - 1`` contiguous positions,
    which straddles at most ``(span - 2) // block_kv + 2`` KV blocks at
    worst-case alignment.  This is what makes windowed attention O(T*W)
    in *grid steps and HBM traffic*, not just FLOPs — without it the
    grid stays (bh, T/bq, T/bkv) and every dead block still costs a DMA
    and a grid step."""
    span = block_q + window - 1
    return min(num_kv, (span - 2) // block_kv + 2)


def _kv_base(i, block_q, block_kv, window, q_offset=0):
    """First KV block index visible to q block ``i`` (floor-clamped to
    0); traced — used in both the BlockSpec index maps and the kernels'
    liveness checks."""
    return jnp.maximum(
        0, (i * block_q + q_offset - (window - 1)) // block_kv
    )


def _q_window_steps(num_q, block_q, block_kv, window):
    """Grid steps along Q for one KV block in the dK/dV pass (rows that
    can see this block span ``block_kv + window - 1`` positions)."""
    span = block_kv + window - 1
    return min(num_q, (span - 2) // block_q + 2)


def _q_base(j, block_q, block_kv, window, q_offset=0):
    """First Q block index that can see KV block ``j`` (causal: rows
    start at the block's own first column, shifted down by the pair's
    static row/col offset)."""
    del window
    return jnp.maximum(0, (j * block_kv - q_offset) // block_q)


def _window_index_map(num_blocks, base_fn, head_map=None):
    """BlockSpec index map for a shrunk windowed grid axis: the inner
    grid step maps to block ``base(mid) + step``, clamped onto the last
    real block (overshoot steps' compute is killed by the kernels'
    liveness predicates; the clamped DMA is the only waste).  Every
    pass's windowed axis has this shape — fwd/dQ run ``(bh, q, kv)``
    with the KV base driven by the q index, dK/dV runs ``(bh, kv, q)``
    with the Q base driven by the kv index — so one helper keeps the
    three derivations from desynchronizing.  ``head_map`` remaps the
    flat batch*head coordinate (GQA: several q heads share a kv head)."""

    def index_map(bh, mid, inner):
        b = bh if head_map is None else head_map(bh)
        return (b, jnp.minimum(base_fn(mid) + inner, num_blocks - 1), 0)

    return index_map


def _kv_head_map(h_q, h_kv):
    """Flat ``b*h`` index of the KV head serving flat q-head ``bh`` —
    grouped-query attention's whole mechanism at the BlockSpec level:
    ``h_q // h_kv`` consecutive q heads read the same KV block, so the
    kernel bodies never know GQA exists.  Identity (None) when the head
    counts match."""
    if h_q == h_kv:
        return None
    g = h_q // h_kv
    return lambda bh: (bh // h_q) * h_kv + (bh % h_q) // g


def _kv_axis(num_kv, block_q, block_kv, window, q_offset, khm):
    """(steps, index map) for the KV grid axis of the fwd and dQ passes
    — the ONE place the windowed-shrink and GQA head-remap derivations
    combine, so the two passes cannot desynchronize."""
    if window is None:
        if khm is None:
            im = lambda bh, i, j: (bh, j, 0)
        else:
            im = lambda bh, i, j: (khm(bh), j, 0)
        return num_kv, im
    steps = _kv_window_steps(num_kv, block_q, block_kv, window)
    im = _window_index_map(
        num_kv,
        lambda i: _kv_base(i, block_q, block_kv, window, q_offset),
        head_map=khm,
    )
    return steps, im


def _mask(s, i, j, block_q, block_kv, window=None, q_offset=0,
          transposed=False):
    """Causal (and window) mask of a score tile; ``transposed`` for the
    dK/dV pass's (block_kv, block_q) tiles, whose rows are key columns."""
    rows = q_offset + i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1 if transposed else 0
    )
    cols = j * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0 if transposed else 1
    )
    keep = cols <= rows
    if window is not None:
        keep = jnp.logical_and(keep, cols > rows - window)
    return jnp.where(keep, s, _NEG)


def _dot(a, b, ca, cb):
    """``a`` x ``b`` contracting dims ``ca``/``cb``, accumulated in
    float32.  The operands keep the dtype the caller passed in (bfloat16
    blocks go to the MXU as bfloat16, float32 blocks as float32); a mixed
    pair is promoted, never rounded down."""
    dt = jnp.promote_types(a.dtype, b.dtype)
    return jax.lax.dot_general(
        a.astype(dt), b.astype(dt), (((ca,), (cb,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _scores(q_ref, k_ref, qi, kj, scale, causal, block_q, block_kv,
            window=None, q_offset=0):
    s = _dot(q_ref[0], k_ref[0], 1, 1) * scale
    if causal:
        s = _mask(s, qi, kj, block_q, block_kv, window, q_offset)
    return s


def _row_to_col(row):
    """(1, n) float32 row -> (n, 1) column.  lse and delta travel as
    lane-dense rows (a (n, 1) block moves one number per 512-byte DMA row,
    which alone was most of the kernels' time at 512 tiles); the one
    kernel that needs them down the sublanes turns them here."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T[:, :1]


def _emit_rows(o_ref, lse_ref, acc, m, l):
    """Normalize ``acc`` into the output block and write the lse row.
    ``m`` / ``l`` are per-row columns, (block_q, 1) or lane-replicated
    (block_q, 128); replicated, their transpose's first row IS the
    lane-dense lse row (see :func:`_row_blocks` for why lse does not ride
    as a column)."""
    safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / safe[:, :1]).astype(o_ref.dtype)
    lse = jnp.broadcast_to(m + jnp.log(safe), (acc.shape[0], _LANES))
    lse_ref[0, 0] = lse.T[:1]


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
            scale, causal, block_q, block_kv, num_kv, num_kv_total=None,
            window=None, q_offset=0):
    i = pl.program_id(1)
    j = pl.program_id(2)
    if num_kv_total == 1 and window is None:
        # the whole KV sequence is this one block (always live: column 0
        # is visible to every row): a plain softmax, no running max / sum
        # / accumulator to initialize, rescale and read back.  Under a
        # `pl.when` like every ref access of this file: at a kernel's top
        # level the interpreter's block reads fail shard_map's
        # varying-axes check (the ulysses path, on the CPU mesh)
        @pl.when(j == 0)  # always: the one step there is
        def _single():
            s = _scores(q_ref, k_ref, i, 0, scale, causal, block_q,
                        block_kv, None, q_offset)
            v = v_ref[0]
            m = s.max(axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            _emit_rows(o_ref, lse_ref, _dot(p.astype(v.dtype), v, 1, 0), m,
                       p.sum(axis=-1, keepdims=True))

        return

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    # under a window the grid's kv axis is shrunk: step j maps to actual
    # kv block base(i) + j (overshoot steps are killed by _block_live)
    kj = j if window is None else _kv_base(
        i, block_q, block_kv, window, q_offset
    ) + j
    live = _block_live(causal, i, kj, block_q, block_kv, window, q_offset)
    if window is not None and q_offset:
        # with rows offset above every real column the causal bound no
        # longer kills a kv overshoot past the array — guard explicitly
        live = jnp.logical_and(live, kj <= num_kv_total - 1)

    @pl.when(live)
    def _compute():
        s = _scores(q_ref, k_ref, i, kj, scale, causal, block_q,
                    block_kv, window, q_offset)
        v = v_ref[0]
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_cur)
        alpha = jnp.exp(m_prev - m_cur)
        l_new = alpha * l_prev + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _dot(
            p.astype(v.dtype), v, 1, 0
        )
        m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == num_kv - 1)
    def _emit():
        _emit_rows(o_ref, lse_ref, acc_ref[...], m_ref[...], l_ref[...])


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, scale, causal, block_q, block_kv, num_kv,
               num_kv_total=None, window=None, q_offset=0):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    i = pl.program_id(1)
    kj = j if window is None else _kv_base(
        i, block_q, block_kv, window, q_offset
    ) + j
    live = _block_live(causal, i, kj, block_q, block_kv, window, q_offset)
    if window is not None and q_offset:
        live = jnp.logical_and(live, kj <= num_kv_total - 1)

    @pl.when(live)
    def _compute():
        s = _scores(q_ref, k_ref, i, kj, scale, causal, block_q,
                    block_kv, window, q_offset)
        k = k_ref[0]
        p = jnp.exp(s - _row_to_col(lse_ref[0, 0]))  # (bq,1) bcast
        dp = _dot(do_ref[0], v_ref[0], 1, 1)
        ds = p * (dp - _row_to_col(delta_ref[0, 0])) * scale
        dq_acc[...] += _dot(ds.astype(k.dtype), k, 1, 0)

    @pl.when(j == num_kv - 1)
    def _emit():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, scale, causal, block_q, block_kv,
                num_q, num_q_total=None, window=None, q_offset=0):
    i = pl.program_id(2)  # q-block index is INNERMOST in the dkv pass

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    j = pl.program_id(1)
    qi = i if window is None else _q_base(
        j, block_q, block_kv, window, q_offset
    ) + i
    live = _block_live(causal, qi, j, block_q, block_kv, window, q_offset)
    if window is not None:
        # unlike KV overshoot (killed by the causal bound at zero
        # offset), a derived qi past the last real q block still passes
        # both predicates when the window span runs off the end of the
        # sequence — and would double-count the clamped block under a
        # phantom-row mask
        live = jnp.logical_and(live, qi <= num_q_total - 1)

    @pl.when(live)
    def _compute():
        # every tile here is TRANSPOSED, (block_kv, block_q): p^T and ds^T
        # are then the left operands of dV = p^T dO and dK = ds^T Q as
        # they stand (no (block, block) transpose before either product),
        # and lse / delta broadcast down the sublanes as the rows they are
        q, do = q_ref[0], do_ref[0]
        s_t = _dot(k_ref[0], q, 1, 1) * scale
        if causal:
            s_t = _mask(s_t, qi, j, block_q, block_kv, window, q_offset,
                        transposed=True)
        p_t = jnp.exp(s_t - lse_ref[0, 0])  # (1,bq) bcast
        dv_acc[...] += _dot(p_t.astype(do.dtype), do, 1, 0)
        dp_t = _dot(v_ref[0], do, 1, 1)
        ds_t = p_t * (dp_t - delta_ref[0, 0]) * scale
        dk_acc[...] += _dot(ds_t.astype(q.dtype), q, 1, 0)

    @pl.when(i == num_q - 1)
    def _emit():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# bh and the middle block axis are independent; the innermost axis carries
# the accumulators (all three kernels)
_GRID_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)


def _scratch(shapes):
    return [pltpu.VMEM(s, jnp.float32) for s in shapes]


def _flat(x):
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _unflat(xf, b, h):
    bh, t, d = xf.shape
    return xf.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _sds(shape, dtype, like):
    """ShapeDtypeStruct that inherits ``like``'s varying-manual-axes type,
    so the kernel composes inside shard_map (e.g. as Ulysses' inner
    attention) under vma typing."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _row_spec(block_q, q_im):
    """BlockSpec of the (1, block_q) lse / delta row that belongs to the
    q block ``q_im`` picks; the arrays are ``_row_blocks``-shaped."""

    def index_map(*grid):
        b, i, _ = q_im(*grid)
        return (b, i, 0, 0)

    return pl.BlockSpec((1, 1, 1, block_q), index_map)


def _row_blocks(x, block_q):
    """Per-row statistics (lse, delta: ``bh * t`` float32 numbers in
    ``(bh, t)`` order, any shape) as ``(bh, t / block_q, 1, block_q)``, so
    that a kernel's block is one lane-dense ``(1, block_q)`` row whose last
    two dims equal the array's (legal on TPU at any ``block_q``).  As
    ``(bh, t, 1)`` columns — what the kernels read until PR 29 — a block
    is ``block_q`` DMA rows of one number each: at 512 tiles that traffic
    alone took 0.8 ms of a 1.3 ms backward call (PERF.md, PR 29)."""
    bh = x.shape[0]
    return x.reshape(bh, x.size // (bh * block_q), 1, block_q)


def _check_blocks(t, block, name):
    if t % block:
        raise ValueError(
            f"sequence length {t} must divide {name}={block} "
            "(pad upstream or pick smaller blocks)"
        )


def _flash_fwd_impl(q, k, v, causal, scale, block_q, block_kv, interpret,
                    out_dtype=None, window=None, q_offset=0):
    """Returns (out (B,T,H,D), flat residuals (qf,kf,vf,of,lse)).

    ``out_dtype`` overrides the output dtype (default: q's) — ring_flash
    requests f32 so its cross-block combination accumulates unrounded
    partials (the kernel's internal accumulator is f32 regardless).
    ``q_offset`` (static): global position of q row 0 minus kv col 0 —
    the windowed ring variant runs this on (my queries x an earlier
    shard's KV) where the offset is a static shard multiple; k/v may
    then have a different sequence length than q.

    GQA: k/v may carry fewer heads than q (``h % h_kv == 0``); the KV
    BlockSpecs then map each q head onto its group's shared KV head."""
    b, t, h, d = q.shape
    tk, h_kv = k.shape[1], k.shape[2]
    _check_blocks(t, block_q, "block_q")
    _check_blocks(tk, block_kv, "block_kv")
    _check_window_overshoot(window, q_offset, t, tk)
    if h % h_kv:
        raise ValueError(
            f"q heads {h} must be a multiple of kv heads {h_kv} (GQA)"
        )
    if v.shape[2] != h_kv:
        raise ValueError(
            f"k has {h_kv} heads but v has {v.shape[2]} — the shared "
            "KV head map would silently read wrong v blocks"
        )
    qf, kf, vf = _flat(q), _flat(k), _flat(v)
    num_q = t // block_q
    num_kv = tk // block_kv
    khm = _kv_head_map(h, h_kv)
    kv_steps, kv_im = _kv_axis(
        num_kv, block_q, block_kv, window, q_offset, khm
    )

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, block_q=block_q,
        block_kv=block_kv, num_kv=kv_steps, num_kv_total=num_kv,
        window=window, q_offset=q_offset,
    )
    q_im = lambda bh, i, j: (bh, i, 0)
    q_spec = pl.BlockSpec((1, block_q, d), q_im)
    of, lse = pl.pallas_call(
        kernel,
        grid=(b * h, num_q, kv_steps),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, block_kv, d), kv_im),
            pl.BlockSpec((1, block_kv, d), kv_im),
        ],
        out_specs=[q_spec, _row_spec(block_q, q_im)],
        out_shape=[
            _sds((b * h, t, d), out_dtype or q.dtype, qf),
            _sds((b * h, num_q, 1, block_q), jnp.float32, qf),
        ],
        scratch_shapes=_scratch([
            (block_q, d), (block_q, _LANES), (block_q, _LANES)
        ]),
        compiler_params=_GRID_SEMANTICS,
        interpret=resolve_interpret(interpret),
        name="flash_fwd",
    )(qf, kf, vf)
    return _unflat(of, b, h), (qf, kf, vf, of, lse.reshape(b * h, 1, t))


def _check_window(causal, window):
    if window is None:
        return
    if not causal:
        raise ValueError("window (sliding-window attention) requires causal=True")
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")


def _check_window_overshoot(window, q_offset, tq, tk):
    """Enforce the windowed-overshoot invariant the kernels rely on: a
    clamped last-KV-block overshoot at ``q_offset == 0`` is only killed
    by the causal bound when ``Tk == Tq`` (true for every current call
    site — full sequences and same-shard ring pairs).  ``Tk != Tq`` with
    a zero offset would read the clamped block with a LIVE mask and
    silently attend out of window, so fail loudly instead (ADVICE r5)."""
    if window is not None and not q_offset and tk != tq:
        raise ValueError(
            f"windowed attention with q_offset=0 requires Tk == Tq (got "
            f"Tq={tq}, Tk={tk}): the overshoot clamp relies on the causal "
            "bound to kill the last KV block, which only holds for "
            "same-length pairs; pass the pair's static q_offset"
        )


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8)
)
def flash_attention(q, k, v, causal=False, scale=None, block_q=128,
                    block_kv=128, interpret=None, window=None):
    """Fused block-wise attention; same contract as ``full_attention``:
    q/k/v (B, T, H, D) -> (B, T, H, D).

    ``T`` must divide by both block sizes (pick blocks accordingly or pad
    upstream).  ``interpret`` follows :func:`resolve_interpret`.

    GQA/MQA: k/v may carry FEWER heads than q (``H % H_kv == 0``) —
    each group of ``H // H_kv`` q heads reads the same KV head, purely
    through the KV BlockSpec index maps (kernel bodies are unchanged,
    and KV HBM traffic drops by the group factor); dK/dV group-sums
    per-q-head f32 partials onto the shared head.

    ``window=W`` (requires ``causal=True``) is sliding-window attention:
    each query attends to its own and the previous ``W - 1`` positions.
    ``W`` is static, so every pass (forward, dQ, dK/dV) *shrinks its
    grid*: the KV (resp. Q) axis runs only the ~``W / block`` blocks a
    block can see, with a per-block base offset in the BlockSpec index
    map — grid steps, DMA traffic, and FLOPs all scale O(T*W) instead
    of O(T^2/2).
    """
    _check_window(causal, window)
    scale = _default_scale(scale, q.shape[-1])
    out, _ = _flash_fwd_impl(q, k, v, causal, scale, block_q, block_kv,
                             interpret, window=window)
    return out


def _fwd(q, k, v, causal, scale, block_q, block_kv, interpret, window):
    _check_window(causal, window)
    scale_v = _default_scale(scale, q.shape[-1])
    out, res = _flash_fwd_impl(
        q, k, v, causal, scale_v, block_q, block_kv, interpret,
        window=window
    )
    return out, res + (q.shape,)


def _dq_pass(qf, kf, vf, dof, lse, delta, causal, scale, block_q,
             block_kv, interpret, out_dtype=None, window=None,
             q_offset=0, heads=None):
    """dQ for one (Tq, Tk) pair of flat arrays — used over the full
    sequence by :func:`flash_attention`'s vjp and per ring-block pair by
    :func:`blendjax.parallel.ring_attention.ring_flash_attention` (which
    passes ``out_dtype=f32`` so its cross-block accumulation never sums
    rounded partials).  ``lse`` / ``delta``: one float32 per q row in
    ``(bh, tq)`` order, in any shape (:func:`_row_blocks` shapes them)."""
    bh, tq, d = qf.shape
    tk = kf.shape[1]
    _check_window_overshoot(window, q_offset, tq, tk)
    num_q, num_kv = tq // block_q, tk // block_kv
    khm = _kv_head_map(*heads) if heads else None
    kv_steps, kv_im = _kv_axis(
        num_kv, block_q, block_kv, window, q_offset, khm
    )
    q_im = lambda bh, i, j: (bh, i, 0)
    q_spec_i = pl.BlockSpec((1, block_q, d), q_im)
    kv_spec_j = pl.BlockSpec((1, block_kv, d), kv_im)
    row_spec_i = _row_spec(block_q, q_im)
    return pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal, block_q=block_q,
            block_kv=block_kv, num_kv=kv_steps, num_kv_total=num_kv,
            window=window, q_offset=q_offset,
        ),
        grid=(bh, num_q, kv_steps),
        in_specs=[q_spec_i, kv_spec_j, kv_spec_j, q_spec_i, row_spec_i,
                  row_spec_i],
        out_specs=q_spec_i,
        out_shape=_sds((bh, tq, d), out_dtype or qf.dtype, qf),
        scratch_shapes=_scratch([(block_q, d)]),
        compiler_params=_GRID_SEMANTICS,
        interpret=resolve_interpret(interpret),
        name="flash_bwd_dq",
    )(qf, kf, vf, dof, _row_blocks(lse, block_q),
      _row_blocks(delta, block_q))


def _dkv_pass(qf, kf, vf, dof, lse, delta, causal, scale, block_q,
              block_kv, interpret, out_dtype=None, window=None,
              q_offset=0, heads=None):
    """dK/dV for one (Tq, Tk) pair: kv blocks in the MIDDLE grid dim, q
    blocks INNERMOST so the accumulators carry across q steps.

    Under GQA (``heads=(h_q, h_kv)``) the INPUT k/v blocks come from the
    shared KV head while the OUTPUT stays per Q head — the caller
    group-sums the ``h_q // h_kv`` per-head partials (XLA fuses it)."""
    bh, tq, d = qf.shape
    tk = kf.shape[1]
    num_q, num_kv = tq // block_q, tk // block_kv
    khm = _kv_head_map(*heads) if heads else None
    if window is None:
        q_steps = num_q
        q_im = lambda bh, j, i: (bh, i, 0)
    else:
        q_steps = _q_window_steps(num_q, block_q, block_kv, window)
        q_im = _window_index_map(
            num_q,
            lambda j: _q_base(j, block_q, block_kv, window, q_offset),
        )
    q_spec_inner = pl.BlockSpec((1, block_q, d), q_im)
    kv_out_spec = pl.BlockSpec((1, block_kv, d), lambda bh, j, i: (bh, j, 0))
    if khm is None:
        kv_in_spec = kv_out_spec
    else:
        kv_in_spec = pl.BlockSpec(
            (1, block_kv, d), lambda bh, j, i: (khm(bh), j, 0)
        )
    row_spec_inner = _row_spec(block_q, q_im)
    return pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal, block_q=block_q,
            block_kv=block_kv, num_q=q_steps, num_q_total=num_q,
            window=window, q_offset=q_offset,
        ),
        grid=(bh, num_kv, q_steps),
        in_specs=[q_spec_inner, kv_in_spec, kv_in_spec, q_spec_inner,
                  row_spec_inner, row_spec_inner],
        out_specs=[kv_out_spec, kv_out_spec],
        out_shape=[
            _sds((bh, tk, d), out_dtype or kf.dtype, qf),
            _sds((bh, tk, d), out_dtype or vf.dtype, qf),
        ],
        scratch_shapes=_scratch([(block_kv, d), (block_kv, d)]),
        compiler_params=_GRID_SEMANTICS,
        interpret=resolve_interpret(interpret),
        name="flash_bwd_dkv",
    )(qf, kf, vf, dof, _row_blocks(lse, block_q),
      _row_blocks(delta, block_q))


def _delta(dof, of):
    """``D_i = rowsum(dO * O)``, the softmax-jacobian correction term, per
    row in ``(bh, t)`` order like lse (the passes shape both themselves,
    :func:`_row_blocks`)."""
    return (dof.astype(jnp.float32) * of.astype(jnp.float32)).sum(-1)


def _bwd(causal, scale, block_q, block_kv, interpret, window, res, g):
    qf, kf, vf, of, lse, qshape = res
    b, t, h, d = qshape
    h_kv = kf.shape[0] // b
    heads = (h, h_kv) if h_kv != h else None
    scale_v = _default_scale(scale, d)
    dof = _flat(g)
    delta = _delta(dof, of)
    dq = _dq_pass(qf, kf, vf, dof, lse, delta, causal, scale_v, block_q,
                  block_kv, interpret, window=window, heads=heads)
    dk, dv = _dkv_pass(qf, kf, vf, dof, lse, delta, causal, scale_v,
                       block_q, block_kv, interpret, window=window,
                       heads=heads,
                       out_dtype=jnp.float32 if heads else None)
    if heads is not None:
        # GQA: the dkv pass emitted per-Q-HEAD partials (f32, so the
        # fold never sums rounded values); fold each group's onto its
        # shared KV head (fuses in XLA), then match the primal dtype
        tk = kf.shape[1]
        g_sz = h // h_kv

        def _fold(x, dt):
            return x.reshape(b, h_kv, g_sz, tk, d).sum(2).reshape(
                -1, tk, d
            ).astype(dt)

        return (_unflat(dq, b, h),
                _unflat(_fold(dk, kf.dtype), b, h_kv),
                _unflat(_fold(dv, vf.dtype), b, h_kv))
    return (_unflat(dq, b, h), _unflat(dk, b, h), _unflat(dv, b, h))


flash_attention.defvjp(_fwd, _bwd)


def make_flash_attention(causal=True, block_q=128, block_kv=128,
                         interpret=None, window=None):
    """``attn_fn`` closure for :func:`blendjax.models.seqformer.apply` —
    drop-in for the default ``full_attention``.

    ``block_q``/``block_kv`` may be ``'auto'``: the tile is then sized
    per call via :func:`flash_block_size` (from the call's sequence
    length, head size, dtype and window), so the closure works at any
    32-multiple sequence length (or any length up to 128, which fits a
    single tile) instead of requiring T to divide a fixed block.  Ragged
    lengths beyond that are rejected — the only "tile" dividing them is
    T itself, which would materialize the (T, T) score block the kernel
    exists to avoid (pad upstream instead).  A number wins over the
    policy.

    ``window=W`` enables sliding-window attention (causal only; see
    :func:`flash_attention`)."""
    _check_window(causal, window)

    def attn(q, k, v):
        t = q.shape[1]
        auto = flash_block_size(t, q.shape[-1], q.dtype, window)
        if (block_q == "auto" or block_kv == "auto") and t % 32 and t > 128:
            raise ValueError(
                f"sequence length {t} has no flash tile (not a multiple "
                "of 32 and too long for a single tile); pad to a "
                "32-multiple upstream"
            )
        bq = auto if block_q == "auto" else block_q
        bkv = auto if block_kv == "auto" else block_kv
        return flash_attention(
            q, k, v, causal, None, bq, bkv, interpret, window
        )

    return attn
