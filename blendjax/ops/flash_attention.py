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
the output on the last one.

Differentiation is fully fused too (``custom_vjp``): the forward also
emits the per-row logsumexp, and the backward runs two block-wise
kernels — a dQ pass (KV innermost, dQ accumulator carried) and a dK/dV
pass (Q innermost) — recomputing probabilities from the saved logsumexp
(FlashAttention-2 recurrence, with ``D = rowsum(dO * O)`` as the
softmax-jacobian correction).  No (T, T) matrix exists in either
direction; gradient parity vs the einsum reference is tested to ~5e-5.

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


def flash_block_size(seq_len):
    """Largest flash tile dividing ``seq_len`` (or ``seq_len`` itself —
    legal on TPU via the 'equal to the array dim' tiling clause).  THE
    tile-selection policy, shared by the ring/ulysses parallel paths and
    user code sizing the kernel for arbitrary sequence lengths."""
    return next((b for b in (128, 64, 32) if seq_len % b == 0), seq_len)


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


def _mask(s, i, j, block_q, block_kv, window=None, q_offset=0):
    rows = q_offset + i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0
    )
    cols = j * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1
    )
    keep = cols <= rows
    if window is not None:
        keep = jnp.logical_and(keep, cols > rows - window)
    return jnp.where(keep, s, _NEG)


def _scores(q_ref, k_ref, qi, kj, scale, causal, block_q, block_kv,
            window=None, q_offset=0):
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if causal:
        s = _mask(s, qi, kj, block_q, block_kv, window, q_offset)
    return q, k, s


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
            scale, causal, block_q, block_kv, num_kv, num_kv_total=None,
            window=None, q_offset=0):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    i = pl.program_id(1)
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
        _, _, s = _scores(q_ref, k_ref, i, kj, scale, causal, block_q,
                          block_kv, window, q_offset)
        v = v_ref[0].astype(jnp.float32)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_cur)
        alpha = jnp.exp(m_prev - m_cur)
        l_new = alpha * l_prev + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == num_kv - 1)
    def _emit():
        l = l_ref[:, :1]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)
        # lse rides as (bh, t, 1) — a (block_q, 1) block keeps the
        # Mosaic tiling rule (last two block dims divisible by (8, 128)
        # or equal to the array dims); a flat (1, block_q) lse block is
        # rejected by the TPU lowering (caught by the tpu-platform
        # export test, tests/test_tpu_lowering.py)
        lse_ref[0] = m_ref[:, :1] + jnp.log(safe)


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
        _, k, s = _scores(q_ref, k_ref, i, kj, scale, causal, block_q,
                          block_kv, window, q_offset)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        p = jnp.exp(s - lse_ref[0].astype(jnp.float32))  # (bq,1) bcast
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0].astype(jnp.float32)) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

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
        q, _, s = _scores(q_ref, k_ref, qi, j, scale, causal, block_q,
                          block_kv, window, q_offset)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        p = jnp.exp(s - lse_ref[0].astype(jnp.float32))  # (bq,1) bcast
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0].astype(jnp.float32)) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(i == num_q - 1)
    def _emit():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


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
    of, lse = pl.pallas_call(
        kernel,
        grid=(b * h, num_q, kv_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_kv, d), kv_im),
            pl.BlockSpec((1, block_kv, d), kv_im),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            # (bh, t, 1): a (block_q, 1) trailing block satisfies the
            # Mosaic (8, 128)-or-equal tiling rule; (1, block_q) doesn't
            pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            _sds((b * h, t, d), out_dtype or q.dtype, qf),
            _sds((b * h, t, 1), jnp.float32, qf),
        ],
        scratch_shapes=_scratch([
            (block_q, d), (block_q, 128), (block_q, 128)
        ]),
        interpret=resolve_interpret(interpret),
        name="flash_fwd",
    )(qf, kf, vf)
    return _unflat(of, b, h), (qf, kf, vf, of, lse)


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
    rounded partials)."""
    bh, tq, d = qf.shape
    tk = kf.shape[1]
    _check_window_overshoot(window, q_offset, tq, tk)
    num_q, num_kv = tq // block_q, tk // block_kv
    khm = _kv_head_map(*heads) if heads else None
    kv_steps, kv_im = _kv_axis(
        num_kv, block_q, block_kv, window, q_offset, khm
    )
    q_spec_i = pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0))
    kv_spec_j = pl.BlockSpec((1, block_kv, d), kv_im)
    row_spec_i = pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0))
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
        interpret=resolve_interpret(interpret),
        name="flash_bwd_dq",
    )(qf, kf, vf, dof, lse, delta)


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
    row_spec_inner = pl.BlockSpec((1, block_q, 1), q_im)
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
        interpret=resolve_interpret(interpret),
        name="flash_bwd_dkv",
    )(qf, kf, vf, dof, lse, delta)


def _bwd(causal, scale, block_q, block_kv, interpret, window, res, g):
    qf, kf, vf, of, lse, qshape = res
    b, t, h, d = qshape
    h_kv = kf.shape[0] // b
    heads = (h, h_kv) if h_kv != h else None
    scale_v = _default_scale(scale, d)
    dof = _flat(g)
    # D_i = rowsum(dO * O): the softmax-jacobian correction term; rides
    # as (bh, t, 1) like lse (Mosaic trailing-block tiling rule)
    delta = (dof.astype(jnp.float32) * of.astype(jnp.float32)).sum(
        -1, keepdims=True
    )
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
    per call via :func:`flash_block_size`, so the closure works at any
    32-multiple sequence length (or any length up to 128, which fits a
    single tile) instead of requiring T to divide a fixed block.  Ragged
    lengths beyond that are rejected — the only "tile" dividing them is
    T itself, which would materialize the (T, T) score block the kernel
    exists to avoid (pad upstream instead).

    ``window=W`` enables sliding-window attention (causal only; see
    :func:`flash_attention`)."""
    _check_window(causal, window)

    def attn(q, k, v):
        t = q.shape[1]
        auto = flash_block_size(t)
        if (block_q == "auto" or block_kv == "auto") and auto == t and t > 128:
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
