"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has no sequence models at all (SURVEY.md §5 "long-context:
absent"), but blendjax treats long-context as first-class: episodes
streamed out of Blender are *sequences* (frames, observations, actions),
and temporal models over long episodes need the sequence dimension sharded
across chips.  Four TPU-native schemes, all pure-JAX collectives (plus
the Pallas kernel) over the ICI mesh:

- **Ring attention** (:func:`ring_attention`): every device holds one
  contiguous sequence shard of Q, K and V.  K/V blocks rotate around the
  ring with ``lax.ppermute`` while each device accumulates its queries'
  attention over every block using an online (flash-style) softmax, so
  memory stays O(S/n) per device and the permute overlaps with the block
  matmul.  Exact — not an approximation.
- **Ring + flash** (:func:`ring_flash_attention`): the same ring, with
  the fused Pallas flash kernel as the per-block-pair attention — no
  (S/n, S/n) score matrix materializes even within a block, and
  differentiation is a ring-level custom VJP whose backward rotates K/V
  *and* their gradient accumulators (fused dQ and dK/dV kernels per
  visible pair).  The long-context configuration: ring scales past
  Ulysses' ``heads % n`` constraint while keeping flash's O(block)
  memory.
- **Zigzag ring + flash** (:func:`zigzag_flash_attention`): ring+flash
  with the load-balanced chunk layout for CAUSAL attention — plain
  causal ring leaves early devices idle (device 0's queries see one
  block, device n-1's see all n); pairing chunks from both sequence
  ends (shard d holds chunks d and 2n-1-d) gives every device identical
  visible work per rotation.
- **Ulysses** (:func:`ulysses_attention`): ``lax.all_to_all`` reshards
  [seq-sharded, all heads] -> [all seq, head-sharded], runs ordinary full
  attention per head group, and reshards back.  Cheaper collectives for
  moderate sequence lengths; requires ``heads % axis_size == 0``
  (``inner_attn`` slots the flash kernel in per head group).

All run *inside* ``shard_map`` (the functions take an ``axis_name``);
:func:`make_ring_attention` wraps one up to act on globally-sharded arrays.
Causal masking uses global positions reconstructed from
``lax.axis_index``, so results match single-device attention bit-for-bit
in structure (small float differences only from blockwise accumulation).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from blendjax.ops.flash_attention import resolve_interpret

_NEG = -1e30  # finite mask value: keeps the online-softmax nan-free


def _pvary(x, axes):
    """Mark ``x`` device-varying over ``axes`` under shard_map's vma
    typing.  Idempotent: axes the value already varies over are skipped
    — zeros_like of a sharded input is already varying, and re-casting
    raises."""
    vma = jax.typeof(x).vma
    axes = tuple(a for a in axes if a not in vma)
    if not axes:
        return x
    return lax.pcast(x, axes, to="varying")


def full_attention(q, k, v, causal=False, scale=None, q_offset=0, k_offset=0,
                   window=None):
    """Plain softmax attention; the single-device reference implementation.

    q: (B, Sq, H, D), k/v: (B, Sk, H, D).  ``*_offset`` give the global
    position of element 0 along the sequence axis (used by the parallel
    schemes for causal masking across shards).  ``window=W`` (causal
    only) is sliding-window attention: query i sees keys in
    ``(i - W, i]`` — the reference semantics for
    ``blendjax.ops.flash_attention``'s windowed kernel.  k/v with fewer
    heads than q (GQA) are broadcast per group — the reference
    semantics for the kernel's grouped KV head mapping.
    """
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if k.shape[2] != q.shape[2]:
        if q.shape[2] % k.shape[2]:
            raise ValueError(
                f"q heads {q.shape[2]} must be a multiple of kv heads "
                f"{k.shape[2]}"
            )
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(q.dtype)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])
        kpos = k_offset + jnp.arange(k.shape[1])
        mask = kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        scores = jnp.where(mask[None, None], scores, _NEG)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _window_ring_deltas(window, s_loc, n):
    """How many earlier neighbor shards a sliding window reaches: shard
    me needs shard me-d iff the newest key there, position
    ``(me-d+1)*s_loc - 1``, is within ``window`` of me's oldest query
    ``me*s_loc`` — i.e. ``(d-1)*s_loc + 2 <= window``.  This is the
    windowed ring's whole point: compute AND ring traffic become
    O(window), not O(S) — a ring step rotates only ``dmax`` times."""
    if window < 2:
        return 0
    return min(n - 1, (window - 2) // s_loc + 1)


def ring_attention(q, k, v, axis_name, causal=False, scale=None,
                   vary_axes=None, window=None):
    """Exact blockwise attention over a ring of sequence shards.

    Call inside ``shard_map``: q/k/v are the *local* shards
    (B, S/n, H, D) of arrays sharded ``P(None, axis_name, None, None)``.
    Returns the local shard of the attention output.

    ``window=W`` (causal only) is sliding-window attention: the ring
    then rotates BACKWARD and stops after ``_window_ring_deltas`` steps
    — shards older than the window are never fetched, so ring traffic
    scales with the window, not the sequence.
    """
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    if k.shape[2] != q.shape[2]:
        raise ValueError(
            "ring does not support GQA (kv heads != q heads); "
            "use impl='ulysses' or repeat kv heads before the ring"
        )
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(jnp.float32)

    qf = q.astype(jnp.float32)
    # Receive from the next device: after t rotations we hold block (me + t) % n.
    perm = [(j, (j - 1) % n) for j in range(n)]
    qpos = me * s_loc + jnp.arange(s_loc)

    def accumulate(o, m, l, kb, vb, blk):
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kb.astype(jnp.float32)) * scale
        if causal:
            kpos = blk * s_loc + jnp.arange(s_loc)
            mask = kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask &= kpos[None, :] > qpos[:, None] - window
            scores = jnp.where(mask[None, None], scores, _NEG)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bhqd", p, vb.astype(jnp.float32))
        return o * corr[..., None] + pv, m_new, l

    o0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    m0 = jnp.full((b, h, s_loc), _NEG, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), jnp.float32)
    # Constant-initialized carries are "unvarying" under shard_map's vma
    # typing while the loop body makes them device-varying; align the types
    # over every axis the inputs vary over (seq + optional batch axis).
    axes = tuple(vary_axes) if vary_axes else (axis_name,)
    o0, m0, l0 = (_pvary(x, axes) for x in (o0, m0, l0))
    # Own block first (no rotation): every query sees itself (window >= 1),
    # so m is finite before any possibly-all-masked rotation pair — an
    # all-masked pair then contributes exp(_NEG - m) = 0, not garbage.
    o, m, l = accumulate(o0, m0, l0, k, v, me)

    if window is None:
        def body(carry, t):
            o, m, l, kb, vb = carry
            kb = lax.ppermute(kb, axis_name, perm)
            vb = lax.ppermute(vb, axis_name, perm)
            o, m, l = accumulate(o, m, l, kb, vb, (me + t) % n)
            return (o, m, l, kb, vb), None

        (o, _, l, _, _), _ = lax.scan(body, (o, m, l, k, v), jnp.arange(1, n))
    else:
        # windowed: rotate BACKWARD (earlier shards) and stop once the
        # window is exhausted — t rotations put shard (me - t) % n here
        perm_back = [(j, (j + 1) % n) for j in range(n)]
        dmax = _window_ring_deltas(window, s_loc, n)

        def body(carry, t):
            o, m, l, kb, vb = carry
            kb = lax.ppermute(kb, axis_name, perm_back)
            vb = lax.ppermute(vb, axis_name, perm_back)
            # (me - t) % n wraps to a FUTURE shard on devices me < t;
            # its columns fail the causal mask, so the all-masked pair
            # is a (wasted but exact) no-op on those devices
            o, m, l = accumulate(o, m, l, kb, vb, (me - t) % n)
            return (o, m, l, kb, vb), None

        if dmax > 0:
            (o, _, l, _, _), _ = lax.scan(
                body, (o, m, l, k, v), jnp.arange(1, dmax + 1)
            )
    out = o / l[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _ring_blk(s_loc, q, window=None):
    """Flash tile for a local shard of ``q`` — the shared policy from
    :func:`blendjax.ops.flash_attention.flash_block_size`."""
    from blendjax.ops.flash_attention import flash_block_size

    return flash_block_size(s_loc, q.shape[-1], q.dtype, window)


def _lse_combine(o, lse, o_b, lse_b):
    """Merge a new normalized partial (o_b, lse_b) into a running
    (o, lse) by logsumexp reweighting — the online-softmax recurrence at
    ring granularity, shared by the ring_flash and zigzag variants.
    ``o``: (B, S, H, D) f32; ``lse``: (B, H, S) f32."""
    lse_new = jnp.logaddexp(lse, lse_b)
    w_old = jnp.exp(lse - lse_new).transpose(0, 2, 1)[..., None]
    w_new = jnp.exp(lse_b - lse_new).transpose(0, 2, 1)[..., None]
    return o * w_old + o_b * w_new, lse_new


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def ring_flash_attention(q, k, v, axis_name, causal=False, scale=None,
                         interpret=None, vary_axes=None, window=None):
    """:func:`ring_attention` with the fused Pallas flash kernel per
    block pair — O(S/n) memory per device AND no (S/n, S/n) score matrix
    materialized within a block.

    Call inside ``shard_map`` with local shards (B, S/n, H, D).  Each
    ring step runs the flash kernel on (my queries x held KV block):
    blocks strictly before mine attend unmasked, my own block attends
    causally, later blocks are skipped entirely (their probabilities are
    exactly zero); partial outputs combine across blocks by logsumexp
    reweighting — the same online-softmax recurrence the kernel runs
    internally, lifted to ring granularity.  Differentiation is a
    custom VJP at the ring level: the backward rotates K/V *and* their
    gradient accumulators around the ring, running the fused dQ and
    dK/dV kernels per visible pair, so no pass materializes scores.

    ``window=W`` (causal only) is sliding-window attention: the ring
    rotates BACKWARD and stops after ``_window_ring_deltas(W, S/n, n)``
    steps, each pair running the windowed kernel with a STATIC
    ``q_offset`` (the rotation count is a Python loop index, so every
    pair's row/col offset is known at trace time) — per-device compute,
    HBM traffic, AND ring collectives all scale O(W) instead of O(S).
    A window wider than the sequence degrades gracefully to the full
    causal ring.
    """
    out, _ = _ring_flash_fwd(
        q, k, v, axis_name, causal, scale, interpret, vary_axes, window
    )
    return out


def _ring_flash_fwd(q, k, v, axis_name, causal, scale, interpret,
                    vary_axes, window=None):
    from blendjax.ops.flash_attention import _default_scale, _flash_fwd_impl

    if k.shape[2] != q.shape[2]:
        # the kernel itself handles GQA, but the ring-level custom VJP
        # rotates per-q-head gradient accumulators — threading the head
        # map through it is not implemented.  Raise here rather than let
        # the forward silently succeed and the backward emit mis-shaped
        # cotangents (use ulysses, or repeat kv heads upstream)
        raise ValueError(
            "ring_flash does not support GQA (kv heads != q heads); "
            "use impl='ulysses' or repeat kv heads before the ring"
        )
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        return _ring_flash_fwd_windowed(
            q, k, v, axis_name, scale, interpret, vary_axes, window
        )

    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    scale_v = _default_scale(scale, d)
    blk = _ring_blk(s_loc, q)
    perm = [(j, (j - 1) % n) for j in range(n)]

    def pair(kb, vb, diag):
        # out_dtype=f32: the kernel's internal accumulator is f32 —
        # emitting f32 partials keeps the cross-block combination free
        # of per-block rounding (bf16 inputs still feed the MXU as bf16)
        o_b, res = _flash_fwd_impl(
            q, kb, vb, diag, scale_v, blk, blk, interpret,
            out_dtype=jnp.float32,
        )
        lse_b = res[4].reshape(b, h, s_loc)
        return o_b, lse_b

    combine = _lse_combine

    def step_compute(o, lse, kb, vb, blk_idx):
        if not causal:
            return combine(o, lse, *pair(kb, vb, False))
        # 0: later block (skip — all-masked), 1: earlier (full), 2: own
        # (causal diagonal).  The kernel must NOT run on an all-masked
        # pair: its online softmax would renormalize over masked columns.
        mode = jnp.where(blk_idx > me, 0, jnp.where(blk_idx < me, 1, 2))
        return lax.switch(
            mode,
            [
                lambda: (o, lse),
                lambda: combine(o, lse, *pair(kb, vb, False)),
                lambda: combine(o, lse, *pair(kb, vb, True)),
            ],
        )

    def body(carry, t):
        o, lse, kb, vb = carry
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        o, lse = step_compute(o, lse, kb, vb, (me + t) % n)
        return (o, lse, kb, vb), None

    o0 = jnp.zeros((b, s_loc, h, d), jnp.float32)
    lse0 = jnp.full((b, h, s_loc), _NEG, jnp.float32)
    axes = tuple(vary_axes) if vary_axes else (axis_name,)
    o0, lse0 = (_pvary(x, axes) for x in (o0, lse0))
    o, lse = step_compute(o0, lse0, k, v, me)  # own block, no rotation
    (o, lse, _, _), _ = lax.scan(body, (o, lse, k, v), jnp.arange(1, n))
    out = o.astype(q.dtype)
    return out, (q, k, v, out, lse)


def _ring_flash_fwd_windowed(q, k, v, axis_name, scale, interpret,
                             vary_axes, window):
    """Sliding-window ring + flash forward.

    Rotation ``t`` (a PYTHON loop index — ``dmax`` is static) holds
    shard ``(me - t) % n``: an earlier shard at static offset
    ``t * s_loc`` for devices ``me >= t``, a wrapped future shard
    otherwise.  The pair kernel runs with ``causal=True, window,
    q_offset=t*s_loc`` — at that offset the causal mask is all-true and
    the window mask prunes — under ``lax.cond`` so wrapped devices skip
    the compute entirely (the ppermute itself is unconditional: it is a
    collective).  Rows beyond a pair's window emit ``lse = -1e30`` and
    weigh zero in the logsumexp combine."""
    from blendjax.ops.flash_attention import _default_scale, _flash_fwd_impl

    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    scale_v = _default_scale(scale, d)
    blk = _ring_blk(s_loc, q, window)
    perm_back = [(j, (j + 1) % n) for j in range(n)]
    dmax = _window_ring_deltas(window, s_loc, n)

    def pair(kb, vb, q_offset):
        o_b, res = _flash_fwd_impl(
            q, kb, vb, True, scale_v, blk, blk, interpret,
            out_dtype=jnp.float32, window=window, q_offset=q_offset,
        )
        return o_b, res[4].reshape(b, h, s_loc)

    # own shard: every query sees itself, so (o, lse) start finite
    o, lse = pair(k, v, 0)
    kb, vb = k, v
    for t in range(1, dmax + 1):
        kb = lax.ppermute(kb, axis_name, perm_back)
        vb = lax.ppermute(vb, axis_name, perm_back)
        o, lse = lax.cond(
            me >= t,
            lambda kb=kb, vb=vb, o=o, lse=lse, t=t: _lse_combine(
                o, lse, *pair(kb, vb, t * s_loc)
            ),
            lambda o=o, lse=lse: (o, lse),
        )
    out = o.astype(q.dtype)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd_windowed(axis_name, scale, interpret, window, res, g):
    """Backward of the windowed ring: dK/dV accumulators TRAVEL with
    their shard for the ``dmax`` rotations (each visiting device adds
    its pair's contribution), then a single ``ppermute`` jumps every
    accumulator straight home — ``dmax + 1`` collectives per gradient
    array instead of the full ring's ``n``.

    Takes no ``vary_axes`` (unlike the forwards): every accumulator is
    seeded from ``pair_grads`` outputs, which are already device-varying
    (they consume the per-device ``q``/``k``/``v`` shards), so no
    ``_pvary`` seeding is needed — a zeros-init refactor would reintroduce
    the shard_map varying-axis mismatch and must re-thread ``vary_axes``
    through here."""
    from blendjax.ops.flash_attention import (
        _default_scale,
        _delta,
        _dkv_pass,
        _dq_pass,
        _flat,
        _unflat,
    )

    q, k, v, out, lse = res
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    scale_v = _default_scale(scale, d)
    blk = _ring_blk(s_loc, q, window)
    perm_back = [(j, (j + 1) % n) for j in range(n)]
    dmax = _window_ring_deltas(window, s_loc, n)

    qf, dof, of = _flat(q), _flat(g), _flat(out)
    delta = _delta(dof, of)
    lse_f = lse.reshape(b * h, s_loc)

    def pair_grads(kbf, vbf, q_offset):
        dq_c = _dq_pass(qf, kbf, vbf, dof, lse_f, delta, True, scale_v,
                        blk, blk, interpret, out_dtype=jnp.float32,
                        window=window, q_offset=q_offset)
        dk_c, dv_c = _dkv_pass(qf, kbf, vbf, dof, lse_f, delta, True,
                               scale_v, blk, blk, interpret,
                               out_dtype=jnp.float32, window=window,
                               q_offset=q_offset)
        return dq_c, dk_c, dv_c

    # own pair seeds both the local dQ and the traveling dK/dV
    dq, dk_t, dv_t = pair_grads(_flat(k), _flat(v), 0)
    kbf, vbf = _flat(k), _flat(v)
    for t in range(1, dmax + 1):
        kbf = lax.ppermute(kbf, axis_name, perm_back)
        vbf = lax.ppermute(vbf, axis_name, perm_back)
        dk_t = lax.ppermute(dk_t, axis_name, perm_back)
        dv_t = lax.ppermute(dv_t, axis_name, perm_back)
        dq, dk_t, dv_t = lax.cond(
            me >= t,
            lambda kbf=kbf, vbf=vbf, dq=dq, dk_t=dk_t, dv_t=dv_t, t=t: (
                lambda c: (dq + c[0], dk_t + c[1], dv_t + c[2])
            )(pair_grads(kbf, vbf, t * s_loc)),
            lambda dq=dq, dk_t=dk_t, dv_t=dv_t: (dq, dk_t, dv_t),
        )
    if dmax > 0:
        # one jump home: the accumulator traveling with shard
        # (me - dmax) % n returns to its owner
        perm_home = [(j, (j - dmax) % n) for j in range(n)]
        dk_t = lax.ppermute(dk_t, axis_name, perm_home)
        dv_t = lax.ppermute(dv_t, axis_name, perm_home)
    return (
        _unflat(dq, b, h).astype(q.dtype),
        _unflat(dk_t, b, h).astype(k.dtype),
        _unflat(dv_t, b, h).astype(v.dtype),
    )


def _ring_flash_bwd(axis_name, causal, scale, interpret, vary_axes,
                    window, res, g):
    if window is not None:
        return _ring_flash_bwd_windowed(
            axis_name, scale, interpret, window, res, g
        )
    from blendjax.ops.flash_attention import (
        _default_scale,
        _delta,
        _dkv_pass,
        _dq_pass,
        _flat,
        _unflat,
    )

    q, k, v, out, lse = res
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    scale_v = _default_scale(scale, d)
    blk = _ring_blk(s_loc, q)
    perm = [(j, (j - 1) % n) for j in range(n)]

    qf, dof, of = _flat(q), _flat(g), _flat(out)
    delta = _delta(dof, of)
    lse_f = lse.reshape(b * h, s_loc)

    def pair_grads(kbf, vbf, diag):
        # out_dtype=f32: per-pair gradients leave the kernels unrounded
        # so the n-block accumulation never sums bf16-rounded partials
        dq_c = _dq_pass(qf, kbf, vbf, dof, lse_f, delta, diag, scale_v,
                        blk, blk, interpret, out_dtype=jnp.float32)
        dk_c, dv_c = _dkv_pass(qf, kbf, vbf, dof, lse_f, delta, diag,
                               scale_v, blk, blk, interpret,
                               out_dtype=jnp.float32)
        return dq_c, dk_c, dv_c

    def step_compute(dq, dk, dv, kbf, vbf, blk_idx):
        if not causal:
            dq_c, dk_c, dv_c = pair_grads(kbf, vbf, False)
            return dq + dq_c, dk + dk_c, dv + dv_c

        def visible(diag):
            dq_c, dk_c, dv_c = pair_grads(kbf, vbf, diag)
            return dq + dq_c, dk + dk_c, dv + dv_c

        mode = jnp.where(blk_idx > me, 0, jnp.where(blk_idx < me, 1, 2))
        return lax.switch(
            mode,
            [
                lambda: (dq, dk, dv),
                lambda: visible(False),
                lambda: visible(True),
            ],
        )

    def body(carry, t):
        # held block's dK/dV accumulators travel WITH the block: after
        # the full cycle of n rotations each lands back on its owner
        dq, dk, dv, kbf, vbf = carry
        dq, dk, dv = step_compute(dq, dk, dv, kbf, vbf, (me + t) % n)
        kbf = lax.ppermute(kbf, axis_name, perm)
        vbf = lax.ppermute(vbf, axis_name, perm)
        dk = lax.ppermute(dk, axis_name, perm)
        dv = lax.ppermute(dv, axis_name, perm)
        return (dq, dk, dv, kbf, vbf), None

    axes = tuple(vary_axes) if vary_axes else (axis_name,)
    dq0, dk0, dv0 = (
        _pvary(jnp.zeros((b * h, s_loc, d), jnp.float32), axes)
        for _ in range(3)
    )
    (dq, dk, dv, kbf, vbf), _ = lax.scan(
        body, (dq0, dk0, dv0, _flat(k), _flat(v)), jnp.arange(n - 1)
    )
    # final block: compute, then rotate ONLY the accumulators home — the
    # K/V blocks are done, and their last ppermute would be wasted ring
    # traffic on every training step's critical path
    dq, dk, dv = step_compute(dq, dk, dv, kbf, vbf, (me + (n - 1)) % n)
    dk = lax.ppermute(dk, axis_name, perm)
    dv = lax.ppermute(dv, axis_name, perm)
    return (
        _unflat(dq, b, h).astype(q.dtype),
        _unflat(dk, b, h).astype(k.dtype),
        _unflat(dv, b, h).astype(v.dtype),
    )


ring_flash_attention.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def _zigzag_perm(seq_len, n):
    """Global index permutation laying the sequence out so contiguous
    shard ``d`` holds chunks ``(d, 2n-1-d)`` of ``2n`` contiguous
    chunks.  Numpy (static): the permutation is data-independent."""
    import numpy as _np

    c = 2 * n
    if seq_len % c:
        raise ValueError(
            f"zigzag layout needs sequence length {seq_len} divisible "
            f"by 2*n_devices = {c}"
        )
    chunk = seq_len // c
    order = []
    for dd in range(n):
        order += [dd, c - 1 - dd]
    return _np.concatenate(
        [_np.arange(o * chunk, (o + 1) * chunk) for o in order]
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def zigzag_flash_attention(q, k, v, axis_name, scale=None,
                           interpret=None, vary_axes=None):
    """Load-balanced CAUSAL ring attention with the fused flash kernel.

    Plain causal ring attention is imbalanced: device 0's queries see one
    block, device n-1's see all n — the ring's total compute slots are
    ~2x the visible work, and every step waits for the busiest device.
    The zigzag layout pairs chunks from both ends of the sequence
    (shard ``d`` holds chunks ``d`` and ``2n-1-d`` of ``2n``), making
    every device's total visible work identical (``2n+1`` chunk pairs).

    Call inside ``shard_map`` with local shards ALREADY in zigzag layout
    (:func:`make_ring_attention` with ``impl='zigzag_flash'`` applies
    the global permutation and its inverse around the shard_map).  Each
    ring step runs up to 4 flash-kernel pair calls (2 query half-chunks
    x 2 held KV half-chunks), each unmasked / causal-diagonal / skipped
    by chunk-index comparison; the backward rotates KV *and* per-half
    dK/dV accumulators like :func:`ring_flash_attention`.  Causal only —
    non-causal rings have no imbalance to fix.
    """
    out, _ = _zz_fwd(q, k, v, axis_name, scale, interpret, vary_axes)
    return out


def _zz_fwd(q, k, v, axis_name, scale, interpret, vary_axes):
    from blendjax.ops.flash_attention import _default_scale, _flash_fwd_impl

    if k.shape[2] != q.shape[2]:
        # same limitation as ring_flash: the ring-level VJP rotates
        # per-q-head accumulators (see _ring_flash_fwd)
        raise ValueError(
            "zigzag_flash does not support GQA (kv heads != q heads); "
            "use impl='ulysses' or repeat kv heads before the ring"
        )
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    half = s_loc // 2
    c = 2 * n
    scale_v = _default_scale(scale, d)
    blk = _ring_blk(half, q)
    perm = [(j, (j - 1) % n) for j in range(n)]

    q_halves = (q[:, :half], q[:, half:])
    q_idx = (me, c - 1 - me)  # chunk indices of my query halves

    def pair(qh, kh, vh, diag):
        o_b, res = _flash_fwd_impl(
            qh, kh, vh, diag, scale_v, blk, blk, interpret,
            out_dtype=jnp.float32,
        )
        return o_b, res[4].reshape(b, h, half)

    def half_step(acc, qh, qi, kh, vh, ki):
        o, lse = acc
        mode = jnp.where(ki > qi, 0, jnp.where(ki < qi, 1, 2))
        return lax.switch(
            mode,
            [
                lambda: (o, lse),
                lambda: _lse_combine(o, lse, *pair(qh, kh, vh, False)),
                lambda: _lse_combine(o, lse, *pair(qh, kh, vh, True)),
            ],
        )

    def step_compute(accs, kb, vb, src):
        k_halves = (kb[:, :half], kb[:, half:])
        v_halves = (vb[:, :half], vb[:, half:])
        k_idx = (src, c - 1 - src)
        out_accs = []
        for qh, qi, acc in zip(q_halves, q_idx, accs):
            for kh, vh, ki in zip(k_halves, v_halves, k_idx):
                acc = half_step(acc, qh, qi, kh, vh, ki)
            out_accs.append(acc)
        return tuple(out_accs)

    def body(carry, t):
        accs, kb, vb = carry
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        accs = step_compute(accs, kb, vb, (me + t) % n)
        return (accs, kb, vb), None

    axes = tuple(vary_axes) if vary_axes else (axis_name,)
    accs0 = tuple(
        (
            _pvary(jnp.zeros((b, half, h, d), jnp.float32), axes),
            _pvary(jnp.full((b, h, half), _NEG, jnp.float32), axes),
        )
        for _ in range(2)
    )
    accs = step_compute(accs0, k, v, me)  # own pair, no rotation
    (accs, _, _), _ = lax.scan(body, (accs, k, v), jnp.arange(1, n))
    (oa, lse_a), (ob, lse_b) = accs
    out = jnp.concatenate([oa, ob], axis=1).astype(q.dtype)
    lse = jnp.concatenate([lse_a, lse_b], axis=2)
    return out, (q, k, v, out, lse)


def _zz_bwd(axis_name, scale, interpret, vary_axes, res, g):
    from blendjax.ops.flash_attention import (
        _default_scale,
        _delta,
        _dkv_pass,
        _dq_pass,
        _flat,
        _unflat,
    )

    q, k, v, out, lse = res
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    half = s_loc // 2
    c = 2 * n
    scale_v = _default_scale(scale, d)
    blk = _ring_blk(half, q)
    perm = [(j, (j - 1) % n) for j in range(n)]

    def half_flat(x, i):  # (b, s_loc, h, d) -> flat (bh, half, d) half i
        return _flat(x[:, i * half:(i + 1) * half])

    qf_h = (half_flat(q, 0), half_flat(q, 1))
    dof_h = (half_flat(g, 0), half_flat(g, 1))
    of_h = (half_flat(out, 0), half_flat(out, 1))
    delta_h = tuple(_delta(do, o) for do, o in zip(dof_h, of_h))
    lse_h = (
        lse[:, :, :half].reshape(b * h, half),
        lse[:, :, half:].reshape(b * h, half),
    )
    q_idx = (me, c - 1 - me)

    def pair_grads(qi_f, kf, vf, dof, lse_f, delta, diag):
        dq_c = _dq_pass(qi_f, kf, vf, dof, lse_f, delta, diag, scale_v,
                        blk, blk, interpret, out_dtype=jnp.float32)
        dk_c, dv_c = _dkv_pass(qi_f, kf, vf, dof, lse_f, delta, diag,
                               scale_v, blk, blk, interpret,
                               out_dtype=jnp.float32)
        return dq_c, dk_c, dv_c

    def step_compute(dqs, dks, dvs, kbf_h, vbf_h, src):
        k_idx = (src, c - 1 - src)
        dqs, dks, dvs = list(dqs), list(dks), list(dvs)
        for a, qi in enumerate(q_idx):
            for kk, ki in enumerate(k_idx):

                def visible(diag, a=a, kk=kk):
                    dq_c, dk_c, dv_c = pair_grads(
                        qf_h[a], kbf_h[kk], vbf_h[kk], dof_h[a],
                        lse_h[a], delta_h[a], diag,
                    )
                    return dqs[a] + dq_c, dks[kk] + dk_c, dvs[kk] + dv_c

                mode = jnp.where(ki > qi, 0, jnp.where(ki < qi, 1, 2))
                dqs[a], dks[kk], dvs[kk] = lax.switch(
                    mode,
                    [
                        lambda a=a, kk=kk: (dqs[a], dks[kk], dvs[kk]),
                        lambda: visible(False),
                        lambda: visible(True),
                    ],
                )
        return tuple(dqs), tuple(dks), tuple(dvs)

    def body(carry, t):
        dqs, dks, dvs, kbf_h, vbf_h = carry
        dqs, dks, dvs = step_compute(dqs, dks, dvs, kbf_h, vbf_h,
                                     (me + t) % n)
        kbf_h = tuple(lax.ppermute(x, axis_name, perm) for x in kbf_h)
        vbf_h = tuple(lax.ppermute(x, axis_name, perm) for x in vbf_h)
        dks = tuple(lax.ppermute(x, axis_name, perm) for x in dks)
        dvs = tuple(lax.ppermute(x, axis_name, perm) for x in dvs)
        return (dqs, dks, dvs, kbf_h, vbf_h), None

    axes = tuple(vary_axes) if vary_axes else (axis_name,)

    def zeros2():
        return tuple(
            _pvary(jnp.zeros((b * h, half, d), jnp.float32), axes)
            for _ in range(2)
        )

    kbf_h = (half_flat(k, 0), half_flat(k, 1))
    vbf_h = (half_flat(v, 0), half_flat(v, 1))
    carry = (zeros2(), zeros2(), zeros2(), kbf_h, vbf_h)
    (dqs, dks, dvs, kbf_h, vbf_h), _ = lax.scan(
        body, carry, jnp.arange(n - 1)
    )
    # final pair: compute, then rotate ONLY the dK/dV accumulators home
    dqs, dks, dvs = step_compute(dqs, dks, dvs, kbf_h, vbf_h,
                                 (me + (n - 1)) % n)
    dks = tuple(lax.ppermute(x, axis_name, perm) for x in dks)
    dvs = tuple(lax.ppermute(x, axis_name, perm) for x in dvs)

    def join(halves, dtype):
        return _unflat(
            jnp.concatenate(halves, axis=1), b, h
        ).astype(dtype)

    return (join(dqs, q.dtype), join(dks, k.dtype), join(dvs, v.dtype))


zigzag_flash_attention.defvjp(_zz_fwd, _zz_bwd)


def ulysses_attention(q, k, v, axis_name, causal=False, scale=None,
                      inner_attn=None, window=None):
    """All-to-all (DeepSpeed-Ulysses style) sequence-parallel attention.

    Call inside ``shard_map`` with local shards (B, S/n, H, D); requires
    ``H % n == 0`` (enforced by ``all_to_all``).  Reshards seq->heads,
    attends over the full sequence for the local head group, reshards back.

    ``inner_attn(q, k, v, causal=..., scale=...)`` overrides the
    full-sequence attention — the natural slot for the fused Pallas
    kernel (:func:`blendjax.ops.flash_attention`), since after the
    all-to-all each device holds the COMPLETE sequence for its head
    group and pays the O(S^2) score matrix right here.

    ``window`` passes straight to the inner attention (after the
    all-to-all each head group sees the full sequence, so sliding-window
    masking needs no cross-shard machinery here).
    """
    inner = inner_attn or full_attention
    n = lax.psum(1, axis_name)
    for name, arr in (("q", q), ("k", k), ("v", v)):
        if arr.shape[2] % n:
            raise ValueError(
                f"ulysses needs {name}'s head count ({arr.shape[2]}) "
                f"divisible by the sequence axis size ({n}); under GQA "
                "pick n_kv_heads as a multiple of the axis, or repeat "
                "kv heads upstream"
            )
    kwargs = dict(causal=causal, scale=scale)
    if window is not None:
        # only passed when set, so inner_attn closures predating the
        # window option keep working
        kwargs["window"] = window
    # (B, S/n, H, D) -> (B, S, H/n, D)
    qh = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    kh = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    vh = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    out = inner(qh, kh, vh, **kwargs)
    # back to (B, S/n, H, D)
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2, tiled=True)


def make_ring_attention(
    mesh, seq_axis="seq", causal=False, impl="ring", batch_axis=None,
    head_axis=None, inner_attn=None, flash_interpret=None, window=None,
):
    """Wrap :func:`ring_attention` / :func:`ring_flash_attention` /
    :func:`ulysses_attention` for global arrays sharded
    ``P(batch_axis, seq_axis, head_axis, None)`` over ``mesh``.

    Returns ``attn(q, k, v) -> out`` usable directly under ``jax.jit``.
    ``inner_attn`` (ulysses only) swaps the per-head-group full-sequence
    attention, e.g. for the fused Pallas flash kernel;
    ``impl='ring_flash'`` instead fuses the kernel into the ring itself
    (``flash_interpret`` is the kernel's ``interpret``: ``None`` follows
    :func:`~blendjax.ops.flash_attention.resolve_interpret`).
    Composes with data parallelism (``batch_axis='data'``) and — ring
    variants only — with head-sharded tensor parallelism
    (``head_axis='model'``): each device then ring-rotates K/V for its
    head block, so sequence and tensor parallelism stack.  Ulysses
    repurposes the head axis for its all-to-all and cannot also shard it.

    ``window=W`` (causal only) is sliding-window attention.  The ring
    variants then rotate only ``ceil`` of window/shard steps — compute
    and ring traffic O(W) — and ulysses passes the window to its inner
    attention.  ``zigzag_flash`` rejects it: zigzag balances the FULL
    causal ring's triangular load, while a windowed ring's per-device
    work is already ~uniform (diagonal + the same few neighbor shards
    everywhere), so plain ``ring_flash`` is the windowed configuration.
    """
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    spec = P(batch_axis, seq_axis, head_axis, None)
    vary = tuple(a for a in (batch_axis, seq_axis, head_axis) if a is not None)
    if impl == "ring":
        inner = functools.partial(
            ring_attention, axis_name=seq_axis, causal=causal,
            vary_axes=vary, window=window,
        )
    elif impl == "ring_flash":

        def inner(q, k, v, _axis=seq_axis, _vary=vary,
                  _interp=flash_interpret):
            # positional call: custom_vjp rejects nondiff args by keyword
            return ring_flash_attention(
                q, k, v, _axis, causal, None, _interp, _vary, window
            )
    elif impl == "zigzag_flash":
        if not causal:
            raise ValueError(
                "zigzag_flash balances the CAUSAL ring's load; a "
                "non-causal ring has no imbalance — use ring_flash"
            )
        if window is not None:
            raise ValueError(
                "zigzag_flash + window is pointless: the windowed ring "
                "is already load-balanced — use impl='ring_flash'"
            )

        def inner(q, k, v, _axis=seq_axis, _vary=vary,
                  _interp=flash_interpret):
            return zigzag_flash_attention(
                q, k, v, _axis, None, _interp, _vary
            )
    elif impl == "ulysses":
        if head_axis is not None:
            raise ValueError("ulysses uses the head dim for its all-to-all; "
                             "head_axis sharding is ring-only")
        inner = functools.partial(ulysses_attention, axis_name=seq_axis,
                                  causal=causal, inner_attn=inner_attn,
                                  window=window)
    else:
        raise ValueError(f"unknown impl {impl!r} (want 'ring', "
                         "'ring_flash', 'zigzag_flash' or 'ulysses')")
    sm_kwargs = {}
    if impl in ("ring_flash", "zigzag_flash") \
            and resolve_interpret(flash_interpret):
        # The Pallas HLO interpreter's grid-carry slicing trips
        # shard_map's vma typing for non-causal kernel instances (jax
        # 0.9; the error text itself recommends this flag as the
        # workaround).  Interpreter-only: the compiled TPU path keeps
        # full vma checking, and the parity tests check the numbers.
        sm_kwargs["check_vma"] = False
    mapped = shard_map(
        inner, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        **sm_kwargs,
    )

    n_seq = mesh.shape[seq_axis]

    def attn(q, k, v):
        sh = NamedSharding(mesh, spec)
        if impl == "zigzag_flash":
            # permute the global sequence into zigzag layout so each
            # contiguous shard holds a balanced (front, back) chunk
            # pair; undo on the way out.  Models that keep their whole
            # residual stream zigzag-permuted (with true positions in
            # the embeddings) can call zigzag_flash_attention directly
            # and skip these gathers.
            idx = jnp.asarray(_zigzag_perm(q.shape[1], n_seq))
            inv = jnp.argsort(idx)
            q, k, v = (jnp.take(x, idx, axis=1) for x in (q, k, v))
        q, k, v = (lax.with_sharding_constraint(x, sh) for x in (q, k, v))
        out = mapped(q, k, v)
        if impl == "zigzag_flash":
            out = jnp.take(out, inv, axis=1)
        return out

    return attn
