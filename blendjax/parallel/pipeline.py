"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh axis.

The reference has no model parallelism of any kind (SURVEY.md §2.4);
blendjax provides the full TPU-native set.  This module is the pipeline
leg: the model is split into S stages whose parameters stack on a leading
stage axis sharded ``P(pipe_axis)`` — one stage per device group — and
microbatches flow stage-to-stage over ICI with ``lax.ppermute``, the
idiomatic XLA/SPMD pipelining pattern (no send/recv primitives, no
schedulers: one ``lax.scan`` over clock ticks, collectives inserted by
XLA).

Schedule: with M microbatches and S stages the scan runs M + S - 1 ticks;
at tick t stage s works on microbatch t - s (bubble ticks compute values
that are masked out of the collected output).  Reverse-mode AD through
the scan + ppermute gives the backward schedule automatically.

Usage::

    stage_fn(stage_params, x) -> y            # one stage, same x/y shape
    stacked = stack_stage_params([p0, p1, ...])   # leading stage axis
    apply = make_pipeline(stage_fn, mesh, pipe_axis='pipe')
    y = apply(stacked, x)                     # x: (M, mb, ...) microbatched

Constraints: one stage per pipe-axis shard (stack size == axis size) and
stage input/output shapes equal (they ride the same ppermute buffer).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map

from blendjax.parallel.ring_attention import _pvary


def stack_stage_params(stage_params_list):
    """Stack per-stage param pytrees on a new leading stage axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *stage_params_list)


def unstack_stage_params(stacked, n_stages):
    """Inverse of :func:`stack_stage_params`."""
    return [jax.tree.map(lambda x, i=i: x[i], stacked) for i in range(n_stages)]


def pipeline(stage_params, x, stage_fn, axis_name, vary_axes=None):
    """Run the pipeline *inside* ``shard_map``.

    ``stage_params``: this shard's stage params (leading stage axis of
    local size 1, squeezed here).  ``x``: microbatched input (M, mb, ...)
    replicated over the pipe axis.  Returns (M, mb, ...) final-stage
    outputs, replicated over the pipe axis via a masked psum.
    """
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    params = jax.tree.map(lambda p: p[0], stage_params)  # drop stage axis
    m = x.shape[0]
    axes = tuple(vary_axes) if vary_axes else (axis_name,)
    # Stage s receives stage s-1's output.
    perm = [(j, (j + 1) % n) for j in range(n)]

    def tick(carry, t):
        acc, state = carry
        # Stage 0 ingests microbatch t (clamped on bubble ticks); other
        # stages ingest the neighbor's previous output.
        mb = lax.dynamic_index_in_dim(x, jnp.clip(t, 0, m - 1), keepdims=False)
        inp = jnp.where(me == 0, _pvary(mb, (axis_name,)), state)
        out = stage_fn(params, inp)
        # The last stage finished microbatch t - (n - 1) this tick.
        widx = t - (n - 1)
        upd = lax.dynamic_update_index_in_dim(acc, out, jnp.maximum(widx, 0), 0)
        acc = jnp.where((me == n - 1) & (widx >= 0), upd, acc)
        state = lax.ppermute(out, axis_name, perm)
        return (acc, state), None

    acc0 = _pvary(jnp.zeros((m,) + x.shape[1:], x.dtype), axes)
    state0 = _pvary(jnp.zeros(x.shape[1:], x.dtype), axes)
    (acc, _), _ = lax.scan(tick, (acc0, state0), jnp.arange(m + n - 1))
    # Only the last stage holds real outputs; mask and psum replicates the
    # result across the pipe axis.
    return lax.psum(jnp.where(me == n - 1, acc, 0), axis_name)


def make_pipeline(stage_fn, mesh, pipe_axis="pipe", x_spec=None):
    """Wrap :func:`pipeline` for globally-sharded stacked stage params.

    ``x_spec``: PartitionSpec of the microbatched input *excluding* the
    pipe axis (e.g. ``P(None, 'data')`` to keep the per-microbatch batch
    dim data-sharded); defaults to fully replicated.  Returns
    ``apply(stacked_params, x)`` usable under ``jax.jit``.
    """
    x_spec = x_spec if x_spec is not None else P()
    n = mesh.shape[pipe_axis]
    vary = (pipe_axis,) + tuple(
        a for axes in x_spec if axes is not None
        for a in ((axes,) if isinstance(axes, str) else axes)
    )
    inner = functools.partial(
        pipeline, stage_fn=stage_fn, axis_name=pipe_axis, vary_axes=vary
    )
    mapped = shard_map(
        inner, mesh=mesh, in_specs=(P(pipe_axis), x_spec), out_specs=x_spec
    )

    def apply(stacked_params, x):
        n_stages = jax.tree.leaves(stacked_params)[0].shape[0]
        if n_stages != n:
            raise ValueError(
                f"stacked params have {n_stages} stages but mesh axis "
                f"{pipe_axis!r} has size {n} (need exactly one per shard)"
            )
        stacked_params = jax.tree.map(
            lambda p: lax.with_sharding_constraint(
                p, NamedSharding(mesh, P(pipe_axis))
            ),
            stacked_params,
        )
        return mapped(stacked_params, x)

    return apply


def _identity_proj(_params, x):
    return x


def _zeros_like_tree(t):
    return jax.tree.map(jnp.zeros_like, t)


def _fwd_loss(stage_params, proj_params, x, targets, *, stage_fn, loss_fn,
              in_proj, out_proj, axis_name, vary_axes):
    """GPipe forward (inside shard_map) that reduces straight to the mean
    microbatch loss; reverse-mode AD through the scan gives the classic
    GPipe backward (all M microbatch activations live across the forward
    sweep — the memory profile 1F1B exists to avoid)."""
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    params = jax.tree.map(lambda p: p[0], stage_params)
    ep, rp = proj_params
    m = x.shape[0]
    perm = [(j, (j + 1) % n) for j in range(n)]
    wire = jax.eval_shape(in_proj, ep, jax.eval_shape(lambda a: a[0], x))

    def tick(carry, t):
        state, loss_acc = carry
        mb = lax.dynamic_index_in_dim(x, jnp.clip(t, 0, m - 1), keepdims=False)
        inp = jnp.where(me == 0, _pvary(in_proj(ep, mb), vary_axes), state)
        out = stage_fn(params, inp)
        widx = t - (n - 1)
        tgt = lax.dynamic_index_in_dim(
            targets, jnp.clip(widx, 0, m - 1), keepdims=False
        )
        lj = loss_fn(out_proj(rp, out), tgt)
        loss_acc = loss_acc + jnp.where(
            (me == n - 1) & (widx >= 0) & (widx < m), lj, 0.0
        )
        return (lax.ppermute(out, axis_name, perm), loss_acc), None

    state0 = _pvary(jnp.zeros(wire.shape, wire.dtype), vary_axes)
    loss0 = _pvary(jnp.zeros((), jnp.float32), vary_axes)
    (_, loss_acc), _ = lax.scan(tick, (state0, loss0), jnp.arange(m + n - 1))
    return lax.psum(loss_acc, axis_name) / m


def _1f1b_grads(stage_params, proj_params, x, targets, *, stage_fn, loss_fn,
                in_proj, out_proj, axis_name, vary_axes):
    """1F1B (eager-backward) pipeline training step inside shard_map.

    Schedule: iteration ``k`` runs forward for microbatch ``k - s`` on
    stage ``s`` and backward for microbatch ``k - (2(n-1) - s)`` — the
    last stage backpropagates a microbatch the same iteration its forward
    completes, so at most ``2(n-1-s)+1`` activations are ever live per
    stage (a ring buffer of ``2n-1``), independent of the microbatch
    count M.  GPipe-by-AD instead holds all M.  Backward recomputes the
    stage forward from the saved stage *input* (rematerialization), the
    standard trade on HBM-bound TPUs.

    Returns ``(loss, stage_grads[local 1, ...], (d_in_proj, d_out_proj))``
    with gradients averaged over microbatches; projection grads are
    psum-replicated, stage grads stay stage-sharded.
    """
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    params = jax.tree.map(lambda p: p[0], stage_params)
    ep, rp = proj_params
    m = x.shape[0]
    L = 2 * n - 1  # ring-buffer depth: max in-flight activations + 1
    # Differentiating wrt a REPLICATED (non-varying) input under shard_map
    # makes AD insert a psum for the cotangent; inside the role switch that
    # collective would run on a subset of devices and deadlock.  Cast the
    # proj params varying up front; the accumulated grads are psum'd once,
    # uniformly, at the end.
    ep = jax.tree.map(lambda p: _pvary(p, vary_axes), ep)
    rp = jax.tree.map(lambda p: _pvary(p, vary_axes), rp)
    x = _pvary(x, vary_axes)
    targets = _pvary(targets, vary_axes)
    perm_fwd = [(j, (j + 1) % n) for j in range(n)]
    perm_bwd = [((j + 1) % n, j) for j in range(n)]
    wire = jax.eval_shape(in_proj, ep, jax.eval_shape(lambda a: a[0], x))

    def pv(val):
        return _pvary(val, vary_axes)

    def tick(carry, k):
        acc_p, acc_e, acc_r, act_buf, fwd_wire, bwd_wire, loss_acc = carry

        # ---- forward unit: microbatch j_f = k - me -----------------------
        j_f = k - me
        fwd_active = (j_f >= 0) & (j_f < m)
        mb_f = lax.dynamic_index_in_dim(
            x, jnp.clip(j_f, 0, m - 1), keepdims=False
        )
        inp = jnp.where(me == 0, pv(in_proj(ep, mb_f)), fwd_wire)
        out = stage_fn(params, inp)
        act_buf = jnp.where(
            fwd_active,
            lax.dynamic_update_index_in_dim(
                act_buf, inp, jnp.mod(jnp.maximum(j_f, 0), L), 0
            ),
            act_buf,
        )

        # ---- backward unit: microbatch j_b = k - (2(n-1) - me) -----------
        j_b = k - (2 * (n - 1) - me)
        bwd_active = (j_b >= 0) & (j_b < m)
        jb_c = jnp.clip(j_b, 0, m - 1)
        xs = lax.dynamic_index_in_dim(
            act_buf, jnp.mod(jb_c, L), keepdims=False
        )
        mb_b = lax.dynamic_index_in_dim(x, jb_c, keepdims=False)
        tgt = lax.dynamic_index_in_dim(targets, jb_c, keepdims=False)
        g_in = bwd_wire

        def norm(*out):
            # branches must agree on vma types; pvary (idempotent) unifies
            return jax.tree.map(pv, out)

        def mid_branch(_):
            _, vjp = jax.vjp(lambda p, a: stage_fn(p, a), params, xs)
            dp, dx = vjp(g_in)
            return norm(dp, _zeros_like_tree(ep), _zeros_like_tree(rp), dx,
                        jnp.zeros((), jnp.float32))

        def first_branch(_):
            _, vjp = jax.vjp(
                lambda p, e, mbx: stage_fn(p, in_proj(e, mbx)),
                params, ep, mb_b,
            )
            dp, de, _dmb = vjp(g_in)
            return norm(dp, de, _zeros_like_tree(rp),
                        jnp.zeros(wire.shape, wire.dtype),
                        jnp.zeros((), jnp.float32))

        def last_branch(_):
            lj, vjp = jax.vjp(
                lambda p, r, a: loss_fn(out_proj(r, stage_fn(p, a)), tgt),
                params, rp, xs,
            )
            dp, dr, dx = vjp(jnp.ones_like(lj))  # seed keeps lj's vma type
            return norm(dp, _zeros_like_tree(ep), dr, dx,
                        lj.astype(jnp.float32))

        role = jnp.where(me == 0, 1, jnp.where(me == n - 1, 2, 0))
        dp, de, dr, dx, lj = lax.switch(
            role, [mid_branch, first_branch, last_branch], None
        )

        def macc(acc, g):
            return jax.tree.map(
                lambda a, d: a + jnp.where(bwd_active, d, 0), acc, g
            )

        acc_p, acc_e, acc_r = macc(acc_p, dp), macc(acc_e, de), macc(acc_r, dr)
        loss_acc = loss_acc + jnp.where(bwd_active, lj, 0.0)

        fwd_wire = lax.ppermute(out, axis_name, perm_fwd)
        bwd_wire = lax.ppermute(dx, axis_name, perm_bwd)
        return (acc_p, acc_e, acc_r, act_buf, fwd_wire, bwd_wire,
                loss_acc), None

    carry0 = (
        jax.tree.map(lambda p: pv(jnp.zeros_like(p)), params),
        jax.tree.map(lambda p: pv(jnp.zeros_like(p)), ep),
        jax.tree.map(lambda p: pv(jnp.zeros_like(p)), rp),
        pv(jnp.zeros((L,) + wire.shape, wire.dtype)),
        pv(jnp.zeros(wire.shape, wire.dtype)),
        pv(jnp.zeros(wire.shape, wire.dtype)),
        pv(jnp.zeros((), jnp.float32)),
    )
    (acc_p, acc_e, acc_r, *_rest, loss_acc), _ = lax.scan(
        tick, carry0, jnp.arange(m + 2 * n - 2)
    )
    loss = lax.psum(loss_acc, axis_name) / m
    stage_grads = jax.tree.map(lambda g: g[None] / m, acc_p)
    proj_grads = (
        jax.tree.map(lambda g: lax.psum(g, axis_name) / m, acc_e),
        jax.tree.map(lambda g: lax.psum(g, axis_name) / m, acc_r),
    )
    return loss, stage_grads, proj_grads


def make_pipeline_train(stage_fn, loss_fn, mesh, pipe_axis="pipe",
                        schedule="1f1b", in_proj=None, out_proj=None,
                        x_spec=None):
    """Pipeline-parallel training step factory.

    ``stage_fn(stage_params, wire) -> wire`` runs one stage at the common
    wire width; ``in_proj(proj_params[0], microbatch) -> wire`` and
    ``out_proj(proj_params[1], wire) -> pred`` lift the equal-shape
    constraint at the model boundary (raw observations in, task outputs
    out — the wire itself keeps one shape because every stage's output
    rides the same ppermute buffer); ``loss_fn(pred, target) -> scalar``.

    ``schedule``:
      - ``"gpipe"``: forward sweep then AD backward; activation memory
        grows with the microbatch count M.
      - ``"1f1b"``: eager backward — at most ``2*stages-1`` activations
        live per stage regardless of M (see :func:`_1f1b_grads`).

    Returns ``train(stacked_params, proj_params, x, targets) ->
    (loss, (stage_grads, proj_grads))`` for ``x``/``targets`` microbatched
    ``(M, mb, ...)`` (see :func:`microbatch`); gradients are averaged over
    microbatches, i.e. M controls gradient accumulation.
    """
    if mesh.shape[pipe_axis] < 2:
        raise ValueError(
            f"pipeline needs mesh axis {pipe_axis!r} >= 2, got "
            f"{mesh.shape[pipe_axis]}"
        )
    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"unknown schedule {schedule!r}")
    in_proj = in_proj if in_proj is not None else _identity_proj
    out_proj = out_proj if out_proj is not None else _identity_proj
    x_spec = x_spec if x_spec is not None else P()
    vary = (pipe_axis,) + tuple(
        a for axes in x_spec if axes is not None
        for a in ((axes,) if isinstance(axes, str) else axes)
    )
    common = dict(stage_fn=stage_fn, loss_fn=loss_fn, in_proj=in_proj,
                  out_proj=out_proj, axis_name=pipe_axis, vary_axes=vary)
    if schedule == "gpipe":
        fwd = shard_map(
            functools.partial(_fwd_loss, **common),
            mesh=mesh,
            in_specs=(P(pipe_axis), P(), x_spec, x_spec),
            out_specs=P(),
        )

        def train(stacked_params, proj_params, x, targets):
            loss, (gs, gp) = jax.value_and_grad(fwd, argnums=(0, 1))(
                stacked_params, proj_params, x, targets
            )
            return loss, (gs, gp)

    else:
        inner = shard_map(
            functools.partial(_1f1b_grads, **common),
            mesh=mesh,
            in_specs=(P(pipe_axis), P(), x_spec, x_spec),
            out_specs=(P(), P(pipe_axis), P()),
        )

        def train(stacked_params, proj_params, x, targets):
            loss, gs, gp = inner(stacked_params, proj_params, x, targets)
            return loss, (gs, gp)

    return train


def microbatch(batch, num_microbatches):
    """Host/device-side reshape (B, ...) -> (M, B/M, ...) for the pipeline.

    Every leaf's leading axis must split evenly — a ragged split would
    silently change the per-microbatch loss weighting, so it raises the
    same actionable shape error :func:`blendjax.btt.prefetch.put_batch`
    uses, naming the offending leaf."""
    m = int(num_microbatches)
    if m < 1:
        raise ValueError(f"num_microbatches must be >= 1, got {num_microbatches}")

    def split(x):
        b = x.shape[0]
        if b % m:
            raise ValueError(
                f"batch leaf of shape {tuple(x.shape)} not splittable into "
                f"{m} microbatches: leading axis {b} leaves remainder "
                f"{b % m}; pick batch/num_microbatches divisible "
                f"(e.g. batch {b - b % m} or {b + m - b % m})"
            )
        return x.reshape((m, b // m) + x.shape[1:])

    return jax.tree.map(split, batch)
