"""The gated delta rule's one-position update, stepped where the state lies.

A serving pool keeps each linear-attention layer's float32 matrix state as
one leaf ``(S, pieces, H / pieces, dv, dk)`` (or ``(S, H, dv, dk)``), a row
a slot (:func:`blendjax.models.seqformer.init_cache`).  A decode step
advances the stepped rows' states by one position each::

    s_k = S k    s_q = S q    u = beta (v - alpha s_k)
    S'  = alpha S + u k^T     o = alpha s_q + u (k . q)

:func:`gdn_update` does that in one Pallas kernel: a grid over (row, piece
of heads), each step's block ``(1, 1, H / pieces, dv, dk)`` of the pool
chosen by the row's slot (a scalar-prefetch argument), read into VMEM once,
used for both products and the update there, and written back to the same
block of the same buffer (``input_output_aliases``): the stepped rows'
state crosses HBM twice, once in and once out, and no other row is
touched.  The arithmetic is :func:`blendjax.models.deltanet.mix_step`'s
before this kernel, element by element in float32 on the vector unit; only
the order of the sums over ``dk`` is the compiler's.

Without the delta term (``delta=False``: a Mamba-2 layer's step, ``u = beta
v``, ``o = alpha S q + u (k . q)``, with ``q`` and ``k`` one vector a row
that every head reads) the kernel needs no ``S k``; it is the same grid,
the same blocks and the same in-place write, under the name
``ssd_update``.

Rows may repeat a slot only where they are padding (a padded bucket repeats
the pool's extra row): such steps read and write one block in turn, and
that row's state is garbage either way.  Interpret mode follows the one
rule in :func:`blendjax.ops.flash_attention.resolve_interpret`.
"""

from __future__ import annotations

import importlib
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the module (the package re-exports its function under the same name),
# read at each call: one rule steers every kernel, and a test that steers it
# steers this one
_RULE = importlib.import_module("blendjax.ops.flash_attention")


def _kernel(rows_ref, s_ref, q_ref, k_ref, v_ref, alpha_ref, beta_ref,
            o_ref, s_out_ref):
    del rows_ref  # read by the index maps
    s = s_ref[0, 0]                                     # (h, dv, dk)
    q, k = q_ref[0, 0][:, None, :], k_ref[0, 0][:, None, :]
    alpha, beta = alpha_ref[0, 0], beta_ref[0, 0]       # (h, 1)
    s_k = jnp.sum(s * k, -1)                            # (h, dv)
    s_q = jnp.sum(s * q, -1)
    u = beta * (v_ref[0, 0] - alpha * s_k)
    s_out_ref[0, 0] = alpha[:, :, None] * s + u[:, :, None] * k
    o_ref[0, 0] = alpha * s_q + u * jnp.sum(k * q, -1)


def _kernel_without_delta(rows_ref, s_ref, q_ref, k_ref, v_ref, alpha_ref,
                          beta_ref, o_ref, s_out_ref):
    del rows_ref  # read by the index maps
    s = s_ref[0, 0]                                     # (h, dv, dk)
    q, k = q_ref[0, 0][:, None, :], k_ref[0, 0][:, None, :]   # (1, 1, dk)
    alpha, beta = alpha_ref[0, 0], beta_ref[0, 0]       # (h, 1)
    u = beta * v_ref[0, 0]                              # (h, dv)
    s_out_ref[0, 0] = alpha[:, :, None] * s + u[:, :, None] * k
    o_ref[0, 0] = alpha * jnp.sum(s * q, -1) + u * jnp.sum(k * q, -1)


def gdn_update(pool, rows, q, k, v, alpha, beta, interpret=None, *,
               delta=True):
    """Step ``pool``'s ``rows`` by one position: ``pool`` ``(S, ..., dv,
    dk)`` float32 (the heads on the axes between), ``rows`` ``(B,)`` int32
    slots, ``q, k`` ``(B, H, dk)``, ``v`` ``(B, H, dv)``, ``alpha`` (the
    decay, ``exp(g)``) and ``beta`` ``(B, H)``, all float32 -> ``(o (B, H,
    dv), the pool with the rows' new state)``.  The pool is written in
    place: donate it, or XLA copies it first.  ``delta=False`` drops the
    delta term (``u = beta v``) and takes ``q, k`` ``(B, dk)``, one vector
    a row for every head."""
    s, *heads, dv, dk = pool.shape
    pieces = heads[0] if len(heads) == 2 else 1
    h = math.prod(heads) // pieces
    b = rows.shape[0]
    flat = pool.reshape(s, pieces, h, dv, dk)

    def by_row(width):
        return pl.BlockSpec((1, 1, h, width), lambda i, j, rows: (i, j, 0, 0))

    state = pl.BlockSpec((1, 1, h, dv, dk),
                         lambda i, j, rows: (rows[i], j, 0, 0, 0))
    if delta:
        kernel, reads, flops, name = _kernel, by_row(dk), 7, "gdn_update"
        q, k = (a.reshape(b, pieces, h, -1) for a in (q, k))
    else:
        # every head reads the row's one q and k
        kernel, flops, name = _kernel_without_delta, 5, "ssd_update"
        reads = pl.BlockSpec((1, 1, 1, dk), lambda i, j, rows: (i, 0, 0, 0))
        q, k = (a.reshape(b, 1, 1, dk) for a in (q, k))
    o, flat = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, pieces),
            in_specs=[state, reads, reads, by_row(dv), by_row(1),
                      by_row(1)],
            out_specs=[by_row(dv), state]),
        out_shape=[jax.ShapeDtypeStruct((b, pieces, h, dv), jnp.float32),
                   jax.ShapeDtypeStruct(flat.shape, flat.dtype)],
        input_output_aliases={1: 1},
        cost_estimate=pl.CostEstimate(
            flops=flops * b * pieces * h * dv * dk, transcendentals=0,
            bytes_accessed=2 * b * pieces * h * dv * dk * 4),
        interpret=_RULE.resolve_interpret(interpret),
        name=name,
    )(rows.astype(jnp.int32), flat, q.astype(jnp.float32),
      k.astype(jnp.float32),
      *(a.reshape(b, pieces, h, -1).astype(jnp.float32)
        for a in (v, alpha, beta)))
    return o.reshape(b, pieces * h, dv), flat.reshape(pool.shape)
