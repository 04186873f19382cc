"""Trustworthy completion fences for timing and synchronization.

``jax.block_until_ready`` is only as honest as the backend's
implementation: a proxied PJRT backend can acknowledge the *local client
buffer* rather than device completion, and any timing, duty-cycle, or
backpressure logic built on it then silently measures fiction.  On the
directly attached TPU v5e it IS a real fence (``chip_smoke.py``'s fence
check, recorded in PERF.md), so this module is a cross-check there, not
a necessity; ROADMAP D1 decides whether it stays.

A VALUE FETCH cannot lie: the bytes of a computation's output cannot
reach the host before the computation (and every transfer it depends
on) actually finished.  This module provides:

- :func:`value_fence` — fence an arbitrary pytree by fetching one
  scalar reduced from every leaf (one tiny jit, cached per structure;
  one scalar D2H per call);
- :func:`fence_chain` — a running on-device accumulator for streaming
  loops: fold batches in as they are dispatched, fetch the accumulator
  at a measurement boundary to fence everything folded so far;
- :func:`fences_valid` — quick self-check of ``block_until_ready``
  against a known-FLOPs matmul (``chip_smoke.py``'s train leg runs the
  chained-matmul version with a value-fetch cross-check).

The benchmark suite (``benchmarks/suite_device.py``) times with value
fetches throughout.
"""

from __future__ import annotations

import time

import numpy as np

# jax is imported lazily: this module rides in ``blendjax.utils``'s
# public surface, which jax-free fast-start processes (replay shards,
# the serve tier's LinearModel server) import for StageTimer — they
# must not pay ``import jax`` (nor take the chip) for fences they never
# call.
_jit = None


def _fns():
    global _jit
    if _jit is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def leaf_sum(leaves):
            return sum(
                jnp.mean(leaf.astype(jnp.float32)) for leaf in leaves
            )

        @jax.jit
        def fold(acc, leaves):
            # one canonical reduction (jit inlines)
            return acc + leaf_sum(leaves)

        _jit = (leaf_sum, fold)
    return _jit


def _leaves(tree):
    import jax

    return [x for x in jax.tree.leaves(tree) if hasattr(x, "dtype")]


def value_fence(tree):
    """Block until every leaf of ``tree`` is actually materialized on
    device, by fetching a scalar that depends on all of them.  Returns
    the fetched float (occasionally useful as a checksum)."""
    leaves = _leaves(tree)
    if not leaves:
        return 0.0
    leaf_sum, _ = _fns()
    return float(np.asarray(leaf_sum(leaves)))


class fence_chain:
    """Streaming fence: ``fold`` each dispatched batch into an on-device
    scalar chain, ``sync`` at measurement boundaries.

    The fold is one fused reduction per batch (dispatched async, cheap);
    ``sync`` costs one scalar fetch and fences EVERY batch folded since
    construction — which is what a throughput window must bill::

        chain = fence_chain()
        t0 = time.perf_counter()
        for batch in stream:
            state, loss = train_step(state, batch)
            chain.fold(loss)
        chain.sync()                      # all steps actually retired
        elapsed = time.perf_counter() - t0
    """

    def __init__(self):
        import jax.numpy as jnp

        self._acc = jnp.float32(0.0)

    def fold(self, tree):
        leaves = _leaves(tree)
        if leaves:
            _, fold = _fns()
            self._acc = fold(self._acc, leaves)

    def sync(self):
        """Fetch the accumulator — returns only when everything folded
        has truly executed/landed."""
        return float(np.asarray(self._acc))


def fences_valid(peak_flops_per_sec, n=2048, reps=2, slack=1.02):
    """Is ``block_until_ready`` a real fence on this backend?

    Times one ``n^3`` bf16 matmul under ``block_until_ready``; if the
    implied FLOP/s beat ``peak_flops_per_sec`` the fence is phantom.
    Returns ``(block_ok, details)``.  Costs two small matmuls.
    """
    import jax
    import jax.numpy as jnp

    x = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.bfloat16)
    mm = jax.jit(lambda a, b: a @ b)
    value_fence(mm(x, w))  # compile + land operands
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(mm(x, w))
        best = min(best, time.perf_counter() - t0)
    implied = 2.0 * n ** 3 / max(best, 1e-9)
    ok = implied <= peak_flops_per_sec * slack
    return ok, {"min_s": best, "implied_flops_per_sec": implied,
                "peak_flops_per_sec": peak_flops_per_sec}
