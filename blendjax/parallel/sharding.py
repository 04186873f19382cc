"""Sharding rules and the mesh-sharded train step.

How blendjax scales model-side (SURVEY.md §2.4: the reference has *no*
model parallelism — consumer scale-out there is DataLoader workers only):

- **data axis**: the stream feeds per-host batch shards
  (``BatchLoader(shard=(process_index, process_count))``), the batch is
  sharded ``P('data')``, and XLA turns the gradient sum into a psum over
  ICI.
- **model axis**: wide dense layers shard their output features
  ``P(None, 'model')``; XLA inserts the all-gather/reduce-scatter pairs.

Rules map pytree paths to PartitionSpecs; anything unmatched replicates.
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from blendjax.models.train import TrainState


def detector_rules(axis="model"):
    """Tensor-parallel rules for :mod:`blendjax.models.detector`: the two
    dense layers carry the parameter mass and split their features; convs
    replicate (tiny, bandwidth-bound)."""
    return {
        ("fc", "w"): P(None, axis),
        ("fc", "b"): P(axis),
        ("head", "w"): P(axis, None),  # row-parallel: consumes fc's sharded out
        ("head", "b"): P(),
    }


def seqformer_rules(model_axis="model", expert_axis=None):
    """Sharding rules for :mod:`blendjax.models.seqformer`.

    Attention projections shard over the head axis (head-major layout),
    the MLP is column/row tensor-parallel, and MoE expert stacks shard
    over ``expert_axis`` (defaults to ``model_axis`` when the mesh has no
    dedicated expert axis) so the gate-weighted mixture psums over expert
    shards.
    """
    e = expert_axis or model_axis
    return {
        ("wq", "w"): P(None, model_axis, None),
        ("wq", "b"): P(model_axis, None),
        ("wk", "w"): P(None, model_axis, None),
        ("wk", "b"): P(model_axis, None),
        ("wv", "w"): P(None, model_axis, None),
        ("wv", "b"): P(model_axis, None),
        ("wo", "w"): P(model_axis, None, None),
        ("wo", "b"): P(),
        ("mlp", "fc", "w"): P(None, model_axis),
        ("mlp", "fc", "b"): P(model_axis),
        ("mlp", "proj", "w"): P(model_axis, None),
        ("mlp", "proj", "b"): P(),
        ("moe", "w1"): P(e, None, None),
        ("moe", "b1"): P(e, None),
        ("moe", "w2"): P(e, None, None),
        ("moe", "b2"): P(e, None),
    }


def _path_key(path):
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(p.key)
        elif hasattr(p, "idx"):
            out.append(p.idx)
        elif hasattr(p, "name"):
            out.append(p.name)
    return tuple(out)


def param_specs(params, rules):
    """PartitionSpec pytree for ``params``: longest-suffix match of each
    leaf path against ``rules`` keys; default replicate."""

    def spec_for(path):
        key = _path_key(path)
        for rule_key, spec in rules.items():
            if key[-len(rule_key):] == tuple(rule_key):
                return spec
        return P()

    return jax.tree_util.tree_map_with_path(lambda path, _: spec_for(path), params)


def shard_pytree(tree, mesh, specs):
    """Place a pytree on the mesh according to a spec pytree."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        tree,
        specs,
        is_leaf=lambda x: x is None,
    )


def make_sharded_train_step(loss_fn, optimizer, mesh, rules=None, data_axis="data"):
    """Build ``(init_sharded, step)`` for SPMD training over ``mesh``.

    ``init_sharded(params)`` places params (and fresh optimizer state)
    according to ``rules``; ``step(state, batch)`` is jitted with sharded
    in/out so XLA lays gradients' psum over the data axis and the tensor-
    parallel collectives over the model axis automatically.  The batch must
    arrive sharded ``P(data_axis)`` (use
    ``JaxStream(sharding=data_sharding(mesh))``).
    """
    rules = rules or {}

    def init_sharded(params):
        specs = param_specs(params, rules)
        params = shard_pytree(params, mesh, specs)
        opt_state = optimizer.init(params)  # inherits param shardings
        return TrainState(params=params, opt_state=opt_state, step=0)

    def _step(state, batch):
        import optax

        loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1), loss

    return init_sharded, jax.jit(_step, donate_argnums=(0,))


def make_seqformer_train_step(
    optimizer,
    mesh,
    data_axis="data",
    seq_axis="seq",
    model_axis="model",
    expert_axis=None,
    attn_impl="ring",
    moe_impl="dense",
    moe_k=2,
    moe_capacity_factor=1.25,
    moe_aux_weight=0.0,
    compute_dtype=None,
    flash_interpret=None,
    attn_window=None,
):
    """4-way-parallel training step for the SeqFormer world-model.

    Composes every parallelism the framework supports in one jitted step:
    batch dp-sharded over ``data_axis``, sequence sharded over
    ``seq_axis`` — ``attn_impl`` picks the scheme: ``'ring'`` (blockwise
    ring), ``'ring_flash'`` (the fused Pallas kernel per ring block
    pair, the long-context configuration), ``'zigzag_flash'`` (ring +
    flash with the load-balanced zigzag chunk layout — every device
    does equal causal work), ``'ulysses'`` (all-to-all),
    or ``'ulysses_flash'`` (all-to-all with the fused kernel as the
    per-head-group inner attention) — attention heads + MLP
    tensor-parallel over ``model_axis`` (ring variants only), MoE
    experts over ``expert_axis`` (see :func:`seqformer_rules`).
    ``moe_impl='topk'`` switches the expert layer from the dense mixture
    to routed expert parallelism (top-k gating + capacity,
    :mod:`blendjax.models.moe`) with an optional load-balance aux loss.
    ``attn_window=W`` enables sliding-window attention through whichever
    scheme is selected (ring variants then rotate only the shards the
    window reaches — compute and ring traffic O(W); zigzag rejects it,
    the windowed ring is already balanced).

    Returns ``(init_sharded, step, batch_sharding)``; device_put batches
    with ``batch_sharding`` (leading dims sharded data x seq).
    """
    import functools

    from blendjax.models import seqformer
    from blendjax.parallel.ring_attention import make_ring_attention

    inner_attn = None
    if attn_impl == "ulysses_flash":
        from blendjax.ops.flash_attention import (
            flash_attention,
            flash_block_size,
        )

        attn_impl = "ulysses"

        def inner_attn(q, k, v, causal=False, scale=None, window=None):
            # one tile-selection policy for the ulysses and ring paths.
            # flash_interpret=None follows the kernel's own backend
            # rule; tests/test_tpu_lowering.py passes False to force
            # the compiled path when EXPORTING for tpu from a CPU host
            blk = flash_block_size(q.shape[1], q.shape[-1], q.dtype, window)
            return flash_attention(
                q, k, v, causal, scale, blk, blk, flash_interpret, window
            )
    attn = make_ring_attention(
        mesh,
        seq_axis=seq_axis,
        causal=True,
        impl=attn_impl,
        batch_axis=data_axis,
        head_axis=(model_axis
                   if attn_impl in ("ring", "ring_flash", "zigzag_flash")
                   else None),
        inner_attn=inner_attn,
        flash_interpret=(flash_interpret
                         if attn_impl in ("ring_flash", "zigzag_flash")
                         else None),
        window=attn_window,
    )
    rules = seqformer_rules(model_axis, expert_axis)
    loss_kwargs = dict(
        attn_fn=attn,
        moe_impl=moe_impl,
        moe_k=moe_k,
        moe_capacity_factor=moe_capacity_factor,
        moe_aux_weight=moe_aux_weight,
    )
    if compute_dtype is not None:
        # passthrough (default stays the model's bf16): single-device
        # parity checks pin f32 so sharded-vs-reference agreement is
        # numerically tight
        loss_kwargs["compute_dtype"] = compute_dtype
    loss = functools.partial(seqformer.loss_fn, **loss_kwargs)
    init_sharded, step = make_sharded_train_step(
        loss, optimizer, mesh, rules=rules, data_axis=data_axis
    )
    batch_sharding = NamedSharding(mesh, P(data_axis, seq_axis, None))
    return init_sharded, step, batch_sharding
