"""Driver: closed-loop serving of a token model of sliding-window and full
grouped-query attention, each kind with its own rotation, and softmax-routed
experts held 16 of 64, through an in-thread ``PolicyServer``.

The window, the set-up that gives every slot a tenant and the comparison's
arrangement are ``serve_tokens_hybrid.py``'s, run as they stand: that driver
looks its ``build_model``, ``compare`` and ``FAULTS`` up by name when it is
called, and this one gives it its own for the call (as
``serve_tokens_linear.py`` does), because that driver names the reference of
its own model.  Here they name ``chipbench/reference_mellum2.py``.

**The set-up's heap is frozen before the window.**  Once every slot has a
tenant (the last step of set-up), what the process has built so far (the
traces and caches of the nine 28-layer programs it compiled, the weights'
pytree) is collected once and moved out of the collector's reach
(``gc.freeze``), as a long-running server's start-up heap would be.  Left
in reach, a full collection that fell in the window walked all of it and
stalled the serve thread for seconds: two runs of six read 570 and 578
tokens/s where the rest read 618-628 (``PERF.md`` section 6).

Faults (``--fault``): ``answer_altered`` (one reply's first logit moved by
8 standard deviations where it is produced), ``renorm_left_out`` (the
decode step routes with the top 8 softmax weights as they come, not
renormalised to sum 1; the prefill keeps the renormalisation),
``window_left_out_in_prefill`` (the sliding layers' prefill attends over
every earlier position; the decode step's rings stay as they are) and
``rope_kinds_swapped`` (the sliding layers rotate by the full layers' YaRN
table, in the prefill and the step alike).
"""

from __future__ import annotations

import dataclasses
import gc

import numpy as np

from chipbench.drivers import serve_tokens_hybrid as hybrid
from chipbench.drivers.serve_tokens import ALTERED_BY
from chipbench.traffic import closed_loop_token_clients

FAULTS = (None, "answer_altered", "renorm_left_out",
          "window_left_out_in_prefill", "rope_kinds_swapped")


def _program():
    """The program's modules, or a clean exit where the program cannot
    describe this model (no sliding-window layer kind, no softmax
    routing)."""
    from blendjax.models import moe, seqformer

    try:
        seqformer.hybrid_layer_kinds({"layer_types": ["sliding_attention"],
                                      "num_hidden_layers": 1})
        moe.RouteSpec(top_k=1, score="softmax", renorm=True)
        seqformer.AttnSpec
    except (AttributeError, KeyError, TypeError):
        raise SystemExit(
            "chipbench: this program serves no model of sliding-window "
            "beside full attention with softmax-routed experts "
            "(blendjax.models.seqformer.hybrid_layer_kinds has no "
            "sliding_attention, or blendjax.models.moe.RouteSpec no "
            "softmax score)") from None
    return seqformer


def _respec(served, fn):
    """``served`` with each block's static entries as ``fn(blk)`` gives
    them (a dict of entries to replace), the arrays shared."""
    import jax

    tree = jax.tree.map(lambda x: x, served)
    for blk in tree["blocks"]:
        for path, spec in fn(blk).items():
            node = blk
            for name in path[:-1]:
                node = node[name]
            node[path[-1]] = spec
    return tree


def _swap_rope(served):
    full = next(blk["attn"] for blk in served["blocks"]
                if "attn" in blk and blk["attn"].window is None)
    return _respec(served, lambda blk: {("attn",): dataclasses.replace(
        full, window=blk["attn"].window)} if "attn" in blk
        and blk["attn"].window else {})


def _no_window(served):
    return _respec(served, lambda blk: {("attn",): dataclasses.replace(
        blk["attn"], window=None)} if "attn" in blk else {})


def _no_renorm(served):
    return _respec(served, lambda blk: {("moe", "route"): dataclasses.replace(
        blk["moe"]["route"], renorm=False)} if "moe" in blk else {})


def _with_params(fn, params):
    """A jitted ``fn(params, pool, idx, arr)`` called with ``params`` in
    place of the model's own (the same arrays, other static entries)."""
    def call(_, *args):
        return fn(params, *args)
    return call


def build_model(cfg, seed, fault=None):
    """(the seeded arrays, the served model over them)."""
    import jax
    import jax.numpy as jnp

    seqformer = _program()
    from blendjax.serve.server import SeqFormerModel
    from chipbench import reference_mellum2

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    arrays = reference_mellum2.make_params(cfg, seed,
                                           dtypes[cfg["param_dtype"]])
    served = seqformer.describe_token_model(
        jax.tree.map(lambda x: x, arrays), cfg, cfg.get("held_first", 0))
    if fault == "rope_kinds_swapped":
        served = _swap_rope(served)
    model = SeqFormerModel(served, cfg["slots"], cfg["length"],
                           compute_dtype=dtypes[cfg["compute_dtype"]],
                           cache_dtype=dtypes[cfg["cache_dtype"]])
    if fault == "renorm_left_out":  # where the step is made
        model._step = _with_params(model._step, _no_renorm(served))
    if fault == "window_left_out_in_prefill":
        model._prefill = _with_params(model._prefill, _no_window(served))
    if fault == "answer_altered":
        real_step_rows = model.step_rows

        def step_rows(idx, obs):  # one answer altered where it is produced
            replies = np.array(real_step_rows(idx, obs))
            replies[0, 0] += ALTERED_BY  # the logits' spread is about 1
            return replies
        model.step_rows = step_rows
    return arrays, model


_TENANT_EVERY_SLOT = hybrid._tenant_every_slot


def _tenant_every_slot_then_freeze(model, length, vocab, seed):
    """The set-up's last step, then its heap frozen (module docstring)."""
    _TENANT_EVERY_SLOT(model, length, vocab, seed)
    gc.collect()
    gc.freeze()


def run(ctx):
    _program()
    mine = {"build_model": build_model, "compare": compare, "FAULTS": FAULTS,
            "_tenant_every_slot": _tenant_every_slot_then_freeze}
    theirs = {name: getattr(hybrid, name) for name in mine}
    for name, fn in mine.items():
        setattr(hybrid, name, fn)
    try:
        return hybrid.run(ctx)
    finally:
        gc.unfreeze()
        for name, fn in theirs.items():
            setattr(hybrid, name, fn)


def compare(cfg, arrays, traffic, seed, sample, control_quant):
    """``serve_tokens_hybrid.compare`` against this model's reference: one
    pass over each sampled episode's ids (padded to one length, which a
    causal model ignores), the reference's logits at the ids each served
    reply names, its logsumexp and its logits' standard deviation at every
    served position.  With ``control_quant`` the reference computed in
    that lower precision takes the served replies' place."""
    import jax.numpy as jnp

    from chipbench import reference_mellum2 as reference

    nan = float("nan")
    if not sample:
        return {"logit_gap_p50": nan, "logit_gap_rms": nan,
                "logit_gap_max": nan, "lse_gap_max": nan, "n": 0}
    span = max(traffic["prefix_lengths"]) + traffic["steps_max"]
    most = traffic["steps_max"] + 1

    def view(ids, pos, served_ids, quant):
        x = reference.hidden(arrays, cfg, ids, quant)[pos]
        return [np.asarray(a) for a in reference.served_view(
            arrays, x, served_ids, quant)]

    got, ref_top, ref_lse, ref_std = [], [], [], []
    for client, index, replies in sample:
        prefix, steps = closed_loop_token_clients.episode_plan(
            traffic, seed, client, index)
        n = len(replies)
        k = (replies.shape[1] - 1) // 2
        ids = np.zeros(span, np.int32)
        ids[:len(prefix) + len(steps)] = np.concatenate([prefix, steps])[:, 0]
        pos = np.minimum(len(prefix) - 1 + np.arange(most),
                         len(prefix) - 1 + n - 1)
        served_ids = np.zeros((most, k), np.int32)
        served_ids[:n] = replies[:, k:2 * k].astype(np.int32)
        args = jnp.asarray(ids), jnp.asarray(pos), jnp.asarray(served_ids)
        top, lse, std = view(*args, None)
        if control_quant:
            low_top, low_lse, _ = view(*args, control_quant)
            replies = np.concatenate(
                [low_top[:n], served_ids[:n], low_lse[:n, None]], 1)
        got.append(replies)
        ref_top.append(top[:n])
        ref_lse.append(lse[:n])
        ref_std.append(std[:n])
    gaps = reference.reply_gaps(
        np.concatenate(got), np.concatenate(ref_top),
        np.concatenate(ref_lse), np.concatenate(ref_std))
    return dict(gaps, n=int(sum(len(r) for r in got)))
