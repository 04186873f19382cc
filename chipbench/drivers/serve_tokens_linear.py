"""Driver: closed-loop serving of a token model of gated delta-rule linear
attention (a float32 matrix state a head) beside full attention, through an
in-thread ``PolicyServer``.

The window, the set-up that gives every slot a tenant and the fault
``state_not_reset`` are ``serve_tokens_hybrid.py``'s, run as they stand:
that driver looks its ``build_model``, ``compare`` and ``FAULTS`` up by name
when it is called, and this one gives it its own for the call (as
``closed_loop_token_clients.py`` gives the client loop its plan), because
that driver names the reference of its own model.  Here they name
``chipbench/reference_olmohybrid.py``.

Faults (``--fault``): ``answer_altered`` (one reply's first logit moved by
8 standard deviations where it is produced), ``state_not_reset`` (the
model's ``reset_rows`` never lands: a prefill goes on from the state and
the tails its slot's last tenant left) and ``decay_left_out`` (``alpha = 1``
where the decode step is made: the gate's decay dropped from
``deltanet.mix_step``; the prefill keeps it).
"""

from __future__ import annotations

import numpy as np

from chipbench.drivers import serve_tokens_hybrid as hybrid
from chipbench.drivers.serve_tokens import ALTERED_BY
from chipbench.traffic import closed_loop_token_clients

FAULTS = (None, "answer_altered", "state_not_reset", "decay_left_out")


def _mixer():
    """The program's linear-attention module, or a clean exit where the
    program has none."""
    try:
        from blendjax.models import deltanet
    except ImportError:
        raise SystemExit("chipbench: this program serves no linear-attention "
                         "model (blendjax.models.deltanet)") from None
    return deltanet


def _step_without_decay(deltanet):
    """``deltanet.mix_step`` with the decay dropped (``g = 0``) where the
    step is made; the sequence form keeps its gates."""
    import jax.numpy as jnp

    real_step, real_gates = deltanet.mix_step, deltanet.gates

    def gates(p, x, dtype):
        g, beta = real_gates(p, x, dtype)
        return jnp.zeros_like(g), beta

    def mix_step(*args, **kwargs):
        deltanet.gates = gates
        try:
            return real_step(*args, **kwargs)
        finally:
            deltanet.gates = real_gates

    return mix_step


def build_model(cfg, seed, fault=None):
    """(the seeded arrays, the served model over them)."""
    import jax
    import jax.numpy as jnp

    deltanet = _mixer()
    from blendjax.models import seqformer
    from blendjax.serve.server import SeqFormerModel
    from chipbench import reference_olmohybrid

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    arrays = reference_olmohybrid.make_params(cfg, seed,
                                              dtypes[cfg["param_dtype"]])
    served = seqformer.describe_token_model(
        jax.tree.map(lambda x: x, arrays), cfg)
    if fault == "decay_left_out":  # before any step is traced
        deltanet.mix_step = _step_without_decay(deltanet)
    model = SeqFormerModel(served, cfg["slots"], cfg["length"],
                           compute_dtype=dtypes[cfg["compute_dtype"]],
                           cache_dtype=dtypes[cfg["cache_dtype"]])
    if fault == "answer_altered":
        real_step_rows = model.step_rows

        def step_rows(idx, obs):  # one answer altered where it is produced
            replies = np.array(real_step_rows(idx, obs))
            replies[0, 0] += ALTERED_BY  # the logits' spread is about 1
            return replies
        model.step_rows = step_rows
    return arrays, model


def run(ctx):
    deltanet = _mixer()
    real_step = deltanet.mix_step
    mine = {"build_model": build_model, "compare": compare, "FAULTS": FAULTS}
    theirs = {name: getattr(hybrid, name) for name in mine}
    for name, fn in mine.items():
        setattr(hybrid, name, fn)
    try:
        return hybrid.run(ctx)
    finally:
        for name, fn in theirs.items():
            setattr(hybrid, name, fn)
        deltanet.mix_step = real_step


def compare(cfg, arrays, traffic, seed, sample, control_quant):
    """``serve_tokens_hybrid.compare`` against this model's reference: one
    pass over each sampled episode's ids (padded to one length, which a
    causal model ignores), the reference's logits at the ids each served
    reply names, its logsumexp and its logits' standard deviation at every
    served position.  With ``control_quant`` the reference computed in
    that lower precision takes the served replies' place."""
    import jax.numpy as jnp

    from chipbench import reference_olmohybrid as reference

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
