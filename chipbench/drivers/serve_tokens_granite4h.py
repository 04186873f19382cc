"""Driver: closed-loop serving of a token model of Mamba-2 layers (a
float32 matrix state a head, one group of B and C) beside full attention
without a position encoding, through an in-thread ``PolicyServer``.

The window, the set-up that gives every slot a tenant and the fault
``state_not_reset`` are ``serve_tokens_hybrid.py``'s, run as they stand:
that driver looks its ``build_model``, ``compare`` and ``FAULTS`` up by name
when it is called, and this one gives it its own for the call (as
``serve_tokens_linear.py`` does), because that driver names the reference of
its own model.  Here they name ``chipbench/reference_granite4h.py``.

**The set-up's heap is frozen before the window**, as
``serve_tokens_mellum2.py`` freezes it: once every slot has a tenant, what
the process has built (the traces and caches of the nine 40-layer programs
it compiled, the weights' pytree) is collected once and moved out of the
collector's reach (``gc.freeze``).  Left in reach, full collections in the
window stalled the serve thread for seconds: three of twelve runs read
1196-1356 tokens/s where the rest read 1451-1474, the lost time in the
thread's fetch wait and poll (``PERF.md`` section 6).

**The state is held against the reference's, where the logits cannot
see it.**  Under the published initialisation a Mamba-2 layer's state is a
small part of its output (``D x`` with ``D = 1`` beside a state of steps
``dt`` 1e-3 .. 1e-1), so a state a slot's last tenant left behind moves the
served logits no further than rounding does (``state_not_reset`` read as
the program does, ``PERF.md`` section 2).  So once the window has closed,
before the pool is freed, the driver admits seeded sequences into
:data:`PROBE_SLOTS` seeded slots the way the server admits an episode
(``reset_rows``, then ``prefill_rows`` at the traffic's prefix lengths in
turn), steps them together :data:`PROBE_STEPS` ticks in the smallest bucket
that holds them (the rest of it the pad row), and reads their rows of every
Mamba-2 state leaf out of the pool:

- ``state_gap_max``: the largest, over slots, layers and heads, of a head's
  distance from the reference's state after the same ids
  (``reference_granite4h.ssd_states``, the recurrence position by
  position), over the reference state's norm.  A reset that leaves the
  state behind or zeroes another row, or a step that reads or writes
  another row's state, moves a head's state by a large share of itself.
  With the control the reference in that lower precision takes the pool's
  place.
- ``state_left_after_reset``: the largest magnitude the probed rows of
  every Mamba-2 state and tail leaf hold between their reset and their
  prefill; exactly 0.  A tail left behind touches only a prefill's first
  positions and hardly moves a state, so it is read here.
- ``state_resets_missing``: the window's ``serve_resets`` (resets the
  server answered) less its ``serve_state_resets`` (rows
  ``SeqFormerModel.reset_rows`` was asked to zero): a reset the server
  never passes on.

Faults (``--fault``): ``answer_altered`` (one reply's first logit moved by
8 standard deviations where it is produced), ``state_not_reset`` (the
model's ``reset_rows`` never lands: a prefill goes on from the state and
the tail its slot's last tenant left), ``decay_left_out`` (``exp(dt A) =
1`` where the decode step is made: ``mamba2.decay`` dropped from
``mamba2.mix_step``; the prefill keeps it) and ``gate_norm_left_out``
(``y * silu(z)`` without its RMSNorm in the decode step).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from chipbench.drivers import serve_tokens_hybrid as hybrid
from chipbench.drivers.serve_tokens import ALTERED_BY
from chipbench.traffic import closed_loop_token_clients

FAULTS = (None, "answer_altered", "state_not_reset", "decay_left_out",
          "gate_norm_left_out")
#: slots the probe after the window admits a sequence into, and the ticks
#: it then steps them together (module docstring)
PROBE_SLOTS, PROBE_STEPS = 4, 4
#: the served model while its pool is alive, for the probe after the window
_LIVE = {}


def _mixer():
    """The program's Mamba-2 module, or a clean exit where the program has
    none."""
    try:
        from blendjax.models import mamba2
    except ImportError:
        raise SystemExit("chipbench: this program serves no Mamba-2 model "
                         "(blendjax.models.mamba2)") from None
    return mamba2


def _step_without(mamba2, fault):
    """``mamba2.mix_step`` with the decay (``exp(dt A) = 1``) or the gated
    norm left out where the step is made; the sequence form keeps both."""
    import jax.numpy as jnp

    name, stand_in = {
        "decay_left_out": ("decay", lambda p, dt: jnp.ones_like(dt)),
        "gate_norm_left_out": ("rms_norm", lambda scale, y, eps: y),
    }[fault]
    real_step, real = mamba2.mix_step, getattr(mamba2, name)

    def mix_step(*args, **kwargs):
        setattr(mamba2, name, stand_in)
        try:
            return real_step(*args, **kwargs)
        finally:
            setattr(mamba2, name, real)

    return mix_step


def build_model(cfg, seed, fault=None):
    """(the seeded arrays, the served model over them)."""
    import jax
    import jax.numpy as jnp

    mamba2 = _mixer()
    from blendjax.models import seqformer
    from blendjax.serve.server import SeqFormerModel
    from chipbench import reference_granite4h

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    arrays = reference_granite4h.make_params(cfg, seed,
                                             dtypes[cfg["param_dtype"]])
    served = seqformer.describe_token_model(
        jax.tree.map(lambda x: x, arrays), cfg)
    if fault in ("decay_left_out", "gate_norm_left_out"):
        mamba2.mix_step = _step_without(mamba2, fault)  # before any trace
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
    _LIVE["model"] = model
    return arrays, model


_TENANT_EVERY_SLOT = hybrid._tenant_every_slot


def _tenant_every_slot_then_freeze(model, length, vocab, seed):
    """The set-up's last step, then its heap frozen (module docstring)."""
    _TENANT_EVERY_SLOT(model, length, vocab, seed)
    gc.collect()
    gc.freeze()


def run(ctx):
    mamba2 = _mixer()
    real_step = mamba2.mix_step
    mine = {"build_model": build_model, "compare": compare, "FAULTS": FAULTS,
            "_tenant_every_slot": _tenant_every_slot_then_freeze}
    theirs = {name: getattr(hybrid, name) for name in mine}
    for name, fn in mine.items():
        setattr(hybrid, name, fn)
    try:
        obs = hybrid.run(ctx)
    finally:
        _LIVE.clear()
        gc.unfreeze()
        for name, fn in theirs.items():
            setattr(hybrid, name, fn)
        mamba2.mix_step = real_step
    events, checks = obs["events"], obs["checks"]
    for name in ("state_gap_max", "state_left_after_reset"):
        checks.add(name, obs["notes"]["reply_gaps"][name])
    checks.add("state_resets_missing", events.get("serve_resets", 0)
               - events.get("serve_state_resets", 0))
    return obs


def _probe(model, cfg, traffic, seed):
    """(the ids of each probed slot, its Mamba-2 states ``(slots, H, P, N)``
    by layer read from the pool, the largest magnitude its Mamba-2 states
    and tails held once reset): seeded sequences admitted into seeded
    slots as the server admits an episode, then stepped together (module
    docstring)."""
    rng = np.random.default_rng((int(seed), 5))
    slots = rng.choice(model.slots, PROBE_SLOTS, replace=False)
    lengths = traffic["prefix_lengths"]
    seqs = [rng.integers(0, cfg["vocab_size"],
                         lengths[i % len(lengths)] + PROBE_STEPS,
                         dtype=np.int32) for i in range(PROBE_SLOTS)]
    for slot in slots:
        model.reset_rows(np.asarray([slot]))
    left = max(float(np.max(np.abs(np.asarray(leaf[slots], np.float32))))
               for name in ("ssd_s", "ssd_tail")
               for leaf in model._cache[name] if leaf is not None)
    for slot, ids in zip(slots, seqs):
        model.prefill_rows(np.asarray([slot]), ids[:-PROBE_STEPS, None])
    bucket = min(b for b in cfg["server"]["buckets"] if b >= PROBE_SLOTS)
    idx = np.full(bucket, model.pad_slot, np.int64)
    idx[:PROBE_SLOTS] = slots
    for back in range(PROBE_STEPS, 0, -1):
        obs = np.zeros((bucket, 1), np.int32)
        obs[:PROBE_SLOTS, 0] = [ids[-back] for ids in seqs]
        np.asarray(model.step_rows(idx, obs))
    states = [np.asarray(leaf[slots], np.float32).reshape(
        PROBE_SLOTS, cfg["mamba_n_heads"], cfg["mamba_d_head"],
        cfg["mamba_d_state"])
        for leaf in model._cache["ssd_s"] if leaf is not None]
    return seqs, states, left


def state_gap_max(got, want):
    """The largest distance of a head's state from the reference's, over
    the reference state's norm: ``got``, ``want`` (..., H, P, N)."""
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    num = np.sqrt(np.sum((got - want) ** 2, (-2, -1)))
    return float(np.max(num / np.sqrt(np.sum(want ** 2, (-2, -1)))))


def _state_gap(cfg, arrays, traffic, probe, control_quant):
    """``state_gap_max`` over the probed slots and every Mamba-2 layer."""
    import jax.numpy as jnp

    from chipbench import reference_granite4h as reference

    span = max(traffic["prefix_lengths"]) + traffic["steps_max"]
    worst = 0.0
    for i, seq in enumerate(probe[0]):
        ids = np.zeros(max(span, len(seq)), np.int32)
        ids[:len(seq)] = seq
        want = reference.ssd_states(arrays, cfg, jnp.asarray(ids), len(seq))
        got = ([layer[i] for layer in probe[1]] if not control_quant else
               reference.ssd_states(arrays, cfg, jnp.asarray(ids), len(seq),
                                    control_quant))
        worst = max(worst, state_gap_max(np.stack(got), np.stack(want)))
    return worst


def compare(cfg, arrays, traffic, seed, sample, control_quant):
    """The probe's ``state_gap_max`` (module docstring), then
    ``serve_tokens_hybrid.compare`` against this model's reference (as
    ``serve_tokens_linear.compare``, which names its own): one
    pass over each sampled episode's ids (padded to one length, which a
    causal model ignores), the reference's logits at the ids each served
    reply names, its logsumexp and its logits' standard deviation at every
    served position.  With ``control_quant`` the reference computed in
    that lower precision takes the served replies' place."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_granite4h as reference

    t0 = time.monotonic()
    # under the window's precision, whose programs are compiled: the sibling
    # driver has restored the process's own by now, and under that every
    # program would be compiled anew
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        probe = _probe(_LIVE.pop("model"), cfg, traffic, seed)
    gc.unfreeze()  # the window is over: the pool goes before the reference
    gc.collect()
    t1 = time.monotonic()
    state = {"state_gap_max": _state_gap(cfg, arrays, traffic, probe,
                                         control_quant),
             "state_left_after_reset": probe[2],
             # where the time after the window goes, for PERF.md
             "probe_s": t1 - t0, "state_reference_s": time.monotonic() - t1}
    nan = float("nan")
    if not sample:
        return {"logit_gap_p50": nan, "logit_gap_rms": nan,
                "logit_gap_max": nan, "lse_gap_max": nan, **state, "n": 0}
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
    return dict(gaps, **state, n=int(sum(len(r) for r in got)))
