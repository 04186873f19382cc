"""PolicyServer: continuous batching of ``step()`` over the DEALER wire.

ROADMAP #3 opens the system's third workload family (train -> replay ->
**serve**): production traffic means *inference*, and until now every
consumer owned its own model replica and stepped alone.  This module
puts ONE model behind the existing wire protocol and serves thousands
of concurrent episodes from it:

- **continuous batching** (the TPU-serving scheduling result,
  arXiv:2605.25645): an admission queue is drained every tick, pending
  ``step`` requests are padded to a **bucketed** batch size (XLA
  compiles once per bucket, not once per occupancy), ONE jitted model
  call serves the tick, and replies scatter back per client over the
  ROUTER socket.  A tick is launched (assembled, dispatched) and
  retired (fetched, answered) as two halves, and one launched tick is
  kept in flight: admission, the next launch and the older tick's
  replies run beside the device, not between its ticks;
- **KV-cache slot pool** for stateful world-model serving: every live
  episode holds a row in batched ``(S, ...)`` cache arrays, a slot
  allocator handles admission/eviction on episode end, and
  :func:`blendjax.models.seqformer.decode_step` runs with **per-row
  positions** (``init_cache(per_row=True)``) so one batched decode
  serves episodes at heterogeneous timesteps — parity with per-episode
  serial decode is the correctness bar (tests/test_serve.py).  The
  pool is served IN PLACE: donated to the jitted step and prefill,
  which write only the positions that changed
  (tests/test_serve_pool.py);
- **exactly-once RPCs**: every request carries a ``wire.BTMID_KEY``
  correlation id and a fault-policy retry re-sends the SAME id; the
  server answers a retried mutating request (``step``/``reset``/
  ``close``) from a bounded reply cache instead of decoding twice —
  the ``RemoteControlledAgent`` reply-cache pattern pointed at
  inference.  A duplicate of a request still *queued* is dropped at
  admission (the original's reply answers both);
- an ``--int8`` path serves the model through
  :func:`blendjax.ops.quant.quantize_seqformer` /
  :func:`~blendjax.ops.quant.quantize_policy` — the same model code,
  int8 weights;
- the house telemetry vocabulary end-to-end: ``SERVE_EVENTS`` counters,
  ``SERVE_STAGES`` (queue_wait / batch_assemble / compute / reply)
  with latency histograms via :class:`~blendjax.utils.timing.StageTimer`,
  a ``telemetry`` RPC in the TelemetryHub merge shape (remote scrape
  like ``ReplayShard``), and trace spans riding ``BTMID_KEY``.

Run a server as a process (the ``--model linear`` stand-in is jax-free
and fast-starting, so chaos tests SIGKILL/respawn it cheaply)::

    python -m blendjax.serve.server --address tcp://127.0.0.1:24000 \
        --model seqformer --seed 0 --obs-dim 8 --slots 64 --length 128

or in-process via :func:`start_server_thread`, or supervised via
:class:`ServerProcess` (a launcher-compatible surface, so
:class:`~blendjax.btt.watchdog.FleetWatchdog` respawns a dead server
and clients resume after ``reset()``).

See docs/serving.md.
"""

from __future__ import annotations

import argparse
import logging
import os
import signal
import subprocess
import sys
import threading
import time
from collections import OrderedDict, deque, namedtuple

import numpy as np

from blendjax import wire
from blendjax.btt import shm_rpc
from blendjax.obs.spans import make_span, now_us
from blendjax.utils.timing import StageTimer, fleet_counters, span

logger = logging.getLogger("blendjax")

#: Commands whose replies enter the exactly-once reply cache (they
#: mutate episode state — a retry must NOT re-execute them).
MUTATING_CMDS = ("step", "reset", "close")

#: Idle horizon after which a STATELESS episode leaves the admission
#: window's live-count (window *targeting* only — stateless steps are
#: never refused).  A client idle this long is not co-arriving within a
#: millisecond tick window, and without decay every crashed consumer
#: would inflate the target until every batch waits out its full
#: ``tick_ms``.  Stateful servers use ``slot_ttl_s`` eviction instead.
STATELESS_TTL_S = 30.0

#: Default bound on the reply cache.  Each client keeps at most one RPC
#: outstanding (ServeClient is blocking), so the cache must cover the
#: retry window of roughly the live client count — 1024 replies of a
#: few hundred bytes is comfortably larger than any sane fleet while
#: bounding server memory.
REPLY_CACHE_DEPTH = 1024


def drain_socket(recv, handle, counters, who, what):
    """Drain every message currently on a socket: ``recv()`` (NOBLOCK)
    until ``zmq.Again``, dispatching each to ``handle``.  One copy of
    the survival discipline the serve tier's three receive loops share
    (server front, gateway front, gateway replica backends): a closed
    socket propagates (the serve loop shuts down cleanly), an
    UNDECODABLE frame (garbling proxy, rogue peer) is dropped and
    counted — never fatal; the frames are consumed and the sender's
    retry re-sends intact bytes.  The same contract covers ``handle``:
    a malformed-but-decodable message (e.g. an unhashable correlation
    id — the wire is pickle, a rogue peer can send anything) must cost
    that message, not the serving thread."""
    import zmq

    while True:
        try:
            out = recv()
        except zmq.Again:
            return
        except zmq.ZMQError:
            raise  # socket closed: the outer loop shuts down
        except Exception as exc:  # noqa: BLE001 - the tier survives
            counters.incr("serve_errors")
            logger.warning(
                "%s: undecodable %s dropped (%s: %s)",
                who, what, type(exc).__name__, exc,
            )
            continue
        try:
            handle(out)
        except zmq.ZMQError:
            raise  # socket closed mid-handle: clean shutdown
        except Exception:  # noqa: BLE001 - the tier survives
            counters.incr("serve_errors")
            logger.exception("%s: handling a %s failed (dropped)",
                             who, what)


def _check_tree_like(cur, new, what):
    """WeightBus apply guard: a snapshot must match the served params'
    STRUCTURE and per-leaf shapes before it replaces them — adopting a
    drifted tree would destroy the last good weights and leave every
    subsequent jitted call failing, the exact outage the 'refused
    snapshots keep serving the last good version' contract forbids."""
    import jax

    cur_leaves, cur_def = jax.tree.flatten(cur)
    new_leaves, new_def = jax.tree.flatten(new)
    if cur_def != new_def:
        raise ValueError(
            f"published {what} snapshot structure does not match the "
            f"served params ({new_def} != {cur_def})"
        )
    for c, n in zip(cur_leaves, new_leaves):
        if tuple(np.shape(c)) != tuple(np.shape(n)):
            raise ValueError(
                f"published {what} snapshot leaf shape {np.shape(n)} "
                f"!= served {np.shape(c)}"
            )


def default_buckets(max_batch):
    """Powers of two up to ``max_batch`` (inclusive as the cap): each
    bucket is one XLA compilation, so requests pad to the next bucket
    instead of compiling per occupancy."""
    out = []
    b = 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return tuple(out)


# ---------------------------------------------------------------------------
# served models
# ---------------------------------------------------------------------------


class LinearModel:
    """Jax-free stateful stand-in: ``pred = obs @ W + pos`` with a
    per-slot position counter.  Deterministic from ``seed`` (a
    respawned process rebuilds the same weights), position-sensitive
    (a double-applied step shifts every later prediction, so
    exactly-once violations are *visible*), and import-cheap — the
    chaos tests SIGKILL/respawn servers of this model in well under a
    second.

    ``work_us`` adds a sleep-based per-ROW model-compute stand-in to
    ``step_rows`` (the same disclosed pattern as the RL bench's
    ``physics_us``): the gateway scale-out bench needs replicas whose
    per-request cost is real enough to be the bottleneck, without
    spinning CPU the 2-core CI box does not have.  Zero (the default)
    is byte-identical to the pre-knob model."""

    kind = "linear"

    def __init__(self, obs_dim=8, out_dim=None, slots=16, seed=0,
                 work_us=0):
        self.obs_dim = int(obs_dim)
        self.out_dim = int(out_dim or obs_dim)
        self.slots = int(slots)
        self.work_us = float(work_us)
        rng = np.random.default_rng(seed)
        self.w = rng.standard_normal(
            (self.obs_dim, self.out_dim)
        ).astype(np.float32)
        # +1: the pad row batched ticks scatter their padding into
        self.pos = np.zeros(self.slots + 1, np.int64)
        self.pad_slot = self.slots

    def apply_weights(self, tree):
        """WeightBus hot-swap: replace ``w`` from a published
        ``{"w": (obs_dim, out_dim)}`` tree.  Positions (the per-slot
        KV-cache stand-in) are untouched — live episodes continue at
        their timestep under the new weights."""
        w = np.asarray(tree["w"], np.float32)
        if w.shape != self.w.shape:
            raise ValueError(
                f"published w shape {w.shape} != served {self.w.shape}"
            )
        self.w = w

    def reset_rows(self, idx):
        self.pos[idx] = 0

    def step_rows(self, idx, obs):
        if self.work_us:
            # per-row cost: batching does not amortize model compute
            # away (a batched decode's FLOPs scale with occupancy)
            time.sleep(len(idx) * self.work_us / 1e6)
        pred = obs.astype(np.float32) @ self.w \
            + self.pos[idx, None].astype(np.float32)
        self.pos[idx] += 1
        return pred

    def prefill_rows(self, idx, prefix):
        """Admit a T-step prefix in one pass: the slot's position jumps
        to T and the return is the prediction the T'th serial step would
        have produced — the jax-free analogue of the seqformer's batched
        prefill, so gateway/prefill plumbing tests run without jax."""
        t = prefix.shape[0]
        self.pos[idx] = t
        return prefix[-1].astype(np.float32) @ self.w + np.float32(t - 1)


class PolicyModel:
    """Stateless MLP policy serving (:mod:`blendjax.models.policy`):
    one jitted ``logits`` per bucket, greedy (argmax) actions — the
    deterministic serving convention.  ``int8=True`` serves
    :func:`~blendjax.ops.quant.quantize_policy` output through the same
    ``logits`` body (per-weight-dict dispatch)."""

    kind = "policy"
    slots = 0  # stateless: no cache rows, reset is an accounting no-op
    pad_slot = 0

    def __init__(self, params, obs_dim, int8=False):
        import jax

        from blendjax.models import policy

        if int8:
            from blendjax.ops.quant import quantize_policy

            params = quantize_policy(params)
        self.params = params
        self.obs_dim = int(obs_dim)
        self.int8 = bool(int8)
        self._logits = jax.jit(policy.logits)

    def apply_weights(self, tree):
        """WeightBus hot-swap: adopt a published policy pytree (float,
        or ``quantize_policy`` output when this server is ``--int8`` —
        ``policy.logits`` dispatches per weight dict either way)."""
        import jax
        import jax.numpy as jnp

        if self.int8 and not any(
            "w_q" in lay for lay in tree.get("layers", [{}])
        ):
            raise ValueError(
                "int8 policy server got a float snapshot; publish with "
                "quantize='policy' (or serve float)"
            )
        _check_tree_like(self.params, tree, "policy")
        self.params = jax.tree.map(jnp.asarray, tree)

    def reset_rows(self, idx):
        pass

    def step_rows(self, idx, obs):
        return np.asarray(self._logits(self.params, obs))


#: a token model's reply row: this many top logits, their ids, and the
#: logsumexp over the vocabulary slice (2 * TOKEN_REPLY_TOP + 1 numbers)
TOKEN_REPLY_TOP = 8

#: the three counts a routed (held-share) model's step returns, in
#: ``moe_apply_held``'s order; all in ``SERVE_EVENTS``
MOE_EVENTS = ("serve_moe_assignments", "serve_moe_assignments_held",
              "serve_moe_experts_hit")


#: the counts a step of a model of mixed layer kinds returns, in
#: ``seqformer._HybridStep.counts``' order (a model without window layers
#: counts 0 window positions; a routed one returns them after
#: ``MOE_EVENTS``), the one its resets make (rows whose recurrent state
#: was zeroed: a model without recurrent state makes none), and the bytes
#: of recurrent state and
#: convolution tails its steps' real rows read and wrote (the rows
#: stepped times twice ``seqformer.state_row_bytes``, added where the
#: step's counts are fetched); all in ``SERVE_EVENTS``
HYBRID_EVENTS = ("serve_ctx_positions", "serve_rows_stepped",
                 "serve_window_positions", "serve_state_resets",
                 "serve_state_bytes")


#: The phase clock's phases (``_PhaseClock``), each a counter in
#: ``SERVE_EVENTS`` in microseconds: exclusive, and together the serve
#: loop's wall time (docs/serving.md "The phase clock").  The last,
#: ``serve_loop_us``, is the loop's own code between the others.
SERVE_PHASES = ("serve_idle_us", "serve_poll_us", "serve_slice_us",
                "serve_admit_us", "serve_prefill_dispatch_us",
                "serve_assemble_us", "serve_dispatch_us",
                "serve_fetch_wait_us", "serve_reply_us", "serve_weights_us",
                "serve_loop_us")
(_IDLE, _POLL, _SLICE, _ADMIT, _PREFILL, _ASSEMBLE, _DISPATCH, _FETCH,
 _REPLY, _WEIGHTS, _LOOP) = SERVE_PHASES
#: the phases in which the thread waits on the clients
_WAITS = frozenset((_IDLE, _POLL))


class _PhaseClock:
    """``PolicyServer``'s thread's wall time cut into the exclusive
    :data:`SERVE_PHASES`.  One ``perf_counter_ns`` a switch: the time
    since the last switch goes to the phase being left, and to
    ``serve_drained_us`` (and, for a wait on the clients,
    ``serve_drained_wait_us``) where nothing was launched through it,
    which ``drained()`` says at each switch (a fetch never is: the entry
    being fetched was launched).  The nanoseconds are pushed into the
    counters as whole microseconds, the rest carried, at the first
    switch a millisecond after the last push (so a phase of a
    millisecond or more is in the counters when it ends) and at
    :meth:`settle`: the phases add up to the wall time however short
    they are.  Used by one thread; :meth:`__call__` makes a phase a
    ``with`` block and the span of its name."""

    __slots__ = ("_counters", "_drained", "_ns", "_pushed", "_t", "_empty",
                 "phase")

    def __init__(self, counters, drained):
        self._counters, self._drained = counters, drained
        # counter -> nanoseconds not yet pushed
        self._ns = dict.fromkeys(
            SERVE_PHASES + ("serve_drained_us", "serve_drained_wait_us"), 0)
        self.restart()

    def restart(self):
        """Count from now, in the loop's own phase."""
        self.phase = _LOOP
        self._empty = self._drained()
        self._t = self._pushed = time.perf_counter_ns()

    def switch(self, phase):
        """Enter ``phase``; returns the phase left."""
        now = time.perf_counter_ns()
        ns, self._t = now - self._t, now
        left, acc = self.phase, self._ns
        acc[left] += ns
        if self._empty:
            acc["serve_drained_us"] += ns
            if left in _WAITS:
                acc["serve_drained_wait_us"] += ns
        if now - self._pushed >= 1_000_000:
            self._push(now)
        self.phase = phase
        self._empty = phase != _FETCH and self._drained()
        return left

    def _push(self, now):
        self._pushed = now
        acc, due = self._ns, []
        for name, ns in acc.items():
            if ns >= 1000:
                us, acc[name] = divmod(ns, 1000)
                due.append((name, us))
        if due:
            self._counters.incr_many(due)

    def settle(self):
        """Push everything counted up to now (before a snapshot)."""
        self.switch(self.phase)
        self._push(self._t)

    def __call__(self, phase, name=None, **args):
        """``with clock(phase, name, **args):`` the block is ``phase``
        (the enclosing one resumes after it) and, with a ``name``, the
        :func:`~blendjax.utils.timing.span` of that name."""
        return _Phase(self, phase, None if name is None else span(
            name, **args))


class _Phase:
    __slots__ = ("_clock", "_phase", "_span", "_left")

    def __init__(self, clock, phase, span_):
        self._clock, self._phase, self._span = clock, phase, span_

    def __enter__(self):
        if self._span is not None:
            self._span.__enter__()
        self._left = self._clock.switch(self._phase)
        return self._span

    def __exit__(self, *exc):
        self._clock.switch(self._left)
        if self._span is not None:
            self._span.__exit__(*exc)
        return False


class SlotPoolLost(RuntimeError):
    """A donated call failed after it had taken the slot pool: the
    model holds a fresh, EMPTY pool and every lease on it is void (the
    server drops them, so their clients ``reset()`` and resume)."""


class _Reply:
    """What :meth:`SeqFormerModel.step_rows` and
    :meth:`~SeqFormerModel.prefill_reply` return: the reply rows of
    a call that has been dispatched and not waited for.  ``np.asarray``
    / ``np.array`` of it (or an index into it) is the fetch: it waits
    for the device, banks a routed model's counts and hands out the
    rows; :meth:`is_ready` says whether that would wait.  Fetched once,
    it keeps the rows."""

    __slots__ = ("_model", "_what", "_out", "_rebuilds", "_rows")

    def __init__(self, model, what, out):
        self._model, self._what, self._out = model, what, out
        self._rebuilds = model.pool_rebuilds
        self._rows = None

    def is_ready(self):
        import jax

        return self._rows is not None or all(
            leaf.is_ready() for leaf in jax.tree.leaves(self._out))

    def __array__(self, dtype=None, copy=None):
        if self._rows is None:
            self._rows = self._model._fetch(self._what, self._out,
                                            self._rebuilds)
            self._out = None
        rows = self._rows if dtype is None else self._rows.astype(
            dtype, copy=False)
        return rows.copy() if copy else rows

    def __getitem__(self, key):
        return np.asarray(self)[key]


def _reply_ready(reply):
    """Whether a model's ``step_rows`` or prefill reply can be fetched
    without waiting: rows a model computed on the host (an array) always
    can."""
    ready = getattr(reply, "is_ready", None)
    return ready is None or ready()


class SeqFormerModel:
    """Stateful world-model serving: a slot pool of batched KV caches
    (``init_cache(per_row=True)``) over ``slots + 1`` rows — the extra
    row absorbs batch padding writes — served IN PLACE: the jitted step
    and the jitted prefill take the pool donated, write only the
    positions that changed (``decode_step(slots=idx)``: one K/V
    position per layer and row; the prefill: one row's kept positions)
    and hand the same buffers back, one compilation per bucket size or
    prefix length.  Nothing else may hold the pool's arrays: a donated
    call deletes them.

    **What is in flight when.**  ``step_rows`` dispatches and returns a
    :class:`_Reply` without waiting: the pool is rebound from the
    call's result at once, so a second ``step_rows``, a ``reset_rows``
    or a ``prefill_rows`` made before the reply is fetched runs behind
    it on the device, in the order the calls were made, and the caller
    decides when to wait (``np.asarray(reply)``; ``reply.is_ready()``).
    ``prefill_reply`` hands a prefill out the same way;
    ``prefill_rows`` is the same call waiting for its own reply (and so
    for every step dispatched before it).

    Everything that can refuse a call (shapes, lengths, a first
    compilation) fails BEFORE the pool is donated and leaves it as it
    was.  A call that fails after that, at its dispatch or at its
    fetch, raises :class:`SlotPoolLost` over a rebuilt, empty pool
    (``pool_rebuilds`` counts them; one lost pool is one rebuild,
    however many replies were still to be fetched from it).

    ``int8=True`` serves :func:`~blendjax.ops.quant.quantize_seqformer`
    output — ``decode_step`` already dispatches per weight dict, so the
    same serving code runs both precisions."""

    kind = "seqformer"

    def __init__(self, params, slots, length, *, window=None,
                 compute_dtype=None, cache_dtype=None, int8=False):
        import jax
        import jax.numpy as jnp

        from blendjax.models import seqformer

        if int8:
            from blendjax.ops.quant import quantize_seqformer

            params = quantize_seqformer(params)
        self.params = params
        self.slots = int(slots)
        self.length = int(length)
        self.window = window
        self.int8 = bool(int8)
        self.pad_slot = self.slots
        self.pool_rebuilds = 0
        emb = params["embed"]
        # what a request row is and what a reply row is come from the
        # model: observations (float32, the projection's width) answered
        # by a prediction, or one int32 token id answered by
        # TOKEN_REPLY_TOP logits, their ids and the logsumexp
        self.tokens = "table" in emb
        if self.tokens:
            self.obs_dim, self.obs_dtype = 1, np.int32
        else:
            self.obs_dim = (emb["w"] if "w" in emb else emb["w_q"]).shape[0]
            self.obs_dtype = np.float32
        routed = any("route" in blk.get("moe", ()) for blk in params["blocks"])
        hybrid = seqformer._hybrid(params)
        # recurrent state in the pool: a reset zeroes it (and counts it)
        self._recurrent = seqformer._recurrent(params)
        if window is not None and seqformer._latent(params):
            raise ValueError("latent attention has no windowed path")
        if window is not None and hybrid:
            raise ValueError("a model of mixed layer kinds takes its "
                             "windows from its description")
        # the counts a step returns beside its reply: the held-share
        # layers' (their aux entry `counts`, summed over layers), then a
        # hybrid step's (`live`), under these names in this order
        kinds = (("counts",) if routed else ()) + (("live",) if hybrid
                                                   else ())
        self._step_events = ((MOE_EVENTS if routed else ())
                             + (HYBRID_EVENTS[:3] if hybrid else ()))
        counted = bool(kinds)
        self._events = {}
        cdt = compute_dtype or jnp.float32
        self._cache_dtype = cache_dtype or cdt
        self._jnp = jnp
        self._cache = self._new_pool()
        # what a step reads and writes of each row's recurrent state
        self._state_row_bytes = 2 * seqformer.state_row_bytes(self._cache)
        pad = self.pad_slot

        def reply_row(pred):
            """What goes on the wire for one prediction row."""
            if not self.tokens:
                return pred
            # a vocabulary-wide row a step would make the wire the
            # bottleneck: the top logits, their ids (exact in float32)
            # and the logsumexp over the slice, computed here
            top, ids = jax.lax.top_k(pred, TOKEN_REPLY_TOP)
            lse = jax.nn.logsumexp(pred, axis=-1, keepdims=True)
            return jnp.concatenate(
                [top, ids.astype(jnp.float32), lse], axis=-1)

        # the functions' names and scopes are what a profiler trace
        # and the compile log show: keep them (PERF.md section 3).
        # `gather` (the read of the stepped rows) and `scatter` (the
        # one-position write) are decode_step's own, inside `decode`;
        # padding duplicates in idx all land on the pad row, whose
        # contents are never read
        def serve_step(params, cache, idx, obs):
            with jax.named_scope("decode"):
                if self.tokens:
                    obs = obs[:, 0]
                pred, cache, auxs = seqformer._decode(
                    params, cache, obs, compute_dtype=cdt, window=window,
                    slots=idx, valid=(idx != pad) if counted else None,
                )
            if counted:
                # the model's counts over the real rows: they ride the
                # reply's fence
                row = reply_row(pred)
                counts = [sum(a[kind] for a in auxs if kind in a)
                          for kind in kinds]
                return (row, counts[0] if len(counts) == 1
                        else jnp.concatenate(counts)), cache
            return reply_row(pred), cache

        # one compilation per (bucket,) shape — the bucket/recompile
        # tradeoff the admission queue pads for
        self._step = jax.jit(serve_step, donate_argnums=(1,))

        def serve_prefill(params, cache, row, prefix):
            # ONE teacher-forced pass fills the slot's cache rows
            # (seqformer.prefill, rollout()'s prefill phase too) instead
            # of T serial decode_steps; its `forward` and `scatter`
            # scopes are what a trace shows.  The pool is donated, so
            # the kept positions of the one row are written in place.
            preds, cache = seqformer.prefill(
                params, cache,
                prefix[:, 0][None] if self.tokens else prefix[None], row,
                compute_dtype=cdt, window=window, last_only=self.tokens,
            )
            return reply_row(preds[0, -1]), cache

        # one compilation per prefix LENGTH (prefix rows are real
        # observations — padding them would write fabricated positions
        # into the cache, so lengths are not bucketed)
        self._prefill = jax.jit(serve_prefill, donate_argnums=(1,))
        # a rewind moves `pos` and zeroes what recurrent state there is,
        # in place on the donated pool like every other call on it
        self._rewind = jax.jit(seqformer.rewind_rows, donate_argnums=(0,))

    def _new_pool(self):
        from blendjax.models import seqformer

        return seqformer.init_cache(
            self.params, self.slots + 1, dtype=self._cache_dtype,
            length=self.length, per_row=True,
        )

    def _in_place(self, fn, what, idx, arr):
        """Dispatch ``fn`` (``self._step`` / ``self._prefill``) over the
        donated pool, rebind the pool from the result and start the
        reply's copy to the host.  Nothing here waits for the device:
        the returned :class:`_Reply` fetches when it is asked to.  The
        pool chains through the calls' own data dependencies, so
        whatever is dispatched behind this call (a second step, a
        rewind, a prefill) runs after it on the device.

        A dispatch that fails and finds the buffers it was given
        deleted (the call took them) costs the pool: it is rebuilt
        empty and :class:`SlotPoolLost` raised; so does a fetch that
        fails (:meth:`_fetch`)."""
        import jax

        pool = self._cache
        try:
            with span(f"serve.{what}.dispatch"):
                out, self._cache = fn(
                    self.params, pool, self._jnp.asarray(idx),
                    self._jnp.asarray(arr),
                )
                for leaf in jax.tree.leaves(out):
                    leaf.copy_to_host_async()
        except Exception as exc:
            if not any(leaf.is_deleted() for leaf in jax.tree.leaves(pool)):
                raise  # refused before donation: the pool is intact
            self._write_off(what, exc)
        return _Reply(self, what, out)

    def _fetch(self, what, out, rebuilds):
        """Wait for a dispatched call's reply and bring it to the host
        (a routed model's counts come over with it and are banked for
        :meth:`drain_events`).  A failure that surfaces here finds the
        pool already handed on to whatever was dispatched behind the
        call, so the pool is lost whatever holds it now: once per pool
        (``rebuilds`` is ``pool_rebuilds`` as the call was dispatched),
        a later reply off the same lost pool re-raises as it failed."""
        import jax

        try:
            with span(f"serve.{what}.fence"):
                if not isinstance(out, tuple):
                    return np.asarray(out)
                pred, counts = jax.device_get(out)
        except Exception as exc:
            if self.pool_rebuilds != rebuilds:
                raise  # that pool was written off already
            self._write_off(what, exc)
        for name, n in zip(self._step_events, counts):
            self._events[name] = self._events.get(name, 0) + int(n)
            if name == "serve_rows_stepped":
                self._events[HYBRID_EVENTS[4]] = self._events.get(
                    HYBRID_EVENTS[4], 0) + int(n) * self._state_row_bytes
        return pred

    def _write_off(self, what, exc):
        self._cache = self._new_pool()
        self.pool_rebuilds += 1
        raise SlotPoolLost(
            f"the {what} failed after the slot pool was donated "
            f"({type(exc).__name__}: {exc}); pool rebuilt empty"
        ) from exc

    def prefill_reply(self, idx, prefix):
        """Admit a T-step observation prefix into slot ``idx`` with one
        teacher-forced batched pass (vs T serial ``decode_step``s —
        parity within 1e-5, tests/test_serve.py), dispatched and not
        waited for.  The :class:`_Reply` returned fetches the prediction
        for position T (what the T'th serial step would have returned);
        the slot's next ``step`` decodes at position T, and one
        dispatched before the fetch runs behind the prefill."""
        if np.ndim(prefix) != 2 or np.shape(prefix)[1] != self.obs_dim:
            raise ValueError(
                f"prefix shape {np.shape(prefix)} != (T, {self.obs_dim})"
            )
        t0 = int(prefix.shape[0])
        if t0 > self.length:
            # the teacher-forced pass attends the WHOLE prefix; serial
            # decode through a ring of `length` slots would only see
            # the last `length` (or `window`) positions — refuse the
            # configs where the two paths cannot agree
            if self.window is None or self.window > self.length:
                raise ValueError(
                    f"prefix of {t0} steps exceeds the {self.length}-slot "
                    "cache ring (and no window bounds attention): raise "
                    "length= or serve a windowed model"
                )
        if "pos" in self.params and t0 > self.params["pos"].shape[0]:
            raise ValueError(
                f"prefix of {t0} steps exceeds the learned position "
                f"table ({self.params['pos'].shape[0]}); use "
                "pos_encoding='rope' for longer prefixes"
            )
        return self._in_place(self._prefill, "prefill", idx, prefix)

    def prefill_rows(self, idx, prefix):
        """:meth:`prefill_reply`, fenced where it is made: the
        prediction for position T as an array."""
        return np.asarray(self.prefill_reply(idx, prefix))

    def apply_weights(self, tree):
        """WeightBus hot-swap: adopt a published seqformer pytree (the
        precision this server was built for — float, or
        ``quantize_seqformer`` output under ``--int8``).  The KV-cache
        slot pool is untouched: live episodes keep their rows, leases
        and positions, and the next tick decodes them under the new
        weights (the standard online-learning semantics — the cache
        holds the OLD weights' keys/values until positions ring past
        them, exactly as a learner's own rollout cache would)."""
        import jax
        import jax.numpy as jnp

        emb = tree.get("embed", {})
        if self.int8 != ("w_q" in emb):
            raise ValueError(
                "published snapshot precision (int8=%s) != served "
                "precision (int8=%s); align the publisher's quantize= "
                "with the server's --int8" % ("w_q" in emb, self.int8)
            )
        _check_tree_like(self.params, tree, "seqformer")
        self.params = jax.tree.map(jnp.asarray, tree)

    def drain_events(self):
        """Counts the model's steps and resets made since the last call
        (the routed layers' ``MOE_EVENTS``, a hybrid model's
        ``HYBRID_EVENTS``, or both), for the server's counters."""
        events, self._events = self._events, {}
        return events

    def reset_rows(self, idx):
        with span("serve.reset_rows"):
            self._cache = self._rewind(self._cache, self._jnp.asarray(idx))
        if self._recurrent:
            name = HYBRID_EVENTS[3]
            self._events[name] = self._events.get(name, 0) + len(idx)

    def step_rows(self, idx, obs):
        """Dispatch one decode step of rows ``idx``; the :class:`_Reply`
        returned is fetched by whoever needs the rows."""
        if np.shape(obs) != (len(idx), self.obs_dim):
            raise ValueError(
                f"obs shape {np.shape(obs)} != ({len(idx)}, {self.obs_dim})"
            )
        return self._in_place(self._step, "step", idx, obs)


# ---------------------------------------------------------------------------
# the server
# ---------------------------------------------------------------------------


class _Pending:
    __slots__ = ("ident", "mid", "msg", "t_enq", "span_trace", "t0_us",
                 "mstate")

    def __init__(self, ident, mid, msg, span_trace, t0_us, mstate):
        self.ident = ident
        self.mid = mid
        self.msg = msg
        self.t_enq = time.perf_counter()
        self.span_trace = span_trace
        self.t0_us = t0_us
        self.mstate = mstate


#: One tick between its two halves: assembled and dispatched by
#: ``PolicyServer._launch``, not yet fetched and answered by ``_retire``.
#: ``batch`` holds ``(entry, slot, obs)`` per real row, ``reply`` what
#: the model's ``step_rows`` returned, ``compute_s`` the host's time
#: inside the model call so far (its dispatch).
_Launched = namedtuple(
    "_Launched", "state batch reply bucket pos_before compute_s")


class _Prefilling:
    """One ``reset``'s prefill between its dispatch (``PolicyServer.
    _prefill``) and the fetch that answers the reset (``_retire``): it
    rides in ``_launched`` among the ticks, in dispatch order, and in
    ``_pending`` under the reset's message id, so that a retry meeting
    it in flight re-points ``ident`` and runs nothing.  ``behind`` says
    whether anything launched was still unfetched at its dispatch,
    ``prefill_s`` is the thread's time in this prefill so far."""

    __slots__ = ("state", "reply", "slot", "episode", "length", "behind",
                 "prefill_s", "ident", "msg", "t0_us")

    def __init__(self, state, reply, slot, episode, length, behind,
                 prefill_s):
        self.state, self.reply = state, reply
        self.slot, self.episode, self.length = slot, episode, length
        self.behind, self.prefill_s = behind, prefill_s
        self.ident = self.msg = self.t0_us = None


class _ModelState:
    """One hosted model's serving state: its slot pool (or stateless
    episode registry) — multi-model servers keep one per model id, so
    one model's slot exhaustion can never deny another's resets."""

    __slots__ = ("mid", "model", "free", "live", "stateless_eps",
                 "weight_version")

    def __init__(self, mid, model):
        self.mid = mid
        self.model = model
        self.free = list(range(model.slots))
        # slot -> [episode lease id, monotonic last-use]
        self.live = {}
        # stateless: episode id -> monotonic last-use
        self.stateless_eps = {}
        # WeightBus version THIS model serves (None until its first
        # adopted snapshot) — replies are stamped per executing model,
        # so a co-hosted model the bus never updated is not reported
        # at another model's version
        self.weight_version = None


class PolicyServer:
    """One served model behind a ROUTER socket (continuous batching).

    **What is in flight when.**  One thread runs one loop
    (:meth:`serve_forever`).  A tick's launch dispatches the model call
    without waiting for it; between two turns of the loop at most ONE
    launched tick is outstanding (two inside a turn: the follower is
    dispatched, then the older one retired).  A ``reset``'s prefill is
    launched the same way, where the reset is admitted: dispatched
    behind whatever is in flight, it rides among the launched ticks in
    dispatch order (any number of prefills beside the one tick) and the
    reset is answered where the prefill is retired.  While they run on
    the device the loop admits requests, and by what it can observe
    (is anything queued, can anybody still send, is the oldest launched
    entry ready) it retires that entry, or launches the next tick
    behind it first.  A lone client, an empty queue, or a model that
    computes on the host sees launch-then-retire: the tick, and the
    reset answered at once, as they always were.  Nothing is in flight
    when weights are swapped (a staged snapshot retires what is
    launched first), and a step or a prefilling reset stays pending
    (deduplicated) until its reply has been sent.

    Params
    ------
    address: str
        Endpoint to bind (``tcp://host:*`` binds an ephemeral port;
        resolved endpoint on :attr:`address`).
    model:
        A served-model adapter (:class:`LinearModel`,
        :class:`PolicyModel`, :class:`SeqFormerModel`): ``kind``,
        ``obs_dim``, ``slots`` (0 = stateless), ``pad_slot``,
        ``reset_rows(idx)``, ``step_rows(idx, obs)`` (and optionally
        ``prefill_rows(idx, prefix)``) — OR a ``{model_id: adapter}``
        dict to host several models behind one socket (**multi-model
        routing**): requests carry ``model`` in the envelope, each
        model keeps its OWN slot pool and its own jitted bucket cache,
        and a tick batches one model's requests (requests without a
        ``model`` key go to the first/default model, so a single-model
        workload against a multi-model server is byte-identical to a
        single-model server — test-locked).
    tick_ms: float
        Admission window once the queue is non-empty and nothing is
        launched: how long one tick waits for more arrivals before
        computing (latency it trades for batch occupancy).  With a
        tick launched, the device's own tick is the window.
    max_batch: int
        Largest bucket (and the most requests one tick serves).
    buckets: tuple | None
        Pad-to sizes (one XLA compilation each); default powers of two
        up to ``max_batch``.
    slot_ttl_s: float | None
        Idle-slot eviction horizon: a ``reset`` finding no free slot
        reclaims slots idle longer than this (None = never evict, the
        reset is denied instead).
    subscriber: blendjax.weights.WeightSubscriber | None
        WeightBus subscription (docs/weight_bus.md): polled from the
        serve loop — a complete, digest-verified snapshot is staged
        off-tick and hot-swapped into the hosted model **between
        ticks** (KV-cache slots, leases and in-flight exactly-once
        retries survive; a torn snapshot is discarded and the last
        good version keeps serving).  Every reply is stamped with
        ``weight_version`` once a snapshot has been adopted.
    """

    def __init__(self, address, model, *, tick_ms=2.0,
                 max_batch=64, buckets=None, slot_ttl_s=None,
                 reply_cache_depth=REPLY_CACHE_DEPTH, counters=None,
                 timer=None, context=None, shm_base=None,
                 subscriber=None):
        import zmq

        if isinstance(model, dict):
            if not model:
                raise ValueError("multi-model server needs >= 1 model")
            self._models = {
                str(k): _ModelState(str(k), m) for k, m in model.items()
            }
        else:
            # single adapter: hosted under its kind (what a multi-model
            # dict hosting just this model would naturally be keyed by)
            self._models = {model.kind: _ModelState(model.kind, model)}
        self._default_id = next(iter(self._models))
        #: where the hosted models compute, reported by ``hello`` and
        #: the telemetry scrape: the jax backend's platform/kind/count,
        #: or all-None for a jax-free (linear-only) server
        if all(isinstance(st.model, LinearModel)
               for st in self._models.values()):
            self._device = {"platform": None, "device_kind": None,
                            "device_count": 0}
        else:
            from blendjax.utils.device import device_info

            self._device = device_info()
        self.tick_ms = float(tick_ms)
        self.buckets = tuple(sorted(
            int(b) for b in (buckets or default_buckets(int(max_batch)))
        ))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError(f"buckets must be positive: {self.buckets}")
        # the largest bucket IS the most requests one tick can pad to —
        # a max_batch beyond it would index past the padded arrays
        self.max_batch = min(int(max_batch), self.buckets[-1])
        self.slot_ttl_s = slot_ttl_s
        self.counters = counters if counters is not None else fleet_counters
        self.timer = timer if timer is not None else StageTimer()
        self._reply_cache = OrderedDict()
        self._reply_cache_depth = int(reply_cache_depth)
        self._queue = deque()
        # mid -> _Pending (a step queued or launched) or _Prefilling (a
        # reset whose prefill is launched) not answered yet (dedupe): an
        # entry leaves when its reply has entered the reply cache
        self._pending = {}
        # ticks (_Launched) and prefills (_Prefilling) dispatched and not
        # yet answered, in dispatch order: at most one TICK between two
        # turns of the serve loop, two inside a turn
        self._launched = deque()
        self._clock = _PhaseClock(self.counters,
                                  lambda: not self._launched)
        # Slot pools live per hosted model (:class:`_ModelState`):
        # ``live`` maps slot -> [episode lease id, monotonic last-use].
        # The lease id disambiguates slot REUSE: an evicted episode's
        # client still holds the slot number, and without the lease its
        # next step would silently advance the new tenant's cache row.
        # Stateless models have no slot pool, but the admission window
        # still needs a live-episode count for its early exit (a
        # blocking client keeps one step in flight, so waiting past
        # that count is pure latency): ``stateless_eps`` maps episode
        # id -> last monotonic use, touched by reset AND step (so a
        # client that resumed past a server restart re-registers),
        # pruned after STATELESS_TTL_S idle (a crashed client must not
        # inflate the window target forever — state*ful* slots decay
        # via slot_ttl_s eviction, this is the stateless analogue).
        # The episode-lease sequence is server-GLOBAL, so no two hosted
        # models can ever hand out the same lease id.
        self._episode_seq = 0
        self._ctx = context or zmq.Context.instance()
        self._sock = self._ctx.socket(zmq.ROUTER)
        self._sock.setsockopt(zmq.LINGER, 0)
        if address.endswith(":*") or address.endswith(":0"):
            base = address.rsplit(":", 1)[0]
            port = self._sock.bind_to_random_port(base)
            self.address = f"{base}:{port}"
        else:
            self._sock.bind(address)
            self.address = address
        #: same-host ShmRPC transport (None when disabled): serves the
        #: SAME admission queue/slot pools — a request is a request
        #: whichever wire delivered it; the ZMQ socket stays the
        #: control plane and the remote-client path
        self._shm = None
        if shm_rpc.enabled():
            self._shm = shm_rpc.ShmRpcServer(
                base=shm_base or shm_rpc.new_base("ps"),
                counters=self.counters, bytes_counter="serve_shm_bytes",
                who="policy server",
            )
        self._poller = zmq.Poller()
        self._poller.register(self._sock, zmq.POLLIN)
        if self._shm is not None and self._shm.fd is not None:
            self._poller.register(self._shm.fd, zmq.POLLIN)
        #: WeightBus subscription (None = static weights) and the
        #: version every reply is stamped with after the first adopted
        #: snapshot (None until then, so a bus-less server's replies
        #: stay byte-identical to pre-bus servers)
        self.subscriber = subscriber
        self.weight_version = None
        if subscriber is not None:
            # inherit the server's telemetry sinks unless the caller
            # wired its own, and wake the serve loop for pushed chunks
            if subscriber.counters is None:
                subscriber.counters = self.counters
            if subscriber.timer is None:
                subscriber.timer = self.timer
            self._poller.register(subscriber.sock, zmq.POLLIN)

    @property
    def shm_endpoint(self):
        """The advertised ``shm://`` endpoint (None on pure-ZMQ
        servers)."""
        return self._shm.endpoint if self._shm is not None else None

    @property
    def model(self):
        """The default hosted model's adapter (the single model for
        single-model servers) — the pre-multi-model surface tests and
        benches poke at."""
        return self._models[self._default_id].model

    @property
    def models(self):
        """Hosted model ids, default first."""
        return tuple(self._models)

    def _state_or_error(self, msg):
        """Resolve the request's model state; returns ``(state, None)``
        or ``(None, error reply)`` for an unknown model id."""
        mid = msg.get("model")
        st = self._models.get(self._default_id if mid is None else mid)
        if st is None:
            return None, {"error": (
                f"unknown model {mid!r}; hosted: {sorted(self._models)}"
            )}
        return st, None

    # -- slot pool -----------------------------------------------------------

    def _alloc_slot(self, st):
        """Returns (slot, episode lease id) or (None, None) when full."""
        if st.model.slots == 0:
            self._episode_seq += 1
            st.stateless_eps[self._episode_seq] = time.monotonic()
            return -1, self._episode_seq
        if not st.free and self.slot_ttl_s is not None:
            now = time.monotonic()
            stale = [s for s, (_, ts) in st.live.items()
                     if now - ts > self.slot_ttl_s]
            for s in stale:
                del st.live[s]
                st.free.append(s)
            if stale:
                self.counters.incr("serve_evictions", len(stale))
        if not st.free:
            return None, None
        slot = st.free.pop()
        self._episode_seq += 1
        st.live[slot] = [self._episode_seq, time.monotonic()]
        st.model.reset_rows(np.asarray([slot]))
        return slot, self._episode_seq

    def _free_slot(self, st, slot, episode=None):
        lease = st.live.get(slot)
        if lease is None:
            return False
        if episode is not None and lease[0] != episode:
            return False  # a stale close must not kill the new tenant
        del st.live[slot]
        st.free.append(slot)
        return True

    def _pool_lost(self, st):
        """The model rebuilt its slot pool empty (:class:`SlotPoolLost`):
        every lease on it is void.  Dropping them makes each tenant's
        next step the ``unknown episode slot`` error — never an answer
        computed from an empty cache."""
        self.counters.incr("serve_pool_rebuilds")
        logger.error(
            "policy server: slot pool of model %r rebuilt empty, "
            "%d live episodes dropped", st.mid, len(st.live),
        )
        st.live.clear()
        st.free = list(range(st.model.slots))

    # -- request handling ----------------------------------------------------

    def _live_episodes(self):
        """Live episodes across every hosted model (window targeting,
        stats, the gateway's load scrape)."""
        return sum(
            len(st.live) if st.model.slots > 0 else len(st.stateless_eps)
            for st in self._models.values()
        )

    def _cmd_hello(self, msg):
        st = self._models[self._default_id]
        return {
            "model": st.model.kind,
            "obs_dim": st.model.obs_dim,
            "slots": st.model.slots,
            "free_slots": len(st.free),
            "int8": bool(getattr(st.model, "int8", False)),
            "max_batch": self.max_batch,
            "buckets": list(self.buckets),
            "models": {
                s.mid: {
                    "kind": s.model.kind,
                    "obs_dim": s.model.obs_dim,
                    "slots": s.model.slots,
                    "free_slots": len(s.free),
                    "int8": bool(getattr(s.model, "int8", False)),
                }
                for s in self._models.values()
            },
            "shm": self._shm.info() if self._shm is not None else None,
            "pid": os.getpid(),
            **self._device,
        }

    def _cmd_reset(self, msg):
        """Allocate a slot and answer with it, or (a reset with a prefix
        on a model that hands its prefill out unfetched) return the
        launched :class:`_Prefilling`, whose retire answers instead."""
        st, err = self._state_or_error(msg)
        if err is not None:
            return err
        slot, episode = self._alloc_slot(st)
        if slot is None:
            self.counters.incr("serve_slot_denied")
            return {"error": (
                f"no free episode slot ({st.model.slots} live on model "
                f"{st.mid!r}); close an episode or raise slots="
            )}
        prefix = msg.get("prefix")
        if prefix is not None:
            return self._prefill(st, slot, episode, prefix)
        self.counters.incr("serve_resets")
        return {"slot": slot, "episode": episode}

    def _release(self, st, slot, episode):
        """Give back what a reset that failed had been allocated."""
        if st.model.slots > 0:
            self._free_slot(st, slot, episode)
        else:
            st.stateless_eps.pop(episode, None)

    def _prefill(self, st, slot, episode, prefix):
        """Batched prefill admission: replay a T-step observation
        prefix into the freshly-allocated slot with ONE teacher-forced
        pass instead of T serial decode steps.  The pass is DISPATCHED
        here, behind whatever is launched (the pool chains the device's
        order: tick, rewind, prefill, next tick run as dispatched), and
        not waited for: a model that hands the reply out unfetched
        (``prefill_reply``) gets a :class:`_Prefilling` appended to
        ``_launched`` and returned, and :meth:`_retire` answers the
        reset; an array (a model that computes on the host) is the
        reset's reply at once.  Returns an error reply, with the slot
        freed again, where the prefix or the dispatch is refused."""
        def fail(text):
            self._release(st, slot, episode)
            return {"error": text}

        if not hasattr(st.model, "prefill_rows") or st.model.slots == 0:
            return fail(
                f"model {st.mid!r} ({st.model.kind}) is stateless or "
                "has no prefill path: admit without a prefix"
            )
        dtype = getattr(st.model, "obs_dtype", np.float32)
        try:
            prefix = np.asarray(prefix, dtype)
        except (TypeError, ValueError) as exc:
            return fail(
                f"prefix not coercible to {np.dtype(dtype).name}: {exc}")
        if prefix.ndim != 2 or prefix.shape[0] < 1 \
                or prefix.shape[1] != st.model.obs_dim:
            return fail(
                f"prefix shape {prefix.shape} != (T >= 1, "
                f"{st.model.obs_dim})"
            )
        dispatch = getattr(st.model, "prefill_reply", st.model.prefill_rows)
        behind = bool(self._launched)
        t0 = time.perf_counter()
        # the entry joins ``_launched`` inside the phase, so that the
        # clock's next switch sees it launched
        with self._clock(_PREFILL, "serve.prefill",
                         len=int(prefix.shape[0])):
            try:
                reply = dispatch(np.asarray([slot]), prefix)
            except Exception as exc:  # noqa: BLE001 - surfaced to client
                logger.exception("policy server: prefill failed")
                if isinstance(exc, SlotPoolLost):
                    self._pool_lost(st)
                return fail(f"prefill failed: {type(exc).__name__}: {exc}")
            ent = _Prefilling(st, reply, slot, episode,
                              int(prefix.shape[0]), behind,
                              time.perf_counter() - t0)
            if hasattr(reply, "is_ready"):
                self._launched.append(ent)
                return ent
        return self._prefilled(ent, reply, overlapped=False)

    def _prefilled(self, ent, pred, overlapped):
        """Count a prefill that ran to its end (``overlapped``: it
        shared the device's queue) and make its reset's reply: the
        prediction for position T (what the T'th serial step would have
        returned) and the position the next step consumes."""
        # the one record of the server's thread's time in prefills: the
        # dispatch, and what it waited at the fetch (nothing, where the
        # device had finished behind admission and the ticks)
        self.counters.incr("serve_prefill_us", int(ent.prefill_s * 1e6))
        self.counters.incr("serve_prefills")
        self.counters.incr("serve_prefills_overlapped", int(overlapped))
        self.counters.incr("serve_resets")
        return {"slot": ent.slot, "episode": ent.episode,
                "pred": np.ascontiguousarray(pred), "pos": ent.length}

    def _cmd_close(self, msg):
        st, err = self._state_or_error(msg)
        if err is not None:
            return err
        if st.model.slots == 0:
            closed = st.stateless_eps.pop(
                msg.get("episode"), None
            ) is not None
        else:
            closed = self._free_slot(st, int(msg.get("slot", -1)),
                                     msg.get("episode"))
        if closed:
            # a no-op close (unknown slot, stale/pruned lease, a
            # restarted server) is answered but not counted:
            # serve_resets vs serve_closes must reconcile
            self.counters.incr("serve_closes")
        return {"closed": closed}

    def _cmd_stats(self, msg):
        # top-level slot fields describe the DEFAULT model (the whole
        # server for single-model hosting, where slots/free/live stay
        # mutually consistent); per-model occupancy lives under
        # ``per_model`` so multi-model capacity math has coherent
        # numbers instead of a cross-model mix
        st = self._models[self._default_id]
        return {
            "model": st.model.kind,
            "slots": st.model.slots,
            "live_slots": len(st.live),
            "live_episodes": (
                len(st.live) if st.model.slots > 0
                else len(st.stateless_eps)
            ),
            "free_slots": len(st.free),
            "queued": len(self._queue),
            "models": list(self._models),
            "weight_version": self.weight_version,
            "per_model": {
                s.mid: {
                    "slots": s.model.slots,
                    "free_slots": len(s.free),
                    "live_slots": len(s.live),
                    "live_episodes": (
                        len(s.live) if s.model.slots > 0
                        else len(s.stateless_eps)
                    ),
                }
                for s in self._models.values()
            },
            "counters": self._settled_counters(),
            "pid": os.getpid(),
        }

    def _settled_counters(self):
        """The counters, the phase clock's running phase pushed first."""
        self._clock.settle()
        return self.counters.snapshot()

    def _cmd_telemetry(self, msg):
        """This process's telemetry in the TelemetryHub merge shape —
        the PULL half of remote scraping (a consumer-side hub registers
        ``lambda: client.telemetry()`` and this server needs no
        exporter, no extra socket).  ``queued``/``live_episodes``/
        ``models``/``hello`` ride along for the gateway's cached load
        scrape — one RPC covers liveness, load, capability AND
        telemetry (the gateway's own ``hello`` reply merges the
        capability fields so PR-10 hello consumers work unchanged
        against a gateway address)."""
        st = self._models[self._default_id]
        return {
            "model": st.model.kind,
            "models": list(self._models),
            "queued": len(self._queue),
            "live_episodes": self._live_episodes(),
            # the gateway's canary router learns per-replica versions
            # from this field on its cached scrape (docs/weight_bus.md)
            "weight_version": self.weight_version,
            "hello": {
                "model": st.model.kind,
                "obs_dim": st.model.obs_dim,
                "slots": st.model.slots,
                "int8": bool(getattr(st.model, "int8", False)),
                "max_batch": self.max_batch,
                "buckets": list(self.buckets),
                **self._device,
            },
            "pid": os.getpid(),
            "counters": self._settled_counters(),
            "stages": self.timer.snapshot_serialized(),
        }

    def _control_reply(self, msg):
        cmd = msg.get("cmd")
        handler = getattr(self, f"_cmd_{cmd}", None)
        if handler is None:
            reply = {"error": f"unknown serve command {cmd!r}"}
        else:
            try:
                reply = handler(msg)
            except Exception as exc:  # noqa: BLE001 - surfaced to client
                logger.exception("policy server: %r failed", cmd)
                reply = {"error": f"{type(exc).__name__}: {exc}"}
        if isinstance(reply, dict) and "error" in reply:
            self.counters.incr("serve_errors")
        return reply

    def _poll_weights(self):
        """Drain the WeightBus subscription and hot-swap a staged
        snapshot — called from the serve loop between turns.  A tick
        and prefills may be launched then: a staged snapshot RETIRES
        them first, so the swap happens at a point where nothing is in
        flight — slots/leases/reply-cache state cannot be half-stepped
        under it, and every reply (a prefilling reset's too) is stamped
        (``_finish``) with the version that executed it.  A snapshot
        the model refuses (structure/shape drift) is discarded and
        counted; the last good version keeps serving either way.  The
        whole of it, the poll too, is the phase ``serve_weights_us``."""
        if self.subscriber is None:
            return
        with self._clock(_WEIGHTS, "serve.weights"):
            self._adopt_weights()

    def _adopt_weights(self):
        snap = self.subscriber.poll()
        if snap is None:
            return
        while self._launched:
            self._retire()
        # routing: the snapshot's own model id wins; a publisher that
        # does not stamp one (a learner publishing its only model)
        # targets the model the SUBSCRIBER was attached for, default
        # model last
        target = (snap.model if snap.model is not None
                  else self.subscriber.model
                  if self.subscriber.model is not None
                  else self._default_id)
        st = self._models.get(target)
        t0 = time.perf_counter()
        try:
            if st is None:
                raise KeyError(
                    f"snapshot for unhosted model {target!r} "
                    f"(hosted: {sorted(self._models)})"
                )
            st.model.apply_weights(snap.tree())
        except Exception as exc:  # noqa: BLE001 - keep serving last good
            self.counters.incr("weight_apply_failed")
            logger.warning(
                "policy server: weight snapshot v%d refused (%s: %s); "
                "still serving v%s", snap.version, type(exc).__name__,
                exc, self.weight_version,
            )
            return
        st.weight_version = snap.version
        # the server-level scalar (telemetry/stats — what the gateway
        # scrapes a replica's rollout progress from) tracks the latest
        # adopted snapshot; per-reply stamps come from the EXECUTING
        # model's own version in _finish
        self.weight_version = snap.version
        self.counters.incr("weight_adopted")
        self.timer.add("weight_swap", time.perf_counter() - t0)
        logger.info("policy server: weights v%d hot-swapped (step %d)",
                    snap.version, snap.step)

    def _finish(self, ident, msg, reply, *, span_name, t0_us,
                ding=True):
        """Stamp correlation id + span + weight version, cache mutating
        replies, send.  ``ding=False`` defers the shm doorbell to the
        caller's burst flush (the batched multi-record wake)."""
        st = self._models.get(msg.get("model") or self._default_id)
        if st is not None and st.weight_version is not None:
            # the EXECUTING model's version (a co-hosted model the bus
            # never updated stays unstamped rather than riding another
            # model's version), stamped BEFORE the reply cache below,
            # so a retry answered from the cache reports the version
            # that actually executed it — not the version serving at
            # retry time
            reply["weight_version"] = st.weight_version
        mid = msg.get(wire.BTMID_KEY)
        span_ctx = msg.get(wire.SPAN_KEY)
        if isinstance(span_ctx, dict) and span_ctx.get("trace") is not None:
            reply = dict(reply)
            reply[wire.SPANS_KEY] = [make_span(
                span_name, t0_us, trace=span_ctx["trace"], cat="serve",
            )]
        if mid is not None:
            reply[wire.BTMID_KEY] = mid
            if msg.get("cmd") in MUTATING_CMDS:
                self._reply_cache[mid] = reply
                while len(self._reply_cache) > self._reply_cache_depth:
                    self._reply_cache.popitem(last=False)
        self._send(ident, reply, ding=ding)

    def _shm_gather_send(self, chan, reply, ding=True):
        """Gather-into-ring reply: reserve the ring record up front and
        land the reply's array leaves DIRECTLY in it (``begin_send``
        views) instead of staging them through ``encode`` + the
        ``send_frames`` memcpy — the replay shard's zero-copy reply
        discipline on the serve reply path.  False defers to the
        generic send (array-less reply, ring full/oversized, old
        native layer)."""
        bufs = []
        header = wire.strip_arrays(reply, bufs)
        if not bufs:
            return False
        head_bytes = wire.dumps(header)
        sizes = [len(head_bytes)] + [b.nbytes for b in bufs]
        views = self._shm.begin_send(chan, sizes)
        if views is None:
            return False
        done = False
        try:
            views[0][:] = np.frombuffer(head_bytes, np.uint8)
            for b, dst in zip(bufs, views[1:]):
                if b.nbytes:
                    dst[:] = b.view(np.uint8).reshape(-1)
            done = True
        finally:
            if not done:
                # a torn record with an intact header would decode as
                # WRONG data — poison the header so the client drops
                # the record (its same-mid retry re-fetches from the
                # reply cache), then publish: the reservation must
                # never dangle
                views[0][: min(8, len(head_bytes))] = 0
            try:
                self._shm.commit_send(chan, ding=ding)
            except OSError:
                pass  # channel died mid-reply: the retry re-fetches
        return True

    def _send(self, ident, reply, ding=True):
        import zmq

        # the send stamp the client's next request carries back
        # (``serve_client_turn_us``); a cached reply is stamped anew
        reply[wire.SENT_US_KEY] = now_us()
        if ident is not None and getattr(ident, "shm_channel", False):
            # the request arrived over shm: the reply goes back down
            # the same channel (a dead/full channel is dropped — the
            # client demotes to ZMQ and its same-mid retry re-fetches
            # from the reply cache)
            if self._shm is not None and (
                self._shm_gather_send(ident, reply, ding=ding)
                or self._shm.send(ident, reply, raw_buffers=True,
                                  ding=ding)
            ):
                self.counters.incr("serve_replies")
            return
        try:
            sent = wire.send_message_router(self._sock, ident, reply,
                                            raw_buffers=True)
            self.counters.incr("serve_wire_bytes", sent)
            self.counters.incr("serve_replies")
        except zmq.ZMQError:
            pass  # client gone; its retry will re-dial

    def _admit(self, ident, msg):
        """One decoded request: answer control commands immediately (a
        ``reset`` with a prefix dispatches its prefill here and is
        answered where that is retired), queue ``step``s for the next
        tick, dedupe retries."""
        self.counters.incr("serve_requests")
        mid = msg.get(wire.BTMID_KEY)
        cmd = msg.get("cmd")
        t0_us = now_us()
        if mid is not None and cmd in MUTATING_CMDS \
                and mid in self._reply_cache:
            # retry of a request already executed: exactly-once — the
            # cached reply answers it, nothing re-runs
            self.counters.incr("serve_cache_hits")
            self._send(ident, self._reply_cache[mid])
            return
        if mid is not None and mid in self._pending:
            # retry of a step still QUEUED, or of a step or a reset's
            # prefill launched and not yet answered: the original's
            # reply will answer it — re-point the route and drop the
            # dup (its step or prefill runs once)
            self.counters.incr("serve_dup_inflight")
            self._pending[mid].ident = ident
            return
        self._count_wire(msg, t0_us)
        if cmd != "step":
            reply = self._control_reply(msg)
            if isinstance(reply, _Prefilling):
                # a reset whose prefill was launched: its retire answers
                reply.ident, reply.msg, reply.t0_us = ident, msg, t0_us
                if mid is not None:
                    self._pending[mid] = reply
                return
            self._finish(ident, msg, reply, span_name=f"serve:{cmd}",
                         t0_us=t0_us)
            return
        st, err = self._state_or_error(msg)
        if err is not None:
            self.counters.incr("serve_errors")
            self._finish(ident, msg, err, span_name="serve:step",
                         t0_us=t0_us)
            return
        span_ctx = msg.get(wire.SPAN_KEY)
        trace = (span_ctx or {}).get("trace") \
            if isinstance(span_ctx, dict) else None
        ent = _Pending(ident, mid, msg, trace, t0_us, st)
        self._queue.append(ent)
        if mid is not None:
            self._pending[mid] = ent

    def _count_wire(self, msg, t_us):
        """A request's time on the wire (its send stamp to now) and,
        where it carries the previous reply's send stamp, its client's
        turnaround (that reply's send to this request's send); a request
        without stamps counts nothing."""
        sent = msg.get(wire.SENT_US_KEY)
        if not isinstance(sent, int):
            return
        due = [("serve_wire_in_us", max(0, t_us - sent)),
               ("serve_wire_in_n", 1)]
        prev = msg.get(wire.REPLY_SENT_US_KEY)
        if isinstance(prev, int):
            due += [("serve_client_turn_us", max(0, sent - prev)),
                    ("serve_client_turn_n", 1)]
        self.counters.incr_many(due)

    def _answer_step(self, ent, reply, ding=True):
        """Answer one step entry, wherever it got to (refused at
        assembly, failed, or served): only now does it stop being
        pending, so a retry that arrives while its step is in flight
        finds it in ``_pending`` and one that arrives later finds the
        reply cache."""
        if ent.mid is not None:
            self._pending.pop(ent.mid, None)
        self._finish(ent.ident, ent.msg, reply, span_name="serve:step",
                     t0_us=ent.t0_us, ding=ding)

    def _step_entry_error(self, ent, text, lease=None):
        """Error-reply one step entry.  ``lease`` ("unknown"/"stale")
        rides as a structured field so a gateway can drop its own lease
        entry without parsing error prose."""
        self.counters.incr("serve_errors")
        reply = {"error": text}
        if lease is not None:
            reply["lease"] = lease
        self._answer_step(ent, reply)

    def _step_failed(self, batch, exc):
        for ent, _, _ in batch:
            self._step_entry_error(
                ent, f"batched step failed: {type(exc).__name__}: {exc}")

    def _launch(self):
        """A tick's first half: drain up to ``max_batch`` queued steps
        into one padded, bucketed model call and DISPATCH it.  The
        call's reply is not waited for: the tick joins ``_launched``
        and :meth:`_retire` fetches it and answers, after the serve
        loop has had the device's time for admission, the next launch
        or an older tick's replies.  A tick serves ONE hosted model
        (the queue head's); entries for other models are left in order
        and the return value says so, so the serve loop launches again
        immediately instead of making them wait out another admission
        window."""
        with span("serve.tick") as tick:
            with self._clock(_ASSEMBLE, "serve.tick.assemble"):
                t_assemble = time.perf_counter()
                head = None
                skipped = deque()
                batch = []
                while self._queue and len(batch) < self.max_batch:
                    ent = self._queue.popleft()
                    if head is None:
                        head = ent.mstate
                    elif ent.mstate is not head:
                        skipped.append(ent)
                        continue
                    st = ent.mstate
                    stateful = st.model.slots > 0
                    slot = int(ent.msg.get("slot", -1)) if stateful else -1
                    if not stateful:
                        ep = ent.msg.get("episode")
                        if ep is not None:
                            # touch (or re-register, after a server
                            # restart) the episode's liveness for window
                            # targeting — stateless steps are never refused
                            st.stateless_eps[ep] = time.monotonic()
                    if stateful:
                        lease = st.live.get(slot)
                        if lease is None:
                            self._step_entry_error(ent, (
                                f"unknown episode slot {slot} (closed, "
                                "evicted, or a restarted server): reset() "
                                "and resume"
                            ), lease="unknown")
                            continue
                        if ent.msg.get("episode") not in (None, lease[0]):
                            # slot number reused by a NEW episode: the
                            # stale client must not advance the new
                            # tenant's cache
                            self._step_entry_error(ent, (
                                f"stale episode lease for slot {slot} "
                                "(evicted and reassigned): reset() and resume"
                            ), lease="stale")
                            continue
                    dtype = getattr(head.model, "obs_dtype", np.float32)
                    try:
                        obs = np.asarray(ent.msg.get("obs"), dtype)
                    except (TypeError, ValueError) as exc:
                        self._step_entry_error(
                            ent, "step obs not coercible to "
                                 f"{np.dtype(dtype).name}: {exc}"
                        )
                        continue
                    if obs.shape != (head.model.obs_dim,):
                        self._step_entry_error(ent, (
                            f"step obs shape {obs.shape} != "
                            f"({head.model.obs_dim},)"
                        ))
                        continue
                    batch.append((ent, slot, obs))
                # skipped other-model entries return to the FRONT in order:
                # they are older than anything still queued behind them —
                # ``more`` asks the serve loop to launch again NOW for them
                # (same-model overflow keeps the admission-window pacing)
                more = bool(skipped)
                while skipped:
                    self._queue.appendleft(skipped.pop())
                if not batch:
                    return more
                model = head.model
                stateful = model.slots > 0
                n = len(batch)
                bucket = next((b for b in self.buckets if b >= n),
                              self.buckets[-1])
                for ent, _, _ in batch:
                    self.timer.add("queue_wait", t_assemble - ent.t_enq)
                idx = np.full(bucket, model.pad_slot, np.int64)
                obs_arr = np.zeros((bucket, model.obs_dim), dtype)
                pos_before = []
                now = time.monotonic()
                for j, (ent, slot, obs) in enumerate(batch):
                    idx[j] = slot if stateful else j
                    obs_arr[j] = obs
                    if stateful:
                        head.live[slot][1] = now
                    pos_before.append(
                        int(model.pos[slot])
                        if hasattr(model, "pos") and stateful else None
                    )
                t_compute = time.perf_counter()
                self.timer.add("batch_assemble", t_compute - t_assemble)
            tick.set_metadata(rows=n, bucket=bucket)
            # the tick joins ``_launched`` inside the phase, so that the
            # clock's next switch sees it launched
            with self._clock(_DISPATCH, "serve.tick.compute"):
                try:
                    reply = model.step_rows(idx, obs_arr)
                except Exception as exc:  # noqa: BLE001 - must survive
                    logger.exception("policy server: batched step failed")
                    if isinstance(exc, SlotPoolLost):
                        self._pool_lost(head)
                    self._step_failed(batch, exc)
                    return more
                behind = self._ticks_launched() > 0
                self._launched.append(_Launched(
                    head, batch, reply, bucket, pos_before,
                    time.perf_counter() - t_compute))
            self.counters.incr("serve_ticks_overlapped", int(behind))
            return more

    def _ticks_launched(self):
        """How many of the launched entries are ticks (the others are
        prefills)."""
        return sum(isinstance(t, _Launched) for t in self._launched)

    def _retire(self):
        """The second half of the oldest launched entry, a tick or a
        prefill: fetch its reply (the one place the server's thread
        waits for the device), count it and answer: a tick's rows, or
        the ``reset`` whose prefill it was.  A fetch that fails for a
        lost slot pool (:class:`SlotPoolLost`: raised once per pool)
        also fails whatever was launched behind it on that pool, ticks
        and prefills; the leases are dropped once."""
        ent = self._launched.popleft()
        tick = isinstance(ent, _Launched)
        what = {"rows": len(ent.batch)} if tick else {"prefill": ent.length}
        with span("serve.retire", **what):
            t_fetch = time.perf_counter()
            try:
                with self._clock(_FETCH):  # the model's ``*.fence`` span
                    rows = np.asarray(ent.reply)
            except Exception as exc:  # noqa: BLE001 - must survive
                logger.exception("policy server: a launched %s failed",
                                 "step" if tick else "prefill")
                with self._clock(_REPLY, "serve.tick.reply"):
                    behind = []
                    if isinstance(exc, SlotPoolLost):
                        self._pool_lost(ent.state)
                        behind = [t for t in self._launched
                                  if t.state is ent.state]
                    for t in behind:
                        self._launched.remove(t)
                    for t in [ent] + behind:
                        self._launched_failed(t, exc)
                return
            waited = time.perf_counter() - t_fetch
            with self._clock(_REPLY, "serve.tick.reply"):
                if tick:
                    self._answer_tick(ent, rows, waited)
                else:
                    self._answer_prefill(ent, rows, waited)

    def _launched_failed(self, ent, exc):
        """Error-reply everybody a launched entry was to answer."""
        if isinstance(ent, _Launched):
            self._step_failed(ent.batch, exc)
            return
        self._release(ent.state, ent.slot, ent.episode)
        self.counters.incr("serve_errors")
        self._answer_reset(ent, {
            "error": f"prefill failed: {type(exc).__name__}: {exc}"})

    def _answer_reset(self, ent, reply):
        """Answer the ``reset`` whose prefill was launched: only now
        does it stop being pending (as :meth:`_answer_step`)."""
        mid = ent.msg.get(wire.BTMID_KEY)
        if mid is not None:
            self._pending.pop(mid, None)
        self._finish(ent.ident, ent.msg, reply, span_name="serve:reset",
                     t0_us=ent.t0_us)

    def _answer_prefill(self, ent, pred, waited):
        """A fetched prefill: count it, and whether it shared the
        device's queue (something launched was unfetched at its
        dispatch, or was dispatched behind it before this fetch)."""
        ent.prefill_s += waited
        self._answer_reset(ent, self._prefilled(
            ent, pred, overlapped=ent.behind or bool(self._launched)))

    def _answer_tick(self, tick, preds, waited):
        """A fetched tick: count it and scatter the answers."""
        model = tick.state.model
        n = len(tick.batch)
        t_reply = time.perf_counter()
        # the host's time inside the model call for this tick: its
        # dispatch and its fetch, not what ran between the two
        self.timer.add("compute", tick.compute_s + waited)
        self.counters.incr("serve_batches")
        if hasattr(model, "drain_events"):
            for name, count in model.drain_events().items():
                self.counters.incr(name, count)
        if tick.bucket > n:
            self.counters.incr("serve_batch_pad", tick.bucket - n)
        for j, (ent, _, _) in enumerate(tick.batch):
            reply = {"pred": np.ascontiguousarray(preds[j])}
            if tick.pos_before[j] is not None:
                reply["pos"] = tick.pos_before[j]
            # deferred doorbells: the whole batch's shm replies ride ONE
            # wake per channel (flushed below), not one ding per record
            self._answer_step(ent, reply, ding=False)
        if self._shm is not None:
            self._shm.flush_bells()
        self.timer.add("reply", time.perf_counter() - t_reply)

    # -- serving -------------------------------------------------------------

    def _window_target(self, in_flight=0):
        """Queue occupancy at which an admission window stops waiting.

        With nothing launched: every live episode (a blocking client
        keeps at most one step in flight, so a fuller window cannot
        form), capped at the largest bucket.  Stateless episodes are
        tracked by last use and pruned after :data:`STATELESS_TTL_S`
        idle; the ``max(1, ...)`` keeps a client that never reset
        servable instead of deadlocking the window.

        With ``in_flight`` episodes launched (a tick's rows, and every
        episode whose prefill is): they cannot send, so at most the
        others; and no more than half the live episodes, because with
        one tick on the device and one being gathered an even split
        keeps the device fed by either (a follower that waited for more
        would be launched after the device had gone idle).  Zero or
        less when nobody can send."""
        live = 0
        for st in self._models.values():
            if st.model.slots > 0:
                live += len(st.live)
            else:
                if st.stateless_eps:
                    cutoff = time.monotonic() - STATELESS_TTL_S
                    for ep, ts in list(st.stateless_eps.items()):
                        if ts < cutoff:
                            del st.stateless_eps[ep]
                live += len(st.stateless_eps)
        if not in_flight:
            return min(self.max_batch, max(1, live))
        return min(self.max_batch, live - in_flight, -(-live // 2))

    def _drain(self):
        """Admit every request currently sitting on the socket."""
        import zmq

        def handle(out):
            ident, msg, nbytes = out
            self.counters.incr("serve_wire_bytes", nbytes)
            reply = shm_rpc.control_reply(self._shm, msg)
            if reply is not None:
                # transport negotiation, not workload: answered outside
                # the request/reply counters and the reply cache
                try:
                    wire.send_message_router(self._sock, ident, reply)
                except zmq.ZMQError:
                    pass
                return
            self._admit(ident, msg)

        drain_socket(
            lambda: wire.recv_message_router_sized(self._sock,
                                                   flags=zmq.NOBLOCK),
            handle,
            self.counters, "policy server", "request",
        )

    def _handle_shm_msg(self, chan, msg):
        reply = shm_rpc.control_reply(self._shm, msg)
        if reply is not None:
            self._shm.send(chan, reply)
            return
        self._admit(chan, msg)

    def _drain_shm(self):
        """Admit every request pending on the shm channels (the channel
        object rides as the request's reply ident)."""
        if self._shm is not None:
            self._shm.pump(self._handle_shm_msg)

    def _admit_ready(self):
        """Admit what has arrived on either wire (resets are handled in
        here: a prefill is dispatched, and joins what is launched)."""
        with self._clock(_ADMIT, "serve.admit"):
            self._drain()
            self._drain_shm()

    def _idle_poll(self, poll_ms):
        """Wait, with nothing queued and nothing launched, for the next
        request to arrive: the clients' turnaround, which no change to
        the server recovers.  ``serve_idle_us`` is the one record of
        it."""
        with self._clock(_IDLE, "serve.idle"):
            self._poller.poll(poll_ms)

    def _window(self):
        """The admission window: wait for co-arriving requests (the
        latency the scheduler trades for occupancy) until every episode
        that can send has a step queued (episodes step one request at a
        time, so nobody else can arrive — waiting longer would be pure
        latency) or a full bucket is.

        With nothing launched the window is ``tick_ms`` long and ends
        early on the first empty poll slice.  With a tick or a prefill
        launched the target is smaller (:meth:`_window_target`: no
        launched episode can send) and the device's own work is the
        window: it ends when the OLDEST launched entry's reply is ready
        (its answers should not wait for a follower), however long or
        short ``tick_ms`` is.  Meanwhile the wires are read in slices of
        a millisecond, never slept on: a pump of the shm channels costs
        the same for one request as for a burst, and the follower cannot
        start before what is launched ends anyway.  A ``reset`` read in
        here dispatches its prefill behind what is launched and joins
        it."""
        t_end = time.perf_counter() + self.tick_ms / 1000.0
        with span("serve.window"):
            while True:
                # read each time round: admission launches prefills
                in_flight = sum(
                    len(t.batch) if isinstance(t, _Launched) else 1
                    for t in self._launched)
                if len(self._queue) >= self._window_target(in_flight):
                    break
                if self._launched:
                    if _reply_ready(self._launched[0].reply):
                        break
                    t_slice = time.perf_counter() + 1e-3
                    with self._clock(_POLL, "serve.poll"):
                        arrived = self._poller.poll(1)
                    if not arrived:
                        continue
                    with self._clock(_SLICE, "serve.slice"):
                        time.sleep(max(0.0, t_slice - time.perf_counter()))
                else:
                    rem_ms = (t_end - time.perf_counter()) * 1e3
                    if rem_ms <= 0:
                        break
                    with self._clock(_POLL, "serve.poll"):
                        arrived = self._poller.poll(max(1, int(rem_ms)))
                    if not arrived:
                        break  # window elapsed with nothing new
                self._admit_ready()

    def _turn(self):
        """One move of the serve loop after the window, by what it can
        observe.  The oldest launched entry (a tick or a prefill), if it
        is ready or nothing queued could follow it, is retired (and the
        loop comes round again for what is queued: finished work's
        answers never wait for a follower's launch).  Otherwise what is
        queued is launched, BEHIND whatever is still running, and then
        the launched entries are retired oldest first until one tick is
        left: the launch, the older entries' fetches and replies, and
        the admission that follows all run beside the device, and the
        thread waits in a prefill's fetch only when that prefill is the
        oldest entry.  A lone client, or an empty queue, sees launch
        then retire: a tick as it always was."""
        if self._launched and (
                not self._queue or _reply_ready(self._launched[0].reply)):
            self._retire()
            return
        # a launch serves one model; entries it skipped for model
        # mismatch are launched at once behind it, not parked behind
        # another admission window
        more = True
        while more and self._queue:
            more = self._launch()
            while self._ticks_launched() > 1:
                self._retire()

    def serve_forever(self, stop_event=None, poll_ms=50):
        """The one serve loop.  Between two turns at most ONE tick is
        launched (dispatched, its reply not yet fetched), with the
        prefills admission dispatched before and behind it; a hot-swap
        retires them all first, so weights change with nothing in
        flight, and so does the loop's exit."""
        import zmq

        self._clock.restart()
        while stop_event is None or not stop_event.is_set():
            try:
                self._poll_weights()
                if not self._queue and not self._launched:
                    self._idle_poll(poll_ms)
                    self._admit_ready()
                    if not self._queue:
                        continue
                self._window()
            except zmq.ZMQError:
                return  # socket closed under us: clean shutdown
            self._turn()
        while self._launched:
            self._retire()  # stopped: what was launched is still answered
        self._clock.settle()

    def close(self):
        try:
            self._sock.close(0)
        except Exception:  # noqa: BLE001 - shutdown best-effort
            pass
        if self.subscriber is not None:
            try:
                self.subscriber.close()
            except Exception:  # noqa: BLE001
                pass
        if self._shm is not None:
            try:
                self._shm.close(unlink=True)
            except Exception:  # noqa: BLE001
                pass
            self._shm = None


# ---------------------------------------------------------------------------
# in-process and supervised-process surfaces
# ---------------------------------------------------------------------------


class _LocalServerHandle:
    """An in-process server (thread) for tests and benchmarks."""

    def __init__(self, server, thread, stop):
        self.server = server
        self.address = server.address
        self._thread = thread
        self._stop = stop

    def close(self):
        self._stop.set()
        self._thread.join(timeout=5)
        self.server.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def start_server_thread(model, *, address="tcp://127.0.0.1:*",
                        counters=None, timer=None, **kwargs):
    """Serve a :class:`PolicyServer` from a daemon thread; returns a
    handle with ``.address``, ``.server`` and ``.close()``."""
    server = PolicyServer(
        address, model, counters=counters, timer=timer, **kwargs,
    )
    stop = threading.Event()
    thread = threading.Thread(
        target=server.serve_forever, kwargs={"stop_event": stop},
        daemon=True, name="bjx-policy-server",
    )
    thread.start()
    return _LocalServerHandle(server, thread, stop)


class _ServeLaunchInfo:
    """Duck-typed ``launch_info`` so :class:`~blendjax.btt.watchdog.
    FleetWatchdog` supervises the server process exactly like Blender
    producers or replay shards."""

    def __init__(self, processes, addresses):
        self.processes = processes
        self.addresses = {"SERVE": addresses}


class ServerProcess:
    """One policy-server *process* with a launcher-compatible surface
    (``launch_info`` + ``respawn(idx)``) so ``FleetWatchdog(restart=
    True)`` respawns it after a SIGKILL with its original command line.
    Model state is rebuilt deterministically from ``--seed`` — episode
    slots are fresh, which is exactly the contract clients see: a step
    against a restarted server errors (unknown slot) and the client
    resumes with ``reset()``."""

    def __init__(self, *, model="linear", address=None, seed=0,
                 obs_dim=8, slots=16, length=64, window=None,
                 num_actions=4, int8=False, tick_ms=2.0,
                 max_batch=64, work_us=0, subscribe=None, python=None,
                 ready_timeout=60.0, extra_args=()):
        from blendjax.replay.shard_client import free_port

        self.address = address or f"tcp://127.0.0.1:{free_port()}"
        self.python = python or sys.executable
        self.ready_timeout = ready_timeout
        #: the server's /dev/shm prefix, allocated HERE (the parent) so
        #: teardown and the watchdog respawn path can sweep whatever a
        #: SIGKILLed server (and its clients) left behind
        self.shm_base = shm_rpc.new_base("sp") if shm_rpc.enabled() \
            else None
        self._cmd = [
            self.python, "-m", "blendjax.serve.server",
            "--address", self.address,
            "--model", model,
            "--seed", str(seed),
            "--obs-dim", str(obs_dim),
            "--slots", str(slots),
            "--length", str(length),
            "--num-actions", str(num_actions),
            "--tick-ms", str(tick_ms),
            "--max-batch", str(max_batch),
        ]
        if self.shm_base is not None:
            self._cmd += ["--shm-base", self.shm_base]
        if work_us:
            self._cmd += ["--work-us", str(work_us)]
        if subscribe:
            self._cmd += ["--subscribe", subscribe]
        if window is not None:
            self._cmd += ["--window", str(window)]
        if int8:
            self._cmd.append("--int8")
        self._cmd += list(extra_args)
        self.launch_info = None

    def _spawn(self):
        # one child-environment policy for the whole repo (launcher,
        # shard fleet, serve server): child_env prepends the repo root
        # to PYTHONPATH
        from blendjax.btt.launcher import child_env

        # the platform is the caller's: with nothing set on a TPU
        # machine the server takes the chip, and ``hello`` says so
        return subprocess.Popen(self._cmd, env=child_env(),
                                start_new_session=True)

    def __enter__(self):
        self.launch_info = _ServeLaunchInfo([self._spawn()],
                                            [self.address])
        try:
            self.wait_ready(self.ready_timeout)
        except BaseException:
            self.close()
            raise
        return self

    def wait_ready(self, timeout=60.0):
        from blendjax.serve.client import ServeClient

        deadline = time.monotonic() + timeout
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError(
                    f"policy server at {self.address} not ready within "
                    f"{timeout:.1f}s"
                )
            client = ServeClient(self.address, timeoutms=500)
            try:
                client.hello(timeout_ms=500)
                return
            except TimeoutError:
                continue
            finally:
                client.close()

    def respawn(self, idx=0):
        """Relaunch with the original command line (the watchdog's
        contract).  The dead incarnation's ``/dev/shm`` objects are
        swept first — a SIGKILL runs no cleanup."""
        if self.shm_base is not None:
            shm_rpc.unlink_base(self.shm_base)
        proc = self._spawn()
        self.launch_info.processes[idx] = proc
        return proc

    def close(self):
        info = self.launch_info
        if info is None:
            return
        for p in info.processes:
            try:
                p.terminate()
            except Exception:  # noqa: BLE001
                pass
        for p in info.processes:
            try:
                p.wait(timeout=5)
            except Exception:  # noqa: BLE001
                try:
                    p.kill()
                except Exception:  # noqa: BLE001
                    pass
        if self.shm_base is not None:
            shm_rpc.unlink_base(self.shm_base)

    def __exit__(self, *exc):
        self.close()
        return False


class ServerFleet:
    """N policy-server replica *processes* behind ONE launcher-
    compatible surface (a ``launch_info`` spanning every replica +
    ``respawn(idx)``), so a single :class:`~blendjax.btt.watchdog.
    FleetWatchdog` supervises the whole serve fleet — the
    :class:`~blendjax.serve.gateway.ServeGateway`'s supervision story
    (docs/serving.md).  All replicas share one ``seed`` by default, so
    every replica serves identical weights (what lease failover needs:
    after a ``reset()`` any healthy replica continues the workload);
    pass ``seeds=`` to vary them."""

    def __init__(self, replicas, *, seed=0, seeds=None, **kwargs):
        if seeds is not None and len(seeds) != replicas:
            raise ValueError(
                f"seeds has {len(seeds)} entries for {replicas} replicas"
            )
        # kept for grow(): newcomers are spawned with the same config
        # (and the shared seed, so they serve identical weights)
        self._seed = seed
        self._kwargs = dict(kwargs)
        self._procs = [
            ServerProcess(seed=(seeds[i] if seeds is not None else seed),
                          **kwargs)
            for i in range(int(replicas))
        ]
        self.launch_info = None

    @property
    def addresses(self):
        return [None if p is None else p.address for p in self._procs]

    def __enter__(self):
        try:
            # spawn every replica first, then wait: startup overlaps
            for p in self._procs:
                p.launch_info = _ServeLaunchInfo([p._spawn()],
                                                 [p.address])
            for p in self._procs:
                p.wait_ready(p.ready_timeout)
        except BaseException:
            self.close()
            raise
        self.launch_info = _ServeLaunchInfo(
            [p.launch_info.processes[0] for p in self._procs],
            self.addresses,
        )
        return self

    def respawn(self, idx):
        """Relaunch replica ``idx`` with its original command line (the
        watchdog's contract)."""
        if self._procs[idx] is None:
            raise RuntimeError(
                f"replica {idx} is retired; a retired slot is never "
                "respawned (grow() to add capacity)"
            )
        proc = self._procs[idx].respawn(0)
        self.launch_info.processes[idx] = proc
        return proc

    def grow(self, n=1, *, seeds=None):
        """Spawn ``n`` NEW replicas into the live fleet (autoscale
        scale-up).  They are appended — existing fleet indices (and so
        the gateway's ``r<idx>`` id alignment and any watchdog watching
        ``launch_info``) never move.  Spawns overlap, then each
        newcomer is waited ready.  Returns ``[(idx, address), ...]``
        for the gateway admission."""
        if self.launch_info is None:
            raise RuntimeError("grow() needs an entered fleet")
        if seeds is not None and len(seeds) != int(n):
            raise ValueError(
                f"seeds has {len(seeds)} entries for {n} new replicas"
            )
        added = []
        for j in range(int(n)):
            p = ServerProcess(
                seed=(seeds[j] if seeds is not None else self._seed),
                **self._kwargs,
            )
            self._procs.append(p)
            idx = len(self._procs) - 1
            p.launch_info = _ServeLaunchInfo([p._spawn()], [p.address])
            self.launch_info.processes.append(
                p.launch_info.processes[0])
            self.launch_info.addresses["SERVE"].append(p.address)
            added.append((idx, p.address))
        try:
            for idx, _ in added:
                self._procs[idx].wait_ready(self._procs[idx].ready_timeout)
        except BaseException:
            # a newcomer that never came up is retired on the spot: the
            # established fleet is untouched and indices stay stable
            for idx, _ in added:
                self.retire(idx)
            raise
        return added

    def retire(self, idx):
        """Retire replica ``idx`` permanently (autoscale scale-down,
        AFTER its gateway drain reached zero leases): terminate the
        process and sweep its ``/dev/shm``.  The index slot is kept
        (``None``) so fleet indices stay aligned with gateway ids and
        the watchdog skips it instead of respawning it.  Idempotent."""
        p = self._procs[idx]
        if p is None:
            return False
        # slot goes None BEFORE the kill: a watchdog polling between
        # the two must see a retired slot, not a death to respawn
        self._procs[idx] = None
        if self.launch_info is not None:
            self.launch_info.processes[idx] = None
        p.close()
        return True

    def shrink(self, victims):
        """Retire every index in ``victims``; returns those actually
        retired (already-retired slots are skipped)."""
        return [idx for idx in victims if self.retire(idx)]

    def close(self):
        for p in self._procs:
            if p is not None:
                p.close()
        self.launch_info = None

    def __exit__(self, *exc):
        self.close()
        return False


# ---------------------------------------------------------------------------
# process entry point
# ---------------------------------------------------------------------------


def build_model(args, kind=None, seed=None):
    """Deterministic model construction from CLI args (seeded init —
    what makes a respawned server byte-identical to its predecessor).
    ``kind``/``seed`` override the args' own (the ``--extra-model``
    path builds secondary hosted models through the same code)."""
    if kind is not None or seed is not None:
        args = argparse.Namespace(**{
            **vars(args),
            "model": kind if kind is not None else args.model,
            "seed": seed if seed is not None else args.seed,
        })
    if args.model == "linear":
        return LinearModel(obs_dim=args.obs_dim, slots=args.slots,
                           seed=args.seed,
                           work_us=getattr(args, "work_us", 0))
    import jax

    key = jax.random.PRNGKey(args.seed)
    if args.model == "policy":
        from blendjax.models import policy

        params = policy.init(key, args.obs_dim, args.num_actions)
        return PolicyModel(params, args.obs_dim, int8=args.int8)
    if args.model == "seqformer":
        from blendjax.models import seqformer

        params = seqformer.init(
            key, obs_dim=args.obs_dim, d_model=args.d_model,
            n_heads=args.n_heads, n_layers=args.n_layers,
            max_len=max(args.length, 8),
        )
        return SeqFormerModel(
            params, args.slots, args.length, window=args.window,
            int8=args.int8,
        )
    raise ValueError(f"unknown --model {args.model!r}")


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Serve one blendjax policy/world-model."
    )
    ap.add_argument("--address", required=True)
    ap.add_argument("--model", default="linear",
                    choices=("linear", "policy", "seqformer"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--obs-dim", type=int, default=8)
    ap.add_argument("--num-actions", type=int, default=4)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--length", type=int, default=64)
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--d-model", type=int, default=32)
    ap.add_argument("--n-heads", type=int, default=4)
    ap.add_argument("--n-layers", type=int, default=2)
    ap.add_argument("--int8", action="store_true")
    ap.add_argument("--tick-ms", type=float, default=2.0)
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--work-us", type=float, default=0,
                    help="linear model only: sleep-based per-row "
                         "compute stand-in (gateway scale-out bench)")
    ap.add_argument("--subscribe", default=None,
                    help="WeightBus publisher address to subscribe to "
                         "(docs/weight_bus.md): published snapshots "
                         "hot-swap into the served model between ticks")
    ap.add_argument("--shm-base", default=None,
                    help="/dev/shm name prefix for the ShmRPC transport "
                         "(supervising parents pass one so they can "
                         "sweep a SIGKILLed server's objects)")
    ap.add_argument(
        "--extra-model", action="append", default=[],
        metavar="NAME=KIND",
        help="host an additional model under NAME (multi-model "
             "routing); the i'th extra model inits from seed+1+i, so a "
             "respawned server rebuilds every hosted model "
             "deterministically from the one command line",
    )
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    model = build_model(args)
    if args.extra_model:
        models = {model.kind: model}
        for i, spec in enumerate(args.extra_model):
            name, sep, kind = spec.partition("=")
            if not sep or not name or not kind:
                ap.error(f"--extra-model needs NAME=KIND, got {spec!r}")
            if name in models:
                ap.error(f"duplicate hosted model name {name!r}")
            models[name] = build_model(args, kind=kind,
                                       seed=args.seed + 1 + i)
        model = models
    subscriber = None
    if args.subscribe:
        from blendjax.weights.bus import WeightSubscriber

        subscriber = WeightSubscriber(args.subscribe)
    server = PolicyServer(
        args.address, model, tick_ms=args.tick_ms, max_batch=args.max_batch,
        shm_base=args.shm_base, subscriber=subscriber,
    )
    stop = threading.Event()

    def _term(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)
    logger.info(
        "policy server (%s%s) serving %s", args.model,
        ", int8" if args.int8 else "", server.address,
    )
    try:
        server.serve_forever(stop_event=stop)
    finally:
        server.close()


if __name__ == "__main__":
    main()
