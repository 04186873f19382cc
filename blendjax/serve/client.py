"""ServeClient: one episode's blocking RPC channel to a PolicyServer.

The consumer half of the serving tier (docs/serving.md): a DEALER
socket speaking the empty-delimiter framing from :mod:`blendjax.wire`,
every RPC stamped with a ``wire.BTMID_KEY`` correlation id and run
under a :class:`~blendjax.btt.faults.FaultPolicy` — a retry re-sends
the SAME id, the server's reply cache answers it without a second
decode, and replies whose id does not match the outstanding request are
dropped as stale (the ``ShardClient`` discipline pointed at inference).

Episode protocol::

    client = ServeClient("tcp://host:24000")
    slot = client.reset()            # admit an episode (KV-cache slot)
    for obs in episode:
        pred = client.step(obs)      # one batched-on-the-server decode
    client.close_episode()           # release the slot

A step against a restarted server (fresh slot pool) raises
``RuntimeError`` naming the unknown slot; call :meth:`reset` and
resume — the recovery path the chaos tests exercise under
``FleetWatchdog`` respawns.
"""

from __future__ import annotations

import logging
import random
import time

import numpy as np

from blendjax import wire
from blendjax.btt.faults import FaultPolicy
from blendjax.obs.spans import now_us
from blendjax.utils.timing import fleet_counters

logger = logging.getLogger("blendjax")


def _wire_rows(rows):
    """What a request carries: int32 for whole numbers (a token model's
    ids), float32 for everything else (observations)."""
    rows = np.asarray(rows)
    return rows.astype(np.int32 if rows.dtype.kind in "iu" else np.float32,
                       copy=False)


class ServeRPCError(TimeoutError):
    """A serve RPC failed at the transport level (no reply within the
    policy, circuit open).  Subclasses :class:`TimeoutError` so callers
    that treat outages as retriable-later (reset-and-resume loops)
    handle them uniformly."""


class ServeClient:
    """Blocking exactly-once RPCs to one :class:`~blendjax.serve.server.
    PolicyServer` (DEALER framing against its ROUTER socket)."""

    def __init__(self, address, *, fault_policy=None, counters=None,
                 timeoutms=5000, context=None, span_recorder=None,
                 name="serve", model=None, shm="auto", shm_chaos=None,
                 follow_redirects=True, fallback_backoff_s=0.05,
                 fallback_backoff_max_s=2.0):
        self.address = address
        #: the address this client was CONSTRUCTED with — against a
        #: sharded gateway that is the front, and the recovery anchor:
        #: when a direct-dialed worker dies, the client falls back here
        #: so the next RPC re-resolves (see rpc())
        self._front_address = address
        #: follow a sharded front's ``gw_workers`` handoff (reset
        #: replies name the worker owning the new lease; the client
        #: re-points its channel at that worker's own address so
        #: steady-state traffic never crosses the front again).
        #: ``False`` pins every request to the constructed address —
        #: chaos proxies and probes that must see one fixed peer.
        self.follow_redirects = bool(follow_redirects)
        self.name = name
        self.policy = fault_policy or FaultPolicy()
        self.state = self.policy.new_state()
        self.counters = counters if counters is not None else fleet_counters
        self.timeoutms = int(timeoutms)
        self.slot = None  # the live episode's slot after reset()
        self.episode = None  # ... and its lease id (see reset())
        #: model id this client's episodes run on (multi-model servers
        #: / gateway routing); None = the server's default model
        self.model = model
        #: the replica id that served the LAST reply (stamped by a
        #: ServeGateway; None against a bare server) — surfaced in
        #: ServeRPCError text and span args so a misbehaving replica is
        #: diagnosable from a client traceback alone
        self.replica = None
        #: the gateway WORKER that served the last reply (stamped in
        #: worker mode; None against a bare server or a plain gateway)
        #: — the sharded analog of the replica stamp
        self.gw_worker = None
        #: the WeightBus version that served the LAST reply (stamped by
        #: subscribed servers; None against a bus-less server) —
        #: surfaced alongside the replica stamp, so a bad-version
        #: rollout is diagnosable from a client traceback alone
        self.weight_version = None
        #: cross-process span sink (None = tracing off): client RPC
        #: spans plus the server's piggybacked serve-side spans
        self.spans = span_recorder
        self._ctx = context
        self._shm_mode = shm
        self._shm_chaos = shm_chaos
        self._chan = None
        #: front-fallback pacing: consecutive transport failures since
        #: the last good reply.  Each failure that re-points at the
        #: front first sleeps ``min(max, base * 2**(n-1))`` with
        #: uniform jitter, so a worker-respawn window is not a tight
        #: re-dial loop bursting load onto the relay front.  ``base=0``
        #: disables the pause (latency-critical probes).
        self._fallback_failures = 0
        self._fallback_backoff_s = float(fallback_backoff_s)
        self._fallback_backoff_max_s = float(fallback_backoff_max_s)
        #: the server's send stamp on the last reply (``wire.
        #: SENT_US_KEY``), carried back on the next request so that the
        #: server can count this client's turnaround; None after a
        #: failed RPC
        self._reply_sent_us = None

    def _channel(self):
        if self._chan is None:
            from blendjax.btt.transport import RpcChannel

            self._chan = RpcChannel(
                self.address, context=self._ctx, shm=self._shm_mode,
                shm_chaos=self._shm_chaos, name=self.name,
            )
        return self._chan

    @property
    def transport(self):
        """The wire the next RPC rides: ``"shm"`` or ``"tcp"``."""
        return self._chan.transport if self._chan is not None else "tcp"

    def reset_channel(self):
        """Drop the channel (DEALER socket AND any shm ring pair) so
        the next RPC dials fresh (stale replies of a dead server
        incarnation die with the old one)."""
        if self._chan is not None:
            self._chan.reset()

    close = reset_channel

    def rpc(self, cmd, payload=None, *, timeout_ms=None,
            raw_buffers=False):
        """One exactly-once RPC under the fault policy; returns the
        decoded reply dict.  Raises :class:`ServeRPCError` (transport)
        or ``RuntimeError`` (the server executed and reported failure).
        The retry/stale-reply discipline is the shared
        :func:`blendjax.btt.rpc.exactly_once_rpc`."""
        from blendjax.btt.rpc import exactly_once_rpc

        msg = dict(payload or {})
        msg["cmd"] = cmd
        # the send stamp (a retry re-sends it: the server counts a
        # retry's wire time nowhere), and the last reply's
        msg[wire.SENT_US_KEY] = now_us()
        if self._reply_sent_us is not None:
            msg[wire.REPLY_SENT_US_KEY] = self._reply_sent_us
        self._reply_sent_us = None
        # the last replica (gateway-stamped) and weight version
        # (bus-stamped) that answered ride the transport-error text and
        # the client span: when a fleet or a rollout misbehaves, the
        # traceback names the suspect replica AND the suspect version
        via = (f", last replica {self.replica}"
               if self.replica is not None else "")
        if self.gw_worker is not None:
            via += f", gateway worker {self.gw_worker}"
        if self.weight_version is not None:
            via += f", weights v{self.weight_version}"
        span_args = {}
        if self.replica is not None:
            span_args["replica"] = self.replica
        if self.gw_worker is not None:
            span_args["gw_worker"] = self.gw_worker
        if self.weight_version is not None:
            span_args["weight_version"] = self.weight_version
        try:
            reply = exactly_once_rpc(
                self._channel, msg,
                policy=self.policy, state=self.state,
                counters=self.counters,
                wait_ms=(self.timeoutms if timeout_ms is None
                         else int(timeout_ms)),
                raw_buffers=raw_buffers, spans=self.spans,
                remote_name="policy server",
                span_label="serve_rpc", span_cat="serve_client",
                span_args=span_args or None,
                rpc_name=f"{self.name}:{cmd}",
                exc_factory=lambda text: ServeRPCError(
                    f"policy server ({self.address}{via}): {text}"
                ),
                retryable=(ServeRPCError,),
                pop_mid=True,
            )
        except ServeRPCError:
            if self.follow_redirects and self.address != self._front_address:
                # the direct-dialed gateway worker went silent: fall
                # back to the front so the NEXT rpc re-resolves (the
                # front answers, relays to a live worker, or names the
                # stale lease) — the raised error already carries the
                # dead worker's id in its text.  The fall-back is
                # PACED: bounded exponential backoff + jitter, so N
                # clients losing the same worker (a respawn window) do
                # not re-dial the front in a lockstep burst
                self._fallback_failures += 1
                delay = self._fallback_delay()
                logger.warning(
                    "%s: gateway worker %s at %s unresponsive; falling "
                    "back to the front at %s (after %.3fs backoff)",
                    self.name, self.gw_worker, self.address,
                    self._front_address, delay,
                )
                if delay > 0:
                    time.sleep(delay)
                self._channel().redirect(self._front_address)
                self.address = self._front_address
            else:
                self._fallback_failures += 1
            raise
        self._fallback_failures = 0
        self._reply_sent_us = reply.pop(wire.SENT_US_KEY, None)
        rep = reply.get("replica")
        if rep is not None:
            self.replica = rep
        gw = reply.get("gw_worker")
        if gw is not None:
            self.gw_worker = gw
        wv = reply.get("weight_version")
        if wv is not None:
            self.weight_version = wv
        self._maybe_follow(reply)
        return reply

    def _fallback_delay(self):
        """The paced re-dial delay for the CURRENT consecutive-failure
        count: ``min(cap, base * 2**(n-1))``, jittered to 50–100% so
        concurrent clients de-correlate."""
        if self._fallback_backoff_s <= 0 or self._fallback_failures <= 0:
            return 0.0
        raw = self._fallback_backoff_s * (
            2.0 ** (self._fallback_failures - 1))
        return min(self._fallback_backoff_max_s, raw) * random.uniform(
            0.5, 1.0)

    def _maybe_follow(self, reply):
        """A sharded front's handoff: a reply naming both the worker
        that answered (``gw_worker``) and the live worker address map
        (``gw_workers``) moves this client's channel onto that worker's
        own address — steady-state traffic skips the front entirely."""
        if not self.follow_redirects:
            return
        gwmap = reply.get("gw_workers")
        tag = reply.get("gw_worker")
        if not isinstance(gwmap, dict) or tag is None:
            return
        target = gwmap.get(tag)
        if target is None or target == self.address:
            return
        self._channel().redirect(target)
        self.address = target

    # -- episode protocol ----------------------------------------------------

    def hello(self, timeout_ms=None):
        return self.rpc("hello", timeout_ms=timeout_ms)

    def _model_payload(self, payload):
        if self.model is not None:
            payload["model"] = self.model
        return payload

    def reset(self, prefix=None, timeout_ms=None, scenario=None):
        """Admit an episode: returns (and remembers) its slot id.  The
        reply's episode *lease* id rides every later step/close, so a
        slot the server evicted and reassigned refuses this client's
        stale steps instead of advancing the new tenant's cache.

        ``prefix`` — a ``(T, obs_dim)`` observation prefix — admits the
        episode MID-SEQUENCE: the server replays it in one
        teacher-forced batched pass (not T serial decodes) and the full
        reply dict is returned instead of the slot, with ``pred`` (the
        prediction for position T) and ``pos`` (the position the next
        ``step`` consumes).

        ``scenario`` — an optional traffic label (docs/scenarios.md):
        rides the admission request, and a fronting
        :class:`~blendjax.serve.gateway.ServeGateway` attributes the
        whole episode's requests/latencies to it in its per-scenario
        records (bare servers ignore it)."""
        payload = self._model_payload({})
        if prefix is not None:
            payload["prefix"] = _wire_rows(prefix)
        if scenario is not None:
            payload["scenario"] = str(scenario)
        reply = self.rpc("reset", payload, timeout_ms=timeout_ms,
                         raw_buffers=prefix is not None)
        self.slot = int(reply["slot"])
        self.episode = reply.get("episode")
        if prefix is not None:
            reply["pred"] = np.asarray(reply["pred"])
            return reply
        return self.slot

    def step(self, obs, slot=None, timeout_ms=None):
        """One served ``step``: returns the reply dict (``pred`` is the
        model output row; stateful servers may add ``pos``, the
        position this observation consumed)."""
        use = self.slot if slot is None else slot
        if use is None:
            raise RuntimeError("step() before reset(): no episode slot")
        reply = self.rpc(
            "step",
            self._model_payload(
                {"slot": int(use), "episode": self.episode,
                 "obs": _wire_rows(obs)}
            ),
            timeout_ms=timeout_ms, raw_buffers=True,
        )
        reply["pred"] = np.asarray(reply["pred"])
        return reply

    def close_episode(self, timeout_ms=None):
        if self.slot is None:
            return False
        reply = self.rpc(
            "close",
            self._model_payload(
                {"slot": self.slot, "episode": self.episode}
            ),
            timeout_ms=timeout_ms,
        )
        self.slot = None
        self.episode = None
        return bool(reply.get("closed"))

    def stats(self, timeout_ms=None):
        return self.rpc("stats", timeout_ms=timeout_ms)

    def telemetry(self, timeout_ms=None):
        """The server process's telemetry snapshot (TelemetryHub merge
        shape: counters + serialized per-stage histograms)."""
        return self.rpc("telemetry", timeout_ms=timeout_ms)

    def register_with_hub(self, hub, name="serve"):
        """Wire the served process into a :class:`~blendjax.obs.hub.
        TelemetryHub` as a remote source (pulled per scrape over this
        RPC channel; a dead server surfaces as ``remote_errors``, never
        a failed scrape)."""
        hub.register_remote(name, lambda: self.telemetry(timeout_ms=500))
        return hub
