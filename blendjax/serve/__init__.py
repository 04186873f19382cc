"""Policy-serving inference tier (docs/serving.md) — the system's third
workload family: train -> replay -> **serve**.

One :class:`~blendjax.serve.server.PolicyServer` process owns a model
(MLP policy, seqformer world model, or the jax-free linear stand-in)
and serves ``step()``/``reset()``/``close()`` to many concurrent
episode clients over the DEALER wire with **continuous batching**: the
admission queue drains every tick into bucketed batch sizes, one jitted
call serves the tick, and for stateful world models every live episode
holds a row in a **KV-cache slot pool** decoded at per-row positions
(``seqformer.init_cache(per_row=True)``).  Retries are exactly-once via
the ``wire.BTMID_KEY`` reply cache; ``--int8`` serves the
``ops/quant``-quantized model through the same code.

A fleet of replicas scales the tier out behind a
:class:`~blendjax.serve.gateway.ServeGateway` (ROUTER front, per-replica
DEALER backends): episode-lease affinity pins an episode's steps to the
replica owning its KV-cache row, fresh episodes spread by scraped load,
and a SIGKILLed replica respawned by the watchdog costs its episodes
one actionable stale-lease error before they resume via ``reset()``.

Public surface::

    from blendjax.serve import (
        PolicyServer, ServeClient, ServeRPCError, ServerProcess,
        ServerFleet, ServeGateway, start_gateway_thread,
        LinearModel, PolicyModel, SeqFormerModel, start_server_thread,
    )

Imports stay lazy (PEP 562) so ``ServeClient``-only consumers and the
jax-free ``LinearModel`` server process never pay the model stack.
"""

from __future__ import annotations

_EXPORTS = {
    "PolicyServer": "blendjax.serve.server",
    "LinearModel": "blendjax.serve.server",
    "PolicyModel": "blendjax.serve.server",
    "SeqFormerModel": "blendjax.serve.server",
    "SlotPoolLost": "blendjax.serve.server",
    "ServerProcess": "blendjax.serve.server",
    "ServerFleet": "blendjax.serve.server",
    "start_server_thread": "blendjax.serve.server",
    "default_buckets": "blendjax.serve.server",
    "ServeClient": "blendjax.serve.client",
    "ServeRPCError": "blendjax.serve.client",
    "ServeGateway": "blendjax.serve.gateway",
    "start_gateway_thread": "blendjax.serve.gateway",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name):
    if name in _EXPORTS:
        import importlib

        return getattr(importlib.import_module(_EXPORTS[name]), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
