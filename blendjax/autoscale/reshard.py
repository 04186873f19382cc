"""Live replay resharding: grow the shard fleet under traffic.

The replay tier's resize is not a routing change — the client IS the
draw authority, so adding storage means moving slot *ownership*
crash-exactly (docs/autoscaling.md "Shard handoff"):

1. the source shard checkpoints its full state (``save`` RPC — the
   PR-15 durability machinery; appends keep flowing after the cut);
2. :meth:`~blendjax.replay.service.ShardFleet.grow` copies that
   checkpoint under the new shard's name and spawns it — the newcomer
   boots already holding every source row up to the cut;
3. :meth:`~blendjax.replay.shard_client.ShardedReplay.adopt_shard`
   verifies the restore, copies only the rows appended past the cut
   into the moving range (``written_since`` reconciliation), and flips
   ownership of the range under the buffer lock.

Total capacity, the SumTree and the RNG never change, so the draw
stream continues bit-identically over unmoved ranges — the same
argument that makes an N-shard deployment draw-identical to a local
buffer makes a resize invisible to the learner.

Failure is atomic: any step aborting
(:class:`~blendjax.replay.shard_client.ReshardAborted`, a dead new
shard, a save that never lands) leaves the ownership map untouched and
the source serving its full range; the half-born shard process is
retired and its disk/shm state swept.  A SIGKILL of the NEW shard
mid-handoff is exactly that abort; a SIGKILL of the SOURCE quarantines
it through the ordinary fault path and the handoff aborts without
touching the map.
"""

from __future__ import annotations

import logging
import time

from blendjax.replay.shard_client import ReshardAborted, ShardRPCError
from blendjax.utils.timing import fleet_counters

logger = logging.getLogger("blendjax")


def reshard_replay(replay, fleet, *, source=None, fraction=0.5,
                   counters=None, timer=None):
    """Add one shard to a live deployment and hand it a slot range.

    Params
    ------
    replay: ShardedReplay
        The draw authority; gains a shard on success.
    fleet: ShardFleet
        The shard processes; ``grow``/``retire`` side of the resize.
    source: int | None
        Live shard surrendering the range; defaults to the shard
        owning the most slots (the one a previous reshard split
        least).
    fraction: float
        Share of the source's owned slots that moves.

    Returns ``(shard_index, address)`` of the adopted shard.  Raises
    :class:`~blendjax.replay.shard_client.ReshardAborted` (map
    untouched, source untouched, newcomer retired) on any failure.
    """
    counters = counters if counters is not None else fleet_counters
    timer = timer if timer is not None else replay.timer
    t0 = time.perf_counter()
    if source is None:
        with replay._cond:
            owned = [
                int((replay._owner == s).sum())
                for s in range(replay.num_shards)
            ]
            dead = replay._dead.copy()
        live = [s for s in range(len(owned)) if not dead[s]]
        if not live:
            raise ReshardAborted(
                f"{replay.name}: no live shard to reshard from"
            )
        source = max(live, key=lambda s: owned[s])
    try:
        cut = replay.clients[source].rpc("save")
    except ShardRPCError as exc:
        counters.incr("autoscale_reshard_aborts")
        raise ReshardAborted(
            f"{replay.name}: source shard {source} save failed: {exc}"
        ) from exc
    idx, addr = fleet.grow(restore_ckpt=cut["path"])
    try:
        shard = replay.adopt_shard(
            addr, source=int(source), cut_seq=int(cut["seq"]),
            fraction=fraction,
        )
    except BaseException:
        # abort WHOLE: the newcomer process (and its disk/shm state)
        # goes away; the map and the source were never touched
        fleet.retire(idx)
        raise
    dt = time.perf_counter() - t0
    timer.add("autoscale_resize", dt)
    logger.warning(
        "reshard: shard %d live at %s, %d shards serving (%.2fs "
        "decision-to-settle)", shard, addr, replay.num_shards, dt,
    )
    return shard, addr
