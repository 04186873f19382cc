"""Device feed: double-buffered host->HBM prefetch.

This module is the TPU-native seam the whole framework exists for
(BASELINE.json north star): batches coming off the ZMQ stream are staged
into device memory *while the previous train step runs*, so the TPU never
waits on the host.  ``jax.device_put`` dispatches asynchronously; keeping
``size`` batches in flight from a background thread overlaps H2D DMA with
XLA compute — the reference's equivalent path is torch DataLoader +
``.to(device)`` inside the train loop, which serializes transfer and step.

Multi-device feeds pass a ``jax.sharding.Sharding`` (e.g. batch split over
the mesh's 'data' axis); on multi-host slices each process feeds its local
shard and ``make_array_from_process_local_data`` assembles the global array.
"""

from __future__ import annotations

import contextlib
import logging
import os
import queue
import threading
import time

import jax
import numpy as np

from blendjax.utils.timing import StageTimer

log = logging.getLogger("blendjax")

_SENTINEL = object()


class TransferGate:
    """Pauses feed workers while a host->device transfer is in flight.

    On core-starved hosts (TPU-VM sidecars, CI containers) the PCIe
    client that pumps ``device_put`` shares its core with the collate and
    recv threads; any concurrently running Python thread then stretches the
    transfer by GIL-handoff latency (measured on a 1-core host: 9.8 MB
    batch 5.5 ms alone vs 33.8 ms with one numpy thread running — ~6x).
    Serializing the two is strictly cheaper there: the gate closes for the
    duration of each transfer and feed workers block at their next batch
    boundary instead of stealing the core.

    The gate refcounts in-flight transfers (a ``Condition`` over a
    counter, not a bare ``Event``), so one gate can safely be shared
    across several streams: it opens only when EVERY transfer holding it
    has finished — with an event, the first transfer to finish would
    reopen the gate while a second was still in flight.

    On hosts with cores to spare the gate stays open permanently
    (``JaxStream(transfer_gate='auto')``) and costs one check per batch.

    Params
    ------
    timeout: float
        Liveness backstop for :meth:`wait` — a crashed transfer thread
        must not freeze the feed forever.  When it fires, a warning is
        logged once per stall episode (re-armed each time the gate next
        opens, so a later unrelated stall is visible too; ADVICE r4) and
        the ``transfer_gate_backstops`` fleet counter increments (every
        fire: the counter is the quantitative record, the log is the
        narrative one).
    counters: EventCounters | None
        Backstop-fire sink; defaults to the process-wide
        ``blendjax.utils.timing.fleet_counters`` so
        ``FleetSupervisor.health()`` sees the fires.
    """

    def __init__(self, timeout=5.0, counters=None):
        from blendjax.utils.timing import fleet_counters

        self._cond = threading.Condition()
        self._inflight = 0
        self.timeout = timeout
        self._warned = False
        self._counters = counters if counters is not None else fleet_counters

    def wait(self, timeout=None, stop=None):
        """Feed-worker side: block while any transfer is in flight.

        Returns ``True`` when the gate actually opened, ``False`` when
        the wait ended for another reason — ``stop`` (an optional
        ``threading.Event``) was set, so a closing loader never sits out
        the full backstop, or the liveness backstop expired."""
        deadline = time.monotonic() + (
            self.timeout if timeout is None else timeout
        )
        with self._cond:
            while self._inflight > 0:
                if stop is not None and stop.is_set():
                    return False
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    self._counters.incr("transfer_gate_backstops")
                    if not self._warned:
                        self._warned = True
                        log.warning(
                            "TransferGate backstop fired after %.1fs: a "
                            "transfer is outliving the gate timeout "
                            "(crashed pump, or raise TransferGate("
                            "timeout=...))", self.timeout,
                        )
                    return False
                self._cond.wait(min(0.1, remaining))
        return True

    @contextlib.contextmanager
    def transfer(self):
        """Transfer side: hold the gate closed for the duration of the
        block.  Re-entrant across threads: the gate opens when the LAST
        concurrent transfer exits."""
        with self._cond:
            self._inflight += 1
        try:
            yield
        finally:
            with self._cond:
                self._inflight -= 1
                if self._inflight <= 0:
                    # gate opens: re-arm the backstop warning so the next
                    # stall episode logs again
                    self._warned = False
                    self._cond.notify_all()


def _resolve_gate(transfer_gate, num_workers):
    """'auto' enables the gate only where serializing wins: a non-cpu
    backend (there is a real transfer engine to protect) on a host whose
    cores are outnumbered by feed threads + the transfer pump."""
    if transfer_gate == "auto":
        cores = os.cpu_count() or 1
        if cores <= num_workers + 1 and jax.default_backend() != "cpu":
            return TransferGate()
        return None
    if transfer_gate is True:
        return TransferGate()
    if transfer_gate in (False, None):
        return None
    if isinstance(transfer_gate, TransferGate):
        return transfer_gate  # caller-supplied gate (shared across streams)
    raise ValueError(
        f"transfer_gate must be 'auto', a bool, None, or a TransferGate; "
        f"got {transfer_gate!r}"
    )


def _resolve_arena(arena, dataset, collate_fn, num_workers, prefetch):
    """Resolve JaxStream's ``arena`` option to an ArenaPool (or None).

    'auto' (the default) enables arena-pooled batch assembly whenever
    the dataset supports the batched stream path and the default collate
    is in use — i.e. fixed-shape raw-buffer streams get recycled batch
    buffers out of the box, with the legacy collate fallback applying
    per key for ragged/compat traffic.  Pool depth covers every place a
    batch can be in flight at once (loader queue + device queue + one in
    transfer + one building per worker).
    """
    from blendjax.btt.arena import ArenaPool

    # identity checks: `0 in (False, None)` is True, and arena=0 must hit
    # ArenaPool's pool_size validation below, not silently disable
    if arena is False or arena is None:
        return None
    if isinstance(arena, ArenaPool):
        return arena
    supported = (
        collate_fn is None
        and hasattr(dataset, "supports_batched_stream")
        and dataset.supports_batched_stream()
    )
    if arena == "auto":
        if not supported:
            return None
        return ArenaPool(pool_size=num_workers + prefetch + 3)
    if arena is True:
        if not supported:
            raise ValueError(
                "arena=True requires a dataset whose batched stream path "
                "is available (no recording/per-item transform) and the "
                "default collate"
            )
        return ArenaPool(pool_size=num_workers + prefetch + 3)
    if isinstance(arena, int):
        return ArenaPool(pool_size=arena)
    raise ValueError(
        f"arena must be 'auto', a bool, None, an int pool size, or an "
        f"ArenaPool; got {arena!r}"
    )


def own_arena_leaves(host_batch, arena):
    """Host-copy the leaves of ``host_batch`` still backed by ``arena``
    memory, returning a pytree safe to hold past the arena's recycle.

    On the CPU backend ``jax.device_put`` zero-copies aligned numpy
    arrays (``may_alias=False`` included): the resulting ``jax.Array``
    ALIASES the arena buffer, so recycling the arena would let the next
    batch's scatter mutate an already-transferred "device" batch in
    place.  Leaves a copying transform already detached are passed
    through untouched; real accelerators never need this — their H2D DMA
    is the copy, fenced by ``block_until_ready`` before recycle.  Shared
    by :func:`device_prefetch` and the podracer fan-in
    (:meth:`blendjax.parallel.podracer.SegmentFanIn.to_device`)."""
    bufs = tuple(arena.buffers.values())

    def _own(x):
        arr = np.asarray(x)
        if any(np.may_share_memory(arr, b) for b in bufs):
            return np.array(arr)
        return x

    return jax.tree.map(_own, host_batch)


def put_batch(batch, sharding=None):
    """Place one host batch (numpy pytree) onto device(s).

    With no ``sharding``: default device.  With a sharding on a single-host
    mesh: ``device_put`` shards directly.  On multi-host meshes the local
    batch is treated as this process's shard of the global batch.
    """
    if sharding is None:
        return jax.device_put(batch)
    if jax.process_count() > 1:
        # local arrays are SHARDS of the global batch here — validating
        # them against the global sharding spec would spuriously reject
        # valid feeds; make_array_from_process_local_data does its own
        # global-shape reconstruction and validation
        return jax.tree.map(
            lambda x: jax.make_array_from_process_local_data(
                sharding, np.asarray(x)
            ),
            batch,
        )
    leaf = next(iter(jax.tree.leaves(batch)), None)
    if leaf is not None and hasattr(leaf, "shape"):
        # shard_shape validates per-DIMENSION divisibility against the
        # sharding's partition spec — the old total-device-count check
        # wrongly rejected multi-axis shardings (e.g. P('data','seq')
        # over an 8-device mesh only needs batch % data_axis == 0)
        try:
            sharding.shard_shape(tuple(leaf.shape))
        except Exception as e:
            raise ValueError(
                f"batch of shape {tuple(leaf.shape)} not shardable as "
                f"{sharding}: {e}; pick batch/sequence sizes divisible "
                "by the mesh axes they shard over"
            ) from e
    return jax.device_put(batch, sharding)


def device_prefetch(iterator, size=2, sharding=None, transform=None, timer=None,
                    gate=None):
    """Wrap ``iterator`` (host batches) into an iterator of device batches.

    Params
    ------
    iterator: iterable of numpy pytrees
    size: int
        Batches kept in flight (2 = classic double buffering).
    sharding: jax.sharding.Sharding | None
        Placement for every leaf (leading-axis batch sharding for DP).
    transform: callable | None
        Host-side pre-transfer hook (key selection, dtype cast, layout).
    timer: StageTimer | None
        Records ``device_put`` stage times.
    gate: TransferGate | None
        When set, the gate is held closed for each transfer (including its
        completion, so the pump owns the core end to end) — see
        :class:`TransferGate`.
    """
    if size < 1:
        raise ValueError("prefetch size must be >= 1")
    timer = timer or StageTimer()
    q: queue.Queue = queue.Queue(maxsize=size)
    stop = threading.Event()

    def _producer():
        from blendjax.btt.arena import ArenaBatch

        batch = None
        try:
            for batch in iterator:
                if stop.is_set():
                    if isinstance(batch, ArenaBatch):
                        batch.recycle()
                    return
                host_batch = (
                    batch.data if isinstance(batch, ArenaBatch) else batch
                )
                if transform is not None:
                    host_batch = transform(host_batch)
                if isinstance(batch, ArenaBatch) and \
                        jax.default_backend() == "cpu":
                    # see own_arena_leaves: CPU device_put aliases arena
                    # memory, so detach before the recycle below
                    host_batch = own_arena_leaves(host_batch, batch.arena)
                with timer.stage("device_put"):
                    if gate is not None:
                        with gate.transfer():
                            dev_batch = put_batch(host_batch, sharding)
                            # the gate must stay closed until the bytes have
                            # actually landed, not just been dispatched
                            jax.block_until_ready(dev_batch)
                    else:
                        dev_batch = put_batch(host_batch, sharding)
                if isinstance(batch, ArenaBatch):
                    # the arena returns to the freelist only once the
                    # transfer has COMPLETED (dispatch alone still reads
                    # host memory); a slow trainer therefore backpressures
                    # into the pool instead of allocating unboundedly.
                    # The gated path already blocked above.
                    if gate is None:
                        jax.block_until_ready(dev_batch)
                    with timer.stage("recycle"):
                        batch.recycle()
                while True:
                    try:
                        q.put(dev_batch, timeout=0.5)
                        break
                    except queue.Full:
                        if stop.is_set():
                            return
            q.put(_SENTINEL)
        except BaseException as exc:  # noqa: BLE001 - forwarded to consumer
            # a transform/put failure must not strand the in-hand arena
            # (recycle is idempotent, so an already-recycled batch is safe)
            if isinstance(batch, ArenaBatch):
                batch.recycle()
            q.put(exc)

    thread = threading.Thread(target=_producer, daemon=True, name="bjx-prefetch")
    thread.start()
    try:
        while True:
            item = q.get()
            if item is _SENTINEL:
                return
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        stop.set()
        try:
            while True:
                q.get_nowait()
        except queue.Empty:
            pass
        thread.join(timeout=5)


class JaxStream:
    """End-to-end feed: remote stream -> batches -> device, with timing.

    The one-stop replacement for the reference's
    ``DataLoader(RemoteIterableDataset(...))`` pattern::

        ds = btt.RemoteIterableDataset(addresses, max_items=...)
        stream = btt.JaxStream(ds, batch_size=8, num_workers=4,
                               sharding=data_sharding(mesh))
        for batch in stream:          # jax.Arrays already in HBM
            state, loss = train_step(state, batch)

    ``stream.timer.summary()`` exposes the per-stage feed times (recv /
    scatter / arena_wait / device_put / recycle on the arena path,
    recv / collate / device_put on the legacy path);
    ``stream.duty_cycle(...)`` measures the feed's headroom.

    ``arena='auto'`` (default) assembles batches into recycled
    arena-pooled buffers (:mod:`blendjax.btt.arena`) whenever the
    dataset supports the batched stream path: one host copy from wire
    frame to batch slot, arenas recycled only after each device
    transfer completes (pool exhaustion = backpressure).  Pass False to
    force the legacy per-batch allocation, an int to size the pool, or
    a shared ``ArenaPool``.
    """

    def __init__(
        self,
        dataset,
        batch_size,
        num_workers=1,
        sharding=None,
        transform=None,
        prefetch=2,
        shard=(0, 1),
        drop_last=True,
        collate_fn=None,
        timer=None,
        transfer_gate="auto",
        arena="auto",
    ):
        from blendjax.btt.loader import BatchLoader

        self.gate = _resolve_gate(transfer_gate, num_workers)
        self.arena_pool = _resolve_arena(
            arena, dataset, collate_fn, num_workers, prefetch
        )
        self.loader = BatchLoader(
            dataset,
            batch_size,
            num_workers=num_workers,
            shard=shard,
            drop_last=drop_last,
            collate_fn=collate_fn,
            timer=timer,
            gate=self.gate,
            arena_pool=self.arena_pool,
        )
        self.sharding = sharding
        self.transform = transform
        self.prefetch = prefetch
        self.timer = self.loader.timer

    def __len__(self):
        return len(self.loader)

    def duty_cycle(self, name):
        """Fraction of wall time (since the timer's last reset) spent in
        stage ``name`` — e.g. ``duty_cycle('device_put')`` for the feed's
        transfer share, or a caller-recorded ``'step'`` stage for train
        duty cycle.  Delegates to :meth:`StageTimer.duty_cycle`."""
        return self.timer.duty_cycle(name)

    def __iter__(self):
        return device_prefetch(
            iter(self.loader),
            size=self.prefetch,
            sharding=self.sharding,
            transform=self.transform,
            timer=self.timer,
            gate=self.gate,
        )

    def close(self):
        self.loader.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
