"""Feed-bound benchmark: the consumer-side batch-assembly ceiling,
legacy collate vs arena-pooled zero-copy scatter.

No other benchmark mode observes the FEED ceiling — every number has a
real train step (or a real wire) in the loop, so the assembly cost is
invisible.  This mode isolates it: pre-encoded raw-buffer messages
(exactly what the wire carries) are replayed through both assembly
paths with a **trivial train step** (touch one byte, no jax), so the
measured batches/sec IS the feed limit — the rate above which no
trainer can be fed by one worker, whatever the accelerator does.

Paths compared on identical frames:

- ``legacy``: per-message ``wire.decode`` (``np.frombuffer`` views) ->
  ``collate`` (stack into a freshly allocated batch array) — the
  pre-arena hot path, one alloc + one stacking copy per batch;
- ``arena``: the deferred ``_BatchBuilder`` scattering each message's
  payload frames straight into a recycled :class:`ArenaPool` arena
  (one GIL-released ``gather_into`` per leaf per batch, zero batch
  allocations), recycled after the trivial step "consumes" the batch —
  the production path ``stream_batches`` takes.

Stage timings (``arena_wait`` / ``scatter`` / ``recycle``) ride along so
the BENCH artifact shows where arena time goes.  Runs jax-free: the
feed limit is a host number and needs no accelerator.
"""

from __future__ import annotations

import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _messages(width, height, channels, nmsgs, seed=0):
    import numpy as np

    from blendjax import wire

    rng = np.random.default_rng(seed)
    msgs = []
    for i in range(nmsgs):
        img = rng.integers(0, 255, (height, width, channels), dtype=np.uint8)
        msgs.append(
            wire.encode(
                {"image": img, "frameid": i, "btid": 0}, raw_buffers=True
            )
        )
    return msgs


def _run_legacy(msgs, batch, seconds):
    """stream()-era assembly: decode views, collate-stack each batch."""
    from blendjax import wire
    from blendjax.btt.collate import collate

    nmsgs = len(msgs)
    i = 0
    n = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        items = [wire.decode(msgs[(i + j) % nmsgs]) for j in range(batch)]
        out = collate(items)
        out["image"][0, 0, 0, 0]  # trivial train step: touch the batch
        i += batch
        n += 1
    return n, time.perf_counter() - t0


def _run_arena(msgs, batch, seconds, pool_size, timer, parallel=False):
    """Production arena path: deferred scatter into recycled arenas."""
    from blendjax.btt.arena import ArenaPool
    from blendjax.btt.dataset import _BatchBuilder

    pool = ArenaPool(pool_size)
    builder = _BatchBuilder(
        batch, defer=True, schema_cache={}, parallel=parallel
    )
    nmsgs = len(msgs)
    clock = time.perf_counter
    i = 0
    n = 0
    wait_s = scatter_s = recycle_s = 0.0
    t0 = clock()
    while clock() - t0 < seconds:
        # manual stage accounting, flushed in bulk after the window
        # (a per-batch locked timer.add would itself be a visible stage
        # at ~100 us per batch)
        s0 = clock()
        arena = pool.acquire()
        s1 = clock()
        builder.reset(arena)
        add = builder.add_message
        for j in range(batch):
            add(msgs[(i + j) % nmsgs])
        s2 = clock()
        out = builder.finish()
        s3 = clock()
        out["image"][0, 0, 0, 0]  # trivial train step: touch the batch
        s4 = clock()
        arena.release()
        s5 = clock()
        wait_s += s1 - s0
        scatter_s += s3 - s2
        recycle_s += s5 - s4
        i += batch
        n += 1
    dt = clock() - t0
    timer.add_bulk("arena_wait", wait_s, n)
    timer.add_bulk("scatter", scatter_s, n)
    timer.add_bulk("recycle", recycle_s, n)
    return n, dt


def _run_arena_instrumented(msgs, batch, seconds, pool_size, timer,
                            hub=None, parallel=False):
    """The arena path with FULL telemetry in the loop: one per-batch
    ``timer.add`` per stage, landing in the latency histograms (unlike
    the production path's bulk aggregation) — the deliberately-
    worst-case *enabled* arm of ``telemetry_overhead_x``.  Paired
    against the identical loop with ``StageTimer(histograms=False)``
    and no hub, the ratio isolates what the telemetry plane itself
    costs on the feed hot path.  ``hub`` is scraped AFTER the timed
    window (production scrape cadence is seconds-to-minutes; scraping
    inside a 0.25 s window would price a 40x-production cadence, and
    its allocation burst measurably pollutes the next window)."""
    from blendjax.btt.arena import ArenaPool
    from blendjax.btt.dataset import _BatchBuilder

    import gc

    pool = ArenaPool(pool_size)
    builder = _BatchBuilder(
        batch, defer=True, schema_cache={}, parallel=parallel
    )
    nmsgs = len(msgs)
    clock = time.perf_counter
    add = timer.add
    i = 0
    n = 0
    # both arms start from a settled allocator: the previous window's
    # allocation debt (a hub scrape's in particular) must not be billed
    # to whichever arm happens to run next
    gc.collect()
    t0 = clock()
    while clock() - t0 < seconds:
        s0 = clock()
        arena = pool.acquire()
        s1 = clock()
        add("arena_wait", s1 - s0)
        builder.reset(arena)
        addmsg = builder.add_message
        for j in range(batch):
            addmsg(msgs[(i + j) % nmsgs])
        s2 = clock()
        out = builder.finish()
        s3 = clock()
        add("scatter", s3 - s2)
        out["image"][0, 0, 0, 0]  # trivial train step: touch the batch
        s4 = clock()
        arena.release()
        add("recycle", clock() - s4)
        i += batch
        n += 1
    dt = clock() - t0
    if hub is not None:
        hub.scrape()  # outside the timed window (see docstring)
    return n, dt


def _rate(run_result):
    n, dt = run_result
    return n / dt if dt > 0 else 0.0


def measure_telemetry_overhead(
    width=160, height=120, channels=3, batch=8, seconds=3.2,
    pool_size=4, nmsgs=64,
):
    """``telemetry_overhead_x``: arena-feed throughput with the
    telemetry plane fully ON (per-batch latency-histogram adds + a
    registered TelemetryHub scraped between windows) over the SAME loop
    with histograms off and no hub.  Interleaved order-alternating
    windows, ratio of the two arms' median rates (window noise on
    shared CI hosts is i.i.d., so the medians converge where per-pair
    ratios stay noisy).  1.0 = free; the acceptance floor is 0.95
    (<= 5% overhead)."""
    from blendjax.obs.hub import TelemetryHub
    from blendjax.utils.timing import StageTimer

    msgs = _messages(width, height, channels, nmsgs)
    hub = TelemetryHub()
    timer_on = StageTimer()  # histograms on (the default)
    timer_off = StageTimer(histograms=False)
    hub.register("feed", timer=timer_on)
    # warmup both arms (first-touch faults, import costs)
    _run_arena_instrumented(msgs, batch, 0.2, pool_size, timer_off)
    _run_arena_instrumented(msgs, batch, 0.2, pool_size, timer_on, hub)
    win = 0.2
    # the seconds budget is honored (rounds = seconds / window); 16+
    # windows per arm (seconds >= 3.2, the default) is what the ratio
    # needs for a stable median on this host class — occasional windows
    # run 30% slow, and shallower medians swing ±4% run-to-run
    rounds = max(4, int(seconds / win))
    on_rates, off_rates = [], []
    for r in range(rounds):
        # alternate A/B order per round so slow drift (thermal, noisy
        # CI neighbors) cancels; the verdict is the RATIO OF MEDIANS —
        # on this class of shared host the window-to-window variance is
        # i.i.d. noise (~±5%) rather than drift, so per-pair ratios
        # inherit two windows' noise each while the two medians
        # converge independently
        if r % 2 == 0:
            off_rates.append(_rate(_run_arena_instrumented(
                msgs, batch, win, pool_size, timer_off
            )))
            on_rates.append(_rate(_run_arena_instrumented(
                msgs, batch, win, pool_size, timer_on, hub
            )))
        else:
            on_rates.append(_rate(_run_arena_instrumented(
                msgs, batch, win, pool_size, timer_on, hub
            )))
            off_rates.append(_rate(_run_arena_instrumented(
                msgs, batch, win, pool_size, timer_off
            )))
    on_rates.sort()
    off_rates.sort()
    on_rate = on_rates[len(on_rates) // 2] if on_rates else 0.0
    off_rate = off_rates[len(off_rates) // 2] if off_rates else 0.0

    def spread(rates):
        return {
            "min": round(rates[0], 1), "median": round(
                rates[len(rates) // 2], 1
            ), "max": round(rates[-1], 1), "n": len(rates),
        }

    return {
        "telemetry_overhead_x": (
            round(on_rate / off_rate, 3) if off_rate else 0.0
        ),
        "enabled_batches_per_sec": round(on_rate, 2),
        "disabled_batches_per_sec": round(off_rate, 2),
        # per-arm window spreads: the artifact's own noise witness (a
        # single-core shared host swings individual windows by 30%+;
        # the reader can judge the ratio's confidence from these)
        "enabled_windows": spread(on_rates) if on_rates else None,
        "disabled_windows": spread(off_rates) if off_rates else None,
        # the enabled arm's stage percentiles double as the artifact's
        # proof that the histograms observed the feed
        "stages": timer_on.summary(),
    }


def _run_workers(fn, workers):
    """Run ``fn(worker_id)`` on ``workers`` threads (the production
    BatchLoader shape: each worker assembles whole batches concurrently,
    sharing the GIL); returns aggregate batches/sec.  ``fn`` returns
    (batches, elapsed_s)."""
    import threading

    results = [None] * workers
    threads = []
    start = threading.Barrier(workers)

    def run(w):
        start.wait()
        results[w] = fn(w)

    for w in range(workers):
        t = threading.Thread(target=run, args=(w,), daemon=True)
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    return sum(n / dt for n, dt in results if dt > 0)


def measure(
    width=160,
    height=120,
    channels=3,
    batch=8,
    seconds=2.0,
    pool_size=None,
    nmsgs=64,
    workers=None,
    telemetry_seconds=None,
):
    """Feed-limit record for the BENCH artifact.

    Returns ``{"feed_limit_batches_per_sec": {"legacy": .., "arena": ..},
    "arena_over_legacy": .., "stages": {...}, ...}``; frame geometry
    defaults to the acceptance shape (160x120x3 uint8, batch 8).

    ``workers=1`` (default) measures the per-thread assembly ceiling —
    the stable, scheduler-independent number.  ``workers>1`` runs the
    production BatchLoader shape (N assembly threads sharing the GIL),
    where the arena path's GIL-released native gather additionally
    overlaps copies across cores; on small containers that measurement
    inherits OS-scheduler noise, so it is opt-in rather than the
    headline.
    """
    from blendjax.utils.timing import StageTimer

    if workers is None:
        workers = 1
    if pool_size is None:
        pool_size = 2 * workers + 2
    parallel = workers > 1
    # per-worker message sets so no two threads share frame buffers
    worker_msgs = [
        _messages(width, height, channels, nmsgs, seed=w)
        for w in range(workers)
    ]
    timer = StageTimer()
    # warmup before the timed windows (imports, buffer faults) so neither
    # path pays first-touch costs inside its measurement
    _run_legacy(worker_msgs[0], batch, 0.2)
    _run_arena(worker_msgs[0], batch, 0.2, pool_size, StageTimer(), parallel)
    # Many short PAIRED A/B windows, reported at the median-ratio pair:
    # adjacent windows see the same background noise, so the per-pair
    # ratio is far stabler than any long-window rate on a small shared
    # host (measured: 1.0 s windows swing a 1.35x true ratio between
    # 0.94x and 1.41x; 0.3 s paired medians hold within a few percent).
    win = 0.3
    rounds = max(5, int(seconds / win))
    pairs = []
    for _ in range(rounds):
        legacy_r = _run_workers(
            lambda w: _run_legacy(worker_msgs[w], batch, win), workers
        )
        arena_r = _run_workers(
            lambda w: _run_arena(
                worker_msgs[w], batch, win, pool_size, timer, parallel
            ),
            workers,
        )
        if legacy_r > 0:
            pairs.append((arena_r / legacy_r, legacy_r, arena_r))
    pairs.sort()
    _, legacy, arena = pairs[len(pairs) // 2] if pairs else (0.0, 0.0, 0.0)
    out = {
        "frame": f"{width}x{height}x{channels}",
        "dtype": "uint8",
        "batch": batch,
        "workers": workers,
        "pool_size": pool_size,
        "feed_limit_batches_per_sec": {
            "legacy": round(legacy, 2),
            "arena": round(arena, 2),
        },
        "feed_limit_images_per_sec": {
            "legacy": round(legacy * batch, 2),
            "arena": round(arena * batch, 2),
        },
        "arena_over_legacy": round(arena / legacy, 3) if legacy else None,
        "stages": timer.summary(),
    }
    # telemetry-plane sanity number: hub + histograms on vs off over the
    # same instrumented loop (docs/observability.md; floor 0.95).  Runs
    # at its own default budget (the ratio needs ~16 windows per arm
    # for a stable median on shared hosts) rather than the feed
    # windows' — ``telemetry_seconds`` overrides for quick runs
    try:
        tel = measure_telemetry_overhead(
            width=width, height=height, channels=channels, batch=batch,
            pool_size=pool_size, nmsgs=nmsgs,
            **({} if telemetry_seconds is None
               else {"seconds": telemetry_seconds}),
        )
        out["telemetry"] = tel
        out["telemetry_overhead_x"] = tel["telemetry_overhead_x"]
    except Exception as exc:  # noqa: BLE001 - the feed numbers still land
        out["telemetry_error"] = f"{type(exc).__name__}: {exc}"
    return out


def main():
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--width", type=int, default=160)
    ap.add_argument("--height", type=int, default=120)
    ap.add_argument("--channels", type=int, default=3)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--pool-size", type=int, default=None)
    ap.add_argument("--workers", type=int, default=None)
    ap.add_argument("--telemetry-seconds", type=float, default=None,
                    help="telemetry_overhead_x window budget "
                         "(default 3.2 s; the ratio needs ~16 windows "
                         "per arm for a stable median)")
    args = ap.parse_args()
    print(
        json.dumps(
            {
                "phase": "feed_bound",
                **measure(
                    width=args.width,
                    height=args.height,
                    channels=args.channels,
                    batch=args.batch,
                    seconds=args.seconds,
                    pool_size=args.pool_size,
                    workers=args.workers,
                    telemetry_seconds=args.telemetry_seconds,
                ),
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
