"""Arena-pooled zero-copy batch assembly (ISSUE 1 tentpole).

Locks two contracts:

1. **Parity** — the arena/deferred builder path produces byte-identical
   batches to the legacy ``stream() + collate`` path across nested
   dicts/tuples, ragged leaves, mixed dtypes, non-contiguous arrays, and
   both wire encodings (raw-buffer multipart and compat pickle), with
   and without a recycled arena, including the precompiled-plan fast
   path AND its generic-walk fallback.
2. **Backpressure** — a slow consumer exhausts the ArenaPool and stalls
   assembly (bounded memory) instead of allocating; recycling resumes it.
"""

import threading
import time

import numpy as np
import pytest

from blendjax import wire
from blendjax.btt.arena import Arena, ArenaBatch, ArenaPool
from blendjax.btt.collate import collate
from blendjax.btt.dataset import RemoteIterableDataset, _BatchBuilder
from helpers.producers import ProducerFleet


def assert_tree_equal(a, b, path=""):
    """Structure + dtype + byte equality over collated pytrees."""
    if isinstance(a, dict):
        assert isinstance(b, dict) and a.keys() == b.keys(), path
        for k in a:
            assert_tree_equal(a[k], b[k], f"{path}/{k}")
    elif isinstance(a, (list, tuple)):
        assert type(a) is type(b) and len(a) == len(b), path
        for i, (x, y) in enumerate(zip(a, b)):
            assert_tree_equal(x, y, f"{path}[{i}]")
    elif isinstance(a, np.ndarray):
        assert isinstance(b, np.ndarray), (path, type(b))
        assert a.dtype == b.dtype and a.shape == b.shape, path
        np.testing.assert_array_equal(a, b, err_msg=path)
    else:
        assert a == b, (path, a, b)


def build_batch(msgs, batch_size=None, arena=None, cache=None):
    b = _BatchBuilder(
        batch_size or len(msgs),
        arena=arena,
        defer=True,
        schema_cache=cache if cache is not None else {},
    )
    for m in msgs:
        b.add_message(m)
    return b.finish()


def legacy_batch(msgs):
    return collate([wire.decode(m) for m in msgs])


class TestArenaParity:
    """Arena path == legacy collate path, byte for byte."""

    @pytest.mark.parametrize("raw", [True, False])
    def test_nested_dicts_tuples_scalars(self, raw):
        def make(i):
            return {
                "image": np.full((8, 6, 3), i, np.uint8),
                "nested": {
                    "xy": np.array([i, i + 1], np.float32),
                    "deep": {"flag": bool(i % 2)},
                    "tag": f"t{i}",
                },
                "tup": (np.arange(3, dtype=np.int32) + i, i * 1.5),
                "pts": [np.full((2, 2), i, np.float64)],
                "frameid": i,
            }

        cache = {}
        for trial in range(2):  # second trial exercises the cached plan
            msgs = [wire.encode(make(i), raw_buffers=raw) for i in range(4)]
            got = build_batch(msgs, cache=cache)
            assert_tree_equal(legacy_batch(msgs), got)

    def test_ragged_and_mixed_dtype_degrade(self):
        msgs = []
        for i in range(3):
            msgs.append(wire.encode({
                "img": np.full((4 + i, 3), i, np.uint8),  # ragged
                "val": np.array([i], np.float32 if i < 2 else np.float64),
                "k": i,
            }, raw_buffers=True))
        got = build_batch(msgs, batch_size=4)  # also a partial batch
        ref = legacy_batch(msgs)
        assert_tree_equal(ref, got)
        assert isinstance(got["img"], list)  # ragged stays a list
        assert got["val"].dtype == np.float64  # upcast rule preserved

    def test_non_contiguous_arrays(self):
        base = np.arange(96, dtype=np.int16).reshape(8, 12)
        msgs = [
            wire.encode(
                {"a": np.asfortranarray(base + i), "b": base[::2, ::3] + i},
                raw_buffers=True,
            )
            for i in range(4)
        ]
        assert_tree_equal(legacy_batch(msgs), build_batch(msgs))

    def test_compat_pickle_messages_fall_back_to_collate_rules(self):
        # single-frame pickles carry materialized ndarrays; the builder
        # must match collate exactly for them too (on-by-default path
        # keeps every existing *.blend.py producer working unmodified)
        msgs = [
            wire.encode(
                {"image": np.full((5, 4), i, np.uint8), "frameid": i},
                raw_buffers=False,
            )
            for i in range(4)
        ]
        assert len(msgs[0]) == 1  # really the compat encoding
        assert_tree_equal(legacy_batch(msgs), build_batch(msgs))

    def test_key_semantics_and_plan_fallback(self):
        img = np.zeros((4, 4), np.uint8)
        cache = {}
        # batch 1 fixes the schema/plan
        msgs = [
            wire.encode({"image": img, "frameid": i}, raw_buffers=True)
            for i in range(2)
        ]
        build_batch(msgs, cache=cache)
        # batch 2: an extra key appears -> plan fallback, key adopted
        # (legacy collate keys each batch off its first item)
        msgs2 = [
            wire.encode(
                {"image": img, "frameid": i, "extra": i}, raw_buffers=True
            )
            for i in range(2)
        ]
        got = build_batch(msgs2, cache=cache)
        assert_tree_equal(legacy_batch(msgs2), got)
        assert "extra" in got
        # batch 3: a late-message-only key is dropped, missing key raises
        msgs3 = [
            wire.encode({"image": img, "frameid": 0}, raw_buffers=True),
            wire.encode(
                {"image": img, "frameid": 1, "late": 9}, raw_buffers=True
            ),
        ]
        got3 = build_batch(msgs3, cache=cache)
        assert "late" not in got3
        with pytest.raises(KeyError):
            build_batch([
                wire.encode({"image": img, "frameid": 0}, raw_buffers=True),
                wire.encode({"image": img}, raw_buffers=True),
            ], cache=cache)

    def test_eager_drift_degrade_does_not_alias_recycled_arena(self):
        """Eager (shm-style) assembly: a mid-batch shape drift degrades a
        key to a ragged list; the already-scattered slots must be COPIES,
        not views into the arena buffer a later batch will overwrite."""
        pool = ArenaPool(1)
        arena = pool.acquire()
        b1 = _BatchBuilder(2, arena=arena)
        b1.add_message(wire.encode({"x": np.array([0, 1, 2, 3])},
                                   raw_buffers=True))
        b1.add_message(wire.encode({"x": np.array([9, 9])},
                                   raw_buffers=True))  # drift -> ragged
        batch1 = b1.finish()
        arena.release()
        arena2 = pool.acquire()  # same arena, recycled
        b2 = _BatchBuilder(2, arena=arena2)
        for _ in range(2):
            b2.add_message(wire.encode({"x": np.array([-1, -1, -1, -1])},
                                       raw_buffers=True))
        b2.finish()
        np.testing.assert_array_equal(batch1["x"][0], [0, 1, 2, 3])

    def test_arena_buffers_are_recycled_not_reallocated(self):
        pool = ArenaPool(2)
        cache = {}
        arena = pool.acquire()
        msgs = [
            wire.encode(
                {"image": np.full((16, 16), i, np.uint8)}, raw_buffers=True
            )
            for i in range(4)
        ]
        first = build_batch(msgs, arena=arena, cache=cache)
        buf_id = id(first["image"])
        arena.release()
        arena2 = pool.acquire()
        assert arena2 is arena  # freelist reuse
        msgs2 = [
            wire.encode(
                {"image": np.full((16, 16), 40 + i, np.uint8)},
                raw_buffers=True,
            )
            for i in range(4)
        ]
        second = build_batch(msgs2, arena=arena2, cache=cache)
        # same backing buffer, new bytes — zero per-batch allocation
        assert id(second["image"]) == buf_id
        assert_tree_equal(legacy_batch(msgs2), second)


class TestArenaPoolBackpressure:
    def test_exhaustion_blocks_then_recycle_unblocks(self):
        pool = ArenaPool(2)
        a1, a2 = pool.acquire(), pool.acquire()
        assert pool.in_use == 2
        t0 = time.monotonic()
        assert pool.acquire(timeout=0.2) is None  # exhausted: blocks
        assert time.monotonic() - t0 >= 0.2
        got = []
        waiter = threading.Thread(
            target=lambda: got.append(pool.acquire(timeout=5.0)), daemon=True
        )
        waiter.start()
        time.sleep(0.05)
        a1.release()  # consumer finally recycles
        waiter.join(timeout=5)
        assert got and got[0] is a1
        a2.release()
        assert pool.in_use == 1  # got[0] still checked out

    def test_stop_event_aborts_wait(self):
        pool = ArenaPool(1)
        pool.acquire()
        stop = threading.Event()
        res = {}

        def wait():
            res["a"] = pool.acquire(stop_event=stop)

        t = threading.Thread(target=wait, daemon=True)
        t.start()
        time.sleep(0.05)
        stop.set()
        t.join(timeout=5)
        assert res["a"] is None

    def test_double_recycle_is_idempotent(self):
        pool = ArenaPool(1)
        arena = pool.acquire()
        batch = ArenaBatch({"x": np.zeros(2)}, arena)
        batch.recycle()
        batch.recycle()
        assert pool.in_use == 0
        assert pool.acquire() is arena

    def test_stream_backpressures_into_pool(self):
        """End to end over real sockets: a consumer that never recycles
        stalls the stream once the pool drains; recycling resumes it."""
        pool = ArenaPool(2)
        with ProducerFleet(num_producers=1, raw_buffers=True) as fleet:
            ds = RemoteIterableDataset(
                fleet.addresses, max_items=64, timeoutms=20000
            )
            gen = ds.stream_batches(4, arena_pool=pool)
            held = [next(gen), next(gen)]  # exhausts the pool
            assert all(isinstance(b, ArenaBatch) for b in held)
            assert pool.in_use == 2
            blocked = []
            t = threading.Thread(
                target=lambda: blocked.append(next(gen)), daemon=True
            )
            t.start()
            time.sleep(0.5)
            assert not blocked, "stream must stall while the pool is dry"
            held[0].recycle()  # transfer "completes"
            t.join(timeout=10)
            assert len(blocked) == 1
            assert_is_batch(blocked[0])
            gen.close()

    def test_generator_close_does_not_double_release_yielded_arena(self):
        """Closing the stream generator right at the yield must NOT
        return the just-yielded batch's arena to the pool — the consumer
        still owns it until recycle()."""
        pool = ArenaPool(2)
        with ProducerFleet(num_producers=1, raw_buffers=True) as fleet:
            ds = RemoteIterableDataset(
                fleet.addresses, max_items=64, timeoutms=20000
            )
            gen = ds.stream_batches(4, arena_pool=pool)
            batch = next(gen)
            gen.close()  # GeneratorExit lands at the suspended yield
        assert isinstance(batch, ArenaBatch)
        assert pool.in_use == 1  # still owned by the yielded batch
        # the arena must not have been handed to anyone else meanwhile
        fresh = pool.acquire(timeout=1.0)
        assert fresh is not batch.arena
        batch.recycle()
        assert pool.in_use == 1  # only `fresh` remains out

    def test_shm_stream_yields_arena_batches(self):
        """The native shm transport threads the same pool through its
        eager (record-lifetime-bounded) builder."""
        import os
        import uuid

        from blendjax.btb.publisher import DataPublisher
        from blendjax.native import native_available

        if not native_available():
            pytest.skip("native ring unavailable")
        addr = f"shm://bjx-test-arena-{os.getpid()}-{uuid.uuid4().hex[:6]}"

        def produce():
            pub = DataPublisher(addr, btid=0, raw_buffers=True,
                                sndtimeoms=500)
            i = 0
            while i < 8:
                if pub.publish(image=np.full((8, 8), i, np.uint8),
                               frameid=i):
                    i += 1
            pub.close()

        t = threading.Thread(target=produce, daemon=True)
        t.start()
        pool = ArenaPool(3)
        ds = RemoteIterableDataset([addr], max_items=8, timeoutms=10000)
        batches = []
        for b in ds.stream_batches(4, arena_pool=pool):
            assert isinstance(b, ArenaBatch)
            batches.append(b.data["frameid"].tolist())
            b.recycle()
        t.join(timeout=10)
        assert batches == [[0, 1, 2, 3], [4, 5, 6, 7]]
        assert pool.in_use == 0

    def test_gather_into_matches_numpy(self):
        from blendjax.native.ring import gather_into

        rng = np.random.default_rng(0)
        parts = [rng.integers(0, 255, (40, 7), np.uint8) for _ in range(6)]
        dst = np.empty((6, 40, 7), np.uint8)
        gather_into(dst, parts)
        np.testing.assert_array_equal(dst, np.stack(parts))
        # buffer-protocol sources (the wire-frame case) and fortran order
        dst2 = np.empty((3, 4, 4), np.float32)
        srcs = [
            np.arange(16, dtype=np.float32).reshape(4, 4) + i for i in range(3)
        ]
        gather_into(
            dst2,
            [memoryview(srcs[0].tobytes()), srcs[1], np.asfortranarray(srcs[2])],
        )
        np.testing.assert_array_equal(dst2, np.stack(srcs))
        with pytest.raises(ValueError, match="bytes"):
            gather_into(np.empty(3, np.uint8), [b"toolongbytes"])


def assert_is_batch(b):
    data = b.data if isinstance(b, ArenaBatch) else b
    assert isinstance(data, dict) and "image" in data


class TestFeedBoundBench:
    def test_measure_reports_both_paths_and_stages(self):
        from benchmarks.feed_bound import measure

        out = measure(width=32, height=24, batch=4, seconds=0.4, nmsgs=8,
                      telemetry_seconds=0.8)
        limits = out["feed_limit_batches_per_sec"]
        assert limits["legacy"] > 0 and limits["arena"] > 0
        assert out["arena_over_legacy"] is not None
        assert {"arena_wait", "scatter", "recycle"} <= set(out["stages"])
        # the telemetry-plane sanity ratio rides along (short budget
        # here: structure only, the real floor is benched at 3.2 s)
        assert out["telemetry_overhead_x"] > 0
        assert out["telemetry"]["enabled_windows"]["n"] >= 4

    def test_bench_assemble_carries_feed_bound(self):
        import bench

        fb = {
            "feed_limit_batches_per_sec": {"legacy": 100.0, "arena": 140.0},
            "arena_over_legacy": 1.4,
            "stages": {"scatter": {"count": 1, "total_s": 0.1,
                                   "mean_ms": 100.0}},
        }
        out = bench.assemble({"host_stream": {"items_per_sec": 1.0}}, feed_bound=fb)
        assert out["feed_bound"] is fb
        assert out["feed_bound"]["feed_limit_batches_per_sec"]["arena"] == 140.0
        line = bench.headline(out)
        assert line["feed_arena_x"] == 1.4
        import json

        assert len(json.dumps(line)) + 1 <= bench.HEADLINE_BYTE_BUDGET
