"""The counter that ``serve.batch_pad_pct`` reads, where the program counts
it: ``PolicyServer`` runs a tick of ``n`` real rows at the least bucket that
holds them and adds ``bucket - n`` to ``serve_batch_pad``.  A stub model on
the CPU; every wait is bounded.  The three cases of
``chipbench/tests/test_batch_pad.py``, inside tier 1.
"""

from __future__ import annotations

import threading

import numpy as np
import pytest

from chipbench.readers import serve

WAIT_S = 30.0
BUCKETS = [8, 16, 32, 40, 48, 64]


class _Stub:
    """``pred = sum(obs)``; ``shapes`` holds (bucket, real rows) a tick."""

    kind = "stub"
    obs_dim = 2

    def __init__(self, slots):
        self.slots = self.pad_slot = slots
        self.pos = np.zeros(slots + 1, np.int64)
        self.shapes = []

    def reset_rows(self, idx):
        self.pos[idx] = 0

    def step_rows(self, idx, obs):
        self.pos[idx] += 1
        self.shapes.append((len(idx), int((idx != self.pad_slot).sum())))
        return obs.sum(-1, keepdims=True).astype(np.float32)


@pytest.mark.parametrize("rows,bucket", [(33, 40), (32, 32), (41, 48)])
def test_a_tick_runs_the_least_bucket_and_counts_its_pad_rows(rows, bucket):
    from blendjax.btt.faults import FaultPolicy
    from blendjax.serve import ServeClient, start_server_thread
    from blendjax.utils.timing import EventCounters

    model, counters = _Stub(64), EventCounters()
    # a window long enough that the tick waits for every live episode
    with start_server_thread(model, counters=counters, max_batch=64,
                             buckets=BUCKETS, tick_ms=5000.0) as h:
        clients = [ServeClient(h.address, timeoutms=int(WAIT_S * 1e3),
                               fault_policy=FaultPolicy(max_retries=0))
                   for _ in range(rows)]
        try:
            for c in clients:
                c.reset()
            before = counters.snapshot()
            out = [None] * rows

            def step(i):
                out[i] = clients[i].step(np.full(2, i, np.float32))
            threads = [threading.Thread(target=step, args=(i,), daemon=True)
                       for i in range(rows)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(WAIT_S)
            assert not any(t.is_alive() for t in threads)
            after = counters.snapshot()
        finally:
            for c in clients:
                c.close()
    assert model.shapes == [(bucket, rows)]
    assert [float(np.asarray(o["pred"])[0]) for o in out] == [
        2.0 * i for i in range(rows)]
    events = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    assert events["serve_batches"] == 1
    assert events.get("serve_batch_pad", 0) == bucket - rows
    obs = {"events": events, "step_s": np.zeros(rows)}
    assert serve.batch_pad_pct(obs, None) == pytest.approx(
        100.0 * (bucket - rows) / bucket)
