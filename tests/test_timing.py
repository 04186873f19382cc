"""StageTimer tests: accumulation, duty cycle, thread safety (its spans
in the profiler's trace: tests/test_trace_spans.py)."""

import threading
import time

from blendjax.utils.timing import StageTimer


def test_summary_and_means():
    t = StageTimer()
    with t.stage("a"):
        time.sleep(0.01)
    with t.stage("a"):
        time.sleep(0.01)
    with t.stage("b"):
        pass
    s = t.summary()
    assert s["a"]["count"] == 2
    assert s["a"]["total_s"] >= 0.02
    assert s["a"]["mean_ms"] >= 10
    assert s["b"]["count"] == 1
    assert t.duty_cycle("a") > 0


def test_concurrent_stages():
    t = StageTimer()

    def work():
        for _ in range(100):
            with t.stage("x"):
                pass

    threads = [threading.Thread(target=work) for _ in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert t.count("x") == 400
