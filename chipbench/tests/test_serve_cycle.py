"""The seven per-layer metrics that read ``PolicyServer``'s phase clock and
the requests' stamps (PR 40): each reader on a hand-made window, None on the
parent's counters, None where the phases do not tile the window, and every
new entry of ``BENCHMARK.json`` resolved to its metric file and reader.  CPU
only: ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q``.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import run  # noqa: E402
from chipbench.readers import serve_cycle  # noqa: E402

SERVE_CELLS = ["wm100m.serve_closed16", "sarvam105b.serve_closed64",
               "phi4miniflash.reason_closed64", "olmohybrid7b.serve_closed64"]
NEW = {"serve.host_ms": serve_cycle.host_ms,
       "serve.admit_ms": serve_cycle.admit_ms,
       "serve.dispatch_ms": serve_cycle.dispatch_ms,
       "serve.drained_pct": serve_cycle.drained_pct,
       "serve.drained_wait_pct": serve_cycle.drained_wait_pct,
       "serve.wire_in_ms": serve_cycle.wire_in_ms,
       "serve.client_turn_ms": serve_cycle.client_turn_ms}

#: a 30 s window of 5000 ticks, its 30,000,000 us tiled by the eleven phases
EVENTS = {
    "serve_idle_us": 300_000, "serve_poll_us": 9_000_000,
    "serve_slice_us": 1_500_000, "serve_admit_us": 4_500_000,
    "serve_prefill_dispatch_us": 1_000_000, "serve_assemble_us": 1_000_000,
    "serve_dispatch_us": 6_500_000, "serve_fetch_wait_us": 2_500_000,
    "serve_reply_us": 2_500_000, "serve_loop_us": 1_200_000,
    "serve_batches": 5000, "serve_drained_us": 3_000_000,
    "serve_drained_wait_us": 1_200_000, "serve_wire_in_us": 40_000_000,
    "serve_wire_in_n": 80_000, "serve_client_turn_us": 64_000_000,
    "serve_client_turn_n": 80_000,
}
EXPECTED = {"serve.host_ms": 16.7e6 / 5000 / 1e3,
            "serve.admit_ms": 0.9, "serve.dispatch_ms": 1.3,
            "serve.drained_pct": 10.0, "serve.drained_wait_pct": 40.0,
            "serve.wire_in_ms": 0.5, "serve.client_turn_ms": 0.8}


def _obs(events, window_s=30.0):
    return {"window_s": window_s, "events": events}


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_hand_worked(name):
    assert sum(EVENTS[n] for n in serve_cycle.PHASES if n in EVENTS) \
        == 30_000_000
    assert NEW[name](_obs(EVENTS), None) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_none_on_the_parents_counters(name):
    # the parent counts its idle wait, its ticks' fetch wait and nothing
    # else of the clock
    parent = {"serve_idle_us": 300_000, "serve_fetch_wait_us": 2_500_000,
              "serve_batches": 5000, "serve_prefill_us": 1_500_000}
    assert NEW[name](_obs(parent), None) is None
    assert NEW[name]({"window_s": 30.0}, None) is None
    assert NEW[name](_obs(EVENTS, window_s=0.0), None) is None


@pytest.mark.parametrize("name", sorted(NEW))
@pytest.mark.parametrize("cover", [0.94, 1.02])
def test_reader_none_where_the_phases_do_not_tile_the_window(name, cover):
    assert NEW[name](_obs(EVENTS, window_s=30.0 / cover), None) is None
    inside = 0.96 if cover < 1 else 1.005
    assert NEW[name](_obs(EVENTS, window_s=30.0 / inside), None) is not None


@pytest.mark.parametrize("name,den", [
    ("serve.host_ms", "serve_batches"), ("serve.admit_ms", "serve_batches"),
    ("serve.dispatch_ms", "serve_batches"),
    ("serve.drained_wait_pct", "serve_drained_us"),
    ("serve.wire_in_ms", "serve_wire_in_n"),
    ("serve.client_turn_ms", "serve_client_turn_n")])
def test_reader_none_on_a_zero_divisor(name, den):
    assert NEW[name](_obs(dict(EVENTS, **{den: 0})), None) is None
    events = dict(EVENTS)
    del events[den]
    assert NEW[name](_obs(events), None) is None


def test_absent_phases_read_zero_where_the_clock_is_there():
    # no WeightBus in a cell: ``serve_weights_us`` is never counted
    assert "serve_weights_us" not in EVENTS
    assert serve_cycle.drained_pct(
        _obs(dict(EVENTS, serve_drained_us=0)), None) == 0.0


def test_new_entries_resolve_to_their_readers():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entries = {m["name"]: m for m in bench["per_layer"]}
    for cell in SERVE_CELLS:
        specs = run.resolve_cell(cell).per_layer
        got = {m["name"]: m["reader"] for m in specs}
        for name, fn in NEW.items():
            assert entries[name]["workloads"] == SERVE_CELLS
            assert entries[name]["source"] == "program_counter"
            assert entries[name]["moves"] == "serve_tokens_per_s"
            assert run._resolve(got[name]) is fn
    assert list(entries)[-7:] == list(NEW)
