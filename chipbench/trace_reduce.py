"""From a profiler trace to numbers: the device's busy seconds, the traced
window, the device operations that took most time, and the longest idle gaps
by what the host was doing.

The arithmetic (:func:`busy_seconds`, :func:`idle_gaps`, :func:`reduce_events`)
works on plain intervals and is tested on synthetic ones; only
:func:`reduce_trace_dir` knows the profiler's file.
"""

from __future__ import annotations

import collections
import glob
import os
import re

import numpy as np

OPS_LINE = "XLA Ops"  # the device plane's line of single operations
TOP = 10
GAPS_NAMED = 200      # the longest gaps, which are given a host name


def _merged(intervals):
    """Overlapping or touching (start, end) intervals joined, sorted."""
    out = []
    for lo, hi in sorted((float(a), float(b)) for a, b in intervals if b > a):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def busy_seconds(intervals):
    """Length of the union of (start, end) intervals (nested and
    overlapping ones count once), in the intervals' unit."""
    return sum(hi - lo for lo, hi in _merged(intervals))


def idle_gaps(intervals, lo, hi):
    """The (start, end) stretches of [lo, hi] that no interval covers."""
    gaps, at = [], float(lo)
    for a, b in _merged(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi > at:
        gaps.append((at, float(hi)))
    return gaps


def op_family(name):
    """A device event's name is the whole HLO instruction: keep the
    instruction's own name, less its serial number (``%fusion.123 = ...``
    -> ``fusion``), and for a custom call its target beside it."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    head = re.sub(r"[.\d]+$", "", head) or head
    target = re.search(r'custom_call_target="([^"]+)"', name)
    return f"{head}[{target.group(1)}]" if target else head


def _host_name(gap, host):
    """What the host was doing in a gap: the shortest host span that covers
    at least half of it (the most specific one), else ``unattributed``."""
    if host is None:
        return "unattributed"
    names, start, end = host
    cover = np.minimum(end, gap[1]) - np.maximum(start, gap[0])
    ok = np.flatnonzero(cover >= 0.5 * (gap[1] - gap[0]))
    if not len(ok):
        return "unattributed"
    return names[ok[np.argmin(end[ok] - start[ok])]]


def reduce_events(device_events, host_events, window=None):
    """``device_events``: {device: [(name, start_ns, duration_ns)]} of single
    operations; ``host_events``: [(name, start_ns, duration_ns)] of host
    spans.  Returns busy seconds averaged over the devices, the window's
    seconds, the ``TOP`` operations by time and the ``TOP`` idle causes."""
    every = [e for evs in device_events.values() for e in evs]
    if window is None:
        # the device's own extent: its tracing starts after the host's and
        # stops before it, and outside it no operation could be recorded
        if not every:
            return None
        window = (min(s for _, s, _ in every),
                  max(s + d for _, s, d in every))
    lo, hi = window
    n = max(1, len(device_events))
    busy = sum(busy_seconds((s, s + d) for _, s, d in evs)
               for evs in device_events.values()) / n
    ops = collections.Counter()
    for name, _, d in every:
        ops[op_family(name)] += d / n
    host = None
    # a span over half of the window (a thread's own loop, a sleep) says
    # nothing about a gap inside it
    spans = [(nm, s, s + d) for nm, s, d in host_events
             if 0 < d < 0.5 * (hi - lo)]
    if spans:
        host = ([nm for nm, _, _ in spans],
                np.asarray([s for _, s, _ in spans], np.float64),
                np.asarray([e for _, _, e in spans], np.float64))
    idle = collections.Counter()
    for dev_events in device_events.values():
        gaps = idle_gaps(((s, s + d) for _, s, d in dev_events), lo, hi)
        gaps.sort(key=lambda g: g[0] - g[1])
        for i, gap in enumerate(gaps):
            name = _host_name(gap, host) if i < GAPS_NAMED else "short gaps"
            idle[name] += (gap[1] - gap[0]) / n
    return {
        "busy_s": busy / 1e9, "window_s": (hi - lo) / 1e9,
        "device_ops": [[k, v / 1e9] for k, v in ops.most_common(TOP)],
        "idle_gaps": [[k, v / 1e9] for k, v in idle.most_common(TOP)],
    }


def reduce_trace_dir(trace_dir):
    """Read the newest ``.xplane.pb`` under ``trace_dir`` with jax's own
    reader.  Device planes are ``/device:...``; on each, the operations are
    the ``XLA Ops`` line, or every line where the plane has no such line."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return None
    data = ProfileData.from_file(files[-1])
    device_events, host_events, layout = {}, [], {}
    for plane in data.planes:
        lines = list(plane.lines)
        layout[plane.name] = [ln.name for ln in lines]
        if plane.name.startswith("/device:"):
            if "TPU" not in plane.name and "GPU" not in plane.name:
                continue
            picked = [ln for ln in lines if ln.name == OPS_LINE] or lines
            device_events[plane.name] = [
                (e.name, e.start_ns, e.duration_ns)
                for ln in picked for e in ln.events]
        elif plane.name.startswith("/host:"):
            host_events += [(e.name, e.start_ns, e.duration_ns)
                            for ln in lines for e in ln.events]
    out = reduce_events(device_events, host_events)
    if out is not None:
        out["layout"] = layout
    return out
