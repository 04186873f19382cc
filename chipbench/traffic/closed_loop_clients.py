"""Closed-loop load generator for a world-model server: one jax-free process,
one thread per client, each waiting for its reply before it sends again.

A client's episode is ``reset(prefix=)`` with an observed history, then some
tens of ``step`` calls, then ``close_episode``, again and again until the
window closes.  The shapes come from the traffic parameters (a data file of
the cell): the clients walk one seeded permutation of a grid of (prefix
length, step count) pairs from evenly spread starts, so every seed offers the
same set of sizes in another order.  :func:`episode_plan` is the one generator; the harness
calls it again to rebuild the inputs of the episodes it checks.

The loop is the one of ``benchmarks/serve_benchmark.py`` (``_run_window`` /
``_client_proc_main``): connect every client, report ready, start on ``go``,
stop sending at the deadline, let every call in flight finish.  One thing is
added: a ramp.  Clients that all reset at the same instant queue sixteen
prefills behind each other, a burst that no steady closed loop shows and that
decided the 90th percentile of a short window.  So the clients start spread
evenly over ``ramp_s`` seconds, which count as set-up, and the window opens
when the ramp ends: latencies are those of calls begun in the window, replies
are counted by when they arrive.

Protocol: prints ``ready`` on stdout once every client is connected, reads
``go <seconds>`` on stdin, and when the window has closed and every call has
returned writes one pickled result to stdout.
"""

from __future__ import annotations

import argparse
import json
import pickle
import sys
import threading
import time

import numpy as np

STEP_GRID = 8  # step counts lie on steps_min, steps_min + 8, ... steps_max


def shape_grid(spec):
    steps = list(range(spec["steps_min"], spec["steps_max"] + 1, STEP_GRID))
    return [(p, s) for p in spec["prefix_lengths"] for s in steps]


def episode_plan(spec, seed, client, index):
    """(prefix (P, D), observations (S, D)) of a client's ``index``-th
    episode.  The seed permutes the grid of shapes once; every client walks
    that cycle from a start of its own, the starts spread evenly round it,
    so whatever the seed the clients together work through the same set of
    shapes at the same pace, in another order.  The numbers come from a
    generator of the episode's own."""
    grid = shape_grid(spec)
    order = np.random.default_rng((int(seed), 1)).permutation(len(grid))
    start = (int(client) * len(grid)) // spec["clients"]
    n_prefix, n_steps = grid[order[(start + int(index)) % len(grid)]]
    rng = np.random.default_rng((int(seed), int(client), int(index), 2))
    d = spec["obs_dim"]
    return (rng.standard_normal((n_prefix, d)).astype(np.float32),
            rng.standard_normal((n_steps, d)).astype(np.float32))


class _Client(threading.Thread):
    def __init__(self, address, spec, seed, index, gate):
        super().__init__(daemon=True)
        self.address, self.spec, self.seed = address, spec, seed
        self.index, self.gate = index, gate
        self.reset_s, self.step_s = [], []
        self.attempted = self.failed = 0
        self.replies_in_window = 0
        self.sum_pos_in_window = 0
        self.episodes = []   # (episode index, preds (1 + S, D), exact ok)
        self.errors = []

    def run(self):
        from blendjax.serve.client import ServeClient

        client = ServeClient(self.address,
                             timeoutms=self.spec["rpc_timeout_ms"])
        try:
            client.hello(timeout_ms=self.spec["rpc_timeout_ms"])
            self.gate["ready"].wait()
            self.gate["go"].wait()
            self.opens, self.closes = self.gate["window"]
            ramp = self.spec["ramp_s"]
            time.sleep(ramp * self.index / self.spec["clients"])
            index = 0
            while time.monotonic() < self.closes:
                self._episode(client, index)
                index += 1
        except Exception as exc:  # noqa: BLE001 - reported to the harness
            self.errors.append(f"client {self.index}: {exc!r}")
        finally:
            client.close()

    def _call(self, fn, *args):
        self.attempted += 1
        t0 = time.monotonic()
        try:
            reply = fn(*args, timeout_ms=self.spec["rpc_timeout_ms"])
        except Exception as exc:  # noqa: BLE001 - a failed RPC is counted
            self.failed += 1
            self.errors.append(f"client {self.index}: {exc!r}")
            return None, t0, time.monotonic()
        return reply, t0, time.monotonic()

    def _episode(self, client, index):
        prefix, obs = episode_plan(self.spec, self.seed, self.index, index)
        reply, t0, t1 = self._call(client.reset, prefix)
        if reply is None:
            return
        if t0 >= self.opens:
            self.reset_s.append(t1 - t0)
        exact = reply["pos"] == len(prefix)
        preds = [reply["pred"]]
        for k in range(len(obs)):
            if time.monotonic() >= self.closes:
                break
            reply, t0, t1 = self._call(client.step, obs[k])
            if reply is None:
                break
            if t0 >= self.opens:
                self.step_s.append(t1 - t0)
            pos = len(prefix) + k
            preds.append(reply["pred"])
            if self.opens <= t1 <= self.closes:
                self.replies_in_window += 1
                self.sum_pos_in_window += pos + 1
        closed, _, _ = self._call(client.close_episode)
        exact = exact and closed is True
        finished = len(preds) == len(obs) + 1
        self.episodes.append((index, np.stack(preds), exact, finished))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--address", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--spec", required=True, help="traffic parameters, JSON")
    args = ap.parse_args(argv)
    spec = json.loads(args.spec)
    out = sys.stdout.buffer

    n = spec["clients"]
    gate = {"ready": threading.Barrier(n + 1), "go": threading.Event(),
            "window": (0.0, 0.0)}
    clients = [_Client(args.address, spec, args.seed, i, gate)
               for i in range(n)]
    for c in clients:
        c.start()
    gate["ready"].wait(timeout=spec["rpc_timeout_ms"] / 1000.0)
    out.write(b"ready\n")
    out.flush()
    word = sys.stdin.readline().split()
    if len(word) != 2 or word[0] != "go":
        return 2  # the harness went away, or gave up before the window
    seconds = float(word[1])
    opens = time.monotonic() + spec["ramp_s"]
    gate["window"] = (opens, opens + seconds)
    gate["go"].set()
    for c in clients:
        c.join(timeout=spec["ramp_s"] + seconds
               + 2 * spec["rpc_timeout_ms"] / 1000.0)
    hung = sum(c.is_alive() for c in clients)

    # the episodes to check: drawn from the seed among those finished, and
    # the longest of them
    done = [(c.index, idx, preds) for c in clients
            for idx, preds, _, finished in c.episodes if finished]
    sample = []
    if done:
        longest = max(range(len(done)), key=lambda i: len(done[i][2]) + len(
            episode_plan(spec, args.seed, done[i][0], done[i][1])[0]))
        rng = np.random.default_rng((args.seed, 3))
        want = min(len(done), spec["sample_episodes"])
        picks = {longest}
        for i in rng.permutation(len(done)):
            if len(picks) >= want:
                break
            picks.add(int(i))
        sample = [done[i] for i in sorted(picks)]
    result = {
        "seconds": seconds,
        "reset_s": np.concatenate([np.asarray(c.reset_s) for c in clients]),
        "step_s": np.concatenate([np.asarray(c.step_s) for c in clients]),
        "attempted": sum(c.attempted for c in clients),
        "failed": sum(c.failed for c in clients) + hung,
        "replies_in_window": sum(c.replies_in_window for c in clients),
        "sum_pos_in_window": sum(c.sum_pos_in_window for c in clients),
        "episodes": sum(len(c.episodes) for c in clients),
        "episodes_finished": len(done),
        "episodes_exact": sum(ok for c in clients
                              for _, _, ok, _ in c.episodes),
        "sample": sample,
        "errors": [e for c in clients for e in c.errors][:20],
    }
    out.write(pickle.dumps(result))
    out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
