"""Closed-loop load generator for a token world model: the client loop of
``closed_loop_clients.py`` (connect, ``ready``, ``go``, ramp, window, the
seeded sample of finished episodes) over episodes of int32 token ids.

An episode is ``reset(prefix=)`` with ``prefix_lengths`` ids as a ``(P, 1)``
column, then ``steps_min .. steps_max`` ``step`` calls on a grid of
``step_grid``, each with one id, then ``close_episode``.  Ids are uniform
over ``vocab_size`` (the configuration's slice) from a generator of the
episode's own; every client walks one seeded permutation of the grid of
shapes from a start of its own.  A reply's ``pred`` is the server's 17
numbers (top 8 logits, their ids, the logsumexp).

Only the plan differs from the observation generator, so this module
gives that module's loop its own :func:`shape_grid` and
:func:`episode_plan` and runs it (the loop looks both up by name when it
is called; the child process runs nothing else).
"""

from __future__ import annotations

import sys

import numpy as np

from chipbench.traffic import closed_loop_clients as loop


def shape_grid(spec):
    steps = range(spec["steps_min"], spec["steps_max"] + 1, spec["step_grid"])
    return [(p, s) for p in spec["prefix_lengths"] for s in steps]


def episode_plan(spec, seed, client, index):
    """(prefix ids (P, 1), step ids (S, 1)), int32, of a client's
    ``index``-th episode."""
    grid = shape_grid(spec)
    order = np.random.default_rng((int(seed), 1)).permutation(len(grid))
    start = (int(client) * len(grid)) // spec["clients"]
    n_prefix, n_steps = grid[order[(start + int(index)) % len(grid)]]
    rng = np.random.default_rng((int(seed), int(client), int(index), 2))
    ids = rng.integers(0, spec["vocab_size"], size=(n_prefix + n_steps, 1),
                       dtype=np.int32)
    return ids[:n_prefix], ids[n_prefix:]


def main(argv=None):
    loop.shape_grid, loop.episode_plan = shape_grid, episode_plan
    return loop.main(argv)


if __name__ == "__main__":
    sys.exit(main())
