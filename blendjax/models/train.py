"""Training-step builders: loss -> jitted, donated, optionally sharded step.

The reference has no training infrastructure (its examples hand-roll torch
loops); blendjax standardizes one functional pattern::

    state = TrainState.create(params, optax.adam(1e-3))
    step = make_train_step(loss_fn)
    state, loss = step(state, batch)          # jitted, state donated

and a mesh-sharded variant (see
:func:`blendjax.parallel.sharding.make_sharded_train_step`) where XLA
inserts the gradient all-reduce over the ``'data'`` axis and tensor-
parallel collectives over ``'model'`` from the sharding annotations alone.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import optax


class TrainState(NamedTuple):
    """Functional train state (params + optimizer state + step count)."""

    params: Any
    opt_state: Any
    step: Any

    @classmethod
    def create(cls, params, optimizer):
        return cls(params=params, opt_state=optimizer.init(params), step=0)


def make_train_step(loss_fn, optimizer=None, donate=True):
    """Build ``step(state, batch) -> (state, loss)``.

    ``loss_fn(params, batch) -> scalar``.  The state is donated so params
    update in place in HBM (no double-buffered weights).
    """
    optimizer = optimizer or optax.adam(1e-3)

    # the function's name is the jitted module's in a profiler trace and
    # in the compile log
    def train_step(state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1), loss

    return jax.jit(train_step, donate_argnums=(0,) if donate else ())


def make_eval_step(loss_fn):
    return jax.jit(loss_fn)
