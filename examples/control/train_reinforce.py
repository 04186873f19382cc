"""REINFORCE policy training over a vectorized Blender cartpole fleet —
the net-new learning workload the reference leaves to users (its control
example is a hand-tuned P-controller).

N Blender instances run the cartpole env; an :class:`EnvPool` steps them in
lockstep; a categorical MLP policy (force = ±mag) trains with a jitted
REINFORCE update.  The rollout/update core (``train``) takes any pool-like
object so tests drive it with a CPU physics stub.
"""

from __future__ import annotations

import argparse
import os
from pathlib import Path

from blendjax.btt.launcher import place_compile_cache

place_compile_cache(os.environ)  # before jax reads its configuration

import jax  # noqa: E402
import jax.numpy as jnp
import numpy as np
import optax

from blendjax.btt.envpool import launch_env_pool
from blendjax.models import policy
from blendjax.models.train import TrainState

SCRIPT = Path(__file__).parent / "cartpole.blend.py"
FORCE_MAG = 20.0


def train(
    pool,
    obs_dim=3,
    num_actions=2,
    iterations=50,
    horizon=64,
    lr=3e-3,
    gamma=0.99,
    key=None,
    log_every=5,
    mesh=None,
):
    """Rollout `horizon` steps across the pool per iteration, then one
    REINFORCE update.  Returns (state, per-iteration mean returns).

    With ``mesh`` the update runs SPMD over the mesh's ``data`` axis:
    rollout transitions shard ``P('data')``, the policy replicates, and XLA
    inserts the gradient psum — the modern jax.sharding form of the
    reference-era "train the policy under pmap" (BASELINE.md north star).
    ``horizon * num_envs`` must divide the data-axis size.
    """
    key = key if key is not None else jax.random.PRNGKey(0)
    params = policy.init(jax.random.PRNGKey(1), obs_dim, num_actions)
    opt = optax.adam(lr)

    def batch_loss(p, batch):
        return policy.reinforce_loss(
            p, batch["obs"], batch["actions"], batch["returns"]
        )

    data_sharding = None
    if mesh is not None:
        from blendjax.parallel import data_sharding as make_data_sharding
        from blendjax.parallel import make_sharded_train_step

        data_sharding = make_data_sharding(mesh)
        init_sharded, sharded_step = make_sharded_train_step(
            batch_loss, opt, mesh, rules={}
        )
        state = init_sharded(params)

        def update(state, obs, actions, returns):
            batch = jax.device_put(
                {"obs": obs, "actions": actions, "returns": returns},
                data_sharding,
            )
            return sharded_step(state, batch)

    else:
        state = TrainState.create(params, opt)

        @jax.jit
        def _step(state, batch):
            loss, grads = jax.value_and_grad(batch_loss)(state.params, batch)
            updates, opt_state = opt.update(grads, state.opt_state, state.params)
            return (
                TrainState(
                    optax.apply_updates(state.params, updates),
                    opt_state,
                    state.step + 1,
                ),
                loss,
            )

        def update(state, obs, actions, returns):
            return _step(state, {"obs": obs, "actions": actions, "returns": returns})

    sample = jax.jit(policy.sample_action)

    returns_log = []
    obs, _ = pool.reset()
    for it in range(iterations):
        obs_buf, act_buf, rew_buf, done_buf = [], [], [], []
        for _ in range(horizon):
            key, k = jax.random.split(key)
            actions, _ = sample(state.params, k, jnp.asarray(obs, jnp.float32))
            actions = np.asarray(actions)
            forces = (actions * 2 - 1) * FORCE_MAG  # {0,1} -> {-mag,+mag}
            next_obs, rewards, dones, _ = pool.step(list(forces.astype(float)))
            obs_buf.append(np.asarray(obs, np.float32))
            act_buf.append(actions)
            rew_buf.append(rewards)
            done_buf.append(dones)
            obs = next_obs

        rewards = jnp.asarray(np.stack(rew_buf))          # (T, N)
        dones = jnp.asarray(np.stack(done_buf))
        returns = policy.discounted_returns(rewards, dones, gamma)
        flat_obs = jnp.asarray(np.concatenate(obs_buf))    # (T*N, obs_dim)
        flat_act = jnp.asarray(np.concatenate(act_buf))
        flat_ret = returns.reshape(-1)

        state, loss = update(state, flat_obs, flat_act, flat_ret)
        finished = float(dones.sum())
        if finished:
            mean_ep = float(rewards.sum()) / finished
        else:
            # no episode closed this horizon: report reward per LANE so
            # the log stays comparable instead of printing the raw total
            # as "reward/episode"
            mean_ep = float(rewards.sum()) / rewards.shape[1]
        returns_log.append(mean_ep)
        if log_every and (it + 1) % log_every == 0:
            print(f"iter {it + 1}: loss {float(loss):.4f} reward/episode {mean_ep:.1f}")
    return state, returns_log


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--instances", type=int, default=4)
    ap.add_argument("--iterations", type=int, default=50)
    args = ap.parse_args()

    with launch_env_pool(
        scene="",
        script=str(SCRIPT),
        num_instances=args.instances,
        background=False,
        real_time=False,
    ) as pool:
        train(pool, iterations=args.iterations)


if __name__ == "__main__":
    main()
