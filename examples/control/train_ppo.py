"""PPO training over a vectorized Blender cartpole fleet.

The reference's control example is a hand-tuned P-controller
(``examples/control/cartpole.py:19-35``); blendjax adds learnable
control — REINFORCE (``train_reinforce.py``) and, here, PPO: an MLP
actor-critic with GAE and the clipped surrogate objective, trained over
lockstep rollouts from an :class:`blendjax.btt.envpool.EnvPool`.  The
whole update (K epochs over the rollout) is ONE jitted function — the
TPU-first shape: rollouts stream from the Blender fleet on the host,
the optimization is a single compiled program.

The rollout/update core (``train``) takes any pool-like object so tests
drive it with a CPU physics stub.
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

SCRIPT = Path(__file__).parent / "cartpole.blend.py"
FORCE_MAG = 20.0


def train(
    pool,
    obs_dim=3,
    num_actions=2,
    iterations=40,
    horizon=128,
    lr=3e-3,
    gamma=0.99,
    lam=0.95,
    clip_eps=0.2,
    epochs=4,
    key=None,
    log_every=5,
):
    """Rollout ``horizon`` lockstep steps per iteration, then ``epochs``
    full-batch PPO updates.  Returns ((actor, critic) state, returns log).
    """
    key = key if key is not None else jax.random.PRNGKey(0)
    actor = policy.init(jax.random.PRNGKey(1), obs_dim, num_actions)
    critic = policy.value_init(jax.random.PRNGKey(2), obs_dim)
    opt = optax.adam(lr)
    opt_state = opt.init((actor, critic))

    sample = jax.jit(policy.sample_action)
    values_fn = jax.jit(policy.value_apply)

    @jax.jit
    def update(actor, critic, opt_state, batch):
        def loss_fn(ac):
            a, c = ac
            return policy.ppo_loss(a, c, batch, clip_eps=clip_eps)

        def epoch(carry, _):
            actor, critic, opt_state = carry
            loss, grads = jax.value_and_grad(loss_fn)((actor, critic))
            updates, opt_state = opt.update(
                grads, opt_state, (actor, critic)
            )
            actor, critic = optax.apply_updates((actor, critic), updates)
            return (actor, critic, opt_state), loss

        (actor, critic, opt_state), losses = jax.lax.scan(
            epoch, (actor, critic, opt_state), None, length=epochs
        )
        return actor, critic, opt_state, losses[-1]

    returns_log = []
    obs, _ = pool.reset()
    prev_dones = np.zeros(len(np.asarray(obs)), bool)
    for it in range(iterations):
        obs_buf, act_buf, logp_buf, rew_buf, done_buf = [], [], [], [], []
        mask_buf = []
        for _ in range(horizon):
            key, k = jax.random.split(key)
            obs_j = jnp.asarray(obs, jnp.float32)
            actions, logp = sample(actor, k, obs_j)
            actions = np.asarray(actions)
            forces = (actions * 2 - 1) * FORCE_MAG
            next_obs, rewards, dones, _ = pool.step(
                list(forces.astype(float))
            )
            obs_buf.append(np.asarray(obs, np.float32))
            act_buf.append(actions)
            logp_buf.append(np.asarray(logp, np.float32))
            rew_buf.append(rewards)
            done_buf.append(dones)
            # a lane that reported done executes RESET on the next step:
            # that transition's action never ran — zero-weight it in the
            # loss (its GAE trace is already cut by the done itself)
            mask_buf.append(1.0 - prev_dones.astype(np.float32))
            prev_dones = np.asarray(dones, bool)
            obs = next_obs

        obs_t = jnp.asarray(np.stack(obs_buf))        # (T, N, D)
        rewards = jnp.asarray(np.stack(rew_buf))      # (T, N)
        dones = jnp.asarray(np.stack(done_buf))
        values = values_fn(critic, obs_t)             # (T, N)
        last_values = values_fn(
            critic, jnp.asarray(obs, jnp.float32)
        )
        adv, targets = policy.gae(
            rewards, values, last_values, dones, gamma, lam
        )
        batch = {
            "obs": obs_t.reshape(-1, obs_t.shape[-1]),
            "actions": jnp.asarray(np.concatenate(act_buf)),
            "logp_old": jnp.asarray(np.concatenate(logp_buf)),
            "advantages": adv.reshape(-1),
            "targets": targets.reshape(-1),
            "mask": jnp.asarray(np.concatenate(mask_buf)),
        }
        actor, critic, opt_state, loss = update(
            actor, critic, opt_state, batch
        )
        finished = float(dones.sum())
        # weight the reward sum by the SAME mask the loss uses: the
        # fabricated reset-step transitions (whose actions never ran)
        # must not inflate the logged return any more than they train
        # the policy (ADVICE r5)
        mask_t = batch["mask"].reshape(rewards.shape)
        masked_reward = float((rewards * mask_t).sum())
        if finished:
            mean_ep = masked_reward / finished
        else:
            # no episode closed this horizon: report reward per LANE so
            # the log stays comparable instead of printing the raw total
            # as "reward/episode"
            mean_ep = masked_reward / rewards.shape[1]
        returns_log.append(mean_ep)
        if log_every and (it + 1) % log_every == 0:
            print(f"iter {it + 1}: loss {float(loss):.4f} "
                  f"reward/episode {mean_ep:.1f}")
    return (actor, critic), returns_log


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--instances", type=int, default=4)
    ap.add_argument("--iterations", type=int, default=40)
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
