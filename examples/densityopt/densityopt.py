"""Simulation-parameter optimization through a non-differentiable renderer
(counterpart of reference ``examples/densityopt/densityopt.py``).

A log-normal ``ProbModel`` over supershape parameters (m1, m2) is optimized
so that rendered samples fool a discriminator trained on "real" images
(rendered at hidden target parameters).  Gradients never flow through
Blender: the score-function estimator (REINFORCE with EMA baseline)
converts per-sample discriminator losses into distribution-parameter
gradients — all jitted; only the render round trip is host-side.

Data flow per iteration (reference ``densityopt.py:257-331``):
1. sample parameter batch from ProbModel
2. chunk over N sims, ``DuplexChannel.send(shape_params, shape_id)``
3. sims apply params at pre_frame, publish ``{image, shape_id}``
4. consumer matches images to samples by shape_id
5. discriminator grad step (real vs sim) + ProbModel score-function step

The loop core (``optimize``) takes an abstract ``render_batch`` callable so
tests can swap Blender for a synthetic renderer.
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

from blendjax import btt
from blendjax.models import discriminator, probmodel
from blendjax.ops.image import decode_frames

SCRIPT = Path(__file__).parent / "supershape.blend.py"


def make_blender_renderer(duplexes, dataset_iter, batch_size):
    """render_batch(params (B,2)) -> (B,H,W,C) uint8 via the Blender fleet.

    Parameters are chunked round-robin over the duplex channels with fresh
    shape ids; frames are matched back by ``shape_id`` from the shared
    stream (reference ``densityopt.py:95-107,209-216``).
    """
    counter = {"next": 0}

    def render_batch(params_np):
        ids = []
        for i, p in enumerate(params_np):
            sid = counter["next"]
            counter["next"] += 1
            duplexes[i % len(duplexes)].send(
                shape_params=[float(p[0]), float(p[1])], shape_id=sid
            )
            ids.append(sid)
        pending = dict.fromkeys(ids)
        remaining = len(ids)
        while remaining:
            item = next(dataset_iter)
            sid = item.get("shape_id")
            if sid in pending and pending[sid] is None:
                pending[sid] = item["image"]
                remaining -= 1
        return np.stack([pending[i] for i in ids])

    return render_batch


def optimize(
    render_batch,
    real_images,
    key=None,
    iterations=100,
    batch_size=8,
    d_lr=2e-4,
    p_lr=5e-2,
    target_init=(2.0, 2.0),
    sigma_init=(0.4, 0.4),
    log_every=10,
):
    """Core optimization loop, renderer-agnostic.

    Returns ``(pm_params, history)`` where history holds per-iteration
    (d_loss, sim_loss_mean, pm_mean).
    """
    key = key if key is not None else jax.random.PRNGKey(0)
    pm_params = probmodel.init(mu=np.log(target_init), sigma=sigma_init)
    d_params = discriminator.init(jax.random.PRNGKey(1), in_channels=real_images.shape[-1])

    d_opt = optax.adam(d_lr)
    d_state = d_opt.init(d_params)
    p_opt = optax.adam(p_lr)
    p_state = p_opt.init(pm_params)
    baseline = 0.0

    @jax.jit
    def d_step(d_params, d_state, real, fake):
        loss, grads = jax.value_and_grad(discriminator.d_loss_fn)(d_params, real, fake)
        updates, d_state = d_opt.update(grads, d_state, d_params)
        return optax.apply_updates(d_params, updates), d_state, loss

    @jax.jit
    def p_step(pm_params, p_state, samples, losses, baseline):
        grads = jax.grad(probmodel.score_loss)(pm_params, samples, losses, baseline)
        updates, p_state = p_opt.update(grads, p_state, pm_params)
        return optax.apply_updates(pm_params, updates), p_state

    real_dev = decode_frames(jnp.asarray(real_images))
    history = []
    for it in range(iterations):
        key, k1 = jax.random.split(key)
        samples = probmodel.sample(pm_params, k1, batch_size)
        fake_u8 = render_batch(np.asarray(samples))
        fake_dev = decode_frames(jnp.asarray(fake_u8))

        d_params, d_state, d_loss = d_step(d_params, d_state, real_dev, fake_dev)
        sim_losses = discriminator.sim_scores(d_params, fake_dev)
        pm_params, p_state = p_step(pm_params, p_state, samples, sim_losses, baseline)
        baseline = float(probmodel.ema_update(baseline, sim_losses))

        history.append(
            (float(d_loss), float(sim_losses.mean()), np.asarray(probmodel.mean(pm_params)))
        )
        if log_every and (it + 1) % log_every == 0:
            print(
                f"iter {it + 1}: d_loss {history[-1][0]:.4f} "
                f"sim_loss {history[-1][1]:.4f} mean {history[-1][2]}"
            )
    return pm_params, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--instances", type=int, default=4)
    ap.add_argument("--iterations", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--target", type=float, nargs=2, default=[5.0, 5.0])
    ap.add_argument("--background", action="store_true",
                    help="run Blender headless (producers then use the "
                         "blocking frame loop; offscreen GL must be "
                         "available, e.g. the fake stack)")
    args = ap.parse_args()

    with btt.BlenderLauncher(
        scene="",
        script=str(SCRIPT),
        num_instances=args.instances,
        named_sockets=["DATA", "CTRL"],
        background=args.background,
    ) as bl:
        ds = btt.RemoteIterableDataset(
            bl.launch_info.addresses["DATA"], max_items=10**9, timeoutms=30000
        )
        stream = iter(ds)
        duplexes = [
            btt.DuplexChannel(addr, btid=i)
            for i, addr in enumerate(bl.launch_info.addresses["CTRL"])
        ]
        render_batch = make_blender_renderer(duplexes, stream, args.batch)

        # phase 1: "real" images rendered at the hidden target parameters
        real = render_batch(np.tile(args.target, (args.batch * 4, 1)))
        # phase 2: optimize the distribution to match
        pm_params, _ = optimize(
            render_batch, real, iterations=args.iterations, batch_size=args.batch
        )
        print("final mean:", np.asarray(probmodel.mean(pm_params)))


if __name__ == "__main__":
    main()
