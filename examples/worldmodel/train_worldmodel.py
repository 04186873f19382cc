"""SeqFormer world-model training on streamed Blender episodes.

The sequence-model workload the reference has no counterpart for
(SURVEY.md §5 "long-context: absent"): pendulum episodes stream out of a
Blender fleet (``pendulum.blend.py``) and a causal temporal transformer
trains next-observation prediction on them — the same model family and
wire-efficient feed the benchmark suite measures
(``benchmarks/suite_device.py`` seqformer phase).

Modes:
    python train_worldmodel.py                     # single device
    python train_worldmodel.py --attn flash        # fused Pallas kernel
    python train_worldmodel.py --mesh 2,2,2 --attn ring_flash
        # dp x sp x tp over 8 devices: ring attention with the flash
        # kernel fused per ring block pair (or zigzag_flash — the
        # load-balanced causal layout — ulysses / ulysses_flash)

Episodes ride the wire as float16 (half the bytes; a disclosed input-
precision choice — see seqformer.episode_loss_fn) and obs/target views
are sliced on device.  The training loop is factored into
``train_on_episodes`` so tests can drive it with any batch iterator.
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
from blendjax.models import seqformer
from blendjax.models.train import TrainState, make_train_step

SCRIPT = Path(__file__).parent / "pendulum.blend.py"
T = 64
OBS_DIM = 8


SINGLE_ATTN = ("full", "flash")
PARALLEL_ATTN = ("ring", "ring_flash", "zigzag_flash", "ulysses",
                 "ulysses_flash")


def episode_transform(batch):
    """Collated producer batch -> wire-efficient episode batch (f16)."""
    return {"episode": batch["obs_seq"].astype(np.float16)}


def make_attn(name, seq_len, window=None):
    """Single-device attention override for ``--attn``.

    Parallel scheme names are rejected here — silently running the
    single-device kernel under a parallel scheme's name would invalidate
    any comparison the user thinks they ran (use ``--mesh`` for those).
    """
    if name == "full":
        if window is None:
            return None
        from blendjax.parallel.ring_attention import full_attention

        def windowed_full(q, k, v):
            return full_attention(q, k, v, causal=True, window=window)

        return windowed_full
    if name != "flash":
        raise ValueError(
            f"--attn {name} is a parallel scheme; pass --mesh dp,sp,tp "
            "to use it (single-device options: full, flash)"
        )
    from blendjax.ops.flash_attention import make_flash_attention

    # 'auto': the tile policy sees each call's T, head size, dtype, window
    return make_flash_attention(
        causal=True, block_q="auto", block_kv="auto", window=window,
    )


def train_on_episodes(batches, state=None, attn=None, d_model=128,
                      n_heads=4, n_layers=2, log_every=8,
                      pos_encoding="learned"):
    """Train the SeqFormer over an iterator of device episode batches."""
    import functools

    opt = optax.adam(3e-4)
    if state is None:
        params = seqformer.init(
            jax.random.PRNGKey(0), obs_dim=OBS_DIM, d_model=d_model,
            n_heads=n_heads, n_layers=n_layers, max_len=T,
            pos_encoding=pos_encoding,
        )
        state = TrainState.create(params, opt)
    loss_fn = seqformer.episode_loss_fn
    if attn is not None:
        loss_fn = functools.partial(loss_fn, attn_fn=attn)
    step = make_train_step(loss_fn, opt)
    losses = []
    for i, batch in enumerate(batches):
        state, loss = step(state, batch)
        losses.append(float(loss))
        if log_every and (i + 1) % log_every == 0:
            print(f"batch {i + 1}: loss {losses[-1]:.5f}")
    return state, losses


def simulate_episode(rng, batch, T_steps=None):
    """Host-side damped-pendulum episodes with the producer's dynamics
    (pendulum.blend.py's integrator, minus the scene): held-out
    evaluation data for :func:`dream` without a Blender fleet."""
    T_steps = T_steps or T
    eps = []
    for _ in range(batch):
        th = rng.uniform(-2.0, 2.0)
        om = rng.uniform(-1.0, 1.0)
        amp = rng.uniform(0.2, 1.5)
        freq = rng.uniform(0.5, 2.0)
        t = 0.0
        obs = []
        for _f in range(T_steps + 1):
            drive = amp * np.sin(freq * t)
            om += (-9.81 / 2.0 * np.sin(th) - 0.15 * om + drive) * 0.05
            th += om * 0.05
            t += 0.05
            o = np.zeros(OBS_DIM, np.float32)
            o[0], o[1], o[2] = np.cos(th), np.sin(th), om
            o[3] = amp * np.sin(freq * t)
            # bob world position: Ry(theta) @ (0, 0, -2), matching the
            # producer's parented sphere
            o[4] = -2.0 * np.sin(th)
            o[6] = -2.0 * np.cos(th)
            obs.append(o)
        eps.append(np.stack(obs))
    return np.stack(eps)


def dream(state, episode, prefix_len, n_steps, window=None, int8=False):
    """Roll the trained world model forward without the simulator: feed
    ``prefix_len`` real observations, then its own predictions for
    ``n_steps`` — the KV-cache inference path (seqformer.rollout).
    Returns (predicted (B, n_steps, D), open-loop MSE vs the real
    continuation)."""
    params = jax.device_get(state.params)  # local copy; works for
    # sharded states too (dreaming is cheap single-device math)
    if int8:
        from blendjax.ops.quant import quantize_seqformer

        params = quantize_seqformer(params)
    prefix = jnp.asarray(episode[:, :prefix_len], jnp.float32)
    preds = seqformer.rollout(
        params, prefix, n_steps, compute_dtype=jnp.float32,
        window=window,
    )
    real = episode[:, prefix_len:prefix_len + n_steps]
    mse = float(jnp.mean((preds - jnp.asarray(real, jnp.float32)) ** 2))
    return preds, mse


def sharded_transform(batch):
    """Host-side transform for the mesh path: split the episode into the
    obs/target views the sharded step trains on (an episode's T+1 length
    does not divide the seq axis; the T-length views do)."""
    ep = batch["obs_seq"].astype(np.float32)
    return seqformer.make_episode_batch(ep)


def make_sharded_trainer(mesh_shape, attn_impl, d_model=128, n_heads=4,
                         n_layers=2, window=None, pos_encoding="learned"):
    """(state, step, batch_sharding) for dp x sp x tp training.

    Built BEFORE the stream so JaxStream can place batches directly on
    the mesh (``sharding=batch_sharding``) — staging them on the default
    device and re-transferring per step would double the feed traffic.
    """
    from blendjax.parallel import make_mesh, make_seqformer_train_step

    dp, sp, tp = mesh_shape
    mesh = make_mesh({"data": dp, "seq": sp, "model": tp})
    params = seqformer.init(
        jax.random.PRNGKey(0), obs_dim=OBS_DIM, d_model=d_model,
        n_heads=n_heads, n_layers=n_layers, max_len=T,
        pos_encoding=pos_encoding,
    )
    init_sharded, step, batch_sharding = make_seqformer_train_step(
        optax.adam(3e-4), mesh, attn_impl=attn_impl, attn_window=window
    )
    return init_sharded(params), step, batch_sharding


def train_sharded(batches, state, step, log_every=8):
    """Train over an iterator of mesh-sharded {obs, target} batches."""
    losses = []
    for i, batch in enumerate(batches):
        state, loss = step(state, batch)
        losses.append(float(loss))
        if log_every and (i + 1) % log_every == 0:
            print(f"batch {i + 1}: loss {losses[-1]:.5f}")
    return state, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--instances", type=int, default=2)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--batches", type=int, default=64)
    ap.add_argument("--attn", default=None,
                    choices=list(SINGLE_ATTN) + list(PARALLEL_ATTN),
                    help="default: full (single device) / ring_flash "
                         "(--mesh)")
    ap.add_argument("--pos", choices=["learned", "rope"],
                    default="learned",
                    help="position encoding (rope: relative positions, "
                         "dream horizons unbounded by max_len; works on "
                         "both the single-device and --mesh paths — the "
                         "rotation happens before the attention seam)")
    ap.add_argument("--dream-int8", action="store_true",
                    help="quantize the trained model (w8a8) before "
                         "dreaming — the bandwidth-bound decode phase "
                         "benefits most from int8 weights")
    ap.add_argument("--dream", type=int, default=0,
                    help="after training, roll the model forward this "
                         "many steps open-loop from a held-out episode "
                         "prefix and report the MSE vs the real "
                         "continuation")
    ap.add_argument("--window", type=int, default=None,
                    help="sliding-window attention width (causal); on "
                         "the ring schemes the ring then rotates only "
                         "the shards the window reaches")
    ap.add_argument("--mesh", default=None,
                    help="dp,sp,tp device counts; enables the sharded "
                         "path (attn must then be one of "
                         f"{PARALLEL_ATTN})")
    args = ap.parse_args()

    # validate the attn/mesh pairing BEFORE paying fleet startup
    if args.mesh:
        attn = args.attn or "ring_flash"
        if attn not in PARALLEL_ATTN:
            ap.error(f"--mesh needs a parallel --attn {PARALLEL_ATTN}, "
                     f"got {attn!r}")
        mesh_shape = tuple(int(x) for x in args.mesh.split(","))
        state, step, batch_sharding = make_sharded_trainer(
            mesh_shape, attn, window=args.window,
            pos_encoding=args.pos,
        )
        stream_kwargs = dict(
            transform=sharded_transform, sharding=batch_sharding
        )
    else:
        attn = args.attn or "full"
        attn_fn = make_attn(attn, T, window=args.window)  # rejects parallel names
        stream_kwargs = dict(transform=episode_transform)

    launcher = btt.BlenderLauncher(
        scene="", script=str(SCRIPT), num_instances=args.instances,
        named_sockets=["DATA"], background=True,
    )
    with launcher as bl:
        ds = btt.RemoteIterableDataset(
            bl.launch_info.addresses["DATA"],
            max_items=args.batches * args.batch,
        )
        with btt.JaxStream(
            ds, batch_size=args.batch, num_workers=args.instances,
            **stream_kwargs,
        ) as stream:
            if args.mesh:
                state, losses = train_sharded(iter(stream), state, step)
            else:
                state, losses = train_on_episodes(
                    iter(stream), attn=attn_fn, pos_encoding=args.pos
                )
    print(f"trained {len(losses)} batches; "
          f"loss {losses[0]:.5f} -> {losses[-1]:.5f}")
    if args.dream > 0:
        rng = np.random.default_rng(123)
        prefix_len = T // 2
        if args.pos == "rope":
            # rope has no table bound: honor the requested horizon by
            # simulating a long enough held-out episode to score it
            n_steps = args.dream
        else:
            n_steps = min(args.dream, T - prefix_len)
        # a fresh pendulum episode the model never saw, generated with
        # the producer's own dynamics — long enough to cover the dream
        episode = simulate_episode(rng, batch=2,
                                   T_steps=prefix_len + n_steps)
        _, mse = dream(state, episode, prefix_len, n_steps,
                       window=args.window, int8=args.dream_int8)
        print(f"dream: {n_steps} open-loop steps from a {prefix_len}-step "
              f"prefix, MSE vs real continuation {mse:.5f}")


if __name__ == "__main__":
    main()
