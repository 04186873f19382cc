"""Child for the multi-host TRAIN test (VERDICT r2 task #5): one of N
``jax.distributed`` processes running a data-parallel sharded train step
over the GLOBAL device mesh, so the gradient psum crosses process
boundaries — the v5e-8 story past the feed.

Also exercises checkpointing across processes: process 0 saves the train
state, a global barrier, then EVERY process restores and checks the
restored params equal its live ones.

Run: python multihost_train_child.py <coordinator> <pid> <pcount> <ckpt_dir>
Prints one JSON line: {pid, losses, param_mean, restored_equal}.
"""

import json
import os
import sys


def main():
    coordinator, pid, pcount = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    ckpt_dir = sys.argv[4]

    import jax

    # multi-process CPU harness: pinned whatever the caller's shell says
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        coordinator_address=coordinator, num_processes=pcount, process_id=pid
    )
    assert jax.process_count() == pcount

    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from blendjax.btt.prefetch import put_batch
    from blendjax.parallel.sharding import make_sharded_train_step
    from blendjax.utils.checkpoint import load_train_state, save_train_state

    mesh = Mesh(np.array(jax.devices()), ("data",))  # global: pcount x local
    sharding = NamedSharding(mesh, P("data"))

    def loss_fn(params, batch):
        pred = jax.numpy.tanh(batch["x"] @ params["w1"]) @ params["w2"]
        err = pred - batch["y"]
        return jax.numpy.mean(err * err)

    rng = np.random.default_rng(0)  # identical params on every process
    params = {
        "w1": jax.numpy.asarray(rng.standard_normal((6, 16)), jax.numpy.float32),
        "w2": jax.numpy.asarray(rng.standard_normal((16, 3)), jax.numpy.float32),
    }
    init_sharded, step = make_sharded_train_step(
        loss_fn, optax.adam(1e-2), mesh
    )
    state = init_sharded(params)

    n_local_dev = len(jax.local_devices())
    local_batch = 2 * n_local_dev  # 2 items per local device
    losses = []
    for i in range(3):
        # per-process slice of a deterministic global batch: process p
        # contributes rows seeded (step, p) — different data per process,
        # so matching losses prove the cross-process gradient psum
        prng = np.random.default_rng(100 + 10 * i + pid)
        batch = put_batch(
            {
                "x": prng.standard_normal((local_batch, 6)).astype(np.float32),
                "y": prng.standard_normal((local_batch, 3)).astype(np.float32),
            },
            sharding,
        )
        state, loss = step(state, batch)
        losses.append(float(loss))

    # ---- checkpoint: save on 0, barrier, restore everywhere ------------
    from jax.experimental import multihost_utils

    path = os.path.join(ckpt_dir, "state.npz")
    if pid == 0:
        save_train_state(path, state)
    multihost_utils.sync_global_devices("blendjax-ckpt-saved")
    restored = load_train_state(path, state)
    same = all(
        bool(np.allclose(np.asarray(a), np.asarray(b), atol=1e-7))
        for a, b in zip(
            jax.tree.leaves(jax.device_get(state.params)),
            jax.tree.leaves(jax.device_get(restored.params)),
        )
    )

    print(
        json.dumps(
            {
                "pid": pid,
                "losses": losses,
                "param_mean": float(
                    jax.numpy.mean(state.params["w1"]).block_until_ready()
                ),
                "restored_step": int(restored.step),
                "restored_equal": same,
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
