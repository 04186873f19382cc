"""Child process for the multi-host feed test: one of N ``jax.distributed``
processes, each feeding its local shard of the stream through
``put_batch``/``JaxStream`` -> ``make_array_from_process_local_data``.

Run: python multihost_child.py <coordinator> <pid> <pcount> <addr> [addr...]
Prints one JSON line: {pid, global_shape, mean, frameids}.
"""

import json
import sys


def main():
    coordinator, pid, pcount = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    addrs = sys.argv[4:]

    import jax

    # multi-process CPU harness: pinned whatever the caller's shell says
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        coordinator_address=coordinator, num_processes=pcount, process_id=pid
    )
    assert jax.process_count() == pcount
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from blendjax.btt.dataset import RemoteIterableDataset
    from blendjax.btt.prefetch import JaxStream

    mesh = Mesh(np.array(jax.devices()), ("data",))
    sharding = NamedSharding(mesh, P("data"))

    seen_frameids = []

    def transform(batch):
        seen_frameids.extend(int(f) for f in batch["frameid"])
        return {"image": batch["image"]}

    ds = RemoteIterableDataset(addrs, max_items=16, timeoutms=30000)
    stream = JaxStream(
        ds,
        batch_size=8,
        num_workers=1,
        sharding=sharding,
        transform=transform,
        shard=(pid, pcount),
    )
    batches = list(stream)
    stream.close()
    assert len(batches) == 1, f"expected one global batch, got {len(batches)}"
    img = batches[0]["image"]

    with mesh:
        mean = jax.jit(lambda x: jax.numpy.mean(x.astype(jax.numpy.float32)))(img)
    print(
        json.dumps(
            {
                "pid": pid,
                "global_shape": list(img.shape),
                "local_shard_shape": list(
                    img.addressable_shards[0].data.shape
                ),
                "n_local_shards": len(img.addressable_shards),
                "mean": float(mean),
                "frameids": seen_frameids,
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
