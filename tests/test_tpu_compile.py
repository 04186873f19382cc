"""Compiles for a described (not attached) TPU v5e: what the chip's own
compiler makes of the main path's programs at their real widths.  No
chip, no run, no timing: only what is and is not in the compiled module.

Every test of this kind lives in THIS file (one worker loads the TPU's
library and keeps it); the topology is described inside a fixture,
never at import (the `on-chip-measurement` guide, section 2).
"""

import os
import re

import numpy as np
import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


# the serve cell's pool (chipbench/configs/seqformer_wm100m_serve_f32.json)
# over two of its eight layers: the widths are what the compiler's choices
# hang on, the depth only repeats them
POOL = dict(slots=64, length=1024)
WIDTHS = dict(obs_dim=32, d_model=1024, n_heads=8, n_layers=2, d_ff=4096,
              max_len=1024)


@pytest.mark.parametrize("bucket", [16, 64])
def test_serve_step_moves_no_pool_tensor_on_the_chip(one_chip, bucket):
    """The donated pool is aliased to the output, no pool-shaped `copy`
    and no slice of a whole pool tensor is compiled
    (`mini-gather-slice`: the compiler's cut of a gather's operand,
    which reads and writes all of it), and the temporaries stay under
    one pool tensor plus the bucket's gathered K and V rows."""
    import jax
    import jax.numpy as jnp

    from blendjax.models import seqformer
    from blendjax.serve.server import SeqFormerModel

    tiny = SeqFormerModel(
        seqformer.init(jax.random.PRNGKey(0), obs_dim=32, d_model=32,
                       n_heads=2, n_layers=2, max_len=16),
        slots=2, length=16)
    params = jax.eval_shape(
        lambda: seqformer.init(jax.random.PRNGKey(0), **WIDTHS))
    cache = jax.eval_shape(lambda: seqformer.init_cache(
        params, POOL["slots"] + 1, dtype=jnp.float32,
        length=POOL["length"], per_row=True))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    with jax.default_matmul_precision("highest"):
        compiled = tiny._step.lower(
            on_chip(params), on_chip(cache),
            jax.ShapeDtypeStruct((bucket,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((bucket, 32), jnp.float32,
                                 sharding=one_chip),
        ).compile()
    tensor = cache["k"][0]
    tensor_bytes = int(np.prod(tensor.shape)) * 4
    pool_bytes = 2 * WIDTHS["n_layers"] * tensor_bytes
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    rows_bytes = 2 * bucket * tensor_bytes // tensor.shape[0]
    assert mem.temp_size_in_bytes < tensor_bytes + rows_bytes
    text = compiled.as_text()
    assert "jit_serve_step" in text
    shape = "f32[%s]" % ",".join(map(str, tensor.shape))
    assert not [line for line in text.splitlines()
                if re.search(r" copy\(", line) and shape in line]
    assert "mini-gather-slice" not in text
