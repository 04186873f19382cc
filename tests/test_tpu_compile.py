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


# the token cell's widths (chipbench/configs/sarvam105b_ep4_serve_bf16.json)
# over its dense layer and one of its four expert layers
TOKEN_POOL = dict(slots=128, length=2048)
TOKEN_WIDTHS = dict(
    hidden_size=4096, num_attention_heads=64, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    rope_theta=10000, rope_scaling=dict(
        factor=40, beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1,
        original_max_position_embeddings=4096),
    num_hidden_layers=2, first_k_dense_replace=1, intermediate_size=16384,
    moe_intermediate_size=2048, num_experts=128, num_experts_per_tok=8,
    routed_scaling_factor=2.5, num_shared_experts=1, vocab_size=65536)


@pytest.mark.parametrize("what,n", [("step", 64), ("prefill", 256)])
def test_latent_pool_is_served_in_place_on_the_chip(one_chip, what, n,
                                                    monkeypatch):
    """A latent-attention, routed-expert token model at its published
    widths: the donated latent pool is aliased to the output with no
    pool-shaped `copy` (a row 576 wide would be laid out positions-minor
    and copied twice a layer: `mla.row_width` pads it to whole lanes),
    the rows are read by pieces, the held experts' three products are
    one `expert_ffn` kernel a layer, and the decode step's temporaries
    stay far under one layer of per-head K and V (absorbed attention
    never forms them)."""
    import importlib

    import jax
    import jax.numpy as jnp

    from blendjax.models import seqformer
    from blendjax.serve.server import SeqFormerModel

    flash_attention = importlib.import_module("blendjax.ops.flash_attention")
    # this process's backend is the CPU, where the kernels interpret: the
    # chip compiles them (the guide's "steer such code in the test")
    monkeypatch.setattr(flash_attention, "resolve_interpret",
                        lambda interpret=None: False)

    tiny_widths = dict(
        TOKEN_WIDTHS, hidden_size=32, num_attention_heads=2, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        intermediate_size=32, moe_intermediate_size=16, num_experts=8,
        num_experts_per_tok=2, vocab_size=64)
    tiny = SeqFormerModel(
        seqformer.init_token_model(jax.random.PRNGKey(0), tiny_widths,
                                   held=(0, 4), dtype=jnp.bfloat16),
        slots=2, length=16, compute_dtype=jnp.bfloat16,
        cache_dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda: seqformer.init_token_model(
        jax.random.PRNGKey(0), TOKEN_WIDTHS, held=(0, 32),
        dtype=jnp.bfloat16))
    cache = jax.eval_shape(lambda: seqformer.init_cache(
        params, TOKEN_POOL["slots"] + 1, dtype=jnp.bfloat16,
        length=TOKEN_POOL["length"], per_row=True))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    fn = tiny._step if what == "step" else tiny._prefill
    compiled = fn.lower(
        on_chip(params), on_chip(cache),
        jax.ShapeDtypeStruct((n if what == "step" else 1,), jnp.int32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((n, 1), jnp.int32, sharding=one_chip),
    ).compile()
    tensor = cache["kv"][0]
    assert tensor.shape == (129, 2048, 640)
    tensor_bytes = int(np.prod(tensor.shape)) * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * tensor_bytes
    text = compiled.as_text()
    assert f"jit_serve_{what}" in text
    assert not [line for line in text.splitlines()
                if re.search(r" copy\(", line) and "[129,2048,640]" in line]
    assert "mini-gather-slice" not in text
    # one expert layer of the two: its gate, up and down in one kernel
    assert len(re.findall(r"%expert_ffn[.\d]* = ", text)) == 1
    assert "ragged-dot" not in text
    if what == "step":
        # the gathered latent rows of one layer and little else; one
        # layer's per-head K and V of those rows would be 5.4 GB
        rows_bytes = n * tensor_bytes // tensor.shape[0]
        assert mem.temp_size_in_bytes < 1.25 * rows_bytes
        assert rows_bytes * 20 < n * 2048 * 64 * 320 * 2


# the hybrid cell's widths (chipbench/configs/phi4miniflash_serve_bf16.json)
# over the 32-layer rule at 8 layers: two periods of state-space and
# window, then state-space, full, gated memory, cross
HYBRID_WIDTHS = dict(
    hidden_size=2560, num_attention_heads=40, num_key_value_heads=20,
    intermediate_size=10240, num_hidden_layers=8, mb_per_layer=2,
    sliding_window=512, layer_norm_eps=1e-5, vocab_size=200064,
    mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=160)


@pytest.mark.parametrize("what,n", [("step", 64), ("prefill", 512)])
def test_hybrid_pool_is_served_in_place_on_the_chip(one_chip, what, n,
                                                    monkeypatch):
    """A model of mixed layer kinds at its published widths: the donated
    pool (window rings, the one full-length K/V, float32 recurrent state
    beside bfloat16 tails) is aliased to the output, no leaf of it is
    copied or re-laid whole (a positions-major `(S, C, 10, 128)` K/V or
    a `(S, 3, 5120)` tail was, and a pair-major `(S, 10, C, 128)` K/V:
    `init_cache` keeps every leaf flat behind its row or position), nor
    are the stepped rows once gathered (seen as `(B, C, 10, 128)` they
    were, twice a read: `diffattn.attend_one` reads each pair as whole
    lanes of the flat rows), the rows are read by pieces, and the
    prefill's attention is the flash kernel."""
    import jax
    import jax.numpy as jnp

    import importlib

    from blendjax.models import seqformer
    from blendjax.serve.server import SeqFormerModel

    flash_attention = importlib.import_module("blendjax.ops.flash_attention")
    # this process's backend is the CPU, where the kernels interpret: the
    # chip compiles them (the guide's "steer such code in the test")
    monkeypatch.setattr(flash_attention, "resolve_interpret",
                        lambda interpret=None: False)
    assert seqformer.hybrid_layer_kinds(HYBRID_WIDTHS) == [
        "ssm", "window", "ssm", "window", "ssm", "full", "gmu", "cross"]
    tiny_widths = dict(
        HYBRID_WIDTHS, hidden_size=64, num_attention_heads=8,
        num_key_value_heads=4, intermediate_size=128, sliding_window=8,
        vocab_size=96, mamba_d_state=4, mamba_dt_rank=4)
    tiny = SeqFormerModel(
        seqformer.init_hybrid_model(jax.random.PRNGKey(0), tiny_widths,
                                    dtype=jnp.bfloat16),
        slots=2, length=16, compute_dtype=jnp.bfloat16,
        cache_dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda: seqformer.init_hybrid_model(
        jax.random.PRNGKey(0), HYBRID_WIDTHS, dtype=jnp.bfloat16))
    cache = jax.eval_shape(lambda: seqformer.init_cache(
        params, TOKEN_POOL["slots"] + 1, dtype=jnp.bfloat16,
        length=TOKEN_POOL["length"], per_row=True))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    fn = tiny._step if what == "step" else tiny._prefill
    compiled = fn.lower(
        on_chip(params), on_chip(cache),
        jax.ShapeDtypeStruct((n if what == "step" else 1,), jnp.int32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((n, 1), jnp.int32, sharding=one_chip),
    ).compile()
    leaves = [leaf for leaf in jax.tree.leaves(cache) if leaf.ndim > 1]
    shapes = {(leaf.shape, leaf.dtype.name) for leaf in leaves}
    assert shapes == {
        ((129, 512, 1280), "bfloat16"), ((129, 2048, 1280), "bfloat16"),
        ((129, 16, 5120), "float32"), ((129, 15360), "bfloat16")}
    pool_bytes = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                     for leaf in leaves)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    text = compiled.as_text()
    assert f"jit_serve_{what}" in text
    assert not [line for line in text.splitlines()
                if re.search(r" copy\(", line)
                and re.search(r"= \w+\[129,", line)]
    assert "mini-gather-slice" not in text
    rows_shape = r"\[%d,(512|2048),(1280|10,128)\]|\[%d,10,(512|2048),128\]" % (
        n, n)
    if what == "step":
        # the gathered rows go to the products as they come: no `copy` or
        # `reshape` of them
        assert not [line for line in text.splitlines()
                    if re.search(r" (copy|reshape)\(", line)
                    and re.search(rows_shape, line.split(" = ")[1][:60])]
        # about the gathered rows of the full-length K/V (gathered once:
        # the cross layer reads them again) and of one ring, twice over;
        # eight readers each with rows of their own would be 5.4 GB
        rows = 2 * n * (2048 + 512) * 1280 * 2
        assert mem.temp_size_in_bytes < 2 * rows
    else:
        assert "flash_fwd" in text and "tpu_custom_call" in text


# the linear-attention cell's widths
# (chipbench/configs/olmohybrid7b_l12_serve_bf16.json) over one period:
# three linear-attention layers and one full-attention layer
LINEAR_POOL = dict(slots=72, length=1536)
LINEAR_WIDTHS = dict(
    hidden_size=3840, intermediate_size=11008, num_hidden_layers=4,
    num_attention_heads=30, num_key_value_heads=30,
    layer_types=["linear_attention"] * 3 + ["full_attention"],
    linear_num_key_heads=30, linear_num_value_heads=30,
    linear_key_head_dim=96, linear_value_head_dim=192,
    linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    rms_norm_eps=1e-6, vocab_size=100352, tie_word_embeddings=False)


@pytest.mark.parametrize("what,n", [("step", 32), ("step", 64),
                                    ("prefill", 1024)])
def test_linear_attention_pool_is_served_in_place_on_the_chip(
        one_chip, what, n, monkeypatch):
    """Gated delta-rule linear attention at its published widths: the
    donated pool (a float32 matrix state a head, 2.2 MB a row and layer,
    three tails, the full layers' K/V) is aliased to the output; no leaf
    of it is copied, re-laid or sliced whole (a flat ``(S, 552960)`` state
    was cut by the compiler into whole-pool slices before its gather:
    ``init_cache`` keeps the heads in three pieces of ten, each under the
    gather's limit); the step's temporaries stay near the stepped rows'
    gathered K/V and state; the prefill's full attention is the flash
    kernel; the step's state update is the ``gdn_update`` kernel, which
    reads and writes the stepped rows' state in the pool."""
    import importlib

    import jax
    import jax.numpy as jnp

    from blendjax.models import seqformer
    from blendjax.serve.server import SeqFormerModel

    flash_attention = importlib.import_module("blendjax.ops.flash_attention")
    monkeypatch.setattr(flash_attention, "resolve_interpret",
                        lambda interpret=None: False)
    tiny_widths = dict(
        LINEAR_WIDTHS, hidden_size=64, intermediate_size=128,
        num_attention_heads=2, num_key_value_heads=2, linear_num_key_heads=2,
        linear_num_value_heads=2, linear_key_head_dim=8,
        linear_value_head_dim=16, vocab_size=128)
    tiny = SeqFormerModel(
        seqformer.init_linear_hybrid_model(jax.random.PRNGKey(0), tiny_widths,
                                           dtype=jnp.bfloat16),
        slots=2, length=16, compute_dtype=jnp.bfloat16,
        cache_dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda: seqformer.init_linear_hybrid_model(
        jax.random.PRNGKey(0), LINEAR_WIDTHS, dtype=jnp.bfloat16))
    cache = jax.eval_shape(lambda: seqformer.init_cache(
        params, LINEAR_POOL["slots"] + 1, dtype=jnp.bfloat16,
        length=LINEAR_POOL["length"], per_row=True))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    fn = tiny._step if what == "step" else tiny._prefill
    compiled = fn.lower(
        on_chip(params), on_chip(cache),
        jax.ShapeDtypeStruct((n if what == "step" else 1,), jnp.int32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((n, 1), jnp.int32, sharding=one_chip),
    ).compile()
    leaves = [leaf for leaf in jax.tree.leaves(cache) if leaf.ndim > 1]
    shapes = {(leaf.shape, leaf.dtype.name) for leaf in leaves}
    assert shapes == {
        ((73, 3, 10, 192, 96), "float32"), ((73, 8640), "bfloat16"),
        ((73, 17280), "bfloat16"), ((73, 1536, 3840), "bfloat16")}
    assert seqformer.state_row_bytes(cache) == 3 * (552960 * 4
                                                    + 3 * 11520 * 2)
    pool_bytes = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                     for leaf in leaves)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    text = compiled.as_text()
    assert f"jit_serve_{what}" in text
    # no operation yields a whole leaf of the pool but the in-place
    # writes (fusions over the aliased buffer): no copy, no slice
    whole = [line for line in text.splitlines()
             if re.search(r"= \w+\[(73|219),", line)
             and re.search(r" (copy|slice|copy-start|slice-start|"
                           r"transpose)\(", line)]
    assert not whole, whole[:3]
    assert "mini-gather-slice" not in text
    if what == "step":
        # each linear layer's state is stepped where it lies, by the one
        # kernel: the pool goes in and comes out aliased, and nothing
        # holds the stepped rows' state on its own (no gather, no update,
        # no scatter of it)
        assert len(re.findall(r"%gdn_update[.\d]* = ", text)) == 3
        assert "tpu_custom_call" in text
        assert not re.search(rf"= f32\[{n},3,10,192,96\]", text)
        # what is left is one full layer's gathered keys of the stepped
        # rows, the head's float32 logits and, of the state, the kernel's
        # operands and `o` alone: under a sixteenth of the stepped rows'
        # state, its 96-wide minor axis padded to 128 lanes (the gathered
        # and updated copies took a sixth of it before the kernel)
        keys = n * 1536 * 3840 * 2
        logits = n * 100352 * 4
        state_rows = n * 30 * 192 * 128 * 4
        assert mem.temp_size_in_bytes < keys + logits + state_rows / 16
    else:
        assert "flash_fwd" in text and "tpu_custom_call" in text


@pytest.mark.parametrize("what,n", [("step", 32), ("step", 64),
                                    ("prefill", 1024), ("prefill", 1536),
                                    ("prefill", 2048)])
def test_window_and_full_pool_is_served_in_place_on_the_chip(
        one_chip, what, n, monkeypatch):
    """Sliding-window and full grouped-query attention beside
    softmax-routed experts, at the published widths
    (chipbench/configs/mellum2_ep4_serve_bf16.json) over one period: three
    window layers and one full layer, 16 of 64 experts held.  The donated
    pool (a ring of 1024 positions per window layer, 2560 per full layer,
    K/V flat behind the position) is aliased to the output; no leaf of it
    is copied, re-laid or sliced whole; the rows are read by pieces; the
    held experts' three products are one `expert_ffn` kernel a layer; the
    step's temporaries stay near one layer's gathered full K/V rows and
    the head's logits; the prefill's attention, windowed on the window
    layers, is the flash kernel."""
    import importlib
    import json

    import jax
    import jax.numpy as jnp

    from blendjax.models import seqformer
    from blendjax.serve.server import SeqFormerModel
    from chipbench import reference_mellum2

    flash_attention = importlib.import_module("blendjax.ops.flash_attention")
    monkeypatch.setattr(flash_attention, "resolve_interpret",
                        lambda interpret=None: False)
    path = os.path.join(os.path.dirname(reference_mellum2.__file__),
                        "configs", "mellum2_ep4_serve_bf16.json")
    with open(path) as f:
        cfg = dict(json.load(f), num_hidden_layers=4)
    tiny_cfg = dict(cfg, hidden_size=48, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16,
                    moe_intermediate_size=24, num_experts=8,
                    num_experts_held=4, num_experts_per_tok=2,
                    vocab_size=96, sliding_window=8)
    tiny = SeqFormerModel(
        seqformer.describe_token_model(reference_mellum2.make_params(
            tiny_cfg, 0, jnp.bfloat16), tiny_cfg),
        slots=2, length=16, compute_dtype=jnp.bfloat16,
        cache_dtype=jnp.bfloat16)
    params = seqformer.describe_token_model(jax.eval_shape(
        lambda: reference_mellum2.make_params(cfg, 0, jnp.bfloat16)), cfg)
    cache = jax.eval_shape(lambda: seqformer.init_cache(
        params, cfg["slots"] + 1, dtype=jnp.bfloat16, length=cfg["length"],
        per_row=True))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    fn = tiny._step if what == "step" else tiny._prefill
    compiled = fn.lower(
        on_chip(params), on_chip(cache),
        jax.ShapeDtypeStruct((n if what == "step" else 1,), jnp.int32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((n, 1), jnp.int32, sharding=one_chip),
    ).compile()
    assert sorted(cache) == ["k", "pos", "v"]
    assert [leaf.shape for leaf in cache["k"]] == [(73, 1024, 512)] * 3 + [
        (73, 2560, 512)]
    pool_bytes = sum(int(np.prod(leaf.shape)) * 2
                     for leaf in cache["k"] + cache["v"])
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    text = compiled.as_text()
    assert f"jit_serve_{what}" in text
    whole = [line for line in text.splitlines()
             if re.search(r"= \w+\[73,", line)
             and re.search(r" (copy|slice|copy-start|slice-start|"
                           r"transpose)\(", line)]
    assert not whole, whole[:3]
    assert "mini-gather-slice" not in text
    # the held experts' three products, one kernel in each of four layers
    assert len(re.findall(r"%expert_ffn[.\d]* = ", text)) == 4
    assert "ragged-dot" not in text
    if what == "step":
        # one full layer's gathered K and V rows, the head's float32
        # logits, and little else (measured 0.10 / 0.20 GB at 32 / 64)
        full_rows = 2 * n * 2560 * 512 * 2
        logits = n * cfg["vocab_size"] * 4
        assert mem.temp_size_in_bytes < 1.5 * (full_rows + logits)
    else:
        assert "flash_fwd" in text and "tpu_custom_call" in text


@pytest.mark.parametrize("what,n", [("step", 32), ("step", 64),
                                    ("prefill", 1024)])
def test_mamba2_pool_is_served_in_place_on_the_chip(one_chip, what, n,
                                                    monkeypatch):
    """Mamba-2 layers beside NoPE grouped-query attention at the published
    widths (chipbench/configs/granite4hmicro_serve_bf16.json) over one
    period: five Mamba-2 layers, one attention layer, four Mamba-2 layers.
    The donated pool (a float32 state of 64 heads x 64 x 128 a row and
    layer in two pieces of 32 heads, a flat bfloat16 tail of 3 x 4352, the
    attention layer's K/V) is aliased to the output; no leaf of it is
    copied, re-laid or sliced whole; the step steps each layer's state
    where it lies, by the delta-free ``gdn_update`` kernel (``ssd_update``,
    one a layer), and holds no stepped rows' state of its own; the
    prefill's attention is the flash kernel."""
    import importlib
    import json

    import jax
    import jax.numpy as jnp

    from blendjax.models import seqformer
    from blendjax.serve.server import SeqFormerModel
    from chipbench import reference_granite4h

    flash_attention = importlib.import_module("blendjax.ops.flash_attention")
    monkeypatch.setattr(flash_attention, "resolve_interpret",
                        lambda interpret=None: False)
    path = os.path.join(os.path.dirname(reference_granite4h.__file__),
                        "configs", "granite4hmicro_serve_bf16.json")
    with open(path) as f:
        cfg = dict(json.load(f), num_hidden_layers=10)
    assert seqformer.hybrid_layer_kinds(cfg) == ["ssd"] * 5 + ["full"] + [
        "ssd"] * 4
    tiny_cfg = dict(cfg, hidden_size=64, num_attention_heads=4,
                    num_key_value_heads=2, mamba_n_heads=8, mamba_d_head=16,
                    mamba_d_state=16, shared_intermediate_size=64,
                    vocab_size=96)
    tiny = SeqFormerModel(
        seqformer.describe_token_model(reference_granite4h.make_params(
            tiny_cfg, 0, jnp.bfloat16), tiny_cfg),
        slots=2, length=16, compute_dtype=jnp.bfloat16,
        cache_dtype=jnp.bfloat16)
    params = seqformer.describe_token_model(jax.eval_shape(
        lambda: reference_granite4h.make_params(cfg, 0, jnp.bfloat16)), cfg)
    cache = jax.eval_shape(lambda: seqformer.init_cache(
        params, cfg["slots"] + 1, dtype=jnp.bfloat16, length=cfg["length"],
        per_row=True))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    fn = tiny._step if what == "step" else tiny._prefill
    compiled = fn.lower(
        on_chip(params), on_chip(cache),
        jax.ShapeDtypeStruct((n if what == "step" else 1,), jnp.int32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((n, 1), jnp.int32, sharding=one_chip),
    ).compile()
    leaves = [leaf for leaf in jax.tree.leaves(cache) if leaf.ndim > 1]
    shapes = {(leaf.shape, leaf.dtype.name) for leaf in leaves}
    assert shapes == {
        ((73, 2, 32, 64, 128), "float32"), ((73, 13056), "bfloat16"),
        ((73, 1536, 512), "bfloat16")}
    assert seqformer.state_row_bytes(cache) == 9 * (2_097_152 + 26_112)
    pool_bytes = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                     for leaf in leaves)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    text = compiled.as_text()
    assert f"jit_serve_{what}" in text
    whole = [line for line in text.splitlines()
             if re.search(r"= \w+\[(73|146),", line)
             and re.search(r" (copy|slice|copy-start|slice-start|"
                           r"transpose)\(", line)]
    assert not whole, whole[:3]
    assert "mini-gather-slice" not in text
    if what == "step":
        assert len(re.findall(r"%ssd_update[.\d]* = ", text)) == 9
        assert not re.search(r"%gdn_update[.\d]* = ", text)
        assert "tpu_custom_call" in text
        assert not re.search(rf"= f32\[{n},(2,32|64),64,128\]", text)
        # the attention layer's gathered K/V rows, the head's float32
        # logits, and of the state the kernel's operands and reads alone:
        # under a sixteenth of the stepped rows' state (measured 0.16 /
        # 0.29 GB at 32 / 64 over all 40 layers)
        kv_rows = 2 * n * 1536 * 512 * 2
        logits = n * cfg["vocab_size"] * 4
        state_rows = n * 64 * 64 * 128 * 4
        assert mem.temp_size_in_bytes < kv_rows + logits + state_rows / 16
    else:
        assert "flash_fwd" in text and "tpu_custom_call" in text
        # the chunks' decays and scores of one layer, not of all nine
        assert mem.temp_size_in_bytes < 0.6e9


# the train cell (chipbench/configs/seqformer_wm100m_train_bf16.json: batch
# 64 x 512, 8 heads of 128, bfloat16 compute, block 'auto'), the long
# sequence of chip_smoke.py's kernel leg, and the widest float32 head the
# policy gives its second-largest tile (what `_VMEM_BUDGET` is set against)
@pytest.mark.parametrize("name,b,t,h,d,dtype,tile", [
    ("train_cell", 64, 512, 8, 128, "bfloat16", 512),
    ("t4096", 8, 4096, 8, 128, "bfloat16", 1024),
    ("t4096_f32", 2, 4096, 2, 128, "float32", 1024),
    ("t4096_f32_d256", 2, 4096, 2, 256, "float32", 512),
])
def test_flash_kernels_compile_at_the_policys_tiles(
        one_chip, name, b, t, h, d, dtype, tile):
    """Forward and backward of `flash_attention` under `block='auto'`:
    the three kernels are in the compiled module under their names (the
    benchmark's `kernel.*` readers find device seconds by them), the
    chip's compiler takes the policy's tile within VMEM, q, k, v and dO
    reach the kernels in the dtype they were passed in (bfloat16 blocks
    are not converted to float32 ahead of the call), and lse and delta
    travel as lane-dense rows of one block each, not as columns."""
    import jax
    import jax.numpy as jnp

    from blendjax.ops.flash_attention import (
        flash_block_size,
        make_flash_attention,
    )

    dtype = jnp.dtype(dtype)
    assert flash_block_size(t, d, dtype) == tile
    attn = make_flash_attention(causal=True, block_q="auto",
                                block_kv="auto", interpret=False)
    arg = jax.ShapeDtypeStruct((b, t, h, d), dtype, sharding=one_chip)
    w = jax.ShapeDtypeStruct((b, t, h, d), jnp.float32, sharding=one_chip)

    def loss(q, k, v, w):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) * w)

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        arg, arg, arg, w).compile().as_text()
    calls = {}
    for line in text.splitlines():
        if "custom_call_target=\"tpu_custom_call\"" not in line:
            continue
        for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
            if re.search(rf"%\S*{kernel}\S* = ", line):
                calls[kernel] = line
    assert sorted(calls) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    short = {"bfloat16": "bf16", "float32": "f32"}[dtype.name]
    block = f"{short}[{b * h},{t},{d}]"
    rows = f"f32[{b * h},{t // tile},1,{tile}]"
    for kernel, line in calls.items():
        operands = re.search(
            r"operand_layout_constraints=\{(.*?\})\}", line).group(1)
        n_blocks = 3 if kernel == "flash_fwd" else 4
        assert operands.count(block) == n_blocks, (kernel, operands)
        assert operands.count(rows) == (0 if kernel == "flash_fwd" else 2)
    assert rows in calls["flash_fwd"].split(" custom-call(")[0]  # lse out


# the held experts of the two routed cells, at the rows (top 8) of every
# bucket they serve (8, 16, 32, 40, 48, 64) and of every prefill length
# they warm: the tiles come from the shapes, so each row count is its own
# compile (a 1536-id prefill's row gather was once refused for scoped
# VMEM where 1024 and 2048 compiled)
@pytest.mark.parametrize("d,f,held,prefills", [
    (2304, 896, 16, (1024, 1536, 2048)),   # mellum2_ep4_serve_bf16
    (4096, 2048, 32, (256, 512, 1024)),    # sarvam105b_ep4_serve_bf16
], ids=["mellum2", "sarvam"])
def test_expert_ffn_compiles_at_every_bucket_and_prefill(
        one_chip, d, f, held, prefills):
    """The held experts' three products compile for the chip, within the
    VMEM limit the tiles ask for, as one `expert_ffn` custom call, at
    every row count the cells give them."""
    import jax
    import jax.numpy as jnp

    from blendjax.ops.expert_ffn import expert_ffn

    def arg(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = jax.jit(lambda *a: expert_ffn(*a, interpret=False))
    for n in (8, 16, 32, 40, 48, 64) + prefills:
        text = fn.lower(arg(8 * n, d), arg(held, dtype=jnp.int32),
                        arg(held, d, f), arg(held, d, f),
                        arg(held, f, d)).compile().as_text()
        assert len(re.findall(r"%expert_ffn[.\d]* = ", text)) == 1, n
