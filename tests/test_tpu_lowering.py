"""Mosaic lowering smoke tests — TPU compilability proven on CPU.

``jax.export`` with ``platforms=["tpu"]`` runs the full Pallas->Mosaic
lowering pipeline without TPU hardware.  CI executes the kernels only in
interpret mode, which skips exactly the stage where TPU block-spec rules
are enforced — this suite closes that gap.  It exists because the gap
was real: the flash kernel's original flat ``(1, block_q)`` lse output
block violated the Mosaic trailing-block tiling rule (last two block
dims divisible by (8, 128) or equal to the array dims) and would have
failed its first-ever compiled run on the chip (round 5; the artifact
would have silently degraded to full attention).  Since PR 29 lse rides
as ``(1, block_q)`` rows of a ``(bh, T/block_q, 1, block_q)`` array,
whose last two block dims EQUAL the array's: the legal form of a row.
"""

import jax
import jax.numpy as jnp
import pytest


def _export_ok(fn, *args):
    exp = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    assert len(exp.mlir_module_serialized) > 0


def test_flash_attention_fwd_bwd_lowers_for_tpu():
    """The bench configuration: d=128 heads, 128-blocks, causal."""
    from blendjax.ops.flash_attention import flash_attention

    B, T, H, D = 2, 512, 4, 128

    def loss(q, k, v):
        return flash_attention(q, k, v, True, None, 128, 128, False).sum()

    arg = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16)
    _export_ok(jax.value_and_grad(loss, argnums=(0, 1, 2)), arg, arg, arg)


def test_flash_attention_sliding_window_lowers_for_tpu():
    """Windowed (sliding) attention adds a second grid-level skip
    predicate (below-window blocks) to every pass — fwd, dQ, dK/dV must
    all still clear Mosaic with it."""
    from blendjax.ops.flash_attention import flash_attention

    B, T, H, D = 1, 512, 2, 128

    def loss(q, k, v):
        return flash_attention(
            q, k, v, True, None, 128, 128, False, 192
        ).sum()

    arg = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16)
    _export_ok(jax.value_and_grad(loss, argnums=(0, 1, 2)), arg, arg, arg)


def test_flash_attention_gqa_lowers_for_tpu():
    """GQA (kv heads < q heads): the KV head-mapped BlockSpecs and the
    group-summed dK/dV must clear Mosaic, composed with a window."""
    from blendjax.ops.flash_attention import flash_attention

    B, T, Hq, Hkv, D = 1, 512, 8, 2, 128

    def loss(q, k, v):
        return flash_attention(
            q, k, v, True, None, 128, 128, False, 192
        ).sum()

    q = jax.ShapeDtypeStruct((B, T, Hq, D), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, T, Hkv, D), jnp.bfloat16)
    _export_ok(jax.value_and_grad(loss, argnums=(0, 1, 2)), q, kv, kv)


def test_quantized_seqformer_rollout_lowers_for_tpu():
    """int8 w8a8 SeqFormer dreaming: the quantized rollout (vectorized
    prefill + ring-buffer decode, int8 einsums to int32) must export
    compiled for TPU."""
    from blendjax.models import seqformer
    from blendjax.ops.quant import quantize_seqformer

    params = seqformer.init(
        jax.random.PRNGKey(0), obs_dim=4, d_model=32, n_heads=4,
        n_layers=1, pos_encoding="rope",
    )
    qparams = quantize_seqformer(params)

    def dream(q, prefix):
        return seqformer.rollout(q, prefix, 8, compute_dtype=jnp.float32,
                                 window=8)

    prefix = jax.ShapeDtypeStruct((2, 6, 4), jnp.float32)
    exp = jax.export.export(jax.jit(dream), platforms=["tpu"])(
        qparams, prefix
    )
    assert len(exp.mlir_module_serialized) > 0


def test_flash_attention_small_head_dim_lowers_for_tpu():
    """d=64 < 128 lanes: legal only via the 'equal to the array dim'
    clause of the tiling rule — the multichip dryrun composes the kernel
    at even smaller head dims, so this clause must keep lowering."""
    from blendjax.ops.flash_attention import flash_attention

    B, T, H, D = 1, 256, 2, 64

    def fwd(q, k, v):
        return flash_attention(q, k, v, True, None, 128, 128, False)

    arg = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16)
    _export_ok(fwd, arg, arg, arg)


def test_decode_frames_pallas_lowers_for_tpu():
    from blendjax.ops.image import decode_frames_pallas

    frames = jax.ShapeDtypeStruct((8, 480, 640, 3), jnp.uint8)
    _export_ok(
        lambda x: decode_frames_pallas(x, dtype=jnp.bfloat16), frames
    )


def test_seqformer_flash_train_step_lowers_for_tpu():
    """The exact shape suite_device's seqformer phase runs on the chip:
    episode_loss_fn + compiled flash kernel + adam update."""
    import functools

    import optax

    from blendjax.models import seqformer
    from blendjax.models.train import TrainState, make_train_step
    from blendjax.ops.flash_attention import make_flash_attention

    T = 128
    params = seqformer.init(
        jax.random.PRNGKey(0), obs_dim=8, d_model=256, n_heads=2,
        n_layers=1, max_len=T,
    )
    opt = optax.adam(1e-4)
    state = TrainState.create(params, opt)
    loss = functools.partial(
        seqformer.episode_loss_fn,
        attn_fn=make_flash_attention(causal=True, interpret=False),
    )
    # donation is dropped under export (no real buffers); keep the step
    # undonated so the exported signature matches the abstract args
    step = make_train_step(loss, opt, donate=False)
    batch = {"episode": jax.ShapeDtypeStruct((2, T + 1, 8), jnp.float16)}
    state_abs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype),
        state,
    )
    exp = jax.export.export(step, platforms=["tpu"])(state_abs, batch)
    assert len(exp.mlir_module_serialized) > 0


def test_ulysses_flash_sharded_step_lowers_for_tpu():
    """The dryrun's full composition — 3-axis mesh, Ulysses all-to-all,
    compiled flash inner attention, routed top-k MoE, adam — exported
    for the TPU platform.  ``flash_interpret=False`` forces the Mosaic
    path: the off-TPU auto rule would export the interpreter lowering
    and prove nothing."""
    import numpy as np
    import optax

    from blendjax.models import seqformer
    from blendjax.parallel import make_mesh, make_seqformer_train_step

    mesh = make_mesh({"data": 2, "seq": 2, "model": 2})
    params = seqformer.init(
        jax.random.PRNGKey(1), obs_dim=6, d_model=32, n_heads=4,
        n_layers=1, n_experts=4, max_len=32,
    )
    init_sf, step, batch_sharding = make_seqformer_train_step(
        optax.adam(1e-3), mesh, attn_impl="ulysses_flash",
        moe_impl="topk", moe_k=2, moe_aux_weight=0.01,
        flash_interpret=False,
    )
    state = init_sf(params)
    batch = jax.device_put(
        seqformer.make_episode_batch(
            np.random.default_rng(0).random((4, 33, 6), np.float32)
        ),
        batch_sharding,
    )
    exp = jax.export.export(step, platforms=["tpu"])(state, batch)
    assert len(exp.mlir_module_serialized) > 0


def test_pipeline_1f1b_train_lowers_for_tpu():
    """Pipeline parallelism is plain XLA (ppermute under shard_map), not
    Mosaic — but it too has only ever compiled for CPU in CI; export the
    1F1B training step for the TPU platform like the kernels above."""
    import numpy as np

    from blendjax.models.layers import dense_apply, dense_init, gelu
    from blendjax.parallel import (
        make_mesh,
        make_pipeline_train,
        stack_stage_params,
    )

    mesh = make_mesh({"pipe": 2, "data": 2})
    d, d_in, d_out = 16, 5, 3
    rng = np.random.default_rng(0)
    keys = jax.random.split(jax.random.PRNGKey(0), 2)

    def stage_fn(p, x):
        return x + gelu(dense_apply(p["fc"], x, dtype=jnp.float32))

    stages = stack_stage_params([{"fc": dense_init(k, d, d)} for k in keys])
    proj = (
        {"w": jnp.asarray(rng.standard_normal((d_in, d)), jnp.float32)},
        {"w": jnp.asarray(rng.standard_normal((d, d_out)), jnp.float32)},
    )
    train = make_pipeline_train(
        stage_fn,
        lambda pred, tgt: jnp.mean((pred - tgt) ** 2),
        mesh,
        schedule="1f1b",
        in_proj=lambda pp, mb: mb @ pp["w"],
        out_proj=lambda pp, y: y @ pp["w"],
    )
    x = jnp.asarray(rng.standard_normal((4, 2, d_in)), jnp.float32)
    t = jnp.asarray(rng.standard_normal((4, 2, d_out)), jnp.float32)
    exp = jax.export.export(jax.jit(train), platforms=["tpu"])(
        stages, proj, x, t
    )
    assert len(exp.mlir_module_serialized) > 0


def test_detector_decode_train_step_lowers_for_tpu():
    """The cube stream_to_train program: uint8 frames decoded on device
    (jnp path) into the detector conv net + adam, RGB wire default."""
    import optax

    from blendjax.models import detector
    from blendjax.models.train import TrainState, make_train_step
    from blendjax.ops.image import decode_frames

    params = detector.init(
        jax.random.PRNGKey(0), num_keypoints=8, in_channels=3,
        channels=(8, 16), hidden=32,
    )
    opt = optax.adam(1e-3)
    state = TrainState.create(params, opt)

    def loss_with_decode(params, batch):
        images = decode_frames(batch["image"], dtype=jnp.bfloat16)
        return detector.loss_fn(
            params, {"image": images, "xy": batch["xy"]}
        )

    step = make_train_step(loss_with_decode, opt, donate=False)
    batch = {
        "image": jax.ShapeDtypeStruct((4, 48, 64, 3), jnp.uint8),
        "xy": jax.ShapeDtypeStruct((4, 8, 2), jnp.float32),
    }
    state_abs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype),
        state,
    )
    exp = jax.export.export(step, platforms=["tpu"])(state_abs, batch)
    assert len(exp.mlir_module_serialized) > 0


@pytest.mark.parametrize("dispatch", ["sort", "scatter"])
def test_moe_topk_dispatch_step_lowers_for_tpu(dispatch):
    """The moe_compare phase's routed top-k program, both dispatch
    algorithms — the scatter arena exercises a different Mosaic path
    than the sort/gather default (the topk_alt row on TPU)."""
    import functools

    import optax

    from blendjax.models import seqformer
    from blendjax.models.train import TrainState, make_train_step

    params = seqformer.init(
        jax.random.PRNGKey(0), obs_dim=8, d_model=64, n_heads=2,
        n_layers=1, n_experts=4, max_len=32,
    )
    opt = optax.adam(1e-4)
    state = TrainState.create(params, opt)
    loss = functools.partial(
        seqformer.loss_fn, moe_impl="topk", moe_k=2,
        moe_aux_weight=0.01, moe_dispatch=dispatch,
    )
    step = make_train_step(loss, opt, donate=False)
    batch = {
        "obs": jax.ShapeDtypeStruct((2, 32, 8), jnp.float32),
        "target": jax.ShapeDtypeStruct((2, 32, 8), jnp.float32),
    }
    state_abs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype),
        state,
    )
    exp = jax.export.export(step, platforms=["tpu"])(state_abs, batch)
    assert len(exp.mlir_module_serialized) > 0


def test_ring_flash_sharded_step_lowers_for_tpu():
    """ring_flash = the flash kernel fused into ring attention (rotating
    KV + custom ring-level VJP).  Exported COMPILED (flash_interpret=
    False) for the TPU platform with full vma checking — the interpreter
    path in CI uses the check_vma workaround, so this is the only place
    the compiled lowering's typing is exercised."""
    import numpy as np
    import optax

    from blendjax.models import seqformer
    from blendjax.parallel import make_mesh, make_seqformer_train_step

    mesh = make_mesh({"data": 2, "seq": 2, "model": 2})
    params = seqformer.init(
        jax.random.PRNGKey(1), obs_dim=6, d_model=32, n_heads=4,
        n_layers=1, max_len=32,
    )
    init_sf, step, batch_sharding = make_seqformer_train_step(
        optax.adam(1e-3), mesh, attn_impl="ring_flash",
        flash_interpret=False,
    )
    state = init_sf(params)
    batch = jax.device_put(
        seqformer.make_episode_batch(
            np.random.default_rng(0).random((4, 33, 6), np.float32)
        ),
        batch_sharding,
    )
    exp = jax.export.export(step, platforms=["tpu"])(state, batch)
    assert len(exp.mlir_module_serialized) > 0


def test_windowed_ring_flash_sharded_step_lowers_for_tpu():
    """Sliding-window ring_flash: per-pair windowed kernels at static
    q_offsets, early-stopped rotation, single accumulator jump home in
    the backward — the full sharded train step must export COMPILED for
    TPU with vma checking (the long-context windowed configuration)."""
    import numpy as np
    import optax

    from blendjax.models import seqformer
    from blendjax.parallel import make_mesh, make_seqformer_train_step

    mesh = make_mesh({"data": 2, "seq": 2, "model": 2})
    params = seqformer.init(
        jax.random.PRNGKey(1), obs_dim=6, d_model=32, n_heads=4,
        n_layers=1, max_len=32,
    )
    init_sf, step, batch_sharding = make_seqformer_train_step(
        optax.adam(1e-3), mesh, attn_impl="ring_flash",
        flash_interpret=False, attn_window=20,
    )
    state = init_sf(params)
    batch = jax.device_put(
        seqformer.make_episode_batch(
            np.random.default_rng(0).random((4, 33, 6), np.float32)
        ),
        batch_sharding,
    )
    exp = jax.export.export(step, platforms=["tpu"])(state, batch)
    assert len(exp.mlir_module_serialized) > 0


def test_flash_attention_32_tile_lowers_for_tpu():
    """The bench gate now admits any 32-multiple length; sub-128 tiles
    (lse rows (1, 32) of a (bh, T/32, 1, 32) array, scratch (32, 128))
    must lower too — a Mosaic
    rejection specific to small tiles must surface here, not mid-bench
    on the chip."""
    from blendjax.ops.flash_attention import make_flash_attention

    attn = make_flash_attention(causal=True, block_q="auto",
                                block_kv="auto", interpret=False)
    arg = jax.ShapeDtypeStruct((1, 160, 2, 128), jnp.bfloat16)
    _export_ok(attn, arg, arg, arg)


def test_zigzag_flash_sharded_step_lowers_for_tpu():
    """Compiled zigzag (load-balanced causal ring + flash) sharded step
    exported for the TPU platform with full vma typing, like its
    ring_flash sibling."""
    import numpy as np
    import optax

    from blendjax.models import seqformer
    from blendjax.parallel import make_mesh, make_seqformer_train_step

    mesh = make_mesh({"data": 2, "seq": 2, "model": 2})
    params = seqformer.init(
        jax.random.PRNGKey(1), obs_dim=6, d_model=32, n_heads=4,
        n_layers=1, max_len=32,
    )
    init_sf, step, batch_sharding = make_seqformer_train_step(
        optax.adam(1e-3), mesh, attn_impl="zigzag_flash",
        flash_interpret=False,
    )
    state = init_sf(params)
    batch = jax.device_put(
        seqformer.make_episode_batch(
            np.random.default_rng(0).random((4, 33, 6), np.float32)
        ),
        batch_sharding,
    )
    exp = jax.export.export(step, platforms=["tpu"])(state, batch)
    assert len(exp.mlir_module_serialized) > 0
