"""Supervised data generation + TPU training (counterpart of reference
``examples/datagen/generate.py``: 4 instances, stream with record/replay
switches — but the consumer is the full blendjax TPU pipeline and a
TinyDetector actually trains on the stream).

Modes:
    python generate.py                  # live stream -> train
    python generate.py --record prefix  # live stream -> train + record .btr
    python generate.py --replay prefix  # no Blender: replay recordings

The training loop is factored into ``train_on_stream`` so tests (and other
scripts) can drive it with any batch iterator.
"""

from __future__ import annotations

import argparse
import os
from pathlib import Path

from blendjax.btt.launcher import place_compile_cache

place_compile_cache(os.environ)  # before jax reads its configuration

import jax  # noqa: E402
import numpy as np
import optax

from blendjax import btt
from blendjax.models import detector
from blendjax.models.train import TrainState, make_train_step
from blendjax.ops.image import decode_frames
from blendjax.parallel import data_mesh, data_sharding

SCRIPT = Path(__file__).parent / "cube.blend.py"
IMAGE_HW = (480, 640)


def item_transform(item):
    """Producer message -> training sample: keep the image uint8 (decode
    happens on-device) and normalize keypoints to [0,1]."""
    h, w = IMAGE_HW
    return {
        "image": item["image"],
        "xy": (item["xy"] / np.array([w, h], np.float32)).astype(np.float32),
    }


def make_state(key, num_keypoints=8, in_channels=3):
    params = detector.init(key, num_keypoints=num_keypoints, in_channels=in_channels)
    return TrainState.create(params, optax.adam(1e-3))


def train_on_stream(batches, state=None, log_every=8):
    """Train TinyDetector over an iterator of device batches."""
    state = state or make_state(jax.random.PRNGKey(0))
    opt = optax.adam(1e-3)

    def loss_with_decode(params, batch):
        images = decode_frames(batch["image"], dtype=jax.numpy.bfloat16)
        return detector.loss_fn(params, {"image": images, "xy": batch["xy"]})

    step = make_train_step(loss_with_decode, opt)
    losses = []
    for i, batch in enumerate(batches):
        state, loss = step(state, batch)
        losses.append(float(loss))
        if log_every and (i + 1) % log_every == 0:
            print(f"batch {i + 1}: loss {np.mean(losses[-log_every:]):.5f}")
    return state, losses


def infer_int8(state, raw_frames):
    """w8a8 inference on a trained detector: quantize once, run the
    int8 forward on decoded frames (blendjax.ops.quant; half the weight
    bytes, int8 MXU operands).  Returns (N, K, 2) keypoints."""
    from blendjax.ops.quant import quantize_detector

    qparams = quantize_detector(state.params)
    images = decode_frames(raw_frames, dtype=jax.numpy.float32)
    return _jit_int8_apply(qparams, images)


def _int8_apply(qparams, images):
    from blendjax.ops.quant import detector_apply_int8

    return detector_apply_int8(qparams, images)


_jit_int8_apply = jax.jit(_int8_apply)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--record", metavar="PREFIX", help="record while streaming")
    ap.add_argument("--replay", metavar="PREFIX", help="replay recordings (no Blender)")
    ap.add_argument("--instances", type=int, default=4)
    ap.add_argument("--items", type=int, default=512)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--background", action="store_true",
                    help="run Blender headless (the producer then uses "
                         "the blocking frame loop; offscreen GL must "
                         "be available, e.g. xvfb or the fake stack)")
    ap.add_argument("--infer-int8", action="store_true",
                    help="after training, run one quantized (w8a8) "
                         "inference batch on the live stream")
    args = ap.parse_args()

    mesh = data_mesh()
    sharding = data_sharding(mesh) if len(mesh.devices.flat) > 1 else None

    if args.replay:
        ds = btt.FileDataset(args.replay, item_transform=item_transform)
        from blendjax.btt.collate import collate

        def batches():
            idx = np.random.default_rng(0).permutation(len(ds))
            for s in range(0, len(ds) - args.batch + 1, args.batch):
                batch = collate([ds[int(i)] for i in idx[s : s + args.batch]])
                yield jax.device_put(batch)

        train_on_stream(batches())
        return

    with btt.BlenderLauncher(
        scene="",
        script=str(SCRIPT),
        num_instances=args.instances,
        named_sockets=["DATA"],
        background=args.background,
    ) as bl:
        ds = btt.RemoteIterableDataset(
            bl.launch_info.addresses["DATA"],
            max_items=args.items,
            item_transform=item_transform,
            record_path_prefix=args.record,
        )
        with btt.JaxStream(
            ds, batch_size=args.batch, num_workers=args.workers, sharding=sharding
        ) as stream:
            it = iter(stream)
            # reserve the inference batch BEFORE training: training
            # drains the finite stream completely
            hold = next(it, None) if args.infer_int8 else None
            state, _ = train_on_stream(it)
            if hold is not None:
                xy = infer_int8(state, hold["image"])
                print(f"int8 inference: {xy.shape[0]} frames -> "
                      f"keypoints {tuple(xy.shape[1:])}")
            elif args.infer_int8:
                print("int8 inference SKIPPED: stream yielded no batch")
        print("stage timing:", stream.timer.summary())
        if args.record:
            from blendjax.utils.timing import fleet_counters

            drops = fleet_counters.get("record_drops")
            if drops:
                # the recorders warn once each; this is the end-of-run
                # tally so a truncated dataset is impossible to miss
                print(
                    f"WARNING: recording truncated — {drops} messages "
                    "dropped at recorder capacity (raise --items or "
                    "FileRecorder max_messages)"
                )


if __name__ == "__main__":
    main()
