"""End-to-end streaming benchmark (port of the reference harness,
``benchmarks/benchmark.py``: BATCH=8, 4 producer instances, 4 workers, 512
items, Cube-scene 640x480 RGB (alpha dropped before the wire); first
batch discarded as warmup, prints
sec/image and sec/batch).

Differences, on purpose:
- producers are synthetic (real Blender doesn't run on a TPU-VM CI image);
  they speak the identical wire protocol through the real DataPublisher, so
  everything downstream of rendering — serialize, send, fan-in recv,
  decode, collate, device_put, train — is measured for real.
- the pipeline continues to the TPU: batches land in HBM via the
  double-buffered prefetcher and a detector train step runs per batch
  (pass --no-train for the stream-only configuration of BASELINE.md).
- per-stage timing (recv/collate/device_put) and feed duty cycle printed.

Run: python benchmarks/benchmark.py [--raw] [--instances 4] [--items 512]
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
PRODUCER = os.path.join(HERE, "stream_producer.py")

# runnable directly (python benchmarks/benchmark.py): sys.path[0] is
# benchmarks/, so the package root one level up must be added by hand
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))


def free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def launch_producers(n, raw, width, height, transport="tcp"):
    # children must find blendjax without clobbering the existing
    # PYTHONPATH — child_env() prepends the repo root and preserves the
    # rest.  Producers are jax-free: nothing to pin.
    from blendjax.btt.launcher import child_env

    env = child_env()
    addrs, procs = [], []
    for i in range(n):
        if transport == "shm":
            addr = f"shm://bjx-bench-{os.getpid()}-{i}"
        else:
            addr = f"tcp://127.0.0.1:{free_port()}"
        cmd = [
            sys.executable,
            PRODUCER,
            "--addr", addr,
            "--btid", str(i),
            "--width", str(width),
            "--height", str(height),
        ]
        if raw:
            cmd.append("--raw")
        procs.append(subprocess.Popen(cmd, env=env))
        addrs.append(addr)
    return addrs, procs


def run(args):
    from blendjax.btt.launcher import place_compile_cache

    place_compile_cache(os.environ)  # before jax reads its configuration
    import jax  # the platform is the caller's JAX_PLATFORMS

    from blendjax.btt.dataset import RemoteIterableDataset
    from blendjax.btt.prefetch import JaxStream
    from blendjax.ops.image import decode_frames

    addrs, procs = launch_producers(
        args.instances, args.raw, args.width, args.height, transport=args.transport
    )
    try:
        ds = RemoteIterableDataset(
            addrs, max_items=args.items, timeoutms=60000, queue_size=args.queue
        )

        train_step = None
        state = None
        if args.train:
            import optax

            from blendjax.models import detector
            from blendjax.models.train import TrainState, make_train_step

            params = detector.init(
                jax.random.PRNGKey(0), num_keypoints=8, in_channels=args.channels
            )
            opt = optax.adam(1e-3)
            state = TrainState.create(params, opt)
            base_loss = detector.loss_fn

            def loss_with_decode(params, batch):
                images = decode_frames(batch["image"], dtype=jax.numpy.bfloat16)
                return base_loss(params, {"image": images, "xy": batch["xy"]})

            train_step = make_train_step(loss_with_decode, opt)

        def transform(batch):
            # normalize keypoints to [0,1] on host (tiny); images ship uint8
            return {
                "image": batch["image"],
                "xy": batch["xy"].astype(np.float32),
            }

        stream = JaxStream(
            ds,
            batch_size=args.batch,
            num_workers=args.workers,
            transform=transform,
            prefetch=args.prefetch,
        )

        # Two stopping modes: fixed item count (args.items drives stream
        # length, reference-style) or a measurement window (--seconds) that
        # bounds wall-clock regardless of device speed.  Warmup additionally
        # has its own deadline: if the train step cannot warm up in time,
        # the benchmark degrades to stream-only rather than never finishing.
        #
        # Steps are dispatched asynchronously (XLA queues them); blocking on
        # every step would insert a full host<->device round trip per batch.
        # A bounded
        # in-flight window (--max-inflight) keeps dispatch ahead of
        # execution without accumulating unbounded HBM: we block on the
        # loss from K steps ago, not the latest.  --step-timing restores
        # the blocking per-step mode and reports train_duty_cycle.
        from collections import deque

        n_batches = 0
        measured = 0
        t0 = None
        step_time = 0.0
        warmup_deadline = time.perf_counter() + args.warmup_deadline
        train_alive = train_step is not None
        inflight = deque()
        it = iter(stream)
        try:
            for batch in it:
                if train_alive:
                    if args.step_timing or t0 is None:
                        # warmup always blocks: the first step's compile
                        # must finish before the window opens
                        ts = time.perf_counter()
                        state, loss = train_step(state, batch)
                        jax.block_until_ready(loss)
                        step_time += time.perf_counter() - ts
                    else:
                        state, loss = train_step(state, batch)
                        inflight.append(loss)
                        if len(inflight) > args.max_inflight:
                            jax.block_until_ready(inflight.popleft())
                else:
                    jax.block_until_ready(batch["image"])
                n_batches += 1
                if t0 is None:
                    warm = n_batches >= args.warmup_batches
                    overdue = time.perf_counter() > warmup_deadline
                    if overdue and train_alive:
                        train_alive = False  # degrade: measure the feed only
                    if warm or overdue:
                        if args.trace:
                            # the measured window under the profiler: the
                            # stream's stages are spans in its trace
                            jax.profiler.start_trace(args.trace)
                        t0 = time.perf_counter()
                        step_time = 0.0
                    continue
                measured += 1
                if args.seconds and time.perf_counter() - t0 >= args.seconds:
                    break
            # drain: queued steps must finish inside the measured window.
            # The LAST loss is additionally fenced by VALUE FETCH (one
            # extra scalar D2H): the value cannot arrive before the
            # chain retired.
            last_loss = None
            while inflight:
                last_loss = inflight.popleft()
                jax.block_until_ready(last_loss)
            if last_loss is not None:
                float(np.asarray(last_loss))
            # window closes HERE: teardown below (worker joins, socket
            # closes — up to the recv timeout in the unhappy path) must
            # not be billed to the measurement
            elapsed = time.perf_counter() - t0 if t0 is not None else None
        finally:
            if args.trace and t0 is not None:
                jax.profiler.stop_trace()
            it.close()  # unwinds the prefetch thread promptly
            stream.close()
        if t0 is None or measured == 0:
            raise RuntimeError("benchmark produced no measured batches")
        images = measured * args.batch

        stats = stream.timer.summary()
        if args.trace:
            print(
                f"wrote a jax.profiler trace under {args.trace} (feed "
                "stages beside the device's operations; open it with "
                "xprof / TensorBoard, or jax.profiler.ProfileData)",
                file=sys.stderr,
            )
        return {
            "images_per_sec": images / elapsed,
            "sec_per_image": elapsed / images,
            "sec_per_batch": elapsed / measured,
            "train_duty_cycle": (
                (step_time / elapsed)
                if (train_alive and args.step_timing)
                else None
            ),
            "train_degraded": bool(train_step is not None and not train_alive),
            "stages": stats,
            "batches": measured,
        }
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
        if args.transport == "shm":
            from blendjax.native import unlink_address

            for a in addrs:
                unlink_address(a)


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--instances", type=int, default=4)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--items", type=int, default=512)
    ap.add_argument("--queue", type=int, default=10)
    ap.add_argument("--width", type=int, default=640)
    ap.add_argument("--height", type=int, default=480)
    ap.add_argument("--channels", type=int, default=3)
    ap.add_argument("--warmup-batches", type=int, default=8)
    ap.add_argument(
        "--trace",
        metavar="DIR",
        default=None,
        help="run the measured window under jax.profiler and write its "
        "trace directory to DIR: the feed's StageTimer stages are host "
        "spans in it, beside the device's operations",
    )
    ap.add_argument(
        "--prefetch",
        type=int,
        default=2,
        help="device batches staged ahead (double buffering = 2)",
    )
    ap.add_argument(
        "--max-inflight",
        type=int,
        default=4,
        help="train steps dispatched ahead of execution (latency hiding); "
        "bounds HBM held by queued batches",
    )
    ap.add_argument(
        "--step-timing",
        action="store_true",
        help="block after every step and report train_duty_cycle "
        "(adds one host<->device round trip per batch)",
    )
    ap.add_argument(
        "--seconds",
        type=float,
        default=0.0,
        help="measure for a fixed window instead of exhausting --items",
    )
    ap.add_argument(
        "--warmup-deadline",
        type=float,
        default=300.0,
        help="max seconds to spend warming up (compiles); past it the "
        "train step is dropped and the feed alone is measured",
    )
    ap.add_argument(
        "--transport",
        choices=["tcp", "shm"],
        default="tcp",
        help="shm = native shared-memory rings (workers partition rings; "
        "use workers == instances)",
    )
    ap.add_argument(
        "--json",
        action="store_true",
        help="print the driver's one-line JSON result instead of the report",
    )
    ap.add_argument("--raw", action="store_true", default=True,
                    help="zero-copy wire encoding (blendjax native)")
    ap.add_argument("--pickle", dest="raw", action="store_false",
                    help="reference-compatible pickle encoding")
    ap.add_argument("--no-train", dest="train", action="store_false",
                    help="stream-only (BASELINE.md configuration)")
    return ap.parse_args(argv)


if __name__ == "__main__":
    args = parse_args()
    result = run(args)
    if args.json:
        import json

        suffix = (
            "stream_only" if result.get("train_degraded") else "stream_to_train"
        )
        print(
            json.dumps(
                {
                    "metric": f"cube640x480_images_per_sec_{suffix}",
                    "value": round(result["images_per_sec"], 2),
                    "unit": "images/sec",
                    "vs_baseline": round(result["images_per_sec"] * 0.012, 3),
                }
            ),
            flush=True,
        )
        raise SystemExit(0)
    print(f"images/sec      : {result['images_per_sec']:.1f}")
    print(f"sec/image       : {result['sec_per_image']:.5f}")
    print(f"sec/batch({args.batch})    : {result['sec_per_batch']:.5f}")
    if result["train_duty_cycle"] is not None:
        print(f"train duty cycle: {result['train_duty_cycle']:.1%}")
    for name, s in result["stages"].items():
        print(f"stage {name:11s}: {s['mean_ms']:.2f} ms avg x {s['count']}")
