"""Progressive benchmark suite — jax-free parent orchestrator.

1. the parent (this file) NEVER imports jax, so it cannot hold the chip
   its child needs.  It emits ``{"phase": "boot"}`` as its first act,
   then measures the host half of the pipeline (producers -> fan-in recv
   -> collate) as ``host_stream`` before any accelerator is touched;
2. the jax phases live in ONE child (``benchmarks/suite_device.py``) on
   whatever platform the caller's environment names.  It emits
   ``device_init_start`` / ``device_init`` (platform, device_kind) around
   its backend bring-up, then per-phase JSON lines the moment each
   completes (``stream_to_hbm``, ``stream_to_train``, ``seqformer_train``,
   ``moe_compare``); the parent passes child stdout through live;
3. there is no second child and no stand-in: this program exits with the
   device child's exit code (non-zero if a phase raised, or if the child
   was still running when the budget ran out).

Teardown: the device child runs in its own session so the parent can
``killpg`` it; the parent converts SIGTERM into child-group cleanup +
shm sweep (``bench.py`` escalates TERM -> KILL), and shm ring names embed
the PARENT pid (``--ring-nonce``) so ``bench.py``'s leak sweep keyed on
its child's pid still matches.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from benchmarks._common import Budget, launch_fleet, note  # noqa: E402


def emit(obj):
    print(json.dumps(obj), flush=True)


def make_launcher(args, env):
    """Producer-fleet launcher for the host phase (shared naming scheme:
    :mod:`benchmarks._common`)."""

    def launch(n, extra, tag):
        return launch_fleet(
            n, extra, tag, transport=args.transport, raw=args.raw,
            ring_nonce=args.ring_nonce, env=env,
        )

    return launch


def phase_host_stream(args, budget, launch):
    """Producers -> ZMQ/shm fan-in -> collate, measured with NO jax in the
    process: the floor the device feed builds on, and the number that
    survives even if the accelerator never comes up."""
    from blendjax.btt.dataset import RemoteIterableDataset
    from blendjax.btt.loader import BatchLoader

    producers = launch(
        args.instances,
        ["--width", str(args.width), "--height", str(args.height),
         "--channels", str(args.channels)],
        tag="host",
    )
    try:
        ds = RemoteIterableDataset(
            producers.addrs, max_items=10**9, timeoutms=60000,
            queue_size=args.queue,
        )
        with BatchLoader(
            ds, batch_size=args.batch, num_workers=args.workers
        ) as loader:
            it = iter(loader)
            for _ in range(3):
                next(it)  # warmup: producers up, sockets connected
            t0 = time.perf_counter()
            n = 0
            while time.perf_counter() - t0 < args.host_seconds:
                next(it)
                n += 1
            dt = time.perf_counter() - t0
        emit({
            "phase": "host_stream",
            "overlapped_device_init": bool(args._overlap),
            "batches": n,
            "elapsed_s": round(dt, 3),
            "items_per_sec": round(n * args.batch / dt, 2),
            "batches_per_sec": round(n / dt, 2),
            "platform": "host",
        })
    finally:
        producers.close()


class DeviceChild:
    """suite_device.py child in its own session; passes its stdout lines
    through to ours live."""

    def __init__(self, cmd, env, label):
        self.label = label
        self.proc = subprocess.Popen(
            cmd,
            stdin=subprocess.PIPE,
            stdout=subprocess.PIPE,
            stderr=None,  # inherit: child diagnostics reach parent logs
            text=True,
            env=env,
            start_new_session=True,
        )
        self._t = threading.Thread(target=self._reader, daemon=True)
        self._t.start()

    def go(self):
        """Release a --wait-go child into its measured phases."""
        try:
            self.proc.stdin.write("go\n")
            self.proc.stdin.flush()
        except (BrokenPipeError, ValueError, OSError):
            pass  # child already exited; nothing to release

    def _reader(self):
        for line in self.proc.stdout:
            line = line.strip()
            if not line.startswith("{"):
                continue
            print(line, flush=True)  # verbatim

    def wait(self, timeout_s):
        try:
            self.proc.wait(timeout=max(0.0, timeout_s))
            return True
        except subprocess.TimeoutExpired:
            return False

    def kill(self):
        if self.proc.poll() is None:
            note(f"killing device child [{self.label}]")
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except OSError:
                self.proc.kill()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
        self._t.join(timeout=5)


def _sweep_rings(nonce):
    for path in glob.glob(f"/dev/shm/bjx-suite-*-{nonce}-*"):
        try:
            os.unlink(path)
        except OSError:
            pass


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=float, default=460.0)
    ap.add_argument("--instances", type=int, default=4)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--queue", type=int, default=10)
    ap.add_argument("--width", type=int, default=640)
    ap.add_argument("--height", type=int, default=480)
    ap.add_argument("--channels", type=int, default=3)
    ap.add_argument("--prefetch", type=int, default=12)
    ap.add_argument("--max-inflight", type=int, default=8)
    ap.add_argument("--host-seconds", type=float, default=6.0)
    ap.add_argument("--hbm-seconds", type=float, default=4.0,
                    help="seconds per stream->HBM window")
    ap.add_argument("--train-seconds", type=float, default=5.0,
                    help="seconds per stream->train window")
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--fence-every", type=int, default=8)
    ap.add_argument("--attn", choices=["auto", "full", "flash"],
                    default="auto")
    ap.add_argument("--phase-priority",
                    choices=["auto", "stream-first", "confirm-first"],
                    default="auto",
                    help="forwarded to the device children (see "
                         "suite_device.py): confirm-first banks the owed "
                         "kernel verdicts before wire-heavy streams")
    ap.add_argument("--moe-dispatch", choices=["sort", "scatter"],
                    default="sort")
    ap.add_argument("--transport", choices=["tcp", "shm"], default="tcp")
    ap.add_argument("--raw", action="store_true", default=True)
    ap.add_argument("--pickle", dest="raw", action="store_false")
    ap.add_argument("--config", choices=["big", "small"], default="big")
    ap.add_argument("--skip-host", action="store_true")
    ap.add_argument("--skip-seqformer", action="store_true")
    ap.add_argument("--skip-moe", action="store_true")
    # sizing forwarded to suite_device.py
    ap.add_argument("--seq-instances", type=int, default=2)
    ap.add_argument("--seq-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=513)
    ap.add_argument("--obs-dim", type=int, default=32)
    ap.add_argument("--d-model", type=int, default=1024)
    ap.add_argument("--n-heads", type=int, default=8)
    # forwarded only when set: the child's apply_config owns the
    # default (8 big / 2 small)
    ap.add_argument("--n-layers", type=int, default=None)
    ap.add_argument("--moe-experts", type=int, default=8)
    ap.add_argument("--moe-topk", type=int, default=2)
    args = ap.parse_args(argv)
    args.ring_nonce = str(os.getpid())

    budget = Budget(args.budget)
    emit({"phase": "boot", "pid": os.getpid(), "transport": args.transport,
          "raw": args.raw})

    children = []

    def _cleanup(signum=None, frame=None):
        for c in children:
            c.kill()
        _sweep_rings(args.ring_nonce)
        if signum is not None:
            sys.exit(128 + signum)

    signal.signal(signal.SIGTERM, _cleanup)

    from blendjax.btt.launcher import child_env

    # producers are jax-free (DataPublisher only): nothing to pin
    launch = make_launcher(args, child_env())

    def device_cmd(extra):
        cmd = [
            sys.executable, os.path.join(HERE, "suite_device.py"),
            "--instances", str(args.instances),
            "--workers", str(args.workers),
            "--batch", str(args.batch),
            "--queue", str(args.queue),
            "--width", str(args.width),
            "--height", str(args.height),
            "--channels", str(args.channels),
            "--prefetch", str(args.prefetch),
            "--max-inflight", str(args.max_inflight),
            "--hbm-seconds", str(args.hbm_seconds),
            "--train-seconds", str(args.train_seconds),
            "--transport", args.transport,
            "--seq-instances", str(args.seq_instances),
            "--seq-batch", str(args.seq_batch),
            "--seq-len", str(args.seq_len),
            "--obs-dim", str(args.obs_dim),
            "--d-model", str(args.d_model),
            "--n-heads", str(args.n_heads),
            "--moe-experts", str(args.moe_experts),
            "--moe-topk", str(args.moe_topk),
            "--moe-dispatch", args.moe_dispatch,
            "--phase-priority", args.phase_priority,
            "--windows", str(args.windows),
            "--fence-every", str(args.fence_every),
            "--attn", args.attn,
        ]
        cmd += ["--raw"] if args.raw else ["--pickle"]
        if args.n_layers is not None:
            cmd += ["--n-layers", str(args.n_layers)]
        if args.skip_seqformer:
            cmd.append("--skip-seqformer")
        if args.skip_moe:
            cmd.append("--skip-moe")
        return cmd + extra

    dev_env = dict(child_env())
    # the accelerator child inherits the caller's JAX_PLATFORMS (if any).
    # On an accelerator backend, spawn it BEFORE the host phase: backend
    # init overlaps the host-side measurement for free; --wait-go holds
    # the child's MEASURED phases until the host window closes.  On a CPU
    # backend init itself is CPU-heavy and would contend with the host
    # window, so there the child is spawned after it.
    slack = 10.0
    overlap = (dev_env.get("JAX_PLATFORMS") or "").strip().lower() != "cpu"

    def spawn_device():
        extra = ["--budget", str(max(30.0, budget.remaining() - slack)),
                 "--config", args.config,
                 "--ring-nonce", args.ring_nonce]
        if overlap:
            extra.append("--wait-go")
        d = DeviceChild(device_cmd(extra), dev_env, "device")
        children.append(d)
        return d

    args._overlap = overlap
    dev = spawn_device() if overlap else None

    if not args.skip_host and budget.has(25, "host_stream"):
        try:
            phase_host_stream(args, budget, launch)
        except Exception as e:  # noqa: BLE001 - device phases may still fit
            note(f"host_stream failed: {type(e).__name__}: {e}")

    if dev is None:
        dev = spawn_device()
    else:
        dev.go()  # host measurement done: release the measured phases

    finished = dev.wait(budget.remaining())
    _cleanup()
    if not finished:
        note("device child still running at the end of the budget")
        return 1
    return dev.proc.returncode


if __name__ == "__main__":
    sys.exit(main())
