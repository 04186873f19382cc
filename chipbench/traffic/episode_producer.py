"""Seeded episode producer — stands in for one Blender instance, speaking the
real wire protocol through the real ``DataPublisher``.

Copied from ``benchmarks/stream_producer.py --mode episode`` (which seeds from
``--btid`` alone and cycles a pool of 16 payloads) with two changes: the
stream is a function of ``--seed``, and every episode is its own draw, so a
batch never holds the same row twice.  Episode ``frameid`` of producer
``btid`` is ``amplitude * standard_normal((T+1, D))`` from the generator
``default_rng((seed, btid, frameid))``, the amplitude uniform in
``[amp_lo, amp_hi)``; :func:`episode` is that function, and the harness
calls it again to check what reached the device.

Run as ``python episode_producer.py --addr shm://... --btid 0 --seed 7 --raw``.
"""

from __future__ import annotations

import argparse

import numpy as np


def episode(seed, btid, frameid, seq_len, obs_dim, amp_lo, amp_hi):
    rng = np.random.default_rng((int(seed), int(btid), int(frameid)))
    amp = amp_lo + (amp_hi - amp_lo) * rng.random()
    return (amp * rng.standard_normal((seq_len, obs_dim))).astype(np.float32)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--addr", required=True)
    ap.add_argument("--btid", type=int, default=0)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seq-len", type=int, default=513,
                    help="observations per episode (T+1)")
    ap.add_argument("--obs-dim", type=int, default=32)
    ap.add_argument("--amp-lo", type=float, default=1.0)
    ap.add_argument("--amp-hi", type=float, default=1.0)
    ap.add_argument("--raw", action="store_true",
                    help="zero-copy wire encoding")
    args = ap.parse_args(argv)

    from blendjax.btb.publisher import DataPublisher

    pub = DataPublisher(args.addr, btid=args.btid, raw_buffers=args.raw)
    frameid = 0
    while True:  # terminated by the harness
        pub.publish(frameid=frameid, obs_seq=episode(
            args.seed, args.btid, frameid, args.seq_len, args.obs_dim,
            args.amp_lo, args.amp_hi))
        frameid += 1


if __name__ == "__main__":
    main()
