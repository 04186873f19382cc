"""``python -m blendjax.parallel.stage`` — one MPMD pipeline stage
process.

The launcher surface of the pipeline tier: :class:`~blendjax.parallel.
mpmd.StageFleet` spawns N of these (parent-allocated addresses and
``/dev/shm`` base prefixes on the command line, like every other
fleet), ``FleetWatchdog(restart=True)`` respawns one that dies with the
SAME command line, and the respawned stage restores its params from the
latest per-stage checkpoint cut so the driver's reconcile-replay
(docs/pipeline.md) resumes training crash-exactly.
"""

from __future__ import annotations

import argparse
import json
import logging
import signal
import threading


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="blendjax MPMD pipeline stage process"
    )
    parser.add_argument("--address", required=True,
                        help="ZMQ REP bind address for this stage")
    parser.add_argument("--proc-index", type=int, required=True)
    parser.add_argument("--spec", required=True,
                        help="pipeline spec as a JSON object")
    parser.add_argument("--prev-address", default=None)
    parser.add_argument("--next-address", default=None)
    parser.add_argument("--shm-base", default=None,
                        help="parent-allocated /dev/shm name prefix")
    parser.add_argument("--ckpt-dir", default=None)
    parser.add_argument("--ckpt-every", type=int, default=1)
    parser.add_argument("--work-us", type=int, default=0,
                        help="benchmark compute stand-in: sleep this "
                             "many microseconds per owned layer unit "
                             "per direction")
    parser.add_argument("--log-level", default="INFO")
    args = parser.parse_args(argv)

    logging.basicConfig(
        level=args.log_level,
        format=f"%(asctime)s stage{args.proc_index} %(levelname)s "
               "%(message)s",
    )
    from blendjax.parallel.mpmd import MpmdStage

    stage = MpmdStage(
        args.address, json.loads(args.spec), args.proc_index,
        prev_address=args.prev_address, next_address=args.next_address,
        shm_base=args.shm_base, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, work_us=args.work_us,
    )
    stop = threading.Event()

    def _stop(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, _stop)
    signal.signal(signal.SIGINT, _stop)

    logging.getLogger("blendjax").info(
        "pipe stage %d/%d serving at %s (applied=%d)",
        stage.proc_index, stage.n_procs, stage.address, stage._applied,
    )
    try:
        stage.serve_forever(stop_event=stop)
    finally:
        stage.close()


if __name__ == "__main__":
    main()
