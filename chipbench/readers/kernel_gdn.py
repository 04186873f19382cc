"""Reader of the gated delta rule's decode kernel (``gdn_update``, PR 41):
its device seconds in the traced extent against the bytes its work has to
move there, and the chip's HBM peak.

The bytes are the stepped rows' float32 matrix state alone, read once and
written once a linear layer: ``H dk dv`` x 4 B a head set, unpadded, of the
**real** rows (``serve_rows_stepped``; pad rows are computed and counted by
nobody), so the reading stays under what the kernel's DMA moves (the
state's 96-wide minor axis travels padded to 128 lanes).  The rows of the
traced extent are taken at the whole window's rate (as ``kernels.py``
takes its steps): the tracer slows the window, so this reads up to ~9%
high.  A program whose step has no ``gdn_update`` row reads None.
"""

from __future__ import annotations

from chipbench import flops_olmohybrid
from chipbench.readers.kernels import kernel_seconds

GDN_UPDATE = "gdn_update"  # blendjax/ops/gdn_update.py, pl.pallas_call(name=)


def state_bytes_per_row(model):
    """Bytes ``gdn_update`` must move for one row stepped: every linear
    layer's matrix state, read and written."""
    return 2 * flops_olmohybrid.slot_bytes(model, 0)["state"]


def gdn_update_hbm_pct(obs, ctx):
    """The real rows' state bytes of the traced extent over the kernel's
    device seconds and 819 GB/s."""
    seconds = kernel_seconds(obs, GDN_UPDATE)
    rows = (obs.get("events") or {}).get("serve_rows_stepped")
    if not seconds or not rows or not obs.get("window_s"):
        return None
    traced_s = obs["trace"].get("window_s")
    if not traced_s:
        return None
    need = rows / obs["window_s"] * traced_s * state_bytes_per_row(ctx.config)
    return 100.0 * need / seconds / ctx.peaks["hbm_bytes_per_s"]
