"""Wire protocol shared by producer (Blender) and consumer (JAX host).

The reference spreads its wire format across both packages (pickled dict via
``send_pyobj``/``recv_pyobj`` with an auto-stamped producer id — reference
``pkg_blender/blendtorch/btb/publisher.py:41-43``,
``pkg_pytorch/blendtorch/btt/dataset.py:105``,
``*/duplex.py:60-66``).  blendjax centralizes it here and keeps two
interoperable encodings on every socket:

1. **compat** — one frame holding ``pickle.dumps(dict)``.  Byte-compatible
   with reference producers/consumers, so existing ``*.blend.py`` publisher
   scripts stream into blendjax unmodified and vice versa.
2. **raw-buffer** — multipart ``[header, buf0, buf1, ...]`` where the header
   is a pickled dict with ndarray leaves replaced by placeholders and the
   array payloads ride as separate zero-copy ZMQ frames.  Decoding is a
   ``np.frombuffer`` view per array instead of a pickle memcpy — the biggest
   serialization win for 640x480x4 frames (SURVEY.md §7 "hard parts").

Receivers auto-detect the encoding per message (multipart => raw-buffer), so
mixed fleets work.

Pickle protocol is pinned to 4: the newest protocol that Blender 2.8x's
bundled Python 3.7 can read (the reference pins protocol 3 for the same
reason in ``pkg_pytorch/blendtorch/btt/file.py:59-63``; 4 is available from
Python 3.4 and is faster for large buffers).
"""

from __future__ import annotations

import os
import pickle
import random as _random

import numpy as np
import zmq

#: Newest pickle protocol readable by every Blender >= 2.80 (Python >= 3.7).
PICKLE_PROTOCOL = 4

#: Default high-water mark on both ends of the data plane.  Small on purpose:
#: a slow trainer stalls producers (backpressure) instead of buffering
#: unboundedly (reference ``publisher.py:24-27``, ``dataset.py:73-78``).
DEFAULT_HWM = 10

#: Key stamped into every data-plane message identifying the producer
#: instance (reference ``publisher.py:42``).
BTID_KEY = "btid"

#: Key stamped into every duplex message: a random per-message id usable for
#: request/response correlation (reference ``duplex.py:60-66``).
BTMID_KEY = "btmid"

#: Key under which a tracing client stamps its span context into a
#: request (``{"trace": <correlation id>}``): a server that sees it
#: records its own recv->work->reply span and ships it back under
#: :data:`SPANS_KEY`.  Servers that ignore the key keep working
#: (third-party/legacy producers simply contribute no server-side
#: spans); see :mod:`blendjax.obs.spans`.
SPAN_KEY = "btspan"

#: Key under which a server piggybacks its recorded spans (a list of
#: chrome-tracing event dicts) on a reply.  Clients POP it before the
#: reply becomes user-visible data (infos, replay rows), whether or not
#: they are tracing.
SPANS_KEY = "btspans"

#: Key under which a serve client stamps a request, and a ``PolicyServer``
#: every reply, with the wall-epoch microseconds it was sent at
#: (:func:`blendjax.obs.spans.now_us`, the timebase spans share across
#: processes on one host).  The server reads a request's stamp as the
#: time the request lay on the wire; receivers that ignore it keep
#: working.
SENT_US_KEY = "btsent"

#: Key under which a serve client stamps a request with the
#: :data:`SENT_US_KEY` of the previous reply it received: the server
#: reads the stretch from that reply's send to this request's send as
#: the client's turnaround.
REPLY_SENT_US_KEY = "btprev"

_ARRAY_PLACEHOLDER = "__bjx_nd__"

#: Public alias: key under which a raw-buffer header stores the payload
#: frame index for an ndarray leaf (consumed by the batched shm decode).
ARRAY_PLACEHOLDER = _ARRAY_PLACEHOLDER


def is_array_placeholder(obj) -> bool:
    """True if ``obj`` is a raw-buffer header placeholder for an ndarray."""
    return isinstance(obj, dict) and _ARRAY_PLACEHOLDER in obj


#: producer-side duplicate-suppression window, in replies: a retried
#: request (same :data:`BTMID_KEY`) is answered from the producer's
#: reply cache only while its reply is among the newest
#: ``REPLY_CACHE_DEPTH`` served.  A protocol constant, not a tunable —
#: the consumer's ``pipeline_depth`` must stay within it or a retry of
#: the oldest in-flight request could re-simulate a frame.
REPLY_CACHE_DEPTH = 8

#: process-local generator seeded once from the OS: a per-message
#: ``os.urandom`` costs ~100 us under syscall-intercepting sandboxes,
#: which the pipelined EnvPool would pay per request — ``getrandbits``
#: is pure user-space after the seed
_MID_RNG = _random.Random(os.urandom(16))


def new_message_id() -> str:
    """Random 8-byte hex message id, drawn syscall-free from a
    process-local OS-seeded generator.  The reference's 4 bytes
    (``duplex.py:63``) sufficed for stale-reply detection, but the ids
    now key the producer's exactly-once reply cache: a fresh id
    colliding with one of the :data:`REPLY_CACHE_DEPTH` cached ids
    would silently serve a stale transition, so the width keeps that
    chance negligible over multi-day kHz-rate runs."""
    return f"{_MID_RNG.getrandbits(64):016x}"


def dumps(obj) -> bytes:
    return pickle.dumps(obj, protocol=PICKLE_PROTOCOL)


def loads(buf) -> object:
    return pickle.loads(buf)


# ---------------------------------------------------------------------------
# raw-buffer encoding
# ---------------------------------------------------------------------------


def _strip_arrays(obj, bufs: list):
    """Replace ndarray leaves in a nested container with placeholders.

    Supports the containers the data plane actually carries (dict/list/tuple
    of numpy arrays and scalars).  Non-contiguous arrays are copied once.
    """
    if isinstance(obj, np.ndarray):
        arr = np.ascontiguousarray(obj)
        bufs.append(arr)
        return {
            _ARRAY_PLACEHOLDER: len(bufs) - 1,
            "dtype": arr.dtype.str,
            # the ORIGINAL shape: ascontiguousarray promotes 0-d arrays
            # to (1,), which would silently grow a rank on the receiver
            # (a replay shard rejects the row as schema drift)
            "shape": obj.shape,
        }
    if isinstance(obj, dict):
        return {k: _strip_arrays(v, bufs) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        seq = [_strip_arrays(v, bufs) for v in obj]
        return seq if isinstance(obj, list) else tuple(seq)
    return obj


def _restore_arrays(obj, frames):
    if isinstance(obj, dict):
        if _ARRAY_PLACEHOLDER in obj:
            idx = obj[_ARRAY_PLACEHOLDER]
            arr = np.frombuffer(frames[idx], dtype=np.dtype(obj["dtype"]))
            return arr.reshape(obj["shape"])
        return {k: _restore_arrays(v, frames) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        seq = [_restore_arrays(v, frames) for v in obj]
        return seq if isinstance(obj, list) else tuple(seq)
    return obj


def strip_arrays(data: dict, bufs: list) -> dict:
    """Public half of the raw-buffer encoding: replace ndarray leaves
    with placeholder headers, appending each (contiguous) array to
    ``bufs``.  Gather-into-ring senders use it to learn a reply's frame
    layout BEFORE reserving the ring record, then land each array in
    its reserved view instead of staging through :func:`encode`."""
    return _strip_arrays(data, bufs)


def encode(data: dict, raw_buffers: bool = False) -> list:
    """Encode a message dict into a list of ZMQ frames."""
    if not raw_buffers:
        return [dumps(data)]
    bufs: list = []
    header = _strip_arrays(data, bufs)
    return [dumps(header)] + bufs


def decode(frames) -> dict:
    """Decode frames produced by :func:`encode` (either encoding)."""
    head = pickle.loads(frames[0])
    if len(frames) == 1:
        return head
    return _restore_arrays(head, [memoryview(f) for f in frames[1:]])


# ---------------------------------------------------------------------------
# socket send/recv
# ---------------------------------------------------------------------------


def frames_nbytes(frames) -> int:
    """Total payload bytes of a frame list — the transport-neutral
    wire-bytes unit behind the ``*_wire_bytes``/``*_shm_bytes``
    counters (what :func:`encode` produced, not what any particular
    wire wrapped around it)."""
    total = 0
    for f in frames:
        total += f.nbytes if hasattr(f, "nbytes") else len(f)
    return total


def send_message(socket: zmq.Socket, data: dict, raw_buffers: bool = False, flags: int = 0):
    """Send one message; returns the payload byte count (the senders'
    half of per-request wire-bytes accounting)."""
    frames = encode(data, raw_buffers=raw_buffers)
    if len(frames) == 1:
        socket.send(frames[0], flags=flags)
    else:
        socket.send_multipart(frames, flags=flags, copy=False)
    return frames_nbytes(frames)


def recv_message(socket: zmq.Socket, flags: int = 0) -> dict:
    return recv_message_sized(socket, flags=flags)[0]


def recv_message_sized(socket: zmq.Socket, flags: int = 0):
    """:func:`recv_message` plus the payload byte count — the receive
    half of per-request wire-bytes accounting (and the ONE copy of the
    receive/decode logic; the unsized form delegates here)."""
    frames = socket.recv_multipart(flags=flags, copy=False)
    bufs = [f.buffer for f in frames]
    return decode(bufs), frames_nbytes(bufs)


def stamp_message_id(data: dict) -> str:
    """Stamp ``data`` with a fresh correlation id under :data:`BTMID_KEY`
    and return it.  The async env pipeline uses this to match replies to
    in-flight requests (and the producer-side agent to dedupe re-sent
    ``step`` requests); receivers that ignore the key keep working."""
    mid = new_message_id()
    data[BTMID_KEY] = mid
    return mid


def stamp_span_context(data: dict, trace: str) -> None:
    """Stamp a request with the span context that asks the server for a
    piggybacked span (see :data:`SPAN_KEY`).  ``trace`` is the trace id
    the server's span will be tagged with — by convention the request's
    :data:`BTMID_KEY` correlation id, so client and server spans of one
    RPC share it."""
    data[SPAN_KEY] = {"trace": trace}


def pop_spans(reply: dict):
    """Remove and return a reply's piggybacked span list (None when the
    server attached none).  Reply consumers call this unconditionally so
    span payloads never leak into infos/rows."""
    return reply.pop(SPANS_KEY, None)


# ---------------------------------------------------------------------------
# DEALER <-> REP framing
# ---------------------------------------------------------------------------
#
# A DEALER socket talking to a REP peer must emulate the REQ envelope: an
# empty delimiter frame ahead of the message body.  The REP socket strips
# it on the way in and restores it on the way out, so existing REP-socket
# producers (``blendjax.btb.env.RemoteControlledAgent``) serve DEALER
# clients unmodified.  Unlike REQ, a DEALER has no strict send/recv
# alternation — which is exactly what the pipelined EnvPool needs to keep
# several requests in flight per env.


def send_message_dealer(socket: zmq.Socket, data: dict,
                        raw_buffers: bool = False, flags: int = 0):
    """Send ``data`` from a DEALER socket to a REP peer (empty-delimiter
    framing).  RPC control messages are small, so ``copy=True`` skips
    pyzmq's zero-copy Frame bookkeeping (measurably cheaper per message);
    bulk ndarray traffic belongs on the raw-buffer data plane, not here."""
    frames = encode(data, raw_buffers=raw_buffers)
    socket.send_multipart([b""] + frames, flags=flags,
                          copy=not raw_buffers)


def recv_message_dealer(socket: zmq.Socket, flags: int = 0) -> dict:
    """Receive a REP peer's reply on a DEALER socket, stripping the
    empty delimiter frame the REP socket re-attached."""
    bufs = socket.recv_multipart(flags=flags, copy=True)
    if bufs and len(bufs[0]) == 0:
        bufs = bufs[1:]
    return decode(bufs)


def recv_message_router(socket: zmq.Socket, flags: int = 0):
    """Receive one DEALER client's request on a ROUTER socket: returns
    ``(identity, message)`` where ``identity`` is the routing frame to
    hand back to :func:`send_message_router`.  Strips the empty
    delimiter :func:`send_message_dealer` framed with, so the same
    clients speak to REP servers and ROUTER servers unmodified — the
    many-clients half of the serving tier's continuous batching
    (``blendjax/serve``)."""
    ident, msg, _ = recv_message_router_sized(socket, flags=flags)
    return ident, msg


def recv_message_router_sized(socket: zmq.Socket, flags: int = 0):
    """:func:`recv_message_router` plus the payload byte count (and the
    ONE copy of the delimiter-strip logic; the unsized form delegates
    here)."""
    frames = socket.recv_multipart(flags=flags, copy=True)
    ident, body = frames[0], frames[1:]
    if body and len(body[0]) == 0:
        body = body[1:]
    return ident, decode(body), frames_nbytes(body)


def send_message_router(socket: zmq.Socket, ident: bytes, data: dict,
                        raw_buffers: bool = False, flags: int = 0):
    """Send ``data`` to the DEALER client behind routing frame
    ``ident``, restoring the empty delimiter the client's
    :func:`recv_message_dealer` strips.  Returns the payload byte
    count."""
    frames = encode(data, raw_buffers=raw_buffers)
    socket.send_multipart([ident, b""] + frames, flags=flags,
                          copy=False)
    return frames_nbytes(frames)


def recv_message_raw(socket: zmq.Socket, flags: int = 0):
    """Receive without decoding; returns the raw frame list (bytes).

    Used by the stream recorder, which persists the on-wire bytes verbatim
    (reference ``dataset.py:100-105`` records pre-unpickle bytes).
    """
    return socket.recv_multipart(flags=flags, copy=True)


def decode_raw_frames(frames) -> dict:
    """Decode frames previously captured by :func:`recv_message_raw`."""
    return decode(frames)
