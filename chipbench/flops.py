"""Required operations and bytes of the SeqFormer world model, from shapes.

"Required" means what the mathematics needs, whatever implements it: causal
attention is counted once (the masked half is not work), recomputation and
copies are not counted.  Everything here is plain arithmetic on a
configuration's ``model`` block; nothing imports the program.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind, path=None):
    """The chip's published peaks, keyed by ``device_kind``.  A kind that is
    not in the table is an error, never a default."""
    with open(path or os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks on file for device_kind {device_kind!r}")
    return table[device_kind]


def matmul_params(model):
    """Parameters that take part in a matrix product, per token."""
    d, dff, obs = model["d_model"], model["d_ff"], model["obs_dim"]
    per_layer = 4 * d * d + 2 * d * dff  # q, k, v, o + fc, proj
    return model["n_layers"] * per_layer + 2 * obs * d  # + embed, head


def param_count(model):
    """Every parameter held (biases, LayerNorm and the position table too)."""
    d, dff, obs = model["d_model"], model["d_ff"], model["obs_dim"]
    per_layer = 4 * d * d + 4 * d + 2 * d * dff + dff + d + 4 * d
    return (model["n_layers"] * per_layer + obs * d + d + d * obs + obs
            + 2 * d + model["max_len"] * d)


def forward_flops_per_token(model, seq_len):
    """Forward pass over a causal sequence of ``seq_len``, per position:
    2 per multiply-add of every matmul parameter, plus causal attention
    ``2*T*d`` per layer (scores ``T*d`` + apply ``T*d``, the mean over the
    positions of a causal sequence of ``2*d*(pos+1)`` each way)."""
    return (2.0 * matmul_params(model)
            + model["n_layers"] * 2.0 * seq_len * model["d_model"])


def train_flops_per_token(model, seq_len):
    """Forward and backward: three times the forward pass."""
    return 3.0 * forward_flops_per_token(model, seq_len)


def decode_flops(model, n_steps, sum_pos):
    """``n_steps`` single-position steps whose live cache positions (the
    position each step is taken at, itself included) add up to ``sum_pos``:
    scores and apply are ``2*pos*d`` each, per layer."""
    return (2.0 * matmul_params(model) * n_steps
            + model["n_layers"] * 4.0 * model["d_model"] * sum_pos)


def decode_bytes(model, n_ticks, sum_pos, param_bytes=4, cache_bytes=4):
    """Bytes a decode has to move: every parameter once a tick, and for each
    stepped row its live K and V positions (``sum_pos`` counts the earlier
    ones, read once, and the new one, written once)."""
    kv_pos = 2 * model["d_model"] * cache_bytes * model["n_layers"]
    return (float(param_count(model)) * param_bytes * n_ticks
            + kv_pos * float(sum_pos))
