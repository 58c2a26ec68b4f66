"""Operations and bytes the algorithm needs, from shapes alone.

Attention counts only the causal pairs a query must see; a matrix product
of an ``[m, k]`` by a ``[k, n]`` operand is ``2 m k n`` operations.  Bytes
are what must cross HBM at least once: the K/V pages a query reads (whole
pages, the unit the kernels load), its query row in and its output out.
Recomputation (rematerialisation) is never counted.
"""
from __future__ import annotations

import math


def shape(c: dict) -> dict:
    d, f = c["hidden_size"], c["intermediate_size"]
    H, kvH, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    return {
        "d": d, "f": f, "L": c["num_hidden_layers"], "H": H, "kvH": kvH, "hd": hd,
        "V": c["vocab_size"],
        # weights of the matrix products of one layer
        "layer_mm": d * H * hd + 2 * d * kvH * hd + H * hd * d + 3 * d * f,
    }


# ---------------------------------------------------------------------------
# Attention kernels (one layer, one call site)
# ---------------------------------------------------------------------------


def decode_attention(c: dict, length: int, page: int, kv_bytes: int = 2,
                     act_bytes: int = 2) -> tuple:
    """One query token attending ``length`` cached positions (its own
    included), through whole pages: (flops, bytes)."""
    s = shape(c)
    flops = 4 * s["H"] * s["hd"] * length
    pages = math.ceil(length / page)
    nbytes = 2 * pages * page * s["kvH"] * s["hd"] * kv_bytes + 2 * s["H"] * s["hd"] * act_bytes
    return flops, nbytes


def prefill_chunk_attention(c: dict, start: int, n: int, page: int, kv_bytes: int = 2,
                            act_bytes: int = 2) -> tuple:
    """A chunk of ``n`` prompt rows at positions ``start .. start + n - 1``,
    each attending every earlier position and itself: (flops, bytes)."""
    s = shape(c)
    pairs = n * start + n * (n + 1) // 2
    flops = 4 * s["H"] * s["hd"] * pairs
    pages = math.ceil((start + n) / page)
    nbytes = (2 * pages * page * s["kvH"] * s["hd"] * kv_bytes
              + 2 * n * s["H"] * s["hd"] * act_bytes)
    return flops, nbytes


def chunks(start: int, end: int, width: int):
    """The chunk waves that prefill positions start .. end - 1."""
    at = start
    while at < end:
        n = min(width, end - at)
        yield at, n
        at += n


# ---------------------------------------------------------------------------
# Whole model
# ---------------------------------------------------------------------------


def decode_token(c: dict, length: int) -> int:
    """Model operations of one decoded token attending ``length``
    positions: every layer's products, attention, and the vocabulary
    projection."""
    s = shape(c)
    return (2 * (s["L"] * s["layer_mm"] + s["V"] * s["d"])
            + s["L"] * 4 * s["H"] * s["hd"] * length)


def prefill_row(c: dict, pos: int) -> int:
    """Model operations of one prompt row at position ``pos`` (0-based),
    without the vocabulary projection (only a prompt's last row needs it)."""
    s = shape(c)
    return 2 * s["L"] * s["layer_mm"] + s["L"] * 4 * s["H"] * s["hd"] * (pos + 1)


def prefill_rows(c: dict, start: int, end: int) -> int:
    s = shape(c)
    n = end - start
    pairs = n * start + n * (n + 1) // 2
    return 2 * s["L"] * s["layer_mm"] * n + s["L"] * 4 * s["H"] * s["hd"] * pairs


def unembed(c: dict) -> int:
    s = shape(c)
    return 2 * s["V"] * s["d"]


def train_step(c: dict, batch: int, seq: int) -> int:
    """Forward and backward (three times the forward) of one step over
    ``batch`` rows of ``seq`` tokens, causal attention, no recomputation."""
    s = shape(c)
    fwd = (2 * batch * seq * (s["L"] * s["layer_mm"] + s["V"] * s["d"])
           + s["L"] * 4 * s["H"] * s["hd"] * batch * (seq * (seq + 1) // 2))
    return 3 * fwd
