"""Operations and bytes the work needs, computed from shapes, and the
chip's peaks. Kept with the benchmark so that no change to the program can
move them.

A roofline share is the least time the chip could take (the larger of
FLOPs over peak FLOP/s and bytes over peak bytes/s) over the measured
device time. Model FLOPs utilization is the model's FLOPs over the window
and the peak. Neither counts work the program does beyond the model's own
(padded rows, recomputation), so neither can pass 100% unless the time is
too short.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


class UnknownDevice(KeyError):
    pass


def peaks_for(kind: str, path: str | None = None) -> dict:
    """The peak table's row for ``kind`` (a JAX ``device_kind``). A device
    that is not in the table is an error, never a default."""
    with open(path or os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise UnknownDevice(f"device kind {kind!r} is not in the peak table "
                            f"({sorted(table)})")
    return table[kind]


def matmul_params(m) -> int:
    """Weights that take part in a matmul for each token: the attention and
    FFN projections of every layer and the output head (the embedding
    lookup is a gather, not a matmul)."""
    q, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    per_layer = m.d * (2 * q + 2 * kv) + 3 * m.d * m.ff
    return m.layers * per_layer + m.d * m.vocab


def token_flops(m, ctx: int) -> float:
    """Forward FLOPs of one token that attends to ``ctx`` positions:
    2 per matmul weight, plus QK^T and PV (2 FLOPs per MAC each) over the
    context in every layer."""
    return 2.0 * matmul_params(m) + 4.0 * ctx * m.heads * m.head_dim \
        * m.layers


def prompt_flops(m, plen: int) -> float:
    """Forward FLOPs of a causal prefill of ``plen`` tokens."""
    return 2.0 * matmul_params(m) * plen + 4.0 * m.heads * m.head_dim \
        * m.layers * plen * (plen + 1) / 2


def paged_decode_call(m, ctxs, slots: int, table_width: int,
                      kv_itemsize: int = 2, act_itemsize: int = 2):
    """(FLOPs, bytes) of one ``paged_decode`` call (one layer of one decode
    step) over ``slots`` rows, of which the active ones attend to ``ctxs``
    positions. Bytes: the K and V rows of each active request's valid
    positions, q read and o written for every row, the block table and the
    positions."""
    H, K, hd = m.heads, m.kv_heads, m.head_dim
    valid = sum(ctxs)
    flops = 4.0 * valid * H * hd
    nbytes = (2.0 * valid * K * hd * kv_itemsize
              + 2.0 * slots * H * hd * act_itemsize
              + slots * table_width * 4 + slots * 4)
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
