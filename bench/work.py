"""Useful work of the calls the benchmark times, from shapes alone.

The counts are the algorithm's, not the program's: padding, masked
attention slots and recomputation are not useful work and never count.
"""

from __future__ import annotations

import math
import re

#: Useful FLOP of one dense linear-algebra call on an n x n problem.
#: matmul C = AB: 2n^3; TRSM X U = B with an n x n B: n^3; Cholesky: n^3/3.
LINALG_FLOPS = {
    "matmul": lambda n: 2.0 * n ** 3,
    "trsm": lambda n: float(n) ** 3,
    "cholesky": lambda n: n ** 3 / 3.0,
}


def linalg_flops(op: str, n: int) -> float:
    return LINALG_FLOPS[op](n)


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def matmul_kernel_work(m: int, k: int, n: int, tiles: dict,
                       itemsize: int = 4) -> tuple[float, float]:
    """(FLOP, HBM bytes) of one blocked matmul kernel call C = A B with A
    (m, k) and B (k, n) under ``tiles`` (bm, bn, bk), each block capped at
    its dimension's 128-padded extent as the kernel wrapper caps it.  A is
    read once per column of blocks of C and B once per row of blocks; C is
    written once."""
    bm = min(tiles["bm"], round_up(m, 128))
    bn = min(tiles["bn"], round_up(n, 128))
    mp, np_, kp = round_up(m, bm), round_up(n, bn), round_up(k, 128)
    flops = 2.0 * m * k * n
    reads = mp * kp * (np_ // bn) + kp * np_ * (mp // bm)
    return flops, float(itemsize) * (reads + mp * np_)


_SHAPE = re.compile(r"\b(f32|bf16|f16|s32|u32|s8|u8|pred|f64)\[([0-9,]*)\]")
_ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
             "u8": 1, "pred": 1, "f64": 8}


def hlo_shapes(hlo_text: str) -> tuple[tuple, list]:
    """((dtype, dims) of the result, [(dtype, dims) of each operand]) of one
    HLO instruction as the profiler names it,
    ``%x.1 = f32[2,3]{...} custom-call(f32[2,4]{...} %a, ...), ...``."""
    _, rhs = hlo_text.split(" = ", 1)
    head, _, rest = rhs.partition("(")
    out = _SHAPE.search(head)
    args = rest.split("), ")[0]
    ops = [(m.group(1), tuple(int(d) for d in m.group(2).split(",") if d))
           for m in _SHAPE.finditer(args)]
    return (out.group(1), tuple(int(d) for d in out.group(2).split(",")
                                if d)), ops


def itemsize(dtype: str) -> int:
    return _ITEMSIZE[dtype]


def decoder_matmul_params(cfg: dict) -> int:
    """Weights that multiply activations in one token's forward pass of a
    dense decoder (every layer's projections and the output head; the
    input embedding is a lookup, not a product)."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    h, kv, ff = cfg["n_heads"], cfg["n_kv_heads"], cfg["d_ff"]
    attn = d * h * hd * 2 + d * kv * hd * 2
    mlp = d * ff * (3 if cfg["gated_mlp"] else 2)
    return cfg["n_layers"] * (attn + mlp) + d * cfg["vocab_size"]


def decoder_token_flops(cfg: dict, positions) -> float:
    """Useful FLOP of the forward pass of tokens at the given 0-based
    positions: 2 per multiplying weight, plus causal attention over the
    ``pos + 1`` keys each token sees (QK^T and PV, every layer)."""
    positions = list(positions)
    attn = 4.0 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"]
    return (2.0 * decoder_matmul_params(cfg) * len(positions)
            + attn * sum(p + 1 for p in positions))


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) of a non-empty sample."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]
