"""Operations the algorithm needs: per trained token for the whole model,
and per call for each kernel, from shapes alone. Recomputation is not
counted, so a utilization built on these is of the model, not of the
program's extra work."""
from __future__ import annotations

import math

DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s8": 1, "u8": 1, "s32": 4,
               "u32": 4, "s4": 0.5, "u4": 0.5, "pred": 1, "f8e4m3fn": 1,
               "f8e5m2": 1}


def matmul_params(table: dict) -> int:
    """Every matmul weight at its whole size (``active_matmul_params``)."""
    return active_matmul_params(table, {})


def active_matmul_params(table: dict, active: dict) -> float:
    """Weights that multiply activations, each leaf at the share of it that
    one token uses (``active``: leaf -> fraction, 1 where not given; a
    leaf of routed experts at top-k over all the model's experts): every
    leaf of two or more dimensions, the output head included; the input
    embedding is a lookup and counts only where it is also the (tied)
    output head."""
    skip = {"embed"} if "lm_head" in table else set()
    return sum(math.prod(e["shape"]) * (e["stack"] or 1) * active.get(n, 1)
               for n, e in table.items() if len(e["shape"]) >= 2
               and n not in skip)


def attention_flops_per_token(layers, seq_len: int) -> float:
    """Forward and backward of attention's two matmuls, the scores at the
    query/key head size and the weighted sum at the value head size, over
    the keys a query sees on average. ``layers``: (heads, qk head size,
    v head size, window) for each layer, window 0 for full causal
    attention. A query at position i sees the keys in (i - window, i]; the
    mean is taken over the causal square as an area, so full attention
    sees half the sequence (the diagonal counts half) and a window w < S
    sees w - w^2 / 2S."""
    total = 0.0
    for heads, d_qk, d_v, window in layers:
        w = window if 0 < window < seq_len else seq_len
        keys = w - w * w / (2 * seq_len)
        total += 3 * 2 * keys * heads * (d_qk + d_v)
    return total


def model_flops_per_token(cfg: dict, table: dict, seq_len: int) -> float:
    """Every matmul weight active, full causal attention with heads x head
    size = hidden size in every layer: the dense models' count."""
    h, d = cfg["num_attention_heads"], cfg["hidden_size"]
    layers = [(h, d / h, d / h, 0)] * cfg["num_hidden_layers"]
    return 6 * matmul_params(table) + attention_flops_per_token(layers, seq_len)


def nbytes(shape: tuple[str, tuple[int, ...]]) -> float:
    dtype, dims = shape
    return DTYPE_BYTES[dtype] * math.prod(dims)


def matmul(operands, result) -> tuple[float, float]:
    """x (M, C) times a (C x O) weight given in any layout -> (M, O)."""
    (_, x), (_, out) = operands[0], result
    flops = 2 * x[0] * x[1] * out[-1]
    return flops, sum(nbytes(o) for o in operands) + nbytes(result)


def causal_attention(operands, result) -> tuple[float, float]:
    """q, k (BH, S, Dqk), v (BH, S, Dv) -> (BH, S, Dv); query i sees keys
    0..i. The scores cost Dqk a pair, the weighted sum Dv."""
    (_, q), (_, k), (_, v) = operands[:3]
    bh, s, d_qk = q
    pairs = s * (k[1] + 1) / 2 if k[1] == s else s * k[1]
    flops = 2 * bh * pairs * (d_qk + v[-1])
    return flops, sum(nbytes(o) for o in operands) + nbytes(result)


WORK = {"matmul": matmul, "causal_attention": causal_attention}
