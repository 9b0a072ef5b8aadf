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
    """Weights that multiply activations: every leaf of two or more
    dimensions, the output head included; the input embedding is a lookup
    and counts only where it is also the (tied) output head."""
    skip = {"embed"} if "lm_head" in table else set()
    return sum(math.prod(e["shape"]) * (e["stack"] or 1)
               for n, e in table.items() if len(e["shape"]) >= 2
               and n not in skip)


def attention_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward of causal attention's two matmuls (scores and
    the weighted sum), at the half of the square that causality needs."""
    width = cfg["hidden_size"]          # heads x head size, in both models
    return 3 * 2 * seq_len * width * cfg["num_hidden_layers"]


def model_flops_per_token(cfg: dict, table: dict, seq_len: int) -> float:
    return 6 * matmul_params(table) + attention_flops_per_token(cfg, seq_len)


def nbytes(shape: tuple[str, tuple[int, ...]]) -> float:
    dtype, dims = shape
    return DTYPE_BYTES[dtype] * math.prod(dims)


def matmul(operands, result) -> tuple[float, float]:
    """x (M, C) times a (C x O) weight given in any layout -> (M, O)."""
    (_, x), (_, out) = operands[0], result
    flops = 2 * x[0] * x[1] * out[-1]
    return flops, sum(nbytes(o) for o in operands) + nbytes(result)


def causal_attention(operands, result) -> tuple[float, float]:
    """q, k, v (BH, S, D) -> (BH, S, D); query i sees keys 0..i."""
    (_, q), (_, k) = operands[0], operands[1]
    bh, s, d = q
    pairs = s * (k[1] + 1) / 2 if k[1] == s else s * k[1]
    return 4 * bh * pairs * d, sum(nbytes(o) for o in operands) + nbytes(result)


WORK = {"matmul": matmul, "causal_attention": causal_attention}
