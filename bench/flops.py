"""Operations and bytes the served model needs, from its shapes alone.

The MAC count follows the program's own account (core/costs.macs_per_token:
one MAC per projection weight per token, embedding lookups excluded, plus
QK^T and PV over the context), recomputed here from the configuration file
so the yardstick does not move with the program. Kernel work counts what
the algorithm needs for a call (its rows, its weights at the width the rung
serves, its inputs and outputs), never the kernel's own layout or padding.
"""
from __future__ import annotations

from bench import families


def projections(spec: dict) -> list:
    """[(K, N, calls per decode step)] of every PANN-quantized linear: the
    family's own (bench/families/<name>.py) and the LM head."""
    v = ((spec["vocab_size"] + 255) // 256) * 256
    return families.load(spec).projections(spec) + [(spec["d_model"], v, 1)]


def attention_layers(spec: dict) -> int:
    """Decode-attention calls per decode step."""
    return families.load(spec).attention_layers(spec)


def head_dim(spec: dict) -> int:
    return int(spec.get("head_dim") or spec["d_model"] // spec["num_heads"])


def macs_per_token(spec: dict, context_len: int) -> float:
    weight = sum(k * n * c for k, n, c in projections(spec))
    act = 2.0 * spec["num_heads"] * head_dim(spec) * context_len \
        * attention_layers(spec)
    return float(weight + act)


def matmul_min_s(m: int, k: int, n: int, weight_bits: int, pk: dict) -> float:
    """Least time of one packed projection call: the larger of its int8
    operations over the peak and its bytes (weights at ``weight_bits``,
    fp32 input rows and output rows, per-channel scale and zero-point row)
    over the memory bandwidth."""
    ops = 2.0 * m * k * n
    nbytes = k * n * weight_bits / 8.0 + 4.0 * m * (k + n) + 8.0 * n
    return max(ops / pk["int8_ops_per_s"], nbytes / pk["hbm_bytes_per_s"])


def attention_min_s(rows: int, ctx: int, spec: dict, cache_bits: int,
                    pk: dict) -> float:
    """Least time of one decode-attention call over ``ctx`` cached
    positions: K and V codes at ``cache_bits`` with their per-position
    scale and zero point, the query in and the output out; QK^T and PV
    as int8 operations."""
    heads, kvh = spec["num_heads"], spec["num_kv_heads"]
    hd = head_dim(spec)
    ops = 2.0 * 2.0 * rows * heads * hd * ctx
    nbytes = rows * (2.0 * ctx * kvh * hd * cache_bits / 8.0
                     + 4.0 * 4.0 * ctx + 2.0 * 4.0 * heads * hd)
    return max(ops / pk["int8_ops_per_s"], nbytes / pk["hbm_bytes_per_s"])
