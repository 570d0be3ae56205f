"""Causal flash attention with grouped heads: q, o ``[B, S, H, D]``, k, v
``[B, S, H_kv, D]``, forward and backward.

Operations as ``flash_attention.py`` counts them, per query head: 2
products forward, 2 for the dq kernel, 3 for the dk/dv kernel, each
2 * S^2 * D * (S + 1) / (2 S) under the causal mask.

Bytes: each kernel reads and writes its tensors once; K and V (and their
cotangents) exist once per key/value head, not once per query head.
"""

from benchmarks.trace.roofline import least_seconds


def parts(shapes: dict, peaks: dict, params: dict) -> dict:
    b, s, d = shapes["batch_per_chip"], shapes["seq"], shapes["head_dim"]
    h, hkv = shapes["n_head"], shapes["n_kv_head"]
    product = 2.0 * b * h * s * s * d * (s + 1) / (2.0 * s)
    tq = b * s * h * d * shapes["dtype_bytes"]
    tkv = b * s * hkv * d * shapes["dtype_bytes"]
    lse = b * h * s * 4
    return {
        # q, k, v -> o
        "fwd": least_seconds(2 * product, 2 * tq + 2 * tkv + lse, peaks),
        # q, k, v, o, do -> dq
        "dq": least_seconds(2 * product, 4 * tq + 2 * tkv + lse, peaks),
        # q, k, v, o, do -> dk, dv
        "dkv": least_seconds(3 * product, 3 * tq + 4 * tkv + lse, peaks),
    }
