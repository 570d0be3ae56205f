"""Causal flash attention over ``[B, S, H, D]``, forward and backward.

Per (batch, head): S = Q K^T and O = P V are 2 * S^2 * D operations each;
under the causal mask only (S + 1) / (2 S) of the score matrix is needed.
Forward needs both (2 matrix products). Backward needs dV = P^T dO,
dP = dO V^T, dQ = dS K, dK = dS^T Q and S once more (5 products: the
convention of the FlashAttention paper). The program runs backward as two
kernels, each of which recomputes S and dP; recomputation is not needed
work, so the shared two products are split evenly between them: 2 to the
dQ kernel, 3 to the dK/dV kernel.

Bytes: each kernel reads and writes its tensors once, ``dtype_bytes`` per
element (the program keeps q, k, v in float32), plus the float32
log-sum-exp row.
"""

from benchmarks.trace.roofline import least_seconds


def parts(shapes: dict, peaks: dict, params: dict) -> dict:
    b, s, h, d = (shapes["batch_per_chip"], shapes["seq"], shapes["n_head"],
                  shapes["head_dim"])
    product = 2.0 * b * h * s * s * d * (s + 1) / (2.0 * s)
    tensor = b * s * h * d * shapes["dtype_bytes"]
    lse = b * h * s * 4
    return {
        "fwd": least_seconds(2 * product, 4 * tensor + lse, peaks),
        "dq": least_seconds(2 * product, 6 * tensor + lse, peaks),
        "dkv": least_seconds(3 * product, 7 * tensor + lse, peaks),
    }
