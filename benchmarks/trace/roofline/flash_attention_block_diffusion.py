"""Flash attention under the block-diffusion mask: q, o ``[B, 2 L, H, D]``
(a clean sequence of L rows followed by its noisy copy), k, v ``[B, 2 L,
H_kv, D]``, forward and backward, whatever implements it.

The mask leaves, a query head: the clean rows' own and earlier blocks,
L (L + B) / 2 entries; the noisy rows' strictly earlier clean blocks,
L (L - B) / 2; the noisy rows' own blocks, L B: **L^2 + L B entries**, not
the tiles a kernel visits to cover them. Operations as
``flash_attention_gqa.py`` counts them: 2 products forward, 2 for the dq
kernel, 3 for the dk/dv kernel, each 2 * D an entry.

Bytes: each kernel reads and writes its tensors once; q, o and their
cotangents over 2 L rows at H heads, K and V (and their cotangents) once
per key/value head.
"""

from benchmarks.trace.roofline import least_seconds


def unmasked_entries(seq: int, block: int) -> int:
    """Entries of the ``[2 seq, 2 seq]`` scores the mask leaves."""
    return seq * seq + seq * block


def parts(shapes: dict, peaks: dict, params: dict) -> dict:
    b, seq, d = shapes["batch_per_chip"], shapes["seq"], shapes["head_dim"]
    h, hkv = shapes["n_head"], shapes["n_kv_head"]
    product = 2.0 * b * h * d * unmasked_entries(seq, shapes["block_length"])
    tq = b * 2 * seq * h * d * shapes["dtype_bytes"]
    tkv = b * 2 * seq * hkv * d * shapes["dtype_bytes"]
    lse = b * h * 2 * seq * 4
    return {
        # q, k, v -> o
        "fwd": least_seconds(2 * product, 2 * tq + 2 * tkv + lse, peaks),
        # q, k, v, o, do -> dq
        "dq": least_seconds(2 * product, 4 * tq + 2 * tkv + lse, peaks),
        # q, k, v, o, do -> dk, dv
        "dkv": least_seconds(3 * product, 3 * tq + 4 * tkv + lse, peaks),
    }
