"""Operations one trained token needs in the looped stack, from shapes:
forward plus backward (twice the forward), recomputation not counted.

A layer application, forward, 2 operations per multiply-add: the four
attention projections and the three SwiGLU matrices (2 per weight);
``Q K^T`` and ``P V`` over the (S + 1) / 2 keys a position sees on average.
The stack applies its ``layers`` layers ``passes`` times, and every pass
reads the head once (2 per weight). The embedding is looked up; norms,
rotary embedding, gate and exit distribution are not matmuls and count
nothing.
"""


def forward_flops_per_token(shapes: dict) -> dict:
    d, f, seq = shapes["hidden_size"], shapes["intermediate_size"], shapes["seq"]
    hq, hkv, hd = shapes["n_head"], shapes["n_kv_head"], shapes["head_dim"]
    layer = (2.0 * (2 * d * hq * hd + 2 * d * hkv * hd + 3 * d * f)
             + 2.0 * 2.0 * hq * hd * (seq + 1) / 2.0)
    return {"layer": layer, "head": 2.0 * d * shapes["vocab_size"]}


def flops_per_token(shapes: dict) -> float:
    part = forward_flops_per_token(shapes)
    return 3.0 * shapes["passes"] * (shapes["layers"] * part["layer"]
                                     + part["head"])
