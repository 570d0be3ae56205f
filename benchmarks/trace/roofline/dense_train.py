"""Operations one trained token needs in the GPT-2 block, from shapes:
forward plus backward (twice the forward), recomputation not counted.

Matrix products: 2 per weight per token (QKV d x 3d, attention output
d x d, FFN d x f and f x d, per layer; the d x V head). The embedding and
position tables are looked up, not multiplied, and count nothing.
Attention: Q K^T and P V over the (S + 1) / 2 keys a position sees on
average under the causal mask, 2 * d each per key.
"""


def flops_per_token(shapes: dict) -> float:
    d, f, layers = shapes["n_embd"], shapes["n_inner"], shapes["n_layer"]
    weights = layers * (4 * d * d + 2 * d * f) + d * shapes["vocab_size"]
    attention = layers * 2 * 2 * d * (shapes["seq"] + 1) / 2.0
    return 3.0 * (2.0 * weights + attention)
