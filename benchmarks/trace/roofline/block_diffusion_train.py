"""Operations one trained token needs in block-diffusion training of the
expert stack, from shapes: forward plus backward (twice the forward),
recomputation not counted. A trained token is a position of the sequence;
it puts **two rows** through the stack, its clean and its noisy copy.

A layer, forward, 2 operations per multiply-add, for the two rows: the
four attention projections and the router (2 per weight); the held
experts' three matrices once per assignment *expected* here (experts a
token x held / routed, a row: what an even router sends to this chip's
share); ``Q K^T`` and ``P V`` over the L + B entries the mask leaves a row
pair (the clean row's own and earlier blocks, the noisy row's earlier
clean blocks and its own block). The head reads one row, the noisy one
(2 per weight). The embedding is looked up; norms, rotary embedding,
softmax and top-k are not matmuls and count nothing.
"""


def forward_flops_per_token(shapes: dict) -> dict:
    d, f = shapes["hidden_size"], shapes["moe_intermediate_size"]
    hq, hkv, hd = shapes["n_head"], shapes["n_kv_head"], shapes["head_dim"]
    assignments = (shapes["num_experts_per_tok"] * shapes["experts_held"]
                   / shapes["router_experts"])
    rows = 2.0 * (2 * d * hq * hd + 2 * d * hkv * hd
                  + d * shapes["router_experts"] + assignments * 3 * d * f)
    attention = 2.0 * 2.0 * hq * hd * (shapes["seq"] + shapes["block_length"])
    return {"layer": 2.0 * rows + attention,
            "attention": attention,
            "head": 2.0 * d * shapes["vocab_size"]}


def flops_per_token(shapes: dict) -> float:
    part = forward_flops_per_token(shapes)
    return 3.0 * (shapes["layers"] * part["layer"] + part["head"])
