"""Operations one trained token needs in the hybrid stack, from shapes:
forward plus backward (twice the forward), recomputation not counted.

Per layer kind, forward, 2 operations per multiply-add:

- ``M``: the in- and out-projections (2 per weight); the depthwise
  convolution (k taps per channel); the scan in its chunked dual form:
  ``C B^T`` over the (L + 1) / 2 positions of its chunk a position sees
  on average, once per B/C group; the masked scores times ``x`` per head;
  ``C h`` and ``B (x) x`` against the carried state per head.
- ``*``: the four projections; ``Q K^T`` and ``P V`` over the (S + 1) / 2
  keys a position sees on average.
- ``E``: router, latent down and up projections, shared expert (2 per
  weight); the routed experts' two matrices once per assignment *served*
  (the count the program returns, not the 22 chosen: experts that are
  not held do no work here).
- the output head (2 per weight); the embedding is looked up.
"""


def forward_flops_per_token(shapes: dict) -> dict:
    d, seq = shapes["hidden_size"], shapes["seq"]
    heads, p = shapes["mamba_num_heads"], shapes["mamba_head_dim"]
    n, groups = shapes["ssm_state_size"], shapes["n_groups"]
    chunk = shapes["chunk_size"]
    di, conv_dim = heads * p, heads * p + 2 * groups * n
    mamba = (2.0 * (d * (di + conv_dim + heads) + di * d)
             + 2.0 * shapes["conv_kernel"] * conv_dim
             + 2.0 * groups * n * (chunk + 1) / 2.0
             + 2.0 * heads * p * (chunk + 1) / 2.0
             + 2.0 * 2.0 * heads * n * p)
    hq, hkv, hd = shapes["n_head"], shapes["n_kv_head"], shapes["head_dim"]
    attention = (2.0 * (2 * d * hq * hd + 2 * d * hkv * hd)
                 + 2.0 * 2.0 * hq * hd * (seq + 1) / 2.0)
    lat, inner = shapes["moe_latent_size"], shapes["moe_intermediate_size"]
    experts = (2.0 * (d * shapes["router_experts"] + 2 * d * lat
                      + 2 * d * shapes["moe_shared_expert_intermediate_size"])
               + shapes["assignments_served_per_token"]
               * 2.0 * 2 * lat * inner)
    return {"M": mamba, "*": attention, "E": experts,
            "head": 2.0 * d * shapes["vocab_size"]}


def flops_per_token(shapes: dict) -> float:
    kind = forward_flops_per_token(shapes)
    forward = sum(kind[letter] for letter in shapes["pattern"])
    return 3.0 * (forward + kind["head"])
