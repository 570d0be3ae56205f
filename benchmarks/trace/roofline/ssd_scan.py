"""The Mamba-2 scan over ``[B, S, H, P]`` with state ``[N, P]`` a head and
B, C in G groups, forward and backward, whatever implements it.

Forward, per position (2 operations per multiply-add), in the chunked
dual form with chunks of L: ``C B^T`` over the (L + 1) / 2 positions of
the chunk a position sees on average, once per group (2 G N each); the
masked scores times ``x`` per head (2 H P each); ``C h`` and ``B (x) x``
against the carried state (2 H N P each). Backward: every forward product
has two transposes, so twice the forward; rebuilding scores and decays
is not needed work.

Bytes, float32: forward reads x, B, C, dt and the decay exponent and
writes y; backward reads those and dy and writes the five cotangents.
States carried between chunks are the implementation's business.
"""

from benchmarks.trace.roofline import least_seconds


def parts(shapes: dict, peaks: dict, params: dict) -> dict:
    tokens = shapes["batch_per_chip"] * shapes["seq"]
    heads, p = shapes["mamba_num_heads"], shapes["mamba_head_dim"]
    n, groups = shapes["ssm_state_size"], shapes["n_groups"]
    seen = (shapes["chunk_size"] + 1) / 2.0
    forward = tokens * (2.0 * groups * n * seen + 2.0 * heads * p * seen
                        + 2.0 * 2.0 * heads * n * p)
    x = tokens * heads * p * 4
    bc = tokens * groups * n * 4
    per_head = tokens * heads * 4
    return {
        "fwd": least_seconds(forward, 2 * x + 2 * bc + 2 * per_head, peaks),
        "bwd": least_seconds(2 * forward, 3 * x + 4 * bc + 4 * per_head,
                             peaks),
    }
