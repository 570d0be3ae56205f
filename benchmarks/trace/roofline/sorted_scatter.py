"""``sorted_scatter_accumulate``: ``zeros([rows, aw]).at[ids].add(payload)``
for n payload rows.

What the operation needs: read n row indices and n payload rows of ``aw``
float32 columns, write the accumulator once (one row per key of the pass
table, which is what the push consumes). The adds are n * aw, nothing
beside the bytes.
"""

from benchmarks.trace.roofline import least_seconds


def parts(shapes: dict, peaks: dict, params: dict) -> dict:
    n = shapes["ids_per_step_per_chip"]
    aw = shapes["emb_dim"] + params["columns_beside_emb"]
    rows = shapes["pass_keys_per_chip"]
    return {"call": least_seconds(
        float(n * aw), n * 4 + n * aw * 4 + rows * aw * 4, peaks)}
