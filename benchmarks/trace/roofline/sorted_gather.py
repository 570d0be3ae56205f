"""``sorted_gather``: ``out[i] = table[rows[i], :pw]`` for n request rows.

What the operation needs, whatever the kernel does to get it (this one
sweeps the whole table through VMEM): read n row indices, read n rows of
``pw`` float32 columns, write them. No arithmetic.
"""

from benchmarks.trace.roofline import least_seconds


def parts(shapes: dict, peaks: dict, params: dict) -> dict:
    n = shapes["ids_per_step_per_chip"]
    pw = shapes["emb_dim"] + params["columns_beside_emb"]
    return {"call": least_seconds(0.0, n * 4 + 2 * n * pw * 4, peaks)}
