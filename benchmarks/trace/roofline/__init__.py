"""What each kernel's call needs, from its shapes: operations and bytes,
and from them the least time the chip could take (``peaks.json``)."""


def least_seconds(flops: float, bytes_moved: float, peaks: dict) -> dict:
    """The larger of operations over peak FLOP/s and bytes over peak HBM
    bytes/s, and which of the two it is."""
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = bytes_moved / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_flops, by_bytes),
            "bound": "flops" if by_flops >= by_bytes else "bytes",
            "flops": flops, "bytes": bytes_moved}
