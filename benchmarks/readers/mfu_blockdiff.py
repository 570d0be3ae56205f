"""Model FLOP/s utilization of block-diffusion training, in %: trained
tokens per second per chip over the window, times the operations a
trained token needs (``trace/roofline/block_diffusion_train.py``: two rows
through every layer, the head on one), over the chip's bf16 peak."""

from benchmarks.trace.roofline.block_diffusion_train import flops_per_token


def read(params, observed, traced, peaks):
    rate = observed.get("tokens_per_s_per_chip")
    shapes = observed.get("shapes", {})
    if rate is None or "block_length" not in shapes:
        return None
    return (100.0 * rate * flops_per_token(shapes)
            / peaks["bf16_flops_per_s"])
