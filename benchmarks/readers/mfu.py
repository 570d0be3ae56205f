"""Model FLOP/s utilization, in %: tokens per second per chip over the
window, times the operations a trained token needs
(``trace/roofline/dense_train.py``), over the chip's bf16 peak."""

from benchmarks.trace.roofline.dense_train import flops_per_token


def read(params, observed, traced, peaks):
    rate = observed.get("tokens_per_s_per_chip")
    if rate is None:
        return None
    return (100.0 * rate * flops_per_token(observed["shapes"])
            / peaks["bf16_flops_per_s"])
