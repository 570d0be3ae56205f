"""Model FLOP/s utilization of the looped stack, in %: tokens per second
per chip over the window, times the operations a trained token needs
(``trace/roofline/looped_train.py``: every pass's layer applications and
head), over the chip's bf16 peak."""

from benchmarks.trace.roofline.looped_train import flops_per_token


def read(params, observed, traced, peaks):
    rate = observed.get("tokens_per_s_per_chip")
    shapes = observed.get("shapes", {})
    if rate is None or "passes" not in shapes:
        return None
    return (100.0 * rate * flops_per_token(shapes)
            / peaks["bf16_flops_per_s"])
