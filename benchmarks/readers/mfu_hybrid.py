"""Model FLOP/s utilization of the hybrid stack, in %: tokens per second
per chip over the window, times the operations a trained token needs
(``trace/roofline/nemotron_h_train.py``, the routed experts from the count
the program served), over the chip's bf16 peak."""

from benchmarks.trace.roofline.nemotron_h_train import flops_per_token


def read(params, observed, traced, peaks):
    rate = observed.get("tokens_per_s_per_chip")
    shapes = observed.get("shapes", {})
    if rate is None or "assignments_served_per_token" not in shapes:
        return None
    return (100.0 * rate * flops_per_token(shapes)
            / peaks["bf16_flops_per_s"])
