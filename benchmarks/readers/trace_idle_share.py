"""1 - (union of the device's operation intervals / traced span), in %."""


def read(params, observed, traced, peaks):
    if traced is None:
        return None
    return 100.0 * (1.0 - traced["busy_s"] / traced["window_s"])
