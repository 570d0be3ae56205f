"""Device-busy time of the traced span, per device, over the steps the
trace holds in it (executions of the program that took most time), in ms."""


def read(params, observed, traced, peaks):
    if traced is None or not traced["steps"]:
        return None
    return traced["busy_s"] / traced["steps"] * 1e3
