"""Device time, in ms per traced step and device, of the operations whose
label matches any of ``patterns``."""

from benchmarks.trace.reduce import matching


def read(params, observed, traced, peaks):
    if traced is None or not traced["steps"]:
        return None
    seconds, calls = matching(traced, params["patterns"])
    if not calls:
        return None
    return seconds / traced["steps"] * 1e3
