"""Total duration, in ms, of the program's spans of one name that lie in
the window, per pass or per step (``per``: a count in ``observed``). For
spans that occur many times a pass with one long instance among them,
where a median says nothing."""

from benchmarks.readers._spans import in_window


def read(params, observed, traced, peaks):
    spans = in_window(observed, params["span"])
    if not spans or not observed.get(params["per"]):
        return None
    return sum(e - s for s, e in spans) / observed[params["per"]] / 1e6
