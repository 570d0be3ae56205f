"""Median duration, in ms, of the program's spans of one name that lie in
the window."""

import numpy as np

from benchmarks.readers._spans import in_window


def read(params, observed, traced, peaks):
    spans = in_window(observed, params["span"])
    if not spans:
        return None
    return float(np.median([e - s for s, e in spans]) / 1e6)
