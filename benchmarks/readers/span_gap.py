"""Median time, in ms, from an edge of each span ``from`` in the window to
the start of the next span ``to`` on the clock: what the thread pays
between the two."""

import bisect

import numpy as np

from benchmarks.readers._spans import in_window


def read(params, observed, traced, peaks):
    edge = 0 if params["from"]["edge"] == "start" else 1
    starts = [s for s, _ in in_window(observed, params["to"]["span"])]
    gaps = []
    for span in in_window(observed, params["from"]["span"]):
        i = bisect.bisect_left(starts, span[1])
        if i < len(starts):
            gaps.append(starts[i] - span[edge])
    if not gaps:
        return None
    return float(np.median(gaps) / 1e6)
