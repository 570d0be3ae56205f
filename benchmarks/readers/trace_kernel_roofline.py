"""A kernel's share of its roofline, in %: the least time the chip could
take for the calls the trace shows (``trace/roofline/<kernel>.py``, from
shapes and ``peaks.json``) over the device time of the kernel's events.

``parts`` names, for each part of the kernel, the expressions its events'
labels match. A part none of whose events is in the trace makes the whole
metric absent: a kernel is found by a name or not at all, never by the
order of operations.
"""

import importlib

from benchmarks.trace.reduce import matching


def read(params, observed, traced, peaks):
    if traced is None:
        return None
    module = importlib.import_module(
        "benchmarks.trace.roofline." + params["roofline"])
    least = module.parts(observed["shapes"], peaks, params)
    need = spent = 0.0
    for part, patterns in params["parts"].items():
        seconds, calls = matching(traced, patterns)
        if not calls:
            return None
        need += calls * least[part]["seconds"]
        spent += seconds
    return 100.0 * need / spent
