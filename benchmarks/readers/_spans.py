"""The program's spans (``core/trace.py`` ring, wall-anchored) that lie in
the measured window: ``observed["program_spans"]`` holds ``(start, end,
name, thread)`` in unix ns."""

from typing import List, Tuple


def in_window(observed: dict, name: str) -> List[Tuple[float, float]]:
    lo, hi = observed["window_unix_ns"]
    return sorted((s, e) for s, e, n, _ in observed["program_spans"]
                  if n == name and s >= lo and e <= hi)
