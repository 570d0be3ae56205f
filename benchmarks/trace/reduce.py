"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
prints: device busy and idle time, time per operation, the exposed part of
collectives, and the idle gaps laid against what the host was doing.

Everything is arithmetic on half-open intervals ``[start, end)`` in
nanoseconds of the trace's clock. The traced span is the host annotation
``WINDOW_ANNOTATION`` the harness puts around it; its ``unix_ns`` stat ties
the trace's clock to the wall clock, which is how the program's own spans
(``core/trace.py`` ring, wall-anchored) land on the same axis.

Read with nothing but jax: ``jax.profiler.ProfileData``.
"""

from __future__ import annotations

import functools
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

WINDOW_ANNOTATION = "bench/traced"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"     # one event per execution of a program
COLLECTIVE = re.compile(
    r"all-to-all|all-reduce|all-gather|reduce-scatter|collective-permute"
    r"|collective-broadcast")
# operations that only hold others (their time is their children's)
CONTAINERS = ("while", "conditional", "call")
MAX_LABEL = 200

Interval = Tuple[float, float]


# -- interval arithmetic -----------------------------------------------------

def union(intervals: Iterable[Interval]) -> np.ndarray:
    """Sorted, disjoint ``[n, 2]`` cover of the intervals."""
    arr = np.asarray([iv for iv in intervals if iv[1] > iv[0]], np.float64)
    if arr.size == 0:
        return np.zeros((0, 2))
    arr = arr[np.argsort(arr[:, 0], kind="stable")]
    reach = np.maximum.accumulate(arr[:, 1])
    starts_new = np.concatenate([[True], arr[1:, 0] > reach[:-1]])
    first = np.flatnonzero(starts_new)
    last = np.concatenate([first[1:], [len(arr)]]) - 1
    return np.stack([arr[first, 0], reach[last]], axis=1)


def clip(cover: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if cover.size == 0:
        return cover
    out = np.stack([np.maximum(cover[:, 0], lo),
                    np.minimum(cover[:, 1], hi)], axis=1)
    return out[out[:, 1] > out[:, 0]]


def total(cover: np.ndarray) -> float:
    return float((cover[:, 1] - cover[:, 0]).sum()) if cover.size else 0.0


def complement(cover: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The gaps of a disjoint cover inside ``[lo, hi)``."""
    cover = clip(cover, lo, hi)
    edges = np.concatenate([[lo], cover.reshape(-1), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def overlap(cover: np.ndarray, lo: float, hi: float) -> float:
    """Length of ``[lo, hi)`` that a disjoint cover covers."""
    return total(clip(cover, lo, hi))


def subtract(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """The part of cover ``a`` that cover ``b`` does not touch."""
    if a.size == 0:
        return a
    lo, hi = float(a[0, 0]), float(a[-1, 1])
    keep = complement(b, lo, hi)
    parts = [clip(a, float(s), float(e)) for s, e in keep]
    parts = [p for p in parts if p.size]
    return np.concatenate(parts) if parts else np.zeros((0, 2))


# -- reading -----------------------------------------------------------------

def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


@functools.lru_cache(maxsize=None)  # millions of events, hundreds of texts
def parse_op(text: str) -> Tuple[str, str]:
    """An XLA op event's name on a TPU is its whole HLO instruction,
    ``%name = shape opcode(operands), attributes``: -> (name, opcode). The
    instruction's name is where a jitted helper's or a kernel's name
    survives (``_sorted_accumulate.1``); the operand list names other
    instructions and must not be searched. Text of another form is its own
    name, with no opcode."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text[:MAX_LABEL], ""
    rest = rest.lstrip()
    if rest.startswith("("):                # a tuple shape: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    return head.lstrip("%"), rest.lstrip().partition("(")[0].strip()


def read(path: str) -> Dict:
    """``{"devices": {ordinal: [(start, end, name, opcode)]}, "modules":
    {ordinal: [(start, end, program)]}, "host": [(start, end, name,
    {stat: value})]}`` from one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[int, List] = {}
    modules: Dict[int, List] = {}
    host: List = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = [
                (e.start_ns, e.start_ns + e.duration_ns, *parse_op(e.name))
                for ln in plane.lines if ln.name == OP_LINE
                for e in ln.events]
            modules[int(m.group(1))] = [
                (e.start_ns, e.start_ns + e.duration_ns,
                 e.name.partition("(")[0])
                for ln in plane.lines if ln.name == MODULE_LINE
                for e in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("bench/"):
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name, dict(e.stats)))
    return {"devices": devices, "modules": modules, "host": host}


def structure(path: str, samples: int = 4) -> Dict:
    """Planes, their lines, how many events each holds, and a few events
    with their stat names: what to look at before trusting a reduction
    on a new installation or device kind (``run.py --detail``)."""
    from jax.profiler import ProfileData
    out: Dict = {"bytes": os.path.getsize(path), "planes": {}}
    for plane in ProfileData.from_file(path).planes:
        lines = out["planes"].setdefault(plane.name, {})
        for ln in plane.lines:
            events = list(ln.events)
            lines[ln.name] = {
                "events": len(events),
                "first": [[e.name, e.start_ns, e.duration_ns,
                           {k: str(v)[:120] for k, v in e.stats}]
                          for e in events[:samples]]}
    return out


# -- reduction ---------------------------------------------------------------

def self_intervals(spans: Sequence[Tuple[float, float, str, int]]
                   ) -> List[Tuple[float, float, str]]:
    """Spans ``(start, end, name, thread)`` -> the pieces of each during
    which no span nested inside it, on its thread, was open: a span's own
    time."""
    out: List[Tuple[float, float, str]] = []
    by_thread: Dict[int, List] = {}
    for s in spans:
        by_thread.setdefault(s[3], []).append(s)
    for items in by_thread.values():
        items.sort(key=lambda s: (s[0], -s[1]))
        stack: List[List] = []          # [end, name, cursor]

        def close(upto: float) -> None:
            while stack and stack[-1][0] <= upto:
                end, name, cursor = stack.pop()
                if end > cursor:
                    out.append((cursor, end, name))
                if stack:
                    stack[-1][2] = max(stack[-1][2], end)
        for start, end, name, _ in items:
            close(start)
            if stack and start > stack[-1][2]:
                out.append((stack[-1][2], start, stack[-1][1]))
            if stack:
                stack[-1][2] = max(stack[-1][2], start)
            stack.append([end, name, start])
        close(float("inf"))
    return out


def reduce(raw: Dict, host_spans_unix: Sequence[Tuple[float, float, str, int]]
           = (), expected_steps: Optional[int] = None, top: int = 10
           ) -> Optional[Dict]:
    """The traced span's numbers, or None where the trace holds no device
    operation or no ``WINDOW_ANNOTATION`` (a reader then returns nothing).

    ``host_spans_unix``: the program's spans as ``(start, end, name,
    thread)`` in unix nanoseconds. ``expected_steps``: how many steps the
    runner dispatched inside the span. The profiler's device buffer holds
    a few million events and then drops the rest in silence (a step whose
    gather runs as a 419k-iteration loop fills it in three steps): where
    the trace holds fewer executions of the step program than that, the
    span is cut at the last device event it does hold, and ``steps`` is
    what it holds.
    """
    marks = [h for h in raw["host"] if h[2] == WINDOW_ANNOTATION]
    if not marks or not any(raw["devices"].values()):
        return None
    w_lo, w_hi, _, stats = max(marks, key=lambda h: h[1] - h[0])
    to_trace = w_lo - float(stats.get("unix_ns", 0))

    def steps_seen(hi: float) -> int:
        """Whole executions of the step program: of the programs that ran
        more than once in the span, the one that took most device time.
        (Small transfer programs run more often than the step does, and a
        pass boundary's program runs once and may outweigh a short pass.)
        The execution in flight when the trace stops is recorded cut short,
        0.7 of 82 ms: one under half the median is not a whole one."""
        runs: Dict[str, List[float]] = {}
        for start, end, program in raw.get("modules", {}).get(
                min(raw["devices"]), []):
            if start >= w_lo and end <= hi:
                runs.setdefault(program, []).append(end - start)
        repeated = [r for r in runs.values() if len(r) > 1]
        step = max(repeated or runs.values(), key=sum, default=[])
        return sum(1 for d in step if 2 * d >= np.median(step))
    steps, truncated = steps_seen(w_hi), False
    if expected_steps and steps < expected_steps:
        truncated = True
        w_hi = min(w_hi, max(e[1] for ev in raw["devices"].values()
                             for e in ev if e[0] < w_hi))
        steps = steps_seen(w_hi)

    busy, exposed, per_op = [], [], {}      # "name (opcode)": [ns, calls]
    ordinals = sorted(raw["devices"])
    for d in ordinals:
        events = [e for e in raw["devices"][d] if e[1] > w_lo and e[0] < w_hi]
        cover = clip(union((e[0], e[1]) for e in events), w_lo, w_hi)
        busy.append(total(cover))
        coll = union((e[0], e[1]) for e in events if COLLECTIVE.search(e[3]))
        comp = union((e[0], e[1]) for e in events
                     if not COLLECTIVE.search(e[3])
                     and e[3] not in CONTAINERS)
        exposed.append(total(clip(subtract(coll, comp), w_lo, w_hi)))
        for start, end, name, opcode in events:
            entry = per_op.setdefault(
                f"{name} ({opcode})" if opcode else name, [0.0, 0])
            entry[0] += min(end, w_hi) - max(start, w_lo)
            entry[1] += 1
    n = len(ordinals)

    first = raw["devices"][ordinals[0]]
    gaps = complement(union((e[0], e[1]) for e in first), w_lo, w_hi)
    idle_by: Dict[str, float] = {}
    if to_trace != w_lo:                # the window mark carried unix_ns
        pieces = self_intervals([(s + to_trace, e + to_trace, name, th)
                                 for s, e, name, th in host_spans_unix])
        for s, e, name in pieces:
            got = overlap(gaps, s, e)
            if got > 0:
                idle_by[name] = idle_by.get(name, 0.0) + got
        covered = union((s, e) for s, e, _ in pieces)
        idle_by["(no span open)"] = total(subtract(gaps, covered))

    def ranked(table: Dict[str, float]) -> List[List]:
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v / 1e9] for k, v in rows if v > 0]

    return {
        "window_s": (w_hi - w_lo) / 1e9,
        "busy_s": float(np.mean(busy)) / 1e9,
        "devices": n, "steps": steps, "truncated": truncated,
        "collective_exposed_s": float(np.mean(exposed)) / 1e9,
        "ops": {k: (v[0] / n / 1e9, v[1] / n) for k, v in per_op.items()},
        "longest_gap_s": float((gaps[:, 1] - gaps[:, 0]).max() / 1e9)
        if gaps.size else 0.0,
        "breakdown": {
            "device_ops": ranked({k: v[0] / n for k, v in per_op.items()}),
            "idle_gaps": ranked(idle_by),
        },
    }


def matching(reduced: Dict, patterns: Sequence[str]) -> Tuple[float, float]:
    """(device seconds, calls), per device, of the operations whose
    ``name (opcode)`` matches any of the regular expressions."""
    rx = [re.compile(p) for p in patterns]
    hits = [v for k, v in reduced["ops"].items()
            if any(r.search(k) for r in rx)]
    return sum(v[0] for v in hits), sum(v[1] for v in hits)
