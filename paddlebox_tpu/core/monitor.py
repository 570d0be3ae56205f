"""Process-global metric registry: counters, gauges, histograms.

Role of ``paddle/fluid/platform/monitor.h`` (``platform::Monitor`` /
``StatRegistry`` named int64 stats) grown into a full registry: int
counters (the original API, unchanged), FLOAT gauges (so rate/ratio call
sites don't silently truncate through the int counter path), and
fixed-bucket histograms (step/dispatch latency distributions).

Thread-safe and cheap to bump from the data pipeline, trainer, and RPC
threads. A labeled ``snapshot_all()`` returns one structured view; the
JSONL exporter appends snapshot lines to ``FLAGS_metrics_path`` — one
per pass report plus a periodic background flush thread
(``FLAGS_metrics_flush_interval_s``). Telemetry is default-off: with no
metrics path configured nothing is written and the flush thread never
starts.
"""

from __future__ import annotations

import atexit
import bisect
import json
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Union

from paddlebox_tpu.core.quantiles import LogQuantileDigest, merge_digests

Number = Union[int, float]

# Default latency buckets (ms): exponential-ish 1ms..30s — wide enough
# for both a CPU smoke step and a multi-second dispatch stall.
DEFAULT_BUCKETS = (1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0, 200.0, 500.0,
                   1000.0, 2000.0, 5000.0, 10000.0, 30000.0)


class Histogram:
    """Fixed-bucket histogram: counts per bucket + running sum/min/max.

    Buckets are upper bounds; values above the last bound land in the
    implicit +inf bucket. Percentiles are estimated from bucket counts
    by tools/trace_report.py — the registry itself stores only O(len
    (buckets)) state no matter how many observations arrive."""

    __slots__ = ("buckets", "counts", "count", "sum", "min", "max")

    def __init__(self, buckets: Sequence[float] = DEFAULT_BUCKETS):
        self.buckets = tuple(float(b) for b in buckets)
        if list(self.buckets) != sorted(set(self.buckets)):
            raise ValueError(f"histogram buckets must be strictly "
                             f"increasing: {buckets}")
        self.counts = [0] * (len(self.buckets) + 1)
        self.count = 0
        self.sum = 0.0
        self.min = float("inf")
        self.max = float("-inf")

    def observe(self, value: float) -> None:
        v = float(value)
        self.counts[bisect.bisect_left(self.buckets, v)] += 1
        self.count += 1
        self.sum += v
        self.min = v if v < self.min else self.min
        self.max = v if v > self.max else self.max

    def to_dict(self) -> Dict[str, Any]:
        return {"buckets": list(self.buckets),
                "counts": list(self.counts),
                "count": self.count,
                "sum": round(self.sum, 6),
                "min": self.min if self.count else None,
                "max": self.max if self.count else None}


class Monitor:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._stats: Dict[str, Number] = {}        # counters (add/set)
        self._gauges: Dict[str, float] = {}        # float set-last-wins
        self._hists: Dict[str, Histogram] = {}
        self._digests: Dict[str, LogQuantileDigest] = {}
        self._flush_thread: Optional[threading.Thread] = None
        self._flush_stop = threading.Event()
        self._flush_path: Optional[str] = None
        self._atexit_registered = False

    # -- counters (original StatRegistry API, unchanged) -------------------

    def add(self, name: str, delta: Number = 1) -> None:
        with self._lock:
            self._stats[name] = self._stats.get(name, 0) + delta

    def set(self, name: str, value: Number) -> None:
        with self._lock:
            self._stats[name] = value

    def get(self, name: str) -> Number:
        with self._lock:
            return self._stats.get(name, 0)

    # -- gauges ------------------------------------------------------------

    def set_gauge(self, name: str, value: float) -> None:
        """Float gauge: last-write-wins (rates, ratios, ms figures —
        values the int counter path would truncate)."""
        with self._lock:
            self._gauges[name] = float(value)

    def get_gauge(self, name: str, default: float = 0.0) -> float:
        with self._lock:
            return self._gauges.get(name, default)

    # -- histograms ---------------------------------------------------------

    def define_histogram(self, name: str,
                         buckets: Sequence[float] = DEFAULT_BUCKETS
                         ) -> None:
        """Pre-declare a histogram's buckets (idempotent for identical
        buckets; re-defining with different ones raises — silently
        changing bucket bounds mid-run would corrupt the series)."""
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                self._hists[name] = Histogram(buckets)
            elif h.buckets != tuple(float(b) for b in buckets):
                raise ValueError(
                    f"histogram {name!r} already defined with buckets "
                    f"{h.buckets}")

    def observe(self, name: str, value: float,
                buckets: Sequence[float] = DEFAULT_BUCKETS) -> None:
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = Histogram(buckets)
            h.observe(value)

    # -- streaming quantile digests ------------------------------------------

    def observe_quantile(self, name: str, value: float,
                         rel_error: float = 0.01) -> None:
        """Feed the named log-bucketed quantile sketch (created on first
        observe). Unlike the fixed-bucket histogram, the digest needs no
        pre-chosen bounds and merges across ranks — the p50/p90/p99/p999
        source for the pass report and the serving SLO layer."""
        with self._lock:
            d = self._digests.get(name)
            if d is None:
                d = self._digests[name] = LogQuantileDigest(rel_error)
            d.observe(value)

    def quantile_digest(self, name: str
                        ) -> Optional[LogQuantileDigest]:
        """A COPY of the named digest (safe to keep as a window base for
        :meth:`LogQuantileDigest.delta`); None when never observed."""
        with self._lock:
            d = self._digests.get(name)
            return d.copy() if d is not None else None

    # -- snapshots -----------------------------------------------------------

    def snapshot(self) -> Dict[str, Number]:
        """Flat counters+gauges view (the original API shape — existing
        call sites and tests keep working)."""
        with self._lock:
            out: Dict[str, Number] = dict(self._stats)
            out.update(self._gauges)
            return out

    def snapshot_all(self, labels: Optional[Dict[str, Any]] = None
                     ) -> Dict[str, Any]:
        """One labeled structured snapshot — the JSONL export record."""
        with self._lock:
            return {
                "ts": time.time(),
                "labels": dict(labels or {}),
                "counters": dict(self._stats),
                "gauges": dict(self._gauges),
                "histograms": {n: h.to_dict()
                               for n, h in self._hists.items()},
                "quantiles": {n: d.to_dict()
                              for n, d in self._digests.items()},
            }

    def reset(self) -> None:
        with self._lock:
            self._stats.clear()
            self._gauges.clear()
            self._hists.clear()
            self._digests.clear()

    # -- JSONL exporter -------------------------------------------------------

    def flush_jsonl(self, path: Optional[str] = None,
                    labels: Optional[Dict[str, Any]] = None
                    ) -> Optional[str]:
        """Append one snapshot line to ``path`` (default: the configured
        ``FLAGS_metrics_path``). No-op (returns None) when neither is
        set — callers sprinkle this freely without gating."""
        if path is None:
            path = self._flush_path
            if path is None:
                from paddlebox_tpu.core import flags
                path = flags.flag("metrics_path") or None
        if not path:
            return None
        line = json.dumps(self.snapshot_all(labels), default=str)
        with open(path, "a") as f:
            f.write(line + "\n")
        return path

    def _atexit_flush(self) -> None:
        """Final flush at interpreter exit: short-lived runs (tools,
        crash drills) must not lose their last window just because no
        pass report or flush tick landed before exit. Idempotent with
        the periodic thread — it appends one more labeled snapshot, and
        a de-configured exporter (stop_flush_thread ran) makes it a
        no-op."""
        try:
            self.flush_jsonl(self._flush_path,
                             labels={"event": "final_flush"})
        except OSError:
            pass

    def _register_atexit(self) -> None:
        if not self._atexit_registered:
            self._atexit_registered = True
            atexit.register(self._atexit_flush)

    def start_flush_thread(self, path: str,
                           interval_s: float = 30.0) -> bool:
        """Periodic background JSONL flusher (daemon). Idempotent; a
        non-positive interval means 'no thread' (pass-report flushes
        still append). Arming the exporter also registers the one
        atexit final flush."""
        self._register_atexit()
        with self._lock:
            self._flush_path = path
            if interval_s <= 0 or (self._flush_thread is not None
                                   and self._flush_thread.is_alive()):
                return self._flush_thread is not None
            self._flush_stop.clear()

            def loop():
                while not self._flush_stop.wait(interval_s):
                    try:
                        self.flush_jsonl(path)
                    except OSError:
                        pass

            self._flush_thread = threading.Thread(
                target=loop, name="metrics-flush", daemon=True)
            self._flush_thread.start()
            return True

    def stop_flush_thread(self) -> None:
        """Stop the flusher AND disarm the configured path (tests and
        shutdown paths use this to fully de-configure the exporter)."""
        t = self._flush_thread
        self._flush_stop.set()
        if t is not None:
            t.join(timeout=5.0)
        self._flush_thread = None
        self._flush_path = None

    def init_from_flags(self) -> bool:
        """Idempotent flag-driven setup: a non-empty FLAGS_metrics_path
        arms the exporter (and its flush thread). Returns armed."""
        from paddlebox_tpu.core import flags
        path = flags.flag("metrics_path")
        if not path:
            return self._flush_path is not None
        self.start_flush_thread(
            path, float(flags.flag("metrics_flush_interval_s")))
        return True


# -- cluster-level aggregation ------------------------------------------------

def merge_snapshots(snaps: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """Merge per-rank ``snapshot_all()`` dicts into ONE cluster-level
    snapshot (prep for multi-host: each rank keeps its own registry; a
    collector folds them so the operator reads one report, not N).

    Merge semantics per section:
    - ``counters``: summed (they are totals — bytes, passes, retries).
    - ``gauges``: arithmetic mean across the ranks that reported the
      name, plus ``<name>__max`` for skew-sensitive reads (a mean hides
      the one stalled rank; the max names it).
    - ``histograms``: bucket-wise count addition (identical bucket
      bounds required — mixed bounds raise, same as define_histogram).
    - ``quantiles``: digest merge (the whole point of the log-bucketed
      sketch — associative bucket addition, no accuracy loss).
    """
    if not snaps:
        return {"ts": time.time(), "ranks": 0, "labels": {},
                "counters": {}, "gauges": {}, "histograms": {},
                "quantiles": {}}
    counters: Dict[str, Number] = {}
    for s in snaps:
        for k, v in (s.get("counters") or {}).items():
            counters[k] = counters.get(k, 0) + v
    gauge_vals: Dict[str, List[float]] = {}
    for s in snaps:
        for k, v in (s.get("gauges") or {}).items():
            gauge_vals.setdefault(k, []).append(float(v))
    gauges: Dict[str, float] = {}
    for k, vs in gauge_vals.items():
        gauges[k] = sum(vs) / len(vs)
        if len(vs) > 1:
            gauges[k + "__max"] = max(vs)
    hists: Dict[str, Dict[str, Any]] = {}
    for s in snaps:
        for k, h in (s.get("histograms") or {}).items():
            cur = hists.get(k)
            if cur is None:
                hists[k] = {**h, "counts": list(h["counts"])}
                continue
            if list(cur["buckets"]) != list(h["buckets"]):
                raise ValueError(
                    f"histogram {k!r} has mismatched buckets across "
                    f"ranks — cannot merge")
            cur["counts"] = [a + b for a, b in zip(cur["counts"],
                                                   h["counts"])]
            cur["count"] += h["count"]
            cur["sum"] = round(cur["sum"] + h["sum"], 6)
            mins = [m for m in (cur["min"], h["min"]) if m is not None]
            maxs = [m for m in (cur["max"], h["max"]) if m is not None]
            cur["min"] = min(mins) if mins else None
            cur["max"] = max(maxs) if maxs else None
    digs: Dict[str, List[LogQuantileDigest]] = {}
    for s in snaps:
        for k, d in (s.get("quantiles") or {}).items():
            digs.setdefault(k, []).append(LogQuantileDigest.from_dict(d))
    quantiles = {k: merge_digests(ds).to_dict()
                 for k, ds in digs.items()}
    return {"ts": max(float(s.get("ts", 0.0)) for s in snaps),
            "ranks": len(snaps),
            "labels": dict(snaps[0].get("labels") or {}),
            "counters": counters, "gauges": gauges,
            "histograms": hists, "quantiles": quantiles}


def collect_cluster_snapshot(store, *, labels: Optional[Dict[str, Any]]
                             = None, key: str = "metrics_snapshot",
                             timeout: float = 60.0,
                             snapshot: Optional[Dict[str, Any]] = None,
                             registry: Optional["Monitor"] = None
                             ) -> Dict[str, Any]:
    """All-gather every rank's registry snapshot through a FileStore
    (``distributed.transport.FileStore`` — or anything with its
    ``all_gather(name, bytes, timeout)`` contract) and return the ONE
    merged cluster-level snapshot on every rank. Symmetric: all ranks
    must call it (it is a rendezvous). Rank 0 typically writes the
    result to the metrics JSONL with a ``{"event": "cluster_report"}``
    label."""
    reg = registry if registry is not None else GLOBAL
    mine = snapshot if snapshot is not None else reg.snapshot_all(labels)
    blobs = store.all_gather(key, json.dumps(mine, default=str).encode(),
                             timeout=timeout)
    return merge_snapshots([json.loads(b) for b in blobs])


GLOBAL = Monitor()

add = GLOBAL.add
set_stat = GLOBAL.set
get = GLOBAL.get
snapshot = GLOBAL.snapshot
snapshot_all = GLOBAL.snapshot_all
reset = GLOBAL.reset
set_gauge = GLOBAL.set_gauge
get_gauge = GLOBAL.get_gauge
observe = GLOBAL.observe
observe_quantile = GLOBAL.observe_quantile
quantile_digest = GLOBAL.quantile_digest
define_histogram = GLOBAL.define_histogram
flush_jsonl = GLOBAL.flush_jsonl
start_flush_thread = GLOBAL.start_flush_thread
stop_flush_thread = GLOBAL.stop_flush_thread
init_from_flags = GLOBAL.init_from_flags
