"""Summarize a span trace (and/or metrics JSONL) into per-stage tables.

Point it at the artifacts a telemetry-enabled run wrote
(``FLAGS_trace_path`` / ``FLAGS_metrics_path``) and get, per span name:
count, total ms, p50/p95/max, share of the traced wall —
plus the registry's counters/gauges and bucket-estimated histogram
percentiles from the newest metrics snapshot.

    python tools/trace_report.py /tmp/run.trace.json
    python tools/trace_report.py --metrics /tmp/run.metrics.jsonl
    python tools/trace_report.py trace.json --metrics m.jsonl --top 15

Cross-process merge (``--merge``): stitch N per-process trace files
(each exported by ``core/trace.py`` with its wall-clock anchor and
peer clock offsets in ``otherData``) into ONE Perfetto-loadable trace —
per-process tracks on a single wall-aligned timeline, plus flow arrows
binding each RPC client span to its server span (the ``span``/``parent``
ids the distributed trace context stamps on ``rpc/*`` spans):

    python tools/trace_report.py --merge /tmp/fleet.trace.json \
        router.trace.json replica0.trace.json shard0.trace.json
"""

import argparse
import json
import os
import sys
from collections import defaultdict


def _pct(durs, q):
    """Exact percentile over the recorded durations (nearest-rank)."""
    if not durs:
        return 0.0
    s = sorted(durs)
    i = min(len(s) - 1, max(0, int(round(q * (len(s) - 1)))))
    return s[i]


def report_trace(path: str, top: int) -> None:
    with open(path) as f:
        obj = json.load(f)
    events = [e for e in obj.get("traceEvents", obj
                                 if isinstance(obj, list) else [])
              if e.get("ph") == "X"]
    if not events:
        print(f"{path}: no complete ('X') span events")
        return
    wall_us = (max(e["ts"] + e.get("dur", 0.0) for e in events)
               - min(e["ts"] for e in events))
    by_name = defaultdict(list)
    for e in events:
        by_name[e["name"]].append(e.get("dur", 0.0) / 1e3)  # us -> ms
    print(f"\n== {path}: {len(events)} spans, "
          f"{len(by_name)} names, wall {wall_us / 1e3:.1f} ms ==")
    hdr = (f"{'span':<28} {'count':>6} {'total_ms':>10} {'p50_ms':>9} "
           f"{'p95_ms':>9} {'max_ms':>9} {'share':>7}")
    print(hdr)
    print("-" * len(hdr))
    rows = sorted(by_name.items(), key=lambda kv: -sum(kv[1]))
    for name, durs in rows[:top]:
        total = sum(durs)
        share = total / (wall_us / 1e3) if wall_us else 0.0
        print(f"{name:<28} {len(durs):>6} {total:>10.2f} "
              f"{_pct(durs, 0.50):>9.3f} {_pct(durs, 0.95):>9.3f} "
              f"{max(durs):>9.3f} {share:>6.1%}")
    if len(rows) > top:
        print(f"... {len(rows) - top} more span names (--top to widen)")


def _hist_pct(buckets, counts, q):
    """Bucket-estimated percentile: the upper bound of the bucket where
    the cumulative count crosses q (the +inf bucket reports the last
    finite bound tagged '>')."""
    total = sum(counts)
    if not total:
        return "-"
    need = q * total
    cum = 0
    for i, c in enumerate(counts):
        cum += c
        if cum >= need:
            if i < len(buckets):
                return f"{buckets[i]:g}"
            return f">{buckets[-1]:g}"
    return f">{buckets[-1]:g}"


def _report_quality(gauges: dict, counters: dict) -> None:
    """Model quality & data health section (core/quality.py): the
    COPC/calibration headline, every quality alarm counter, and the
    per-slot health gauges (coverage / zero rate / churn / skew),
    beside the stage tables."""
    qg = {k: v for k, v in gauges.items() if k.startswith("quality/")}
    qa = {k: v for k, v in counters.items()
          if k.startswith("quality/")}
    if not qg and not qa:
        return
    print("\nmodel quality & data health")
    print("-" * 27)
    head = []
    for name, label in (("quality/copc", "copc"),
                        ("quality/calibration_error", "cal_err"),
                        ("quality/key_churn", "churn"),
                        ("quality/skew_top_share", "top_share")):
        v = qg.get(name)
        if v is not None:
            head.append(f"{label}={v:.4f}")
    if head:
        print("  ".join(head))
    alarms = {k: v for k, v in qa.items()
              if k.startswith("quality/alarms/")}
    if alarms:
        print("alarms: " + "  ".join(
            f"{k[len('quality/alarms/'):]}={v}"
            for k, v in sorted(alarms.items())))
    slots = sorted({k.rsplit("/", 1)[1] for k in qg
                    if k.startswith("quality/slot_coverage/")})
    if slots:
        hdr = (f"{'slot':<14} {'coverage':>9} {'zero':>7} "
               f"{'churn':>7} {'top1%':>7} {'auc_drop':>9}")
        print(hdr)
        for s in slots:
            def g(prefix):
                v = qg.get(f"quality/{prefix}/{s}")
                return f"{v:.4f}" if isinstance(v, (int, float)) else "-"
            print(f"{s:<14} {g('slot_coverage'):>9} "
                  f"{g('slot_zero_frac'):>7} {g('slot_churn'):>7} "
                  f"{g('slot_top_share'):>7} {g('slot_auc_drop'):>9}")


def _report_quantiles(quantiles: dict) -> None:
    """Streaming-digest percentiles (core/quantiles.py): exact-count,
    rel-error-bounded p50/p90/p99/p999 — the dispatch-latency and
    serving-SLO view."""
    if not quantiles:
        return
    hdr = (f"\n{'quantile digest':<32} {'count':>8} {'p50':>9} "
           f"{'p90':>9} {'p99':>9} {'p999':>9} {'max':>9}")
    print(hdr)
    print("-" * len(hdr))
    for name, d in sorted(quantiles.items()):
        def fmt(v):
            return f"{v:.3f}" if isinstance(v, (int, float)) else "-"
        print(f"{name:<32} {d.get('count', 0):>8} {fmt(d.get('p50')):>9} "
              f"{fmt(d.get('p90')):>9} {fmt(d.get('p99')):>9} "
              f"{fmt(d.get('p999')):>9} {fmt(d.get('max')):>9}")


def report_metrics(path: str) -> None:
    last = None
    n = 0
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            last = json.loads(line)
            n += 1
    if last is None:
        print(f"{path}: empty")
        return
    print(f"\n== {path}: {n} snapshots, newest ts={last.get('ts')} "
          f"labels={last.get('labels')} ==")
    hists = last.get("histograms", {})
    if hists:
        hdr = (f"{'histogram':<28} {'count':>8} {'mean_ms':>9} "
               f"{'p50<=':>8} {'p95<=':>8} {'max':>9}")
        print(hdr)
        print("-" * len(hdr))
        for name, h in sorted(hists.items()):
            mean = h["sum"] / h["count"] if h["count"] else 0.0
            print(f"{name:<28} {h['count']:>8} {mean:>9.3f} "
                  f"{_hist_pct(h['buckets'], h['counts'], 0.5):>8} "
                  f"{_hist_pct(h['buckets'], h['counts'], 0.95):>8} "
                  f"{(h['max'] if h['max'] is not None else 0):>9.3f}")
    _report_quantiles(last.get("quantiles", {}))
    _report_quality(last.get("gauges", {}), last.get("counters", {}))
    gauges = last.get("gauges", {})
    if gauges:
        print(f"\n{'gauge':<44} {'value':>14}")
        print("-" * 59)
        for name, v in sorted(gauges.items()):
            print(f"{name:<44} {v:>14.4f}")
    counters = last.get("counters", {})
    if counters:
        print(f"\n{'counter':<44} {'value':>14}")
        print("-" * 59)
        for name, v in sorted(counters.items()):
            print(f"{name:<44} {v:>14}")


def merge_traces(objs, names=None) -> dict:
    """Stitch per-process trace objects into ONE Chrome/Perfetto trace.

    - Every file's events shift onto a single wall-clock timeline via
      its ``otherData.wall_anchor_ns`` (unix ns at that ring's ts 0);
      the earliest anchor becomes global ts 0. Files without an anchor
      (legacy exports) keep their local timeline at offset 0.
    - Each file keeps its own process track (pids colliding across
      files — in-process drills exporting multiple rings — are
      remapped), named ``host:pid (filename)``.
    - Flow arrows: an event whose ``args.parent`` matches another
      event's ``args.span`` gets a Chrome flow ``s``→``f`` pair (the
      RPC client→server hop the distributed trace context stamps), so
      Perfetto draws the request's path across process tracks.
    """
    names = names or [f"trace{i}" for i in range(len(objs))]
    anchors = []
    for obj in objs:
        od = obj.get("otherData") or {}
        anchors.append(int(od.get("wall_anchor_ns") or 0))
    known = [a for a in anchors if a]
    t0 = min(known) if known else 0
    merged = []
    used_pids = set()
    span_index = {}   # span id -> (pid, tid, ts)
    file_meta = []
    for i, obj in enumerate(objs):
        od = obj.get("otherData") or {}
        shift_us = (anchors[i] - t0) / 1e3 if anchors[i] else 0.0
        events = obj.get("traceEvents", obj
                         if isinstance(obj, list) else [])
        orig_pids = {e.get("pid", 0) for e in events}
        pid_map = {}
        for p in sorted(orig_pids):
            np_ = p
            while np_ in used_pids:
                np_ = (np_ or 1) + 100000
            pid_map[p] = np_
            used_pids.add(np_)
        label = (f"{od.get('host', '?')}:{od.get('pid', '?')} "
                 f"({os.path.basename(str(names[i]))})")
        for p in sorted(set(pid_map.values())):
            merged.append({"name": "process_name", "ph": "M", "pid": p,
                           "args": {"name": label}})
        file_meta.append({"file": str(names[i]), "label": label,
                          "wall_anchor_ns": anchors[i],
                          "shift_us": round(shift_us, 3),
                          "peer_offsets_ms": od.get("peer_offsets_ms",
                                                    {})})
        for e in events:
            e = dict(e)
            e["pid"] = pid_map.get(e.get("pid", 0), e.get("pid", 0))
            if "ts" in e:
                e["ts"] = e["ts"] + shift_us
            merged.append(e)
            a = e.get("args") or {}
            if e.get("ph") == "X" and a.get("span"):
                span_index[str(a["span"])] = (e["pid"], e.get("tid", 0),
                                              e["ts"])
    flows = []
    for e in merged:
        a = e.get("args") or {}
        parent = a.get("parent")
        if e.get("ph") != "X" or not parent:
            continue
        src = span_index.get(str(parent))
        if src is None:
            continue
        fid = f"{a.get('trace', '')}:{parent}"
        flows.append({"name": "rpc", "cat": "rpc", "ph": "s",
                      "id": fid, "pid": src[0], "tid": src[1],
                      "ts": src[2]})
        flows.append({"name": "rpc", "cat": "rpc", "ph": "f", "bp": "e",
                      "id": fid, "pid": e["pid"],
                      "tid": e.get("tid", 0), "ts": e["ts"]})
    return {"traceEvents": merged + flows,
            "displayTimeUnit": "ms",
            "otherData": {"merged_from": file_meta,
                          "flow_arrows": len(flows) // 2}}


def merge_files(paths, out_path: str) -> dict:
    objs = []
    for p in paths:
        with open(p) as f:
            objs.append(json.load(f))
    merged = merge_traces(objs, names=list(paths))
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(merged, f)
    os.replace(tmp, out_path)
    meta = merged["otherData"]
    print(f"merged {len(paths)} trace file(s) -> {out_path} "
          f"({len(merged['traceEvents'])} events, "
          f"{meta['flow_arrows']} flow arrows)")
    return merged


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", nargs="*", help="Chrome trace JSON "
                    "(FLAGS_trace_path output); several with --merge")
    ap.add_argument("--metrics", help="metrics JSONL "
                    "(FLAGS_metrics_path output)")
    ap.add_argument("--top", type=int, default=20,
                    help="max span rows (default 20)")
    ap.add_argument("--merge", metavar="OUT",
                    help="stitch the given trace files into ONE "
                         "Perfetto trace at OUT (wall-aligned process "
                         "tracks + cross-process flow arrows)")
    args = ap.parse_args(argv)
    if args.merge:
        if not args.trace:
            ap.error("--merge needs at least one input trace file")
        merge_files(args.trace, args.merge)
        report_trace(args.merge, args.top)
        return 0
    if not args.trace and not args.metrics:
        ap.error("pass a trace file and/or --metrics")
    for t in args.trace:
        report_trace(t, args.top)
    if args.metrics:
        report_metrics(args.metrics)
    return 0


if __name__ == "__main__":
    sys.exit(main())
