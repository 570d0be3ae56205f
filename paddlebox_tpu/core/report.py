"""PaddleBox-style pass report: one structured summary line per pass.

Role of ``PrintSyncTimer`` (``fleet/box_wrapper.h:395-420``): at every
pass boundary the reference prints the per-device stage timers
(read / pack / pull / fwd-bwd / push / sync) that attribute the pass's
wall time to pipeline stages. Here the same stage names are host-side
timers (the TPU step fuses pull/fwd-bwd/push into ONE jitted program, so
their device time cannot be split without adding syncs — the host-visible
halves carry the names instead; see OBSERVABILITY.md for the exact
mapping) and the report is one machine-parseable line:

    pass_report {"kind": "train", "steps": 13, "samples_per_s": ..., ...}

The emit also lands in the metric registry (counters/gauges + the
step-latency histogram feed happens at the call sites) and appends one
labeled snapshot line to the metrics JSONL when configured — one report
path for log line, registry, and exporter.
"""

from __future__ import annotations

import json
from typing import Any, Dict, Optional

from paddlebox_tpu.core import log, monitor, timers, trace

# Canonical stage-timer names (the PrintSyncTimer vocabulary). Every
# pass summary carries ALL of them — a stage the host could not observe
# this pass reports 0.0 rather than disappearing, so downstream tooling
# (tools/trace_report.py) sees a stable schema.
STAGES = ("read", "pack", "pull", "fwd_bwd", "push", "dispatch", "sync")

# Last emitted summaries, stashed for the incident flight recorder
# (core/incident.py): a bundle answers "what was the last pass doing"
# without scraping the log.
LAST_PASS_REPORT: Optional[Dict[str, Any]] = None
LAST_QUALITY_REPORT: Optional[Dict[str, Any]] = None


def stage_delta(group: "timers.TimerGroup",
                base_ms: Dict[str, float]) -> Dict[str, float]:
    """Per-pass stage ms from a cumulative TimerGroup: current snapshot
    minus the snapshot taken at pass start (the group is shared across
    passes, so the pass report must difference, not read raw)."""
    now = group.snapshot_ms()
    out = {s: round(now.get(s, 0.0) - base_ms.get(s, 0.0), 3)
           for s in STAGES}
    for name, ms in now.items():
        if name not in out:
            out[name] = round(ms - base_ms.get(name, 0.0), 3)
    return out


def emit_pass_report(kind: str, *, steps: int, samples: int,
                     wall_s: float, stage_ms: Dict[str, float],
                     stats: Optional[Dict[str, Any]] = None,
                     extra: Optional[Dict[str, Any]] = None
                     ) -> Dict[str, Any]:
    """Build + publish one per-pass summary. Returns the summary dict
    (callers may attach it to their stats).

    - logs ONE ``pass_report {json}`` line (the PrintSyncTimer moment)
    - bumps registry counters/gauges under ``pass/``
    - appends a labeled snapshot to the metrics JSONL (if configured)
    - drops a trace instant so the report is visible in the timeline
    """
    summary: Dict[str, Any] = {
        "kind": kind,
        "steps": int(steps),
        "samples": int(samples),
        "wall_s": round(wall_s, 4),
        "samples_per_s": round(samples / wall_s, 1) if wall_s > 0 else 0.0,
        "stage_ms": {s: round(float(stage_ms.get(s, 0.0)), 3)
                     for s in STAGES},
    }
    # Non-canonical timers (host_map, feed_pass, ...) ride along without
    # polluting the stable stage schema.
    other = {k: v for k, v in stage_ms.items() if k not in STAGES}
    if other:
        summary["other_ms"] = other
    for src in (stats or {}), (extra or {}):
        for k, v in src.items():
            if k not in summary:
                summary[k] = v

    reg = monitor.GLOBAL
    reg.add(f"pass/{kind}_passes", 1)
    reg.add(f"pass/{kind}_steps", int(steps))
    reg.add(f"pass/{kind}_samples", int(samples))
    reg.set_gauge(f"pass/{kind}_samples_per_s", summary["samples_per_s"])
    reg.set_gauge(f"pass/{kind}_wall_s", summary["wall_s"])
    for s in STAGES:
        reg.set_gauge(f"pass/{kind}_{s}_ms", summary["stage_ms"][s])
    if stats:
        # Model-health headline beside the systems stages: the shared
        # AUC sweep computes bucket_error / copc / ctr ratios every
        # pass — they land as gauges (and ride the summary via the
        # stats merge above) instead of being dropped on the floor.
        for k in ("loss", "auc", "bucket_error", "copc",
                  "actual_ctr", "predicted_ctr"):
            v = stats.get(k)
            if isinstance(v, (int, float)):
                reg.set_gauge(f"pass/{kind}_{k}", float(v))
        for k in ("dispatch_blocks", "host_syncs", "lookup_overflow",
                  "kernel_fallback", "kernel_hot_served",
                  "lookup_exchange_bytes"):
            v = stats.get(k)
            if isinstance(v, (int, float)):
                reg.set(f"pass/{kind}_{k}", int(v))
    # Pass-boundary breakdown (split build / fused end-begin, round 8):
    # end_ms / build_ms / feed_wait_ms / overlap_frac ride the summary
    # AND land as gauges so the JSONL exporter carries the overlap win.
    b = summary.get("boundary")
    if isinstance(b, dict):
        for k in ("end_ms", "build_ms", "feed_wait_ms", "overlap_frac",
                  "exchange_overlap_frac"):
            v = b.get(k)
            if isinstance(v, (int, float)):
                reg.set_gauge(f"pass/{kind}_boundary_{k}", float(v))
    dq = summary.get("dispatch_ms_quantiles")
    if isinstance(dq, dict):
        for k, v in dq.items():
            if k != "count" and isinstance(v, (int, float)):
                reg.set_gauge(f"pass/{kind}_dispatch_ms_{k}", float(v))

    line = json.dumps(summary, default=str)
    log.info("pass_report %s", line)
    trace.instant(f"pass_report/{kind}", steps=steps,
                  samples_per_s=summary["samples_per_s"])
    reg.flush_jsonl(labels={"event": "pass_report", "kind": kind})
    global LAST_PASS_REPORT
    LAST_PASS_REPORT = summary
    return summary


def emit_quality_report(kind: str, summary: Dict[str, Any]
                        ) -> Dict[str, Any]:
    """Publish one model-quality summary (core/quality.py) the same
    three ways the pass report goes out: ONE structured
    ``quality_report {json}`` log line beside ``pass_report``, a trace
    instant, and a labeled metrics-JSONL snapshot — so a COPC
    excursion or a dark slot is greppable, timeline-visible, and
    scrape-able through the same plane."""
    reg = monitor.GLOBAL
    reg.add("quality/reports", 1)
    line = json.dumps(summary, default=str)
    log.info("quality_report %s", line)
    trace.instant(f"quality_report/{kind}",
                  alarms=len(summary.get("alarms") or ()),
                  copc=summary.get("copc"))
    reg.flush_jsonl(labels={"event": "quality_report", "kind": kind})
    global LAST_QUALITY_REPORT
    LAST_QUALITY_REPORT = summary
    return summary


def init_telemetry_from_flags() -> None:
    """One-call arming of every telemetry plane from flags (trace path,
    metrics path, history sampler, alert engine). Idempotent and
    near-free when all are unset — the trainer/bench/serving entry
    points call it unconditionally."""
    trace.init_from_flags()
    monitor.init_from_flags()
    from paddlebox_tpu.core import alerts, timeseries
    timeseries.init_from_flags()
    alerts.init_from_flags()
