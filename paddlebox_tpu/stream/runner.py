"""StreamRunner: the sub-day sibling of the DayRunner pass loop.

Role of the streaming scenario production CTR actually runs (the
reference's day/pass loop driven at minute granularity): events land in
a log directory, become an incremental pass within
``FLAGS_stream_pass_window_s``, train through the UNCHANGED
``DayRunner.train_pass`` machinery — self-heal retry, rollback,
watchdog, deterministic replay — and publish a per-pass delta through
``checkpoint/protocol.py``'s donefile, which the PR-9/PR-11 serving
publishers already tail: a running PredictServer or fleet replica picks
up minute-fresh models with ZERO new serving code.

Freshness is a first-class metric: per pass, the age of its OLDEST
event (file mtime) at the moment the delta is acked servable lands in
the ``stream/event_to_servable_ms`` registry quantile digest — the
worst-case event→servable latency an SLO would bind. ``ack_fn`` lets
the caller define "servable" (e.g. block until a replica's publisher
applied the delta); the default acks at donefile publication, the
instant the delta became visible to every tailing publisher.

Day rollover: when the source carves a pass for a NEW day label, the
previous day closes through ``DayRunner.day_end`` — lifecycle shrink
(show/click decay, unseen-days TTL, min-show eviction), base dump,
donefile publish — so the store stays bounded under infinite traffic.

Replay purity: the runner's clock is injected (``clock=``) and only
read OUTSIDE the replayed training closure (the freshness ack is
publication metadata, never training state); graftlint's replay-purity
pass walks ``StreamRunner.*`` as a root set to keep it that way.
"""

from __future__ import annotations

import os
import time
from typing import Callable, Dict, List, Optional

from paddlebox_tpu.core import (faults, flags, incident, log, monitor,
                                quality, trace)
from paddlebox_tpu.stream.source import (PassManifest, StreamCursor,
                                         StreamSource)
from paddlebox_tpu.train.day_runner import DayRunner


class StreamRunner(DayRunner):
    """Drive a CTRTrainer from a growing event log at sub-day freshness."""

    def __init__(self, trainer, feed_config, output_root: str, *,
                 log_dir: str,
                 day_of: Optional[Callable[[str], str]] = None,
                 clock: Callable[[], float] = time.time,
                 ack_fn: Optional[Callable[[str, int], Optional[float]]]
                 = None,
                 **day_kwargs):
        # The streaming pass loop addresses data by manifest, not by
        # <data_root>/<day>/<split> — pipelining is per carved pass.
        day_kwargs.setdefault("pipeline_passes", False)
        super().__init__(trainer, feed_config, output_root, **day_kwargs)
        self._clock = clock
        self._ack_fn = ack_fn
        self.cursor = StreamCursor(
            os.path.join(output_root, "stream_cursor.json"))
        self.source = StreamSource(log_dir, day_of=day_of, clock=clock,
                                   consumed=self.cursor.consumed_files())
        self._current_day: Optional[str] = None
        # (day, pass_id) pairs the donefile already covers (pass_id 0 =
        # the day's base, i.e. day_end ran).
        self._published = {(r.day, r.pass_id)
                           for r in self.ckpt.records()}

    # -- resume ------------------------------------------------------------

    def resume(self) -> Optional[Dict[str, object]]:
        """Restart path: load the published model (DayRunner.recover),
        then replay every cursor manifest the donefile does NOT cover —
        the carved-but-unpublished tail a crash left behind. File→pass
        assignment comes from the durable cursor, so the replay trains
        exactly the events the killed process would have: none lost,
        none twice."""
        # Arm fault injection before any cursor/replay work — the
        # stream/* faultpoints fire before the first train_pass would
        # arm it (same reasoning as train_day's early init).
        faults.init_from_flags()
        point = self.recover()
        self._published = {(r.day, r.pass_id)
                           for r in self.ckpt.records()}
        replayed = 0
        for m in self.cursor.manifests:
            replayed += self._run_manifest(m)
        if replayed:
            log.vlog(0, "stream: resumed %d unpublished pass(es) from "
                     "the cursor", replayed)
        return point

    # -- the poll loop -----------------------------------------------------

    def poll_once(self, *, flush: bool = False) -> int:
        """One tail step: scan the log dir, durably carve ready passes,
        train each, publish each delta. Returns passes trained. Tests
        and the crash drill call this directly; ``run`` wraps it
        in the idle-sleep loop."""
        faults.init_from_flags()
        faults.faultpoint("stream/source_poll")
        with trace.span("stream/poll"):
            self.source.poll()
            protos = self.source.carve(flush=flush)
        manifests = [self.cursor.append(day, files, events, oldest)
                     for day, files, events, oldest in protos]
        trained = 0
        for m in manifests:
            trained += self._run_manifest(m)
        return trained

    def run(self, *, duration_s: float, flush_at_end: bool = True) -> int:
        """Tail the log for ``duration_s`` wall seconds (the example /
        soak entry point), sleeping ``FLAGS_stream_poll_s`` between
        empty polls. Returns total passes trained."""
        deadline = self._clock() + float(duration_s)
        total = 0
        while self._clock() < deadline:
            n = self.poll_once()
            total += n
            if n == 0:
                time.sleep(max(float(flags.flag("stream_poll_s")), 0.01))
        if flush_at_end:
            total += self.poll_once(flush=True)
        return total

    def end_day(self) -> int:
        """Explicitly close the current open day (end of a replayed log
        / operator-driven rollover): lifecycle shrink + base + publish
        via the shared DayRunner.day_end sequence."""
        if self._current_day is None:
            return 0
        day, self._current_day = self._current_day, None
        evicted = self.day_end(day)
        self._published.add((day, 0))
        return evicted

    # -- one manifest ------------------------------------------------------

    def _run_manifest(self, m: PassManifest) -> int:
        """Train one carved pass (idempotent: published manifests are
        skipped — the resume/crash-drill contract). Handles the day
        rollover BEFORE the first pass of a new day trains."""
        if self._current_day is not None and m.day != self._current_day:
            if (self._current_day, 0) not in self._published:
                self.day_end(self._current_day)
                self._published.add((self._current_day, 0))
        self._current_day = m.day
        if (m.day, m.pass_id) in self._published:
            return 0
        # One root trace context per carved pass (a no-op when tracing
        # is off): every training-write RPC of this pass — trainer push
        # → shard primary → synchronous backup forward — carries ONE
        # trace id, so a merged fleet trace shows the whole write path
        # of one incremental pass.
        # The carved manifest is the richest pass identity the quality
        # plane can get (event/file counts ride the quality_report) —
        # stamped BEFORE train_pass so the per-pass drift detection
        # over carved passes names the exact sub-day pass that drifted.
        quality.GLOBAL.set_pass_context(m.day, m.pass_id,
                                        events=int(m.events),
                                        files=len(m.files))
        # Same identity on the incident recorder: a bundle captured
        # mid-pass names the exact sub-day pass that was training.
        incident.set_context(day=m.day, pass_id=m.pass_id)
        with trace.use_context(trace.wire_context()), \
                trace.span("stream/pass", day=m.day, pass_id=m.pass_id,
                           files=len(m.files), events=m.events):
            self.train_pass(m.day, m.pass_id, list(m.files))
        # Delta published (train_pass's donefile write) — the window
        # between publication and the freshness ack: a kill here must
        # resume WITHOUT retraining the pass (the donefile covers it).
        faults.faultpoint("stream/delta_publish")
        self._published.add((m.day, m.pass_id))
        ack_ts = None
        if self._ack_fn is not None:
            ack_ts = self._ack_fn(m.day, m.pass_id)
        if ack_ts is None:
            ack_ts = self._clock()
        lat_ms = max(0.0, (float(ack_ts) - m.oldest_ts) * 1e3)
        monitor.observe_quantile("stream/event_to_servable_ms", lat_ms)
        monitor.add("stream/passes", 1)
        monitor.add("stream/events", int(m.events))
        log.vlog(0, "stream: %s pass %d (%d events, %d files) servable "
                 "in %.0f ms", m.day, m.pass_id, m.events, len(m.files),
                 lat_ms)
        return 1

    # -- freshness surface -------------------------------------------------

    def freshness_quantiles(self) -> Optional[Dict[str, float]]:
        """p50/p90/p99/p999 of event→servable ms (None before the first
        pass): the freshness an online deployment is judged by."""
        d = monitor.GLOBAL.quantile_digest("stream/event_to_servable_ms")
        return d.quantiles() if d is not None else None
