"""Online predict service: the CTRPredictor behind the typed wire.

Role of the serving deployment the reference pairs its training stack
with (an online service loads the per-pass xbox exports and answers CTR
requests while deltas stream in — the "realtime model update" half of
the README's pitch): a socket server owning one :class:`CTRPredictor`,
answering predict RPCs on raw svm-format lines and accepting live
base/delta updates between requests, over the same typed-frame protocol
as the PS and graph services (service loop/framing from
``distributed/rpc.py`` — no pickle, version-checked; trusted cluster
network).

Concurrent predict RPCs do not serialize on the device: handler threads
parse their lines and hand the rows to the shared
:class:`~paddlebox_tpu.serving.batcher.MicroBatcher`, which coalesces
everything waiting into ONE ragged device forward per batching window
and demuxes per-request probability slices back. Padding is masked
rows inside the packed batch — never synthesized svm lines — and the
predictor's internal lock gives every micro-batch one consistent model
version against live ``apply_update`` / publisher hot-swaps.
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional

import numpy as np

from paddlebox_tpu.core import (flags, monitor, quality, report,
                                timeseries, trace)
from paddlebox_tpu.core.quantiles import LogQuantileDigest
from paddlebox_tpu.data.parser import parse_lines
from paddlebox_tpu.distributed import rpc
from paddlebox_tpu.serving.batcher import MicroBatcher
from paddlebox_tpu.serving.predictor import CTRPredictor


class PredictServer(rpc.FramedRPCServer):
    """One predictor endpoint (role of a serving replica).

    ``watch_root`` (optional) points at a training day loop's checkpoint
    root: a :class:`~paddlebox_tpu.serving.publisher.DonefilePublisher`
    thread then tails its donefile and hot-swaps each newly published
    per-pass delta into the live predictor — the zero-downtime
    train→serve flow, no RPC required.
    """

    service_name = "serving"

    def __init__(self, endpoint: str, predictor: CTRPredictor, *,
                 watch_root: Optional[str] = None,
                 watch_table: str = "embedding",
                 replica_id: Optional[str] = None):
        self.predictor = predictor
        self.replica_id = replica_id or ""
        # Arm the telemetry sinks (trace/metrics paths) once per replica;
        # per-request cost is one cached-bool check when disabled.
        report.init_telemetry_from_flags()
        # Per-REPLICA registry beside the process-global one: a fleet
        # test/bench runs several replicas in one process, and the
        # router's cluster-stats fan-out must merge per-replica
        # snapshots, not N copies of the same global registry. Serving
        # counters bump both; the global keeps its existing meaning.
        self.metrics = monitor.Monitor()
        # Trend ring over the instance registry (core/timeseries.py):
        # registered with the global sampler, answered by the
        # metrics_history RPC — idle (never sampled) until the sampler
        # is armed.
        self.history = timeseries.history_for(
            self.metrics, label=f"replica:{self.replica_id}")
        # SLO layer: server-side predict latency quantile digest (the
        # log-bucketed sketch — sub-ms CPU predicts and multi-second
        # stalls both land within 1% relative error) + the
        # rotating window snapshots behind the throughput gauge. The
        # digest is per-replica state; the registry copy under
        # serving/predict_ms merges across replicas via
        # monitor.merge_snapshots.
        self._started = time.time()
        self._latency = LogQuantileDigest()
        self._lat_lock = threading.Lock()  # handlers run per-connection
        # Sliding-window throughput state: (anchor time, digest copy at
        # anchor). Rotated every FLAGS_serving_rps_window_s; the rate is
        # delta-counts over the previous anchor, so an idle replica
        # decays to 0 within two windows instead of reporting a stale
        # lifetime average.
        self._win_prev = (self._started, self._latency.copy())
        self._win_cur = (self._started, self._latency.copy())
        self._batcher = MicroBatcher(predictor, metrics=self.metrics)
        # Served-traffic calibration (core/quality.py): sampled
        # prediction logging keyed by request id + late label join —
        # labels trail through the stream tier's event log. Alarms and
        # gauges land in the instance registry too, so the fleet's
        # metrics_snapshot scrape shows THIS replica's model health.
        # Eagerly built (small fixed arrays): no handler-thread race;
        # sampling itself is off until FLAGS_quality_sample_rate > 0.
        self.quality = quality.ServingQuality(registries=(self.metrics,))
        self._publisher = None
        if watch_root is not None:
            from paddlebox_tpu.serving.publisher import DonefilePublisher
            self._publisher = DonefilePublisher(
                predictor, watch_root, table=watch_table)
            self._publisher.start()
        rpc.FramedRPCServer.__init__(self, endpoint)

    # -- throughput window -------------------------------------------------

    def _window_rps(self, now: float) -> float:
        """Requests/s over the sliding window: LogQuantileDigest.delta()
        count against the previous window anchor (callers hold
        _lat_lock)."""
        win = max(float(flags.flag("serving_rps_window_s")), 1e-3)
        if now - self._win_cur[0] >= win:
            self._win_prev = self._win_cur
            self._win_cur = (now, self._latency.copy())
        t0, base = self._win_prev
        return self._latency.delta(base).count / max(now - t0, 1e-9)

    # -- handlers ---------------------------------------------------------

    def handle_predict(self, req) -> np.ndarray:
        """Raw svm-format lines -> CTR probabilities [n_lines]. Requests
        beyond the predictor's feed batch_size are rejected (the caller
        splits; the micro-batcher coalesces many small requests, it
        does not split one huge one). ``degraded=True`` (the fleet
        router's SLO-shed path) packs and dispatches INLINE with
        HBM-hot-rows-only resolution — never coalesced with normal
        requests, whose batch would otherwise inherit the degraded
        lookup."""
        t0 = time.perf_counter()
        lines: List[str] = list(req["lines"])
        degraded = bool(req.get("degraded", False))
        feed = self.predictor.feed
        if len(lines) > feed.batch_size:
            raise ValueError(
                f"{len(lines)} lines exceed the serving batch size "
                f"{feed.batch_size} — split the request")
        n = len(lines)
        with trace.span("serving/predict", lines=n):
            # Real rows only: padding to the packed shape is masked
            # rows inside the batcher's bucketed pack — the old path
            # synthesized '0' svm lines and paid parse work to create
            # rows indistinguishable from real label-0 instances.
            instances = parse_lines(lines, feed)
            if degraded:
                from paddlebox_tpu.serving.batcher import pack_bucketed
                batch = pack_bucketed(instances, feed)
                out = np.asarray(
                    self.predictor.predict(batch, degraded=True)
                    [:len(instances)], np.float32)
                monitor.add("serving/degraded_rpcs", 1)
                self.metrics.add("serving/degraded_rpcs", 1)
            else:
                out = self._batcher.predict(instances)
        # Sampled calibration logging: a request carrying a rid may be
        # selected (crc32 hash, FLAGS_quality_sample_rate) — its
        # predictions wait in the bounded pending window for the late
        # label join (handle_labels).
        rid = req.get("rid")
        if rid is not None and float(
                flags.flag("quality_sample_rate")) > 0.0:
            self.quality.sample(str(rid), out)
        ms = (time.perf_counter() - t0) * 1e3
        monitor.add("serving/predict_rpcs", 1)
        monitor.add("serving/predict_lines", n)
        monitor.observe("serving/predict_ms", ms)
        monitor.observe_quantile("serving/predict_ms", ms)
        self.metrics.add("serving/predict_rpcs", 1)
        self.metrics.add("serving/predict_lines", n)
        # Instance-registry digest too: the per-replica history ring
        # computes window p99s from the registry it samples.
        self.metrics.observe_quantile("serving/predict_ms", ms)
        now = time.time()
        with self._lat_lock:
            self._latency.observe(ms)
            rps = self._window_rps(now)
        # SLO check (FLAGS_serving_slo_p99_ms): each breaching RPC is a
        # counted violation — the p99 the operator reads from
        # handle_stats then says how much margin remains.
        slo = float(flags.flag("serving_slo_p99_ms"))
        if slo > 0 and ms > slo:
            monitor.add("slo/violations", 1)
            self.metrics.add("slo/violations", 1)
        monitor.set_gauge("serving/throughput_rps", rps)
        self.metrics.set_gauge("serving/throughput_rps", rps)
        return out

    def handle_apply_delta(self, req) -> int:
        """Live model refresh from a delta export directory (the online
        update path — serving_online_update's surface over the wire).
        Routed through ``apply_update_export`` so flat, sharded, and
        dim-grouped delta roots all land."""
        kind = str(req.get("kind", "delta"))
        with trace.span("serving/apply_delta", path=req["path"]):
            # kind='xbox' applies a full serving-format BASE export —
            # the canary controller's staging/promote path (autopilot);
            # the default 'delta' stays the per-pass online update.
            n_new = self.predictor.apply_update_export(
                req["path"], req.get("table", "embedding"), kind)
        monitor.add("serving/delta_rpcs", 1)
        return int(n_new)

    def handle_rollback_to(self, req) -> int:
        """Re-apply a prior published record (autopilot canary rollback
        / operator reverse gear): routes through the publisher's
        ``rollback_to`` when this replica tails a donefile — marking
        the record seen so the tail will not re-apply it — else applies
        the export directly. Either way bumps
        ``serving/hotswap_rollbacks``. Returns rows written."""
        from paddlebox_tpu.checkpoint.protocol import DoneRecord
        rec = DoneRecord(str(req["day"]), int(req.get("key", 0)),
                         req["path"], int(req.get("pass_id", 0)))
        table = req.get("table", "embedding")
        with trace.span("serving/rollback_to", path=rec.path):
            if self._publisher is not None:
                return int(self._publisher.rollback_to(rec))
            kind = "xbox" if rec.pass_id == 0 else "delta"
            n_new = self.predictor.apply_update_export(
                rec.path, table, kind)
            monitor.add("serving/hotswap_rollbacks", 1)
            return int(n_new)

    def handle_labels(self, req) -> dict:
        """Late labels for a sampled predict (``rid`` + ``labels`` in
        request order): joins against the pending prediction log and
        feeds the served-traffic COPC/calibration window. An expired
        or never-sampled rid is a counted miss, never an error — the
        label feed (the stream tier's event log) trails serving by
        minutes and may replay."""
        joined = self.quality.join(
            str(req["rid"]), np.asarray(req["labels"], np.float64))
        return {"joined": bool(joined),
                "pending": int(self.quality.pending())}

    def handle_stats(self, req) -> dict:
        snap = monitor.snapshot()
        # Per-REPLICA counters come from the instance registry: with N
        # replicas in one process (fleet tests/bench) the global would
        # conflate them, and the router's SLO admission window must see
        # THIS replica's violations, not the fleet's.
        mine = self.metrics.snapshot()
        now = time.time()
        uptime = now - self._started
        with self._lat_lock:
            lat = {k: (round(v, 3) if v is not None else None)
                   for k, v in self._latency.quantiles().items()}
            n_lat = self._latency.count
            rps = self._window_rps(now)
        return {"keys": int(self.predictor.num_keys),
                "dim": int(self.predictor._dim),
                "replica_id": self.replica_id,
                "predict_rpcs": int(mine.get("serving/predict_rpcs", 0)),
                "predict_lines": int(mine.get("serving/predict_lines",
                                              0)),
                "degraded_rpcs": int(mine.get("serving/degraded_rpcs",
                                              0)),
                "delta_rpcs": int(snap.get("serving/delta_rpcs", 0)),
                "uptime_s": round(uptime, 3),
                # Server-side latency quantiles + the SLO they are read
                # against (client predict keeps its OWN digest, so
                # server time vs wire time separate cleanly).
                "latency_ms": lat,
                "latency_count": n_lat,
                # Sliding-window rate (NOT lifetime count / lifetime
                # uptime — that decays forever on an idle replica).
                "throughput_rps": round(rps, 3),
                "batches": int(mine.get("serving/batches", 0)),
                "batch_fill_frac": float(
                    self.metrics.get_gauge("serving/batch_fill_frac")),
                "hotswap_applied": int(
                    snap.get("serving/hotswap_applied", 0)),
                "slo_p99_ms": float(flags.flag("serving_slo_p99_ms")),
                "slo_violations": int(mine.get("slo/violations", 0)),
                # Process-level conn health (global registry: reconnect/
                # retry totals of every conn this process owns) — the
                # failover-blip drills assert the retry budget actually
                # consumed through the stats surface.
                "rpc_reconnects": int(snap.get("rpc/reconnects", 0)),
                "rpc_retries": int(snap.get("rpc/retries", 0)),
                # Model health of THIS replica (served-traffic sampled
                # calibration): total quality alarms raised here.
                "quality_alarms": int(sum(
                    v for k, v in mine.items()
                    if k.startswith("quality/alarms/")))}

    def handle_metrics_snapshot(self, req) -> dict:
        """This replica's labeled ``snapshot_all()`` (instance registry
        + the per-replica latency digest injected under quantiles) —
        what the fleet router's ``handle_stats`` fan-out merges with
        ``monitor.merge_snapshots`` into one cluster view."""
        out = self.metrics.snapshot_all(
            labels={"replica": self.replica_id,
                    "endpoint": self.endpoint})
        with self._lat_lock:
            out["quantiles"]["serving/predict_ms"] = \
                self._latency.to_dict()
        return out

    def handle_metrics_history(self, req) -> dict:
        """This replica's trend ring (instance registry) — the
        per-replica half of the fleet_top sparkline pane."""
        return self.history.to_dict(window_s=req.get("window_s"),
                                    last_n=req.get("last_n"))

    def handle_stop(self, req) -> bool:
        self.stop()
        return True

    def stop(self) -> None:
        if self._publisher is not None:
            self._publisher.stop()
            self._publisher = None
        self._batcher.close()
        rpc.FramedRPCServer.stop(self)


class PredictClient:
    """Blocking client for one serving endpoint — a replica directly,
    or a :class:`~paddlebox_tpu.serving.router.FleetRouter` (same wire
    protocol; the router's replies carry a ``degraded`` flag surfaced
    via :attr:`last_degraded`).

    ``router`` (optional) names a fleet router endpoint used as a
    TOPOLOGY resolver for a direct-to-replica client: when an
    idempotent retry has to reconnect, the client first re-resolves its
    endpoint through the router's current topology epoch — so a
    predict retried after a replica eject lands on a live replica
    instead of burning the whole retry deadline reconnecting to the
    dead one (the retry loop used to re-resolve against the fixed
    endpoint it was constructed with)."""

    def __init__(self, endpoint: str, timeout: float = 60.0, *,
                 router: Optional[str] = None):
        # predict/stats are pure reads: a serving blip reconnects and
        # retries them under the rpc retry flags; apply_delta/stop are
        # NOT idempotent and surface connection errors to the caller.
        self._router_ep = router
        self._router_conn: Optional[rpc.FramedRPCConn] = None
        self._topology_epoch = -1
        self._conn = rpc.FramedRPCConn(
            endpoint, timeout=timeout, service_name="serving",
            idempotent=("predict", "stats"),
            resolve=(self._resolve_endpoint if router else None))
        # End-to-end predict latency (RPC round-trip included): diffing
        # these quantiles against the server's handle_stats latency_ms
        # separates server time from wire time per percentile.
        self._latency = LogQuantileDigest()
        self.last_degraded = False
        self.last_replica: Optional[str] = None
        # Per-hop decomposition of the newest predict: the reply's
        # server share (router or replica handler wall) vs the client-
        # observed remainder (wire + connect), and — through a router —
        # the router's own hop split (route/wire/replica-server ms).
        self.last_server_ms: Optional[float] = None
        self.last_wire_ms: Optional[float] = None
        self.last_hop: Optional[dict] = None

    def _resolve_endpoint(self, current: str) -> str:
        """Reconnect-time hook: ask the router which replicas serve
        NOW; keep the current endpoint while it is still listed, else
        move to a live one (hashed by client identity so a fleet of
        retrying clients spreads instead of stampeding one replica)."""
        try:
            if self._router_conn is None:
                self._router_conn = rpc.FramedRPCConn(
                    self._router_ep, timeout=10.0,
                    service_name="fleet-router",
                    idempotent=("topology",))
            topo = self._router_conn.call("topology")
        except (OSError, ConnectionError, RuntimeError):
            return current  # router unreachable: retry where we were
        self._topology_epoch = int(topo.get("epoch", -1))
        live = [r["endpoint"] for r in topo.get("replicas", ())
                if r.get("state") == "healthy"]
        if not live:
            return current
        if current in live:
            return current
        monitor.add("serving/client_reresolves", 1)
        return live[hash(id(self)) % len(live)]

    def predict(self, lines: List[str], *,
                rid: Optional[str] = None) -> np.ndarray:
        # The wire serializes str natively (utf-8 frames) — no
        # per-line encode/decode round-trip. ``rid`` tags the request
        # for sampled calibration logging on the replica (late labels
        # follow via send_labels) — direct-replica clients only; the
        # router rebuilds its forwarded request without it.
        t0 = time.perf_counter()
        kwargs = {"lines": list(lines)}
        if rid is not None:
            kwargs["rid"] = str(rid)
        out = self._conn.call("predict", **kwargs)
        if isinstance(out, dict):
            # Router reply: probabilities + routing metadata (degraded
            # = the SLO-shed hot-rows-only path answered; hop = the
            # router's route/wire/replica-server decomposition).
            self.last_degraded = bool(out.get("degraded", False))
            self.last_replica = out.get("replica")
            self.last_hop = out.get("hop")
            out = out["probs"]
        else:
            self.last_degraded = False
            self.last_replica = None
            self.last_hop = None
        total_ms = (time.perf_counter() - t0) * 1e3
        self._latency.observe(total_ms)
        # The reply's _server_ms (every framed reply carries it) lets
        # the client attribute its observed latency: wire share = total
        # minus the peer's handler wall.
        self.last_server_ms = self._conn.last_server_ms
        self.last_wire_ms = self._conn.last_wire_ms
        if self.last_wire_ms is not None:
            monitor.observe_quantile("serving/client_wire_ms",
                                     self.last_wire_ms)
        return out

    def latency_quantiles(self) -> dict:
        """Client-observed end-to-end predict latency (ms): p50/p90/
        p99/p999 + count — the wire-inclusive twin of the server's
        ``stats()['latency_ms']``."""
        out = {k: (round(v, 3) if v is not None else None)
               for k, v in self._latency.quantiles().items()}
        out["count"] = self._latency.count
        return out

    def send_labels(self, rid: str, labels) -> dict:
        """Deliver a sampled request's late labels (the stream tier's
        event log catching up with served traffic) for the replica's
        prediction+label calibration join."""
        return self._conn.call("labels", rid=str(rid),
                               labels=[float(v) for v in labels])

    def apply_delta(self, path: str, table: str = "embedding",
                    kind: str = "delta") -> int:
        return self._conn.call("apply_delta", path=path, table=table,
                               kind=kind)

    def rollback_to(self, day: str, path: str, *, key: int = 0,
                    pass_id: int = 0, table: str = "embedding") -> int:
        """Re-apply a prior published record on the replica (the
        autopilot's canary-rollback actuator)."""
        return self._conn.call("rollback_to", day=str(day), path=path,
                               key=int(key), pass_id=int(pass_id),
                               table=table)

    def stats(self) -> dict:
        return self._conn.call("stats")

    def stop_server(self) -> None:
        try:
            self._conn.call("stop")
        except (RuntimeError, OSError, ConnectionError):
            pass

    def close(self) -> None:
        self._conn.close()
        if self._router_conn is not None:
            self._router_conn.close()
            self._router_conn = None
