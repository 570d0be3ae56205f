"""Typed, env-settable flag registry.

Role of the reference's gflags config core (``paddle/fluid/platform/flags.cc``:
95 exported ``FLAGS_*`` flags, PaddleBox block at ``flags.cc:956-1007``) and the
python ``get_flags``/``set_flags`` API
(``python/paddle/fluid/framework.py`` get_flags/set_flags).

Flags are declared with :func:`define_flag`, may be overridden by environment
variables named ``FLAGS_<name>`` (checked at first read), and are readable /
settable at runtime via :func:`get_flags` / :func:`set_flags`.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Union


class FlagError(Exception):
    pass


def _parse_bool(s: str) -> bool:
    v = s.strip().lower()
    if v in ("1", "true", "yes", "on"):
        return True
    if v in ("0", "false", "no", "off"):
        return False
    raise FlagError(f"cannot parse {s!r} as bool")


def _parse_int(s: str) -> int:
    s = s.strip()
    try:
        # Decimal first so zero-padded values ("08") parse; fall back to
        # base-0 for hex/octal/binary literals ("0x10").
        return int(s, 10)
    except ValueError:
        return int(s, 0)


_PARSERS: Dict[type, Callable[[str], Any]] = {
    bool: _parse_bool,
    int: _parse_int,
    float: float,
    str: lambda s: s,
}


def _parse(ftype: type, raw: str, name: str) -> Any:
    try:
        return _PARSERS[ftype](raw)
    except (ValueError, FlagError) as e:
        raise FlagError(
            f"cannot parse {raw!r} as {ftype.__name__} for flag {name!r}: {e}"
        ) from None


@dataclasses.dataclass
class _Flag:
    name: str
    type: type
    default: Any
    help: str
    value: Any = None
    # Whether an explicit set_flags / env override has happened.
    explicit: bool = False
    env_checked: bool = False


class FlagRegistry:
    """Process-global registry of typed flags with env overrides."""

    def __init__(self, env_prefix: str = "FLAGS_"):
        self._flags: Dict[str, _Flag] = {}
        self._lock = threading.RLock()
        self._env_prefix = env_prefix

    def define(self, name: str, default: Any, help: str = "",
               type: Optional[type] = None) -> None:
        with self._lock:
            if name in self._flags:
                raise FlagError(f"flag {name!r} already defined")
            ftype = type if type is not None else builtins_type(default)
            if ftype not in _PARSERS:
                raise FlagError(f"unsupported flag type {ftype} for {name!r}")
            self._flags[name] = _Flag(name=name, type=ftype, default=default,
                                      value=default, help=help)

    def _resolve_env(self, f: _Flag) -> None:
        if f.env_checked:
            return
        env_name = self._env_prefix + f.name
        raw = os.environ.get(env_name)
        if raw is not None and not f.explicit:
            # Parse before marking checked: a malformed env value raises
            # FlagError on every read rather than silently degrading to the
            # default after the first failure.
            f.value = _parse(f.type, raw, f.name)
            f.explicit = True
        f.env_checked = True

    def get(self, name: str) -> Any:
        with self._lock:
            f = self._require(name)
            self._resolve_env(f)
            return f.value

    def set(self, name: str, value: Any) -> None:
        with self._lock:
            f = self._require(name)
            if isinstance(value, str) and f.type is not str:
                value = _parse(f.type, value, name)
            if not isinstance(value, f.type) and f.type is float and isinstance(value, int):
                value = float(value)
            if not isinstance(value, f.type):
                raise FlagError(
                    f"flag {name!r} expects {f.type.__name__}, got "
                    f"{type(value).__name__}")
            f.value = value
            f.explicit = True
            f.env_checked = True

    def _require(self, name: str) -> _Flag:
        if name not in self._flags:
            raise FlagError(f"unknown flag {name!r}")
        return self._flags[name]

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._flags)

    def describe(self, name: str) -> str:
        with self._lock:
            f = self._require(name)
            return f.help

    def defaults(self) -> Dict[str, Any]:
        """name -> declared default (not the live value)."""
        with self._lock:
            return {n: f.default for n, f in self._flags.items()}

    def validate_all(self) -> List[str]:
        """Every default must round-trip through its own env parser —
        ``_parse(type, str(default)) == default`` — so a bad default
        fails statically (graftlint's flag-hygiene pass calls this at
        review time) instead of at the first env override. Returns a
        list of error strings; empty = all defaults sound."""
        errors: List[str] = []
        with self._lock:
            for f in self._flags.values():
                if not isinstance(f.default, f.type) or (
                        f.type is not bool
                        and isinstance(f.default, bool)):
                    errors.append(
                        f"flag {f.name!r}: default {f.default!r} is "
                        f"{type(f.default).__name__}, declared "
                        f"{f.type.__name__}")
                    continue
                try:
                    rt = _parse(f.type, str(f.default), f.name)
                except FlagError as e:
                    errors.append(
                        f"flag {f.name!r}: default {f.default!r} does "
                        f"not parse under its env parser: {e}")
                    continue
                if rt != f.default:
                    errors.append(
                        f"flag {f.name!r}: default {f.default!r} "
                        f"round-trips to {rt!r} — an env override of "
                        "the documented default would change behavior")
        return errors


def builtins_type(v: Any) -> type:
    if isinstance(v, bool):
        return bool
    if isinstance(v, int):
        return int
    if isinstance(v, float):
        return float
    if isinstance(v, str):
        return str
    raise FlagError(f"cannot infer flag type from {v!r}")


GLOBAL = FlagRegistry()


def define_flag(name: str, default: Any, help: str = "",
                type: Optional[type] = None) -> None:
    GLOBAL.define(name, default, help, type)


def get_flags(names: Union[str, Sequence[str]]) -> Dict[str, Any]:
    """Read one or many flags; mirrors paddle's ``get_flags`` signature."""
    if isinstance(names, str):
        names = [names]
    return {n: GLOBAL.get(n) for n in names}


def set_flags(values: Dict[str, Any]) -> None:
    """Set many flags; mirrors paddle's ``set_flags`` signature."""
    for k, v in values.items():
        GLOBAL.set(k, v)


def validate_all() -> List[str]:
    """Round-trip every registered default through its env parser (see
    :meth:`FlagRegistry.validate_all`). Called by graftlint's
    flag-hygiene pass and tests/test_core.py."""
    return GLOBAL.validate_all()


def pallas_kernels_enabled() -> bool:
    """True when auto-selection may pick a Pallas kernel: on the TPU
    backend. One predicate for every kernel gate (lookup scatter, flash
    attention, seqpool-CVM)."""
    import jax
    return jax.default_backend() == "tpu"


def kernel_mode(kernels: str) -> Dict:
    """How a dense stack's ``kernels`` setting resolves: "auto" the Pallas
    kernels on a TPU and their XLA references elsewhere, "interpret" the
    kernels through the Pallas interpreter (tests), "xla" the references.
    ``name`` is what ``note_kernel`` records."""
    if kernels not in ("auto", "interpret", "xla"):
        raise ValueError(f"unknown kernels mode {kernels!r}; choose "
                         "from 'auto', 'interpret', 'xla'")
    interpret = kernels == "interpret"
    use = interpret or (kernels == "auto" and pallas_kernels_enabled())
    return {"use_pallas": use, "interpret": interpret,
            "name": "interpret" if interpret else "pallas" if use
            else "xla"}


# What each kernel dispatch site actually selected, recorded while its
# caller traces: 'auto' resolves from the backend, and some sites give
# way to XLA on their own (a fused record wider than one lane tile), so
# the flag value does not say what ran. chip_smoke.py and the benchmark's
# runners report this table instead of the flags.
_resolved_kernels: Dict[str, set] = {}
_resolved_lock = threading.Lock()


def note_kernel(site: str, resolved: str) -> None:
    """Record that dispatch ``site`` selected ``resolved`` ('pallas',
    'interpret', 'xla', or 'xla:<why>' for a site-specific give-way)."""
    with _resolved_lock:
        _resolved_kernels.setdefault(site, set()).add(resolved)


def resolved_kernels(reset: bool = False) -> Dict[str, List[str]]:
    """site -> every mode it resolved to since process start (or the
    last ``reset=True`` read). A jitted caller records at trace time
    only, so read it after the first call of the program of interest."""
    with _resolved_lock:
        out = {k: sorted(v) for k, v in sorted(_resolved_kernels.items())}
        if reset:
            _resolved_kernels.clear()
    return out


def compilation_cache_dir() -> str:
    """The persistent compile cache of a chip entry point (chip_smoke.py,
    benchmarks/run.py): ``JAX_COMPILATION_CACHE_DIR`` where the
    environment sets it — then nothing is set in code — and otherwise
    ``.jax_cache`` at the root of this checkout. The path is part of the
    cache key, so it is never derived from a temp dir, a pid or the time.
    Call before the first compile; tests never call it, so CPU
    executables (which carry machine-feature stamps) are not cached.
    Returns the directory."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if d:
        return d
    d = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = d
    jax = sys.modules.get("jax")
    if jax is not None:
        # jax read its config from the environment at import.
        jax.config.update("jax_compilation_cache_dir", d)
    return d


def flag(name: str) -> Any:
    """Scalar read shorthand used on hot paths."""
    return GLOBAL.get(name)


# ---------------------------------------------------------------------------
# Built-in flags. These mirror the *roles* of the reference's PaddleBox flag
# block (``platform/flags.cc:956-1007``) re-expressed for the TPU runtime.
# ---------------------------------------------------------------------------

define_flag("v", 0, "global VLOG verbosity level (role of glog FLAGS_v)")
define_flag("check_nan_inf", False,
            "scan train-step outputs for NaN/Inf and abort the pass "
            "(role of FLAGS_check_nan_inf + nan_inf_utils_detail)")
define_flag("embedding_shard_slack", 1.3,
            "over-allocation factor for per-shard bucket capacity in the "
            "sparse pull/push all-to-all (static-shape padding headroom)")
define_flag("embedding_dedup", True,
            "merge duplicate ids BEFORE the pull/push all-to-all: only the "
            "first occurrence of each id consumes a bucket cell and "
            "duplicate grads sum into that cell pre-exchange (role of "
            "dedup_keys_and_fillidx + dynamic_merge_grad, heter_comm.h:69,"
            "192); hot keys can no longer overflow a shard bucket")
define_flag("embedding_auto_capacity", False,
            "size the pull/push bucket capacity from the MEASURED "
            "per-shard unique-id maximum of each pass's first batch "
            "(x shard slack, pow2-bucketed so steady-state passes reuse "
            "the compiled step) instead of the n-based binomial bound — "
            "removes the unique_frac guesswork on duplicate-heavy data; "
            "a later batch exceeding the measured headroom degrades to "
            "counted drops, surfaced by lookup_overflow")
define_flag("embedding_unique_frac", 1.0,
            "expected unique fraction of per-device ids, used to size the "
            "per-shard bucket capacity when embedding_dedup is on (1.0 = "
            "assume all unique, always safe; CTR batches typically dedup "
            "2-4x, so 0.5 halves the all-to-all bytes). Overflowing ids "
            "degrade to counted drops, never corruption")
define_flag("trainer_prefetch_depth", 2,
            "bounded queue depth for the train-pass host-map producer "
            "thread (batches packed ahead of the device)")
define_flag("trainer_steps_per_dispatch", 1,
            "fuse K train/eval steps into ONE scanned XLA dispatch "
            "(lax.scan megastep): the pass loop pays one host dispatch "
            "and at most one host sync per K steps instead of per step. "
            "1 = per-step dispatch (legacy behavior); "
            "dense_sync_mode='async' (host dense table needs per-step "
            "pull/push) and FLAGS_profile_trainer (per-step timing) "
            "force 1 with a logged note")
define_flag("embedding_exchange_dtype", "f32",
            "wire dtype of the sparse pull-reply and push-gradient "
            "all_to_all payloads: 'f32' (exact, default), 'bf16' "
            "(halves the ICI exchange bytes on top of dedup — "
            "EQuARX-style reduced-precision exchange; accumulation and "
            "the table stay f32), or 'int8' (quarters them: symmetric "
            "per-block quantization with f32 scales riding a second "
            "small all_to_all — block width embedding_quant_block; "
            "grads still merge sender-side in f32 and widen back "
            "before the owner-side accumulate). Row/request exchanges "
            "stay int32 either way")
define_flag("padbox_max_shuffle_wait_count", 16,
            "max concurrent sends per rank in the cross-node shuffle "
            "exchange (flow-control window — role of "
            "FLAGS_padbox_max_shuffle_wait_count; transport.py)")
define_flag("xbox_quant_bits", 0,
            "xbox serving-export embedding quantization: 0 = float32, "
            "8/16 = symmetric per-row int8/int16 with an f32 scale "
            "(role of the reference's quantized pull values, "
            "fused_seqpool_cvm_op.cu:247 quant_ratio — applied at the "
            "export boundary; w and the serving math stay float)")
define_flag("flash_block_q", 512,
            "flash-attention q-tile rows (Pallas kernel); chosen for "
            "VMEM residency, never swept on a chip — override via "
            "FLAGS_flash_block_q without touching call sites")
define_flag("flash_block_k", 512,
            "flash-attention k-tile columns (see flash_block_q)")
define_flag("sparse_scatter_kernel", "auto",
            "push-side scatter-accumulate backend: 'auto' (Pallas sorted "
            "kernel on TPU, XLA scatter elsewhere), 'pallas', 'interpret' "
            "(Pallas interpreter — tests), or 'xla'")
define_flag("sparse_gather_kernel", "auto",
            "pull-side table-row gather backend: 'auto' (Pallas sorted-"
            "stream kernel on TPU, XLA gather elsewhere), 'pallas', "
            "'interpret' (Pallas interpreter — tests), or 'xla'; the "
            "kernel shares one argsort per width group with the push "
            "scatter (embedding/lookup.py compute_bucketing)")
define_flag("pass_split_build", True,
            "device-tier split-key early build: gather the next pass's "
            "NOT-shared rows (and insert its unseen keys) from the "
            "resident store WHILE the active pass trains — only the "
            "shared-key remainder waits for the write-back (role of the "
            "double-buffered build threads, ps_gpu_wrapper.cc:907, "
            "extended to the HBM tier). False = the r04 serial build "
            "(the whole gather waits on end_pass)")
define_flag("pass_boundary_fuse", "auto",
            "compile the pass boundary — previous pass's end_pass "
            "scatter + next pass's shared-remainder gather — into ONE "
            "jitted device program: 'auto'/'on' fuse whenever a split "
            "early build is ready at end_pass (one PJRT dispatch "
            "crosses the host link per boundary instead of two), 'off' "
            "keeps the "
            "two-dispatch boundary (scatter, then merge in the builder)")
define_flag("keymap_lookup_threads", 0,
            "worker threads sharding the per-batch feasign->row keymap "
            "lookup in the NUMPY fallback (searchsorted releases the "
            "GIL, so threads genuinely parallelize the ~426K-id batch "
            "map); 0 = auto (min(4, cores/2) for batches >= 64K ids, "
            "single-threaded below). The native keymap parallelizes "
            "internally and ignores this")
define_flag("trainer_map_ahead", True,
            "run the host keymap lookup of batch i+1 on a dedicated "
            "worker while the prefetch producer packs + transfers "
            "batch i — takes the CopyKeys host map off the prefetch "
            "critical path entirely (it was already off the DEVICE "
            "path via the producer thread). False = map inline in the "
            "producer (r07 behavior)")
define_flag("ingest_workers", 0,
            "worker PROCESSES for dataset load: file blocks parse into "
            "ColumnarChunk CSR arrays in child processes (native C++ "
            "parser, or the vectorized numpy bulk parse when no native "
            "lib) and hand off through zero-copy shared-memory frames — "
            "the GIL-bound thread-reader path cannot use more than one "
            "core for the python parse. 0 (default) = the in-process "
            "thread reader; ignored when an instance-scoped parser_fn "
            "is set (closures don't cross process boundaries)")
define_flag("ingest_file_retries", 1,
            "times a file whose ingest worker DIED mid-parse (SIGKILL/"
            "OOM) is requeued onto a fresh worker before the load fails; "
            "chunks commit only at file completion, so a retry never "
            "duplicates rows. Worker-raised errors (bad data, failing "
            "pipe_command) are never retried — they would fail again")
define_flag("ingest_key_runs", True,
            "dedup each loaded chunk's keys into per-slot sorted runs "
            "DURING ingest and unite them as they arrive (a helper "
            "thread of the load merges runs of like size, the way a "
            "log-structured tree compacts), so every slot's key set is "
            "whole when the last chunk is in and pass_keys() only "
            "unites the slots asked for, instead of one end-of-load "
            "sort over every id. False = the r02 behavior (np.unique at "
            "feed time); results are bit-identical either way")
define_flag("wuauc_spill_records", 4_000_000,
            "per-user-AUC raw records held in RAM before spilling to "
            "uid-hash bucket files on disk (bounds eval-pass host memory; "
            "role of the WuAucMetricMsg shuffle/sort spill)")
define_flag("auc_num_buckets", 1 << 20,
            "prediction histogram buckets for exact distributed AUC "
            "(role of BasicAucCalculator _table size, metrics.cc:33)")
define_flag("profile_trainer", False,
            "per-op/per-stage timing in the trainer hot loop "
            "(role of TrainFilesWithProfiler)")
define_flag("trace_path", "",
            "write a chrome://tracing / Perfetto-loadable span trace to "
            "this path (empty = tracing off, the default; spans wrap "
            "host stage/dispatch/fetch boundaries only — never ops "
            "inside the jitted step). Exported at process exit and on "
            "core.trace.export()")
define_flag("trace_ring_events", 65536,
            "bounded ring-buffer capacity of the span tracer (oldest "
            "events drop first; bounds host memory on multi-hour runs "
            "and sizes the stall-forensics tail)")
define_flag("metrics_path", "",
            "append metric-registry snapshots (counters / gauges / "
            "histograms) as JSON lines to this path (empty = exporter "
            "off, the default). One line per pass report plus the "
            "periodic flush thread")
define_flag("metrics_flush_interval_s", 30.0,
            "period of the metrics JSONL background flush thread "
            "(<= 0 disables the thread; pass reports still append)")
define_flag("fault_spec", "",
            "deterministic fault-injection spec: ';'-separated "
            "'<site>[:hit=N][:times=M]:<raise=Exc|delay_ms=X|kill[=SIG]>'"
            " clauses (empty = injection off, the default — faultpoints "
            "are one cached-bool no-ops). See core/faults.py and "
            "ROBUSTNESS.md")
define_flag("pass_max_retries", 2,
            "max pass-level retries after a TRANSIENT train_pass failure "
            "(IO/connection/timeout/stall): each retry cancels pending "
            "builds, rolls the sparse store + dense state back to the "
            "last published record, and replays the pass — bit-identical "
            "to an unfailed run. Fatal errors (bad data, NaN loss, code "
            "bugs) never retry. 0 disables the self-healing loop")
define_flag("pass_retry_backoff_s", 0.5,
            "base of the capped exponential backoff between pass retries "
            "(sleep = base * 2^(attempt-1), capped by "
            "pass_retry_backoff_max_s)")
define_flag("pass_retry_backoff_max_s", 30.0,
            "cap on the pass-retry backoff sleep")
define_flag("stall_timeout_s", 0.0,
            "abort the current pass when the training heartbeat "
            "(per-block dispatch progress) stalls for this many seconds: "
            "stall forensics (all-thread stacks + trace ring tail) land "
            "in the log and StallError is raised in the training thread "
            "so the pass retries through the normal rollback machinery. "
            "<= 0 disables (default)")
define_flag("rpc_max_retries", 3,
            "max reconnect-and-retry attempts for IDEMPOTENT "
            "FramedRPCConn methods after a connection failure "
            "(pull/stats-class reads — the caller declares which methods "
            "are idempotent); non-idempotent methods never retry (the "
            "request may have executed)")
define_flag("rpc_retry_backoff_s", 0.05,
            "base of the capped exponential backoff between RPC retries "
            "(sleep = base * 2^(attempt-1), capped at 2s)")
define_flag("serving_slo_p99_ms", 0.0,
            "serving predict-latency SLO target in ms: every predict RPC "
            "whose server-side latency exceeds it bumps the "
            "slo/violations counter, and handle_stats reports the "
            "p50/p90/p99/p999 latency quantiles against it so the "
            "operator reads margin, not just breaches. <= 0 disables "
            "(default) — quantiles are still recorded")
define_flag("serving_batch_window_ms", 2.0,
            "server-side ragged micro-batching window: concurrent "
            "predict RPCs enqueue parsed rows and a dispatcher thread "
            "drains everything waiting every this-many ms (or earlier "
            "at serving_batch_max_rows) into ONE packed device forward "
            "— the request-coalescing that turns N per-RPC dispatches "
            "into one ragged dispatch. 0 = dispatch as soon as the "
            "queue is non-empty (still coalesces whatever arrived "
            "together); < 0 = batching off, every RPC packs and "
            "dispatches inline (the pre-r14 path)")
define_flag("serving_batch_max_rows", 4096,
            "dispatch a serving micro-batch early once this many rows "
            "are waiting (bounds the packed batch's device shape and "
            "the head-of-line wait under burst load); also the "
            "per-request row ceiling when it exceeds the feed batch "
            "size")
define_flag("serving_hbm_rows", 0,
            "serving-table hot-tier capacity in rows: a model with more "
            "xbox rows than this serves through the hierarchical cache "
            "(hot rows in HBM, warm in a host-RAM CLOCK cache, cold on "
            "the ssd tier) with misses batch-promoted toward HBM by "
            "access frequency off the predict critical path. 0 "
            "(default) = whole table device-resident, no tiering")
define_flag("serving_host_cache_rows", 0,
            "warm host-RAM tier capacity (rows) of the tiered serving "
            "table; rows evicted from it spill to the ssd/disk tier. "
            "0 = unbounded host RAM (disk tier never used)")
define_flag("serving_cache_dir", "",
            "directory backing the tiered serving table's cold tier "
            "(DiskShards buckets); empty = a per-predictor temp dir")
define_flag("serving_publisher_poll_s", 1.0,
            "donefile poll interval of the serving publisher thread "
            "(serving/publisher.py): how often a replica checks the "
            "training day loop's donefile for freshly published "
            "per-pass deltas to hot-swap via apply_update")
define_flag("serving_rps_window_s", 30.0,
            "sliding window for the serving throughput_rps gauge/stat "
            "(computed from LogQuantileDigest.delta() counts over "
            "rotating window snapshots — an idle replica decays to 0 "
            "instead of reporting lifetime-average rate)")
define_flag("fleet_vnodes", 64,
            "virtual nodes per replica on the fleet router's consistent-"
            "hash ring (serving/router.py): more vnodes = smoother key "
            "spread and smaller remap on join/leave, at O(vnodes * "
            "replicas) ring memory")
define_flag("fleet_health_interval_s", 0.5,
            "fleet router health-check cadence: the health thread polls "
            "every replica's stats RPC this often, drives the SLO "
            "admission window, and adopts elastic membership changes "
            "(join/leave) between polls")
define_flag("fleet_health_fails", 2,
            "consecutive health-check failures before the fleet router "
            "ejects a replica from the ring (a routed predict that hits "
            "a dead connection re-routes immediately and counts one "
            "strike — ejection never waits for a full predict to fail "
            "this many times)")
define_flag("fleet_spillover_inflight", 8,
            "per-replica in-flight predict ceiling for hash-affinity "
            "routing: past it the router spills the request to the "
            "least-loaded healthy replica (cache affinity yields to "
            "load under key skew); a replica whose SLO admission "
            "tripped sheds its overflow to the degraded path instead")
define_flag("fleet_slo_window_s", 5.0,
            "SLO admission window of the fleet router: per-replica "
            "slo/violations deltas are read per health poll and summed "
            "over this window; tripping fleet_slo_trip within it moves "
            "the replica to DEGRADED admission, and one clean window "
            "restores it")
define_flag("fleet_slo_trip", 3,
            "slo/violations within one fleet_slo_window_s that trips a "
            "replica into DEGRADED admission (its overflow beyond "
            "fleet_spillover_inflight is served by the degraded "
            "HBM-hot-rows-only path, flagged degraded=true, instead of "
            "queueing)")
define_flag("embedding_quant_block", 128,
            "values per scale block of the int8 exchange wires: both "
            "the single-host all_to_all payloads "
            "(embedding_exchange_dtype=int8) and the cross-host shard "
            "pull/push (multihost_wire_dtype=int8) carry one f32 "
            "absmax/127 scale per `block` consecutive payload values "
            "(EQuARX-style per-block quantization; a payload row "
            "narrower than the block degrades to one per-row scale)")
define_flag("multihost_wire_dtype", "f32",
            "emb payload dtype of the cross-host shard pull/push DCN "
            "wire (multihost/shard_service.py): 'f32' (exact, default "
            "— the 2-host drill pins bit-parity with single-host), "
            "'f16', or 'int8' (per-block scales via "
            "embedding_quant_block; receivers widen to f32 before "
            "anything accumulates or persists). Optimizer state, "
            "w/show/click, and reshard row moves always travel f32")
define_flag("filestore_chunk_bytes", 1 << 24,
            "FileStore set() payloads above this many bytes split into "
            "numbered chunk files behind an atomic manifest (get() "
            "reassembles transparently) — a multi-MB rank-table or "
            "gathered cluster snapshot can never exceed one framed "
            "message or one atomic-rename window. <= 0 disables "
            "chunking")
define_flag("stream_pass_events", 0,
            "streaming ingest (stream/source.py): close an incremental "
            "pass once this many events (log lines) have accumulated "
            "across pending files — the count half of the sub-day pass "
            "carve. 0 = no count bound (passes close on the time "
            "window, a day change, or an explicit flush)")
define_flag("stream_pass_window_s", 60.0,
            "streaming ingest: close the open incremental pass once its "
            "OLDEST pending event (file mtime) is this many seconds old "
            "even if stream_pass_events has not been reached — the "
            "freshness bound that keeps a trickle of traffic from "
            "sitting unconsumed. <= 0 disables the time trigger")
define_flag("stream_poll_s", 1.0,
            "sleep between streaming source polls in "
            "StreamRunner.run() when a poll carved nothing (the idle "
            "cadence of the files-as-stream tailer; tests "
            "drive poll_once() directly and never sleep)")
define_flag("table_decay_rate", 0.0,
            "show/click decay applied by every store variant's "
            "shrink() at the day boundary (role of the reference's "
            "show_click_decay_rate in ShrinkTable). 0 (default) = use "
            "the TableConfig.show_click_decay the model was built with; "
            "> 0 overrides it fleet-wide without rebuilding configs")
define_flag("table_ttl_days", 0,
            "feature TTL (role of delete_after_unseen_days): a row "
            "whose unseen_days counter — bumped by every shrink(), "
            "reset to 0 by any training write-back of that key — "
            "EXCEEDS this many days is evicted at the day-boundary "
            "shrink, bounding store growth under infinite traffic. "
            "0 disables TTL eviction (default)")
define_flag("table_min_show", 0.0,
            "floor on the min_show eviction threshold applied by "
            "shrink() (role of the reference's delete_threshold): the "
            "effective threshold is max(caller's min_show, this flag), "
            "so the lifecycle can be turned on fleet-wide without "
            "touching DayRunner call sites. 0 = no floor (default)")
define_flag("multihost_replicas", 1,
            "replication factor of the multi-host shard tier: each key "
            "range keeps 1 primary + (R-1) backup copies on DISTINCT "
            "hosts (ring placement — slot i's backups are the next "
            "hosts). Writes apply on the primary and forward "
            "synchronously to backups (a briefly-disconnected backup "
            "catches up from the primary's sequence-numbered delta "
            "journal instead of a full range copy); pure reads fail "
            "over to any live replica. 1 (default) = no replication — "
            "bit-identical to the pre-replication tier")
define_flag("multihost_journal_entries", 256,
            "per-range cap on the primary's delta-journal length "
            "(entries, each one push/apply/shrink mutation): a backup "
            "whose lag exceeds the journal window catches up with a "
            "full range snapshot instead of deltas — the bound that "
            "keeps journal memory and catch-up work finite. <= 0 "
            "disables journaling (every catch-up is a full copy)")
define_flag("multihost_overlap_exchange", True,
            "run the multi-host boundary exchange on a background "
            "worker (multihost/store.py): end_pass pushes and the "
            "split-build early pulls overlap the next pass's training "
            "instead of serializing with the boundary; only the "
            "shared-key remainder (plus the rows the pending pass "
            "needs back — the priority slice of the push) waits. "
            "Pushes are full-row overwrites keyed by the cached owner "
            "plan, so overlap ordering cannot change results. False = "
            "every pull/push synchronous in the caller (the "
            "pre-overlap wire, bit-identical either way)")
define_flag("dense_allreduce_dtype", "f32",
            "wire dtype of the dense-grad cross-replica sync "
            "(parallel/collective.py quantized_psum): 'f32' (exact "
            "lax.psum, default — bit-parity pinned), 'bf16' (halve "
            "the wire, stochastic-free cast), or 'int8' (EQuARX-style "
            "per-block absmax quantize -> scatter -> f32 "
            "dequant-accumulate -> gather; per-block scales via "
            "embedding_quant_block). Under a hierarchical ici+dcn "
            "mesh only the DCN hop narrows; the ICI hop stays f32")
define_flag("dense_zero", "off",
            "ZeRO-1/2 placement of the trainer's dense optimizer state "
            "(parallel/zero.py over the data-parallel axis): 'off' "
            "(default) replicates it on every device (the pre-ZeRO "
            "layout); 'shard' places each state leaf with zero_shardings "
            "and the step updates only the local param shard before an "
            "all-gather — f32 math is bit-identical to replicated while "
            "per-device state HBM drops to ~1/dp; 'offload' routes the "
            "update through OffloadedOptimizer so the state lives in "
            "host (pinned_host) memory between steps — HBM holds ~zero "
            "optimizer bytes at the cost of host-link traffic per step "
            "(requires dense_sync_mode='step'). 'shard' degrades to "
            "'off' under dense_sync_mode='kstep': k-step state is "
            "worker-local (intentionally divergent), so there is no "
            "redundant replica to shard away")
define_flag("dense_zero_min_size", 2048,
            "smallest dense leaf (elements) that FLAGS_dense_zero "
            "shards/offloads; smaller leaves stay replicated in HBM "
            "(gather latency and per-leaf transfer overhead would "
            "dominate their few bytes). Lower it to 0 to shard "
            "everything — what the parity tests do on toy models")
define_flag("table_slot_placement", "fused",
            "column layout of DeviceFeatureStore's persistent HBM "
            "table: 'fused' (default) keeps one [rows, D+3+Ke+Kw] "
            "array (the pre-split layout); 'split' carves the "
            "emb_state/w_state optimizer-slot columns into a sibling "
            "[rows, Ke+Kw] array so the hot array is exactly (D+3)*4 "
            "bytes/row — serving-tier capacity bounded by value bytes; "
            "'host' additionally pins the slot array to host memory "
            "(pinned_host via zero_shardings memory_kind) with "
            "transient HBM crossings around the pass-boundary "
            "push/pull. All three serve bit-identical payloads and "
            "write the same checkpoint/wire format — a checkpoint "
            "saved under one placement loads under any other")
define_flag("reshard_chunk_rows", 65536,
            "row window of the bounded-memory reshard/repair COPY walk "
            "(multihost/reshard.py + replica snapshots): pull_range / "
            "replica_snapshot move at most this many rows per RPC, "
            "pipelined two windows in flight (pull chunk k+1 while "
            "chunk k applies), each chunk an idempotent full-row "
            "overwrite so kill -9 drills carry over unchanged. <= 0 = "
            "whole-range single-shot moves (the pre-chunking wire)")
define_flag("stream_tail_bytes", False,
            "streaming ingest: tail-consume log files still being "
            "APPENDED — the source tracks a durable per-file byte "
            "offset, carves complete-line byte ranges "
            "('path@@start-end' manifest entries) instead of waiting "
            "for the whole segment to be atomically renamed, and "
            "resumes mid-file after kill -9 with no event lost or "
            "duplicated. False (default) = whole-segment mode "
            "(files must appear via write-tmp-then-rename)")
define_flag("quality_collect", False,
            "model-quality & data-health observatory (core/quality.py): "
            "per-slot input health collected on the ingest chunk path, "
            "per-pass COPC/calibration tracking rebinned from the AUC "
            "histogram, drift alarms (quality/alarms/<kind>) and ONE "
            "quality_report line beside each pass_report. Host-side "
            "only — the jitted step is unchanged (test_quality pins "
            "it). False (default) = collection off; the pass report's "
            "headline copc/bucket_error fields are free and always on")
define_flag("quality_sample_rate", 0.0,
            "serving-side sampled calibration: fraction of rid-carrying "
            "predict RPCs whose predictions are logged for a late "
            "label join (deterministic crc32-of-rid selection, no "
            "RNG). 0 (default) disables serving quality sampling")
define_flag("quality_join_window_s", 300.0,
            "bounded pending window of the serving prediction+label "
            "join: a sampled request whose labels have not arrived "
            "within this many seconds expires COUNTED "
            "(quality/label_join_expired), never crashes the join")
define_flag("quality_join_pending", 65536,
            "max sampled requests held pending a label join; beyond it "
            "the oldest entries expire counted (bounds serving host "
            "memory under a label-feed outage)")
define_flag("quality_min_events", 256,
            "joined label rows per serving calibration window: every "
            "this-many joined rows the window's COPC/calibration error "
            "is evaluated against the drift baseline")
define_flag("quality_baseline_passes", 8,
            "previous-N-pass window behind each quality drift baseline "
            "(the EWMA updates over it; alarms compare the new pass "
            "against the baseline built from prior passes only)")
define_flag("quality_warmup_passes", 3,
            "observed passes of a metric before its drift alarms may "
            "fire — early training legitimately moves calibration, and "
            "a baseline of one pass is noise")
define_flag("quality_copc_tol", 0.25,
            "relative COPC (actual ctr / predicted ctr) deviation from "
            "the EWMA baseline that raises quality/alarms/copc — the "
            "within-one-pass calibration-drift trip wire")
define_flag("quality_copc_band", 0.0,
            "absolute |COPC - 1| band that raises quality/alarms/copc "
            "immediately, no baseline needed (a calibrated CTR model "
            "targets COPC 1.0). 0 (default) = band check off — early "
            "training sits far from 1 by construction")
define_flag("quality_calibration_tol", 0.5,
            "relative RISE of the bucket calibration error over its "
            "EWMA baseline (and past a 0.01 absolute floor) that "
            "raises quality/alarms/calibration")
define_flag("quality_coverage_drop", 0.5,
            "relative DROP of a slot's example coverage vs its EWMA "
            "baseline (and past a 0.01 absolute floor) that raises "
            "quality/alarms/slot_dark — the slot-went-dark trip wire")
define_flag("quality_churn_max", 0.0,
            "pass-over-pass key churn (fraction of a slot's keys unseen "
            "last pass) above which quality/alarms/churn raises; "
            "suppressed for the first pass after a day rollover (the "
            "per-day key window slides by design). 0 (default) = off")
define_flag("rpc_mux", True,
            "negotiate the multiplexed v2 wire on connect (one "
            "wire_caps probe per connect): frames carry an in-flight "
            "request id so ONE socket serves N outstanding calls "
            "(call_async/futures) and the per-replica conn pools "
            "collapse to one mux'd conn. A peer that does not answer "
            "the probe keeps the blocking v1 protocol — mixed-version "
            "clusters interoperate per-connection. False = always "
            "speak v1 (the pre-r21 one-RTT-per-call plane)")
define_flag("rpc_worker_threads", 4,
            "bounded worker-pool size of the event-loop FramedRPCServer: "
            "device-touching/blocking handlers (pull, push, predict) "
            "dispatch to at most this many worker threads per server "
            "while cheap handlers (stats, clock_probe, metrics_snapshot, "
            "contains) run inline on the poller thread")
define_flag("rpc_sg_min_bytes", 4096,
            "ndarray payload bytes above which a v2 frame switches to "
            "the zero-copy scatter/gather encoding: arrays ride as "
            "64B-aligned trailing segments sent via sendmsg (no "
            "payload-sized join copy) and are received into the "
            "frame's preallocated buffer (decoded as views, no "
            "intermediate copy). < 0 disables SG frames (mux frames "
            "still carry request ids)")
define_flag("rpc_shm", False,
            "co-located-process shortcut for SG array frames: when "
            "both peers sit on the loopback interface, array segments "
            "ride a one-shot shared-memory block (name on the wire, "
            "receiver attaches/unlinks) instead of the socket. "
            "Off by default — a receiver that dies between frame and "
            "attach leaks the segment until sweep_orphans")
define_flag("rpc_shm_min_bytes", 65536,
            "ndarray payload bytes above which an shm-eligible frame "
            "(FLAGS_rpc_shm, loopback peer) actually uses the shared-"
            "memory path; smaller payloads stay on the socket where "
            "the segment setup cost would dominate")
define_flag("multihost_coalesce_window_ms", 0.0,
            "shard-server coalescing window for concurrent pull/"
            "pull_serving requests: requests for the same slot arriving "
            "within the window merge into ONE union-key store lookup "
            "(the serving micro-batcher pattern applied to the shard "
            "tier; results scatter back per request, bit-identical to "
            "serial). 0 (default) = opportunistic — no added latency, "
            "merge only what queued while the previous lookup ran; "
            "< 0 disables coalescing entirely")
define_flag("rpc_retry_deadline_s", 30.0,
            "overall wall-clock deadline across an idempotent call's "
            "retries: when exceeded the last connection error raises "
            "even if attempts remain (a PS blip should cost ms, not "
            "minutes of blind retry)")
define_flag("history_interval_s", 0.0,
            "metric-history sampler cadence (core/timeseries.py): every "
            "interval one bounded ring point is taken per registered "
            "registry (counter deltas, gauge last-values, digest window "
            "deltas) — the trend source for burn-rate alerts, fleet_top "
            "sparklines and incident bundles. 0 (default) = sampler off; "
            "alerts_enable arms a 5s fallback cadence")
define_flag("history_points", 360,
            "metric-history ring retention in points per registry "
            "(core/timeseries.py): oldest points fall off — 360 points "
            "at a 10s cadence is one hour of trend per process")
define_flag("alerts_enable", False,
            "arm the declarative SLO alert engine (core/alerts.py): the "
            "default rule pack evaluates multi-window burn rates off the "
            "metric history every sampler tick; active alerts surface "
            "via the alerts_active RPC, alert/<name> counters and one "
            "alert_report log line")
define_flag("alerts_fast_window_s", 60.0,
            "fast burn-rate window (core/alerts.py): a rule whose fast-"
            "window value breaches goes PENDING; fast AND slow breach "
            "goes FIRING — the fast window catches the step change")
define_flag("alerts_slow_window_s", 300.0,
            "slow burn-rate window (core/alerts.py): confirms a fast-"
            "window breach is sustained before FIRING, and must come "
            "back clean before an alert RESOLVES")
define_flag("alerts_clear_windows", 2,
            "hysteresis (core/alerts.py): consecutive clean evaluations "
            "(fast AND slow windows healthy) before a FIRING alert "
            "transitions to RESOLVED — one noisy good sample must not "
            "flap a page")
define_flag("alerts_violations_per_s", 0.0,
            "SLO error-budget burn threshold for the slo/violations "
            "counter (core/alerts.py default rule pack): sustained "
            "violations-per-second at or above this rate in both burn "
            "windows pages. 0 (default) disables the rule")
define_flag("alerts_replica_lag", 0.0,
            "page threshold for the multihost/replica_lag_p99 gauge "
            "(journal entries a replica trails the primary); 0 "
            "(default) disables the rule")
define_flag("alerts_freshness_p99_ms", 0.0,
            "warn threshold for the stream/event_to_servable_ms window "
            "p99 (event-to-servable freshness SLO); 0 (default) "
            "disables the rule")
define_flag("alerts_overlap_floor", 0.0,
            "warn floor for pass/train_boundary_exchange_overlap_frac: "
            "a sustained drop below the floor means the PR-17 boundary-"
            "exchange overlap stopped hiding DCN time; 0 (default) "
            "disables the rule")
define_flag("incident_dir", "",
            "directory for incident flight-recorder bundles "
            "(core/incident.py): a FIRING page alert, watchdog stall, "
            "replica eject or STALE_PRIMARY burst writes one atomically-"
            "renamed JSON bundle (history window, trace tail, rpc "
            "tables, active alerts, last reports). Empty (default) = "
            "recorder off")
define_flag("incident_min_interval_s", 60.0,
            "incident capture rate limit (core/incident.py): at most "
            "one bundle per interval per process — a flapping alert "
            "must not turn the flight recorder into a disk-filling "
            "loop; suppressed captures count incident/rate_limited")
define_flag("autopilot_poll_s", 0.5,
            "fleet autopilot control-loop cadence "
            "(serving/autopilot.py): each tick reads the merged fleet "
            "stats + active alerts and may emit at most one scale "
            "action and one canary transition")
define_flag("autopilot_cooldown_s", 5.0,
            "hysteresis guard between consecutive autopilot scale "
            "actions (out, in, or shard repair): inside the cooldown "
            "the loop observes but never acts — a flapping sensor "
            "produces at most one action per window. Persisted in the "
            "controller state file, so a restarted controller honors "
            "the window instead of double-applying")
define_flag("autopilot_min_replicas", 1,
            "scale-in floor: the autopilot never drains the fleet "
            "below this many healthy replicas")
define_flag("autopilot_max_replicas", 8,
            "scale-out ceiling: the autopilot never spawns past this "
            "many healthy replicas, whatever the burn rate says")
define_flag("autopilot_scale_in_fill", 0.1,
            "scale-in trigger: merged batch_fill_frac below this with "
            "zero SLO-violation delta and p99 under half the SLO means "
            "the fleet is over-provisioned — drain the least-loaded "
            "replica (subject to the cooldown and the floor)")
define_flag("autopilot_canary_replicas", 1,
            "canary subset size: a new donefile BASE lands on this "
            "many replicas first (clamped so at least one incumbent "
            "keeps serving the old model for the COPC comparison)")
define_flag("autopilot_canary_min_labels", 64,
            "joined label rows each side (canary and incumbent) of "
            "the quality comparison needs before the controller "
            "renders a promote/rollback verdict")
define_flag("autopilot_canary_copc_margin", 0.2,
            "rollback objective: the canary's |COPC - 1| may exceed "
            "the incumbent's by at most this margin; past it the base "
            "is judged calibration-poisoned and rolled back")
define_flag("autopilot_canary_timeout_s", 60.0,
            "fail-closed canary deadline: a canary that cannot gather "
            "enough joined labels for a verdict within this window is "
            "rolled back (objective 'timeout'), never promoted on "
            "missing evidence. <= 0 disables the deadline")
