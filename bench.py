"""Benchmark harness. Default config: DeepFM CTR end-to-end (driver metric).

Measures the FULL training path the way production runs it — native text
parse -> columnar load -> per-batch host key map -> fused device step
(pull / fwd-bwd / dense+sparse update / AUC) — streaming DISTINCT batches
drawn from a >=50M-feature store, and prints ONE json line:

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": null, ...extras}

vs_baseline is null: no ledger row exists yet to compare against (the
benchmark PR, ROADMAP S0, owns the comparison). Extra keys break the e2e
number down (load / host-map / device) and report the device-only upper
bound. Every record names the platform, device_kind and device count it
ran on.

Other configs (BASELINE.md configs 1-3): `python bench.py resnet50`,
`python bench.py bert_dp`, `python bench.py gpt`.
"""

import json
import os
import sys
import tempfile
import time
from functools import partial

import numpy as np

# PBX_BENCH_SCALE=small = CPU control-flow run of the full harness path
# (never for recorded numbers): pin the CPU platform BEFORE jax
# initializes a backend and shrink every config below.
_SMALL = os.environ.get("PBX_BENCH_SCALE") == "small"

# ---------------------------------------------------------------------------
# Stall watchdog: plain hang protection. A bench that blocks forever in a
# device call records NOTHING, so a monitor thread turns "no progress for
# PBX_BENCH_WATCHDOG_S seconds (900 — longer than any compile here)"
# into a parseable failure JSON + hard exit, and emits a stderr
# heartbeat every 30 s naming the current phase. The heartbeat machinery
# lives in core/watchdog.py (the library version the day loop also
# arms); bench keeps only its own stall POLICY. Armed before the jax
# import: backend init itself can hang.
# ---------------------------------------------------------------------------

from paddlebox_tpu.core.watchdog import Watchdog  # noqa: E402

_WD = {"trace": None, "wd": None}


def _on_bench_stall(phase: str, idle: float) -> None:
    name = sys.argv[1] if len(sys.argv) > 1 else "deepfm"
    # Stall forensics ("no progress in phase 'device-probe'" with
    # nothing else is undiagnosable): every thread's Python stack + the
    # trace ring tail ride in the failure JSON, so the post-mortem names
    # the blocked frame, not just the phase.
    try:
        from paddlebox_tpu.core.trace import stall_forensics
        tail = stall_forensics()
    except Exception as e:  # noqa: BLE001 - keep the record
        tail = {"error": f"forensics unavailable: {e!r}"}
    print(json.dumps({
        "metric": f"{name}_FAILED",
        "value": 0.0,
        "unit": "none",
        "vs_baseline": None,
        "error": (f"watchdog: no progress in phase {phase!r} for "
                  f"{idle:.0f}s — device backend stall"),
        "tail": tail,
    }, default=str), flush=True)
    os._exit(3)


def _tick(phase: str) -> None:
    wd = _WD["wd"]
    if wd is not None:
        wd.beat(phase)
    tr = _WD["trace"]
    if tr is not None and tr.enabled:
        # Phase transitions land in the span-tracer ring, so a stall
        # dump's trace_tail shows the path INTO the hung phase.
        tr.instant("bench/" + phase)


if os.environ.get("PBX_BENCH_WATCHDOG", "1") != "0":
    _WD["wd"] = Watchdog(
        float(os.environ.get("PBX_BENCH_WATCHDOG_S", "900")),
        name="bench", on_stall=_on_bench_stall, poll_s=5.0,
        heartbeat_s=30.0)
    _WD["wd"].arm(phase="import-jax")

from paddlebox_tpu.core import flags
from paddlebox_tpu.core import report as _report
from paddlebox_tpu.core import trace as _trace

# Persistent compilation cache, by the one rule every chip entry point
# shares (flags.compilation_cache_dir): the environment's directory, or
# .jax_cache in this checkout. Off in the CPU small mode: CPU
# executables carry machine-feature stamps and are not worth caching.
if not _SMALL:
    flags.compilation_cache_dir()

# Telemetry: arm the flag-configured sinks (FLAGS_trace_path /
# FLAGS_metrics_path), then ALWAYS keep the span-tracer ring on for the
# bench — phases and pass spans cost ~1 µs each here, and they are the
# watchdog's stall-forensics timeline (ring-only: no file is written
# unless FLAGS_trace_path asks for one).
_report.init_telemetry_from_flags()
_trace.GLOBAL.enable()
_WD["trace"] = _trace.GLOBAL

import jax

if _SMALL:
    jax.config.update("jax_platforms", "cpu")
_tick("post-import")


def _sync(x) -> float:
    """Wait for ``x`` on the device and return its first element — the
    end of every timed region."""
    v = float(np.asarray(jax.block_until_ready(x)).ravel()[0])
    _tick("sync")
    return v


# Per-chip peak bf16 FLOP/s for MFU accounting, keyed by the
# ``device_kind`` jax reports, with its source. A device that is not in
# the table is an error, not a default.
PEAK_FLOPS_BY_DEVICE_KIND = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    # (jax reports a v5e chip as "TPU v5 lite").
    "TPU v5 lite": 197e12,
}


def _peak_flops() -> float:
    kind = jax.devices()[0].device_kind
    try:
        return PEAK_FLOPS_BY_DEVICE_KIND[kind]
    except KeyError:
        raise SystemExit(
            f"bench: no peak FLOP/s recorded for device_kind {kind!r} — "
            f"add it to PEAK_FLOPS_BY_DEVICE_KIND with its source; a "
            f"utilization against an assumed peak is not a measurement"
        ) from None


def _mfu(model_flops_per_s: float):
    """Model-FLOPs utilization vs the device's bf16 peak — the
    analytically-required FLOPs (not hardware-counter FLOPs), so remat
    recompute does not inflate it. None in the CPU small mode, which has
    no peak to divide by."""
    if _SMALL:
        return None
    return round(model_flops_per_s / _peak_flops(), 4)


# ---------------------------------------------------------------------------
# DeepFM CTR end-to-end (BASELINE.md config 4; the driver's default metric)
# ---------------------------------------------------------------------------

NUM_SLOTS = 26
EMB_DIM = 16
# Wide&Deep (bench_wide_deep) shape constants — module-level so the
# scatter preflight probes the SAME shapes the bench will compile.
WIDE_DEEP_EMB_DIM = 8
WIDE_DEEP_SLOTS = 20
WIDE_DEEP_BATCH = 8192
WIDE_DEEP_PASS_KEYS = 1_000_000
DENSE_DIM = 13
BATCH = 16384
STORE_KEYS = 50_000_000       # resident feature store size
PASS_KEYS = 4_000_000         # working set one pass touches
# Distinct timed batches: a real online pass trains minutes of traffic
# against one table build + write-back, so the per-pass fixed costs
# (feed_pass build, end_pass write-back) must amortize over a realistic
# batch count or the bench mis-states steady-state throughput.
N_BATCHES = 64

if _SMALL:
    BATCH = 1024
    STORE_KEYS = 1_000_000
    PASS_KEYS = 100_000
    N_BATCHES = 4


def _prepopulate_store(trainer, n_keys: int, chunk: int = 10_000_000) -> float:
    """Fill the backing store with n_keys initialized features (setup for a
    realistic pull: the pass working set hits a populated store). Returns
    build throughput in keys/s (index insert + value init — the
    PreBuildTask/BuildGPUTask role)."""
    eng = trainer.engine.groups[0].engine
    t0 = time.perf_counter()
    if hasattr(eng.store, "ensure_rows"):
        # Device tier: host index insert + on-device init; values never
        # cross the host boundary.
        for lo in range(1, n_keys + 1, chunk):
            keys = np.arange(lo, min(lo + chunk, n_keys + 1),
                             dtype=np.uint64)
            eng.store.ensure_rows(keys)
            _tick(f"prepopulate:{lo}")
        # Include device completion in the timing.
        jax.block_until_ready(eng.store._parts)
        np.asarray(eng.store._parts[0][:1, :1])
        _tick("prepopulate:done")
    else:
        for lo in range(1, n_keys + 1, chunk):
            keys = np.arange(lo, min(lo + chunk, n_keys + 1),
                             dtype=np.uint64)
            vals = eng.store.pull_for_pass(keys)  # materializes init
            eng.store.push_from_pass(keys, vals)
            _tick(f"prepopulate:{lo}")
    return n_keys / (time.perf_counter() - t0)


def _bench_host_index(n_keys: int) -> float:
    """Pure host-side pass-build throughput: fresh upsert of n_keys into
    the native incremental index. Separate from _prepopulate_store,
    whose number includes on-device row init; the measurement itself is
    the SHARED bench_index_build (one methodology with
    tools/bench_native_store.py)."""
    from paddlebox_tpu.native.store_py import bench_index_build
    return bench_index_build(n_keys,
                             tick=lambda lo: _tick(f"host_index:{lo}"))


def _native_available() -> bool:
    from paddlebox_tpu.native.build import native_available
    return bool(native_available())


def _bench_host_index_bulk(n_keys: int) -> float:
    """Sorted-run store build (round 13): per-chunk dedup → run merge →
    KeyIndex.bulk_build, same keys/chunking/tick as _bench_host_index so
    the two rates stay methodology-comparable (the r02 number was the
    incremental upsert walk)."""
    from paddlebox_tpu.native.store_py import bench_index_build
    return bench_index_build(n_keys, mode="bulk",
                             tick=lambda lo: _tick(f"host_index_bulk:{lo}"))


def _planted_labels(rng, hot_ids: np.ndarray, *, target_rate: float = 0.25,
                    strength: float = 2.0) -> np.ndarray:
    """Labels from a PLANTED sparse signal: each hot key carries a latent
    ±1 weight (a hash of the key), the sample logit is that weight scaled
    by ``strength`` plus the base-rate offset, and labels are Bernoulli
    in that logit. A learner that recovers per-key weights (exactly what
    the sparse w/embedding path trains) must pull AUC well above 0.5
    within a pass — random labels would mask sign/aliasing bugs that
    parity tests can't see (an embedding served to the wrong row still
    produces 0.5 AUC on random labels, never on planted ones). Role of
    the AUC discipline around metrics.cc:286-355."""
    h = (hot_ids * np.uint64(0x9E3779B97F4A7C15)) >> np.uint64(32)
    sign = (h & np.uint64(1)).astype(np.float32) * 2.0 - 1.0   # ±1 per key
    logit = sign * strength + np.log(target_rate / (1.0 - target_rate))
    p = 1.0 / (1.0 + np.exp(-logit))
    return (rng.random(hot_ids.shape[0]) < p).astype(np.int32)


def _gen_pass_files(tmpdir: str, rng, pass_keys: np.ndarray,
                    n_batches: int, *, batch: int = None,
                    n_slots: int = None, dense_dim: int = None,
                    label_rate: float = 0.25,
                    planted_hot: int = 1000,
                    zipf_a: float = None) -> list:
    """Write n_batches*batch svm-format lines across part files (one per
    batch). Slot 0 draws from a HOT head of ``planted_hot`` keys (the
    Zipf head every real CTR stream has — each hot key repeats
    batch*n_batches/planted_hot times, enough for the in-pass optimizer
    to recover its planted weight); the label carries that key's planted
    signal (_planted_labels). Remaining slots draw uniformly from the
    full working set when ``zipf_a`` is None, else Zipf(zipf_a)-ranked
    over it (head-heavy, duplication 2-5x at a~1.2) — the cold tail
    that sizes the store/pass machinery.
    Vectorized string assembly (np.char): a per-line Python loop takes
    minutes at 1M+ lines on one core."""
    batch = BATCH if batch is None else batch
    n_slots = NUM_SLOTS if n_slots is None else n_slots
    dense_dim = DENSE_DIM if dense_dim is None else dense_dim
    hot = pass_keys[:min(planted_hot, pass_keys.size)]
    files = []
    for b in range(n_batches):
        if zipf_a is not None:
            # Zipf-ranked draws over the working set — the head-heavy
            # key distribution every real CTR stream has (and what makes
            # dedup + measured capacity pay: duplication is 2-5x at
            # a~1.2 instead of the uniform draw's ~1.0).
            ranks = (rng.zipf(zipf_a, (batch, n_slots)).astype(np.int64)
                     - 1) % pass_keys.size
            ids = pass_keys[ranks]
        else:
            ids = rng.choice(pass_keys, (batch, n_slots))
        ids[:, 0] = rng.choice(hot, batch)
        labels = _planted_labels(rng, ids[:, 0], target_rate=label_rate)
        line = labels.astype("U1")
        for j in range(n_slots):
            line = np.char.add(line, f" s{j}:")
            line = np.char.add(line, ids[:, j].astype("U20"))
        if dense_dim:
            dense = (rng.random((batch, dense_dim)) * 10000).astype(np.int32)
            line = np.char.add(line, " d:0.")
            line = np.char.add(line, dense[:, 0].astype("U5"))
            for j in range(1, dense_dim):
                line = np.char.add(line, ",0.")
                line = np.char.add(line, dense[:, j].astype("U5"))
        path = os.path.join(tmpdir, f"part-{b:05d}")
        with open(path, "w") as f:
            f.write("\n".join(line.tolist()) + "\n")
        files.append(path)
    return files


def _bench_pull_push(trainer, tables, rows, iters=10):
    """Isolated (pull_ms, push_ms) for width group 0 on the live pass
    tables: jitted shard_map'd pull_local / push_local at the bench's
    real shapes. pull_ms is the op FLAGS_sparse_gather_kernel attacks
    (the last XLA gather of the CTR step), push_ms the one
    FLAGS_sparse_scatter_kernel already converted — recording both keys
    keeps the pull-side win visible in the artifact even when only CPU
    smoke runs are possible. Standalone (unshared-layout) timings: each
    side pays its own bucketing/sort here, so the fused step's total is
    below pull_ms + push_ms."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddlebox_tpu.embedding.lookup import make_pull_fn, push_local

    table0, r0 = tables[0], rows[0]
    d = table0.dim
    n = int(r0.shape[0])
    axis = trainer.axis
    sh = NamedSharding(trainer.mesh, P(axis))

    def timed(thunk):
        out = thunk()                       # compile + warm
        _sync(jax.tree_util.tree_leaves(out)[0])
        t0 = time.perf_counter()
        for _ in range(iters):
            out = thunk()
        _sync(jax.tree_util.tree_leaves(out)[0])
        return (time.perf_counter() - t0) / iters * 1e3

    _tick("deepfm:pull_push_breakdown")
    pull_fn = make_pull_fn(trainer.mesh, axis)
    pull_ms = timed(lambda: pull_fn(table0, r0))

    opt = trainer.sparse_opt

    # Deliberately NOT donating the table: the timed pass still trains
    # on these buffers; the copy is the price of a non-destructive probe.
    @jax.jit
    @functools.partial(
        jax.shard_map, mesh=trainer.mesh,
        in_specs=(P(axis),) * 6, out_specs=P(axis), check_vma=False)
    def push_fn(table, dev_rows, ge, gw, sh_, ck):
        return push_local(table, dev_rows, ge, gw, sh_, ck, axis=axis,
                          opt=opt)

    ge = jax.device_put(np.zeros((n, d), np.float32), sh)
    gs = jax.device_put(np.zeros((n,), np.float32), sh)
    push_ms = timed(lambda: push_fn(table0, r0, ge, gs, gs, gs))
    return pull_ms, push_ms


def bench_deepfm() -> dict:
    import jax
    import jax.numpy as jnp

    from paddlebox_tpu.data.dataset import Dataset
    from paddlebox_tpu.data.slots import DataFeedConfig, SlotConf
    from paddlebox_tpu.embedding import TableConfig
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.parallel import HybridTopology, build_mesh
    from paddlebox_tpu.train import CTRTrainer, TrainerConfig

    ndev = len(jax.devices())
    mesh = build_mesh(HybridTopology(dp=ndev))
    # Criteo-style fixed-length slots: exactly one feasign per slot per
    # sample, so capacity slack is 1.0 (no ragged headroom) — every byte
    # of the per-batch id arrays is real. AMP bf16 compute (master
    # params/optimizer/loss stay f32 — TrainerConfig.compute_dtype).
    slots = tuple(SlotConf(f"s{i}", avg_len=1.0) for i in range(NUM_SLOTS))
    slots += (SlotConf("d", is_dense=True, dim=DENSE_DIM),)
    feed = DataFeedConfig(slots=slots, batch_size=BATCH,
                          slot_capacity_slack=1.0)
    table_cfg = TableConfig(dim=EMB_DIM, learning_rate=0.05)
    model = DeepFM(slot_names=tuple(f"s{i}" for i in range(NUM_SLOTS)),
                   emb_dim=EMB_DIM, dense_dim=DENSE_DIM,
                   hidden=(400, 400, 400))
    from paddlebox_tpu.embedding import DeviceFeatureStore
    trainer = CTRTrainer(
        model, feed, table_cfg, mesh=mesh,
        config=TrainerConfig(auc_num_buckets=1 << 16,
                             compute_dtype="bfloat16"),
        store_factory=lambda cfg: DeviceFeatureStore(
            cfg, mesh=mesh, capacity_hint=STORE_KEYS + PASS_KEYS))
    trainer.init(seed=0)

    rng = np.random.default_rng(0)
    build_keys_per_s = _prepopulate_store(trainer, STORE_KEYS)
    host_index_keys_per_s = _bench_host_index(STORE_KEYS)
    host_index_bulk_keys_per_s = _bench_host_index_bulk(STORE_KEYS)
    # Multi-process ingest: enable on real multi-core hosts when the
    # operator left the flag at its default — the bench measures the
    # shipped fast path; on 1-2 core boxes spawn overhead would swamp
    # the parse and the thread path stays honest.
    if int(flags.flag("ingest_workers")) == 0 and (os.cpu_count() or 1) >= 4:
        flags.set_flags({"ingest_workers": min(8, os.cpu_count() - 1)})
    pass_keys = rng.choice(np.arange(1, STORE_KEYS, dtype=np.uint64),
                           size=PASS_KEYS, replace=False)

    with tempfile.TemporaryDirectory() as tmpdir:
        # Untimed setup: generate text data.
        files = _gen_pass_files(tmpdir, rng, pass_keys, N_BATCHES)

        # Start the timed pass's data preload NOW: it overlaps the
        # device-only warmup below exactly as a production day loop
        # overlaps pass k+1's read with pass k's training
        # (PreLoadIntoMemory role, box_wrapper.h:1140).
        dataset = Dataset(feed, num_reader_threads=4)
        dataset.set_filelist(files)
        t_preload0 = time.perf_counter()
        dataset.preload_into_memory()

        # Device-only upper bound: repeat the jitted step on one fixed
        # batch (no host work in the loop). Feeding the FULL pass key set
        # here puts the table in the same power-of-two size bucket as the
        # timed pass below, so this phase also serves as the compile
        # warmup and the timed pass runs with zero recompilation.
        ds_dev = Dataset(feed, num_reader_threads=2)
        ds_dev.set_filelist(files[:1])
        ds_dev.load_into_memory()
        batch = next(ds_dev.batches_sharded(ndev))
        eng = trainer.engine
        eng.feed_pass([np.sort(pass_keys) for _ in eng.groups])
        tables = eng.begin_pass()
        rows = trainer._map_batch_rows(batch)
        segs = {n: jnp.asarray(batch.segments[n]) for n in batch.ids}
        from paddlebox_tpu.train.ctr_trainer import _concat_dense_host
        import ml_dtypes
        # Same dtype the timed pass's prefetch will feed (bf16 under AMP)
        # or the warmup would compile a different signature and the first
        # timed step would retrace.
        dense_j = jnp.asarray(
            _concat_dense_host(batch).astype(ml_dtypes.bfloat16))
        labels_j = jnp.asarray(batch.labels)
        valid_j = jnp.asarray(batch.valid)
        if trainer._step_fn is None:
            trainer._step_fn = trainer._build_step()
        step = trainer._step_fn
        params, opt_state, auc = (trainer.params, trainer.opt_state,
                                  trainer.auc_state)
        sync0 = jnp.zeros((), jnp.int32)
        for _ in range(3):
            tables, params, opt_state, auc, loss, _of = step(
                tables, params, opt_state, auc, rows, segs, labels_j,
                valid_j, dense_j, sync0)
        _sync(loss)
        t0 = time.perf_counter()
        dev_steps = 20
        for _ in range(dev_steps):
            tables, params, opt_state, auc, loss, _of = step(
                tables, params, opt_state, auc, rows, segs, labels_j,
                valid_j, dense_j, sync0)
        _sync(loss)
        dev_dt = time.perf_counter() - t0
        pull_ms, push_ms = _bench_pull_push(trainer, tables, rows)
        trainer.params, trainer.opt_state, trainer.auc_state = (
            params, opt_state, auc)
        eng.update_tables(tables)
        eng.end_pass()
        device_only = dev_steps * BATCH / dev_dt

        # Timed E2E: the steady-state pass — data was preloaded during the
        # previous phase (as a day loop hides pass k+1's read under pass
        # k's training), so the timed region is wait-remainder + the real
        # pass loop (feed_pass build -> per-batch host map + device step
        # -> end_pass write-back) over distinct batches.
        t0 = time.perf_counter()
        dataset.wait_preload_done()
        t_load = time.perf_counter() - t0          # exposed remainder
        preload_wall = time.perf_counter() - t_preload0
        t0 = time.perf_counter()
        stats = trainer.train_pass(dataset)
        t_pass = time.perf_counter() - t0

        # Opt-in slot-importance block (--slot-auc[=s0,s1,...]): the
        # AUC-runner slot-replacement eval on the freshly trained
        # model over the timed pass's (still-loaded) data — per-slot
        # AUC degradation becomes a recorded artifact + quality/
        # slot_auc gauges instead of a print. Untimed by construction:
        # every perf number above is already captured.
        slot_auc_block = None
        if SLOT_AUC is not None:
            from paddlebox_tpu.train.auc_runner import \
                slot_replacement_eval
            names = SLOT_AUC or [f"s{i}"
                                 for i in range(min(4, NUM_SLOTS))]
            _tick("deepfm:slot_auc")
            sa = slot_replacement_eval(trainer, dataset, slots=names)
            slot_auc_block = {
                "base_auc": round(float(sa["base_auc"]), 5),
                "ranking": sa["ranking"],
                "slots": {n: {"auc": round(v["auc"], 5),
                              "drop": round(v["auc_drop"], 5)}
                          for n, v in sa["slots"].items()}}

    n_samples = N_BATCHES * BATCH
    e2e = n_samples / (t_load + t_pass)
    tm = trainer.timers
    host_map_s = tm["host_map"].elapsed_sec
    device_step_s = tm["device_step"].elapsed_sec
    # Analytic model FLOPs/sample (MLP fwd 2*in*out, bwd ~2x fwd).
    dims = [NUM_SLOTS * EMB_DIM + DENSE_DIM, 400, 400, 400, 1]
    mults = sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    flops_per_sample = 3 * 2 * mults
    per_chip = e2e / ndev
    # HBM residency (ZeRO-sharded dense state + slot-column offload):
    # measured bytes from the live arrays, not an asserted formula —
    # *_hbm_bytes keys gate lower-better in perf_gate through the
    # "_bytes" suffix; the placement strings are provenance (ungated).
    dense_mem = trainer.dense_memory_stats()
    store_mem = trainer.engine.groups[0].engine.store.memory_stats()
    return {
        "metric": "deepfm_ctr_e2e_samples_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "samples/s/chip",
        "vs_baseline": None,
        "device_only_per_chip": round(device_only / ndev, 1),
        "e2e_over_device_only": round(e2e / device_only, 4),
        "pull_ms": round(pull_ms, 3),
        "push_ms": round(push_ms, 3),
        # What the dispatch sites resolved to while the step traced
        # (never the flag strings: "auto" says nothing about what ran).
        "sparse_gather_kernel": ",".join(
            flags.resolved_kernels().get("sparse_gather", [])),
        "sparse_scatter_kernel": ",".join(
            flags.resolved_kernels().get("sparse_scatter", [])),
        # Dispatch amortization (FLAGS_trainer_steps_per_dispatch):
        # dispatch_ms is the host-side enqueue wall per BLOCK (the
        # device_step scope records async dispatch, not completion) —
        # at K>1 the same number covers K steps.
        "steps_per_dispatch": int(stats["steps_per_dispatch"]),
        "dispatch_blocks": int(stats["dispatch_blocks"]),
        "dispatch_ms": round(
            device_step_s / max(int(stats["dispatch_blocks"]), 1) * 1e3,
            3),
        "embedding_exchange_dtype": flags.flag("embedding_exchange_dtype"),
        # Pass-boundary breakdown (round 8): end_pass write-back ms and
        # the pass build's total vs blocked ms, so the split-build /
        # fused-boundary path is visible in the artifact even on CPU
        # smoke runs. This bench feeds with no pass active (feed_wait~0);
        # the pipelined day loop is where feed_wait vs build_ms shows
        # the real contention and overlap_frac its hidden fraction.
        "end_ms": (stats.get("boundary") or {}).get("end_ms"),
        "build_ms": (stats.get("boundary") or {}).get("build_ms"),
        "feed_wait_ms": (stats.get("boundary") or {}).get("feed_wait_ms"),
        "overlap_frac": (stats.get("boundary") or {}).get("overlap_frac"),
        # Critical-path attribution (round 11): the pass's bottleneck
        # verdict (bounding stage + device idle fraction + per-stage
        # busy/blocked shares + queue depths) and the dispatch-latency
        # quantiles — what tools/perf_gate.py gates across rounds, so
        # "store_build is the wall" is a machine-checked field, not a
        # post-hoc bench analysis.
        "bottleneck": stats.get("bottleneck"),
        "dispatch_ms_quantiles": stats.get("dispatch_ms_quantiles"),
        "pass_split_build": bool(flags.flag("pass_split_build")),
        "pass_boundary_fuse": flags.flag("pass_boundary_fuse"),
        "load_s": round(t_load, 3),
        "preload_wall_s": round(preload_wall, 3),
        "pass_s": round(t_pass, 3),
        "host_map_s": round(host_map_s, 3),
        "device_step_dispatch_s": round(device_step_s, 3),
        "achieved_gflops_per_chip": round(
            per_chip * flops_per_sample / 1e9, 2),
        "store_build_keys_per_s": round(build_keys_per_s, 0),
        "host_index_build_keys_per_s": round(host_index_keys_per_s, 0),
        # Round 13: the sorted-run build rate (dedup-as-chunks-arrive →
        # k-way merge → bulk_build) next to the incremental walk above,
        # plus ingest provenance — which reader produced the pass data
        # and how fast the bytes became ColumnarChunks (preload wall is
        # the in-situ rate: it overlaps device warmup like a day loop).
        "host_index_bulk_build_keys_per_s": round(
            host_index_bulk_keys_per_s, 0),
        "ingest_rows_per_s": round(n_samples / max(preload_wall, 1e-9), 0),
        "ingest_workers": int(flags.flag("ingest_workers")),
        "store_build_native": _native_available(),
        "store_keys": STORE_KEYS,
        "pass_keys": PASS_KEYS,
        "auc": round(float(stats["auc"]), 5),
        "auc_floor": _auc_floor(stats["auc"]),
        "lookup_overflow": _overflow_guard(stats),
        "lookup_exchange_bytes": int(stats["lookup_exchange_bytes"]),
        "scale_sparse_grad_by_batch": stats["scale_sparse_grad_by_batch"],
        **({"slot_auc": slot_auc_block}
           if slot_auc_block is not None else {}),
        "dense/params_hbm_bytes": int(dense_mem["params_hbm_bytes"]),
        "dense/opt_state_hbm_bytes": int(
            dense_mem["opt_state_hbm_bytes"]),
        "table/hot_hbm_bytes": int(store_mem["hot_hbm_bytes"]),
        "table/slot_hbm_bytes": int(store_mem["slot_hbm_bytes"]),
        "dense_zero": str(dense_mem["dense_zero"]),
        "table_slot_placement": str(store_mem["placement"]),
        "n_devices": ndev,
    }


def _overflow_guard(stats: dict) -> int:
    """VERDICT-r04 #8: dropped grads must never hide inside a throughput
    number. Any bucket-overflowed lookup during the TIMED pass fails the
    bench record outright — with dedup-before-exchange on (default),
    even planted hot-key skew must not overflow at default slack."""
    n = int(stats.get("lookup_overflow", 0))
    if n:
        raise RuntimeError(
            f"{n} sparse lookups overflowed their shard bucket during the "
            f"timed pass (dropped pull+grad) — the throughput number would "
            f"be measuring dropped work; raise FLAGS_embedding_shard_slack "
            f"or FLAGS_embedding_unique_frac")
    return 0


def _auc_floor(auc: float, floor: float = 0.7):
    """Learning proof on the planted-signal labels: a full-scale pass
    must pull AUC past the floor; below it the sparse path is broken
    (sign/aliasing/routing): the record says so and main() exits
    non-zero after printing it. Small smoke runs see each key ~once —
    the floor doesn't apply."""
    if _SMALL:
        return None
    return {"floor": floor, "passed": float(auc) > floor}


# ---------------------------------------------------------------------------
# ResNet-50 (BASELINE.md config 1): single-chip fwd+bwd images/s
# ---------------------------------------------------------------------------

def bench_resnet50() -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from paddlebox_tpu.models.resnet import ResNet

    from paddlebox_tpu.amp import cast_compute_except_stats as cast_compute
    from paddlebox_tpu.amp import merge_bn_stats as merge_bn

    model = ResNet(depth=50, num_classes=1000)
    params = model.init(jax.random.PRNGKey(0))
    opt = optax.sgd(0.1, momentum=0.9)
    opt_state = opt.init(params)
    bs = 8 if _SMALL else 128

    def loss_fn(p, x, y):
        # bf16 compute (MXU path), f32 master params; BN statistics stay
        # f32 end-to-end (cast_compute skips them, batchnorm_apply
        # computes in f32, merge_bn writes them back to the master).
        logits, p_new = model.apply(cast_compute(p), x, train=True)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), y).mean()
        return loss, p_new

    @jax.jit
    def step(p, s, x, y):
        (loss, p_new), g = jax.value_and_grad(loss_fn, has_aux=True)(
            p, x, y)
        updates, s = opt.update(g, s, p)
        return merge_bn(optax.apply_updates(p, updates), p_new), s, loss

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(bs, 224, 224, 3)), jnp.bfloat16)
    y = jnp.asarray(rng.integers(0, 1000, bs), jnp.int32)
    for _ in range(1 if _SMALL else 3):
        params, opt_state, loss = step(params, opt_state, x, y)
    _sync(loss)
    t0 = time.perf_counter()
    n = 2 if _SMALL else 20
    for _ in range(n):
        params, opt_state, loss = step(params, opt_state, x, y)
    _sync(loss)
    dt = time.perf_counter() - t0
    ips = n * bs / dt
    # ResNet-50 @224: ~4.09 GFLOP forward/image (standard conv+fc
    # multiply-add count x2); train step ~3x forward (bwd ~2x fwd).
    flops_per_image = 3 * 4.09e9
    return {
        "metric": "resnet50_images_per_sec_per_chip",
        "value": round(ips, 1),
        "unit": "images/s/chip",
        "vs_baseline": None,
        "batch_size": bs,
        "achieved_mfu": _mfu(ips * flops_per_image),
    }


# ---------------------------------------------------------------------------
# BERT-base DP (BASELINE.md config 2): tokens/s over the dp mesh
# ---------------------------------------------------------------------------

def bench_bert_dp() -> dict:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddlebox_tpu.models.bert import (BertConfig, bert_mlm_loss,
                                           init_bert)
    from paddlebox_tpu.parallel import HybridTopology, build_mesh

    ndev = len(jax.devices())
    mesh = build_mesh(HybridTopology(dp=ndev))
    if _SMALL:
        cfg = BertConfig(d_model=128, n_layers=2, n_heads=2, d_ff=256)
    else:
        cfg = BertConfig()  # BERT-base defaults
    params = init_bert(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(1e-4)
    bs, seq = (2 * ndev, 64) if _SMALL else (8 * ndev, 128)

    data_sh = NamedSharding(mesh, P("dp"))
    rep = NamedSharding(mesh, P())
    params = jax.device_put(params, rep)

    def loss_fn(p, tokens, targets, mask):
        return bert_mlm_loss(p, cfg, tokens, targets, mask)

    # FLAGS_dense_zero applies to the dense workloads exactly as to the
    # CTR trainer: "shard" places adamw moments ZeRO-1 over dp (each
    # chip stores 1/dp of every large leaf; params output pinned
    # replicated so the sharded state can't leak into p+u), "offload"
    # keeps them in host memory between steps via OffloadedOptimizer.
    from paddlebox_tpu.parallel import zero as zero_lib
    dense_zero = str(flags.flag("dense_zero"))
    zero_min = int(flags.flag("dense_zero_min_size"))
    if dense_zero == "offload":
        off_tx = zero_lib.OffloadedOptimizer(
            opt, mesh, axis="dp", min_size=zero_min)
        opt_state = off_tx.init(params)
        grad_step = jax.jit(jax.value_and_grad(loss_fn))

        def step(p, s, tokens, targets, mask):
            loss, g = grad_step(p, tokens, targets, mask)
            p, s = off_tx.update_apply(g, s, p)
            return p, s, loss
    else:
        opt_state = opt.init(params)
        if dense_zero == "shard":
            opt_sh = zero_lib.zero_shardings(
                opt_state, mesh, axis="dp", min_size=zero_min)
            opt_state = jax.device_put(opt_state, opt_sh)
            jit_kw = {"out_shardings": (
                jax.tree.map(lambda _: rep, params), opt_sh, rep)}
        else:
            opt_state = jax.device_put(opt_state, rep)
            jit_kw = {}

        @partial(jax.jit, **jit_kw)
        def step(p, s, tokens, targets, mask):
            loss, g = jax.value_and_grad(loss_fn)(p, tokens, targets,
                                                  mask)
            updates, s = opt.update(g, s, p)
            return optax.apply_updates(p, updates), s, loss

    rng = np.random.default_rng(0)
    tokens = jax.device_put(jnp.asarray(
        rng.integers(0, cfg.vocab_size, (bs, seq)), jnp.int32), data_sh)
    targets = jax.device_put(jnp.asarray(
        rng.integers(0, cfg.vocab_size, (bs, seq)), jnp.int32), data_sh)
    # Standard MLM masking rate: predict ~15% of positions.
    mask = jax.device_put(jnp.asarray(
        rng.random((bs, seq)) < 0.15, jnp.float32), data_sh)
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, tokens,
                                       targets, mask)
    _sync(loss)
    t0 = time.perf_counter()
    n = 2 if _SMALL else 10
    for _ in range(n):
        params, opt_state, loss = step(params, opt_state, tokens,
                                       targets, mask)
    _sync(loss)
    dt = time.perf_counter() - t0
    tps = n * bs * seq / dt
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    # Measured per-device HBM residency of the dense state (what
    # FLAGS_dense_zero exists to shrink) — not a formula.
    params_hbm = zero_lib.tree_hbm_bytes_per_device(params)
    opt_hbm = zero_lib.tree_hbm_bytes_per_device(opt_state)
    return {
        "metric": "bert_base_dp_tokens_per_sec",
        "value": round(tps, 1),
        "unit": "tokens/s",
        "vs_baseline": None,
        "n_devices": ndev,
        "batch_size": bs,
        "seq_len": seq,
        "n_params": n_params,
        "dense/params_hbm_bytes": int(params_hbm),
        "dense/opt_state_hbm_bytes": int(opt_hbm),
        "dense_zero": dense_zero,
        # 6ND estimate over ALL chips -> divide by ndev for per-chip MFU.
        "achieved_mfu": _mfu(6.0 * n_params * tps / ndev),
    }


# ---------------------------------------------------------------------------
# GPT (BASELINE.md config 3, scaled to available chips): tokens/s + MFU-ish
# ---------------------------------------------------------------------------

def bench_gpt() -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from paddlebox_tpu.models.gpt import (GPTConfig, init_gpt,
                                          make_gpt_train_step)
    from paddlebox_tpu.parallel import HybridTopology, build_mesh

    ndev = len(jax.devices())
    # GPT-350M-class on one chip; hybrid axes engage when chips allow.
    if _SMALL:
        cfg = GPTConfig(vocab_size=1024, d_model=128, n_heads=4,
                        n_layers=2, d_ff=256, max_seq_len=128)
    else:
        cfg = GPTConfig(vocab_size=50304, d_model=1024, n_heads=16,
                        n_layers=24, d_ff=4096, max_seq_len=1024)
    mesh = build_mesh(HybridTopology(dp=ndev))
    params, specs = init_gpt(jax.random.PRNGKey(0), cfg, pp_stages=1)
    opt = optax.adafactor(1e-3)
    opt_state = opt.init(params)

    # Same FLAGS_dense_zero wiring as bert_dp: "shard" ZeRO-1-places the
    # adafactor state over dp (params pinned replicated through the
    # step's out_shardings), "offload" keeps it host-resident between
    # steps; "off" is the replicated baseline.
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddlebox_tpu.parallel import zero as zero_lib
    dense_zero = str(flags.flag("dense_zero"))
    zero_min = int(flags.flag("dense_zero_min_size"))
    rep = NamedSharding(mesh, P())
    if dense_zero == "offload":
        from paddlebox_tpu.models.gpt import gpt_loss_fn
        params = jax.device_put(params, rep)
        off_tx = zero_lib.OffloadedOptimizer(
            opt, mesh, axis="dp", min_size=zero_min)
        opt_state = off_tx.init(params)
        vg = jax.jit(jax.value_and_grad(
            gpt_loss_fn(cfg, mesh, specs, num_microbatches=1)))

        def step(p, s, tokens, targets):
            loss, g = vg(p, tokens, targets)
            p, s = off_tx.update_apply(g, s, p)
            return p, s, loss
    elif dense_zero == "shard":
        params = jax.device_put(params, rep)
        opt_sh = zero_lib.zero_shardings(
            opt_state, mesh, axis="dp", min_size=zero_min)
        opt_state = jax.device_put(opt_state, opt_sh)
        step = make_gpt_train_step(
            cfg, mesh, specs, opt, num_microbatches=1,
            out_shardings=(jax.tree.map(lambda _: rep, params),
                           opt_sh, rep))
    else:
        step = make_gpt_train_step(cfg, mesh, specs, opt,
                                   num_microbatches=1)

    bs, seq = (2 * ndev, 128) if _SMALL else (4 * ndev, 1024)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (bs, seq)),
                         jnp.int32)
    targets = jnp.asarray(rng.integers(0, cfg.vocab_size, (bs, seq)),
                          jnp.int32)
    for _ in range(2):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
    _sync(loss)
    t0 = time.perf_counter()
    n = 2 if _SMALL else 5
    for _ in range(n):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
    _sync(loss)
    dt = time.perf_counter() - t0
    tps = n * bs * seq / dt
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    flops = 6.0 * n_params * tps  # standard 6ND estimate
    params_hbm = zero_lib.tree_hbm_bytes_per_device(params)
    opt_hbm = zero_lib.tree_hbm_bytes_per_device(opt_state)
    return {
        "metric": "gpt_tokens_per_sec",
        "value": round(tps, 1),
        "unit": "tokens/s",
        "vs_baseline": None,
        "n_devices": ndev,
        "n_params": n_params,
        "dense/params_hbm_bytes": int(params_hbm),
        "dense/opt_state_hbm_bytes": int(opt_hbm),
        "dense_zero": dense_zero,
        "achieved_tflops": round(flops / 1e12, 2),
        "achieved_mfu": _mfu(flops / ndev),
    }


# ---------------------------------------------------------------------------
# Wide&Deep CTR (BASELINE.md config 5): the HeterPS-style path — CVM
# (show/click) features flowing through the pull, device-resident store.
# ---------------------------------------------------------------------------

def bench_wide_deep() -> dict:
    import jax
    import jax.numpy as jnp

    from paddlebox_tpu.data.dataset import Dataset
    from paddlebox_tpu.data.slots import DataFeedConfig, SlotConf
    from paddlebox_tpu.embedding import DeviceFeatureStore, TableConfig
    from paddlebox_tpu.models.wide_deep import WideDeep
    from paddlebox_tpu.parallel import HybridTopology, build_mesh
    from paddlebox_tpu.train import CTRTrainer, TrainerConfig

    ndev = len(jax.devices())
    mesh = build_mesh(HybridTopology(dp=ndev))
    n_slots, emb_dim, batch = (WIDE_DEEP_SLOTS, WIDE_DEEP_EMB_DIM,
                               WIDE_DEEP_BATCH)
    store_keys, pass_keys_n, n_batches = (10_000_000,
                                          WIDE_DEEP_PASS_KEYS, 32)
    if _SMALL:
        batch, store_keys, pass_keys_n, n_batches = 512, 200_000, 20_000, 4
    slots = tuple(SlotConf(f"s{i}", avg_len=1.0) for i in range(n_slots))
    feed = DataFeedConfig(slots=slots, batch_size=batch,
                          slot_capacity_slack=1.0)
    model = WideDeep(slot_names=tuple(f"s{i}" for i in range(n_slots)),
                     emb_dim=emb_dim, hidden=(256, 128))
    trainer = CTRTrainer(
        model, feed, TableConfig(dim=emb_dim, learning_rate=0.05),
        mesh=mesh,
        config=TrainerConfig(auc_num_buckets=1 << 16,
                             compute_dtype="bfloat16"),
        store_factory=lambda cfg: DeviceFeatureStore(
            cfg, mesh=mesh, capacity_hint=store_keys + pass_keys_n))
    trainer.init(seed=0)
    build_keys_per_s = _prepopulate_store(trainer, store_keys)
    rng = np.random.default_rng(0)
    pass_keys = rng.choice(np.arange(1, store_keys, dtype=np.uint64),
                           size=pass_keys_n, replace=False)
    # Zipf key stream + measured bucket capacity: the HeterPS-style
    # config is the duplicate-heavy one, so it carries the dedup
    # demonstration — capacity sizes to measured unique ids and the
    # record's lookup_exchange_bytes shows the reduction (overflow
    # still hard-fails via _overflow_guard). The flag itself is only
    # needed around train_pass (the warmup seeds _step_caps directly),
    # so it is set there under try/finally — a failure anywhere in this
    # function cannot leak it into a same-process deepfm run.
    from paddlebox_tpu.core import flags as flagmod
    with tempfile.TemporaryDirectory() as tmpdir:
        files = _gen_pass_files(tmpdir, rng, pass_keys, n_batches,
                                batch=batch, n_slots=n_slots, dense_dim=0,
                                label_rate=0.2, zipf_a=1.2)
        dataset = Dataset(feed, num_reader_threads=4)
        dataset.set_filelist(files)
        dataset.preload_into_memory()
        # Compile warmup at the TIMED pass's table size: feed the full
        # pass key set (same pow2 bucket), run the jitted step twice on
        # one batch, close the pass — the timed pass then reuses the
        # compiled program (same discipline as bench_deepfm).
        ds_warm = Dataset(feed, num_reader_threads=2)
        ds_warm.set_filelist(files[:1])
        ds_warm.load_into_memory()
        batch0 = next(ds_warm.batches_sharded(ndev))
        eng = trainer.engine
        eng.feed_pass([np.sort(pass_keys) for _ in eng.groups])
        tables = eng.begin_pass()
        rows = trainer._map_batch_rows(batch0)
        # Warm the MEASURED-capacity step (auto-capacity is on for this
        # config): the timed pass measures the same Zipf distribution
        # into the same pow2 bucket and reuses this compile.
        trainer._step_caps = tuple(trainer._measure_caps(tables, rows))
        trainer._step_fn = trainer._build_step(caps=trainer._step_caps)
        segs = {n: jnp.asarray(batch0.segments[n]) for n in batch0.ids}
        from paddlebox_tpu.train.ctr_trainer import _concat_dense_host
        import ml_dtypes
        dense_j = jnp.asarray(
            _concat_dense_host(batch0).astype(ml_dtypes.bfloat16))
        params, opt_state, auc = (trainer.params, trainer.opt_state,
                                  trainer.auc_state)
        sync0 = jnp.zeros((), jnp.int32)
        for _ in range(2):
            tables, params, opt_state, auc, loss, _of = trainer._step_fn(
                tables, params, opt_state, auc, rows, segs,
                jnp.asarray(batch0.labels), jnp.asarray(batch0.valid),
                dense_j, sync0)
        _sync(loss)
        trainer.params, trainer.opt_state, trainer.auc_state = (
            params, opt_state, auc)
        eng.update_tables(tables)
        eng.end_pass()

        dataset.wait_preload_done()
        t0 = time.perf_counter()
        _prev_autocap = flagmod.flag("embedding_auto_capacity")
        flagmod.set_flags({"embedding_auto_capacity": True})
        try:
            stats = trainer.train_pass(dataset)
        finally:
            flagmod.set_flags(
                {"embedding_auto_capacity": _prev_autocap})
        t_pass = time.perf_counter() - t0
    per_chip = n_batches * batch / t_pass / ndev
    return {
        "metric": "wide_deep_ctr_e2e_samples_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "samples/s/chip",
        "vs_baseline": None,
        "store_build_keys_per_s": round(build_keys_per_s, 0),
        "auc": round(float(stats["auc"]), 5),
        "auc_floor": _auc_floor(stats["auc"]),
        "lookup_overflow": _overflow_guard(stats),
        "lookup_exchange_bytes": int(stats["lookup_exchange_bytes"]),
        "scale_sparse_grad_by_batch": stats["scale_sparse_grad_by_batch"],
        "n_devices": ndev,
    }


# ---------------------------------------------------------------------------
# Graph engine at non-toy scale (SURVEY §2.3): 10M-edge weighted build +
# sharded deepwalk throughput — the roles of GraphGpuWrapper::load_edge_file
# + upload_batch and GraphDataGenerator's walk loop
# (graph_gpu_ps_table_inl.cu), measured instead of merely covered.
# ---------------------------------------------------------------------------

GRAPH_EDGES = 10_000_000
GRAPH_NODES = 1_000_000
GRAPH_MAX_DEGREE = 64
GRAPH_WALK_LEN = 24
GRAPH_WALK_BATCH = 65_536
if _SMALL:
    GRAPH_EDGES, GRAPH_NODES = 1_000_000, 100_000
    GRAPH_WALK_BATCH = 8_192


def bench_graph() -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddlebox_tpu.graph import DeviceGraph, build_csr
    from paddlebox_tpu.graph.sampler import (random_walk,
                                             random_walk_weighted)
    from paddlebox_tpu.parallel import HybridTopology, build_mesh

    ndev = len(jax.devices())
    mesh = build_mesh(HybridTopology(dp=ndev))
    rng = np.random.default_rng(0)

    # Power-law-ish destinations (Zipf hubs — the degree skew real graphs
    # have, which is exactly what stresses the hub truncation path) with
    # integer weights.
    _tick("graph:gen")
    src = rng.integers(0, GRAPH_NODES, GRAPH_EDGES).astype(np.int64)
    dst = (rng.zipf(1.3, GRAPH_EDGES) % GRAPH_NODES).astype(np.int64)
    w = rng.integers(1, 10, GRAPH_EDGES).astype(np.float32)

    _tick("graph:build")
    t0 = time.perf_counter()
    g = build_csr(src, dst, num_nodes=GRAPH_NODES, weights=w)
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    dg = DeviceGraph.from_csr(g, max_degree=GRAPH_MAX_DEGREE)
    pad_s = time.perf_counter() - t0

    _tick("graph:upload")
    rep = NamedSharding(mesh, P())
    shd = NamedSharding(mesh, P("dp"))
    nbrs = jax.device_put(jnp.asarray(dg.nbrs), rep)
    degree = jax.device_put(jnp.asarray(dg.degree), rep)
    cdf = jax.device_put(jnp.asarray(dg.nbr_cdf), rep)
    starts = jax.device_put(
        jnp.asarray(rng.integers(0, GRAPH_NODES, GRAPH_WALK_BATCH),
                    jnp.int32), shd)

    def timed_walks(fn, *arrays):
        # jitted fns shard the start batch over dp; the adjacency is
        # device-resident and replicated (each GPU holds its graph shard
        # in the reference; one chip holds the whole padded table here).
        _tick("graph:walk-compile")
        out = fn(*arrays, starts, jax.random.key(0), GRAPH_WALK_LEN)
        _sync(out[-1, -1])
        t0 = time.perf_counter()
        iters = 10
        for i in range(iters):
            out = fn(*arrays, starts, jax.random.key(i + 1),
                     GRAPH_WALK_LEN)
        _sync(out[-1, -1])
        dt = time.perf_counter() - t0
        return iters * GRAPH_WALK_BATCH * GRAPH_WALK_LEN / dt

    uniform_sps = timed_walks(random_walk, nbrs, degree)
    weighted_sps = timed_walks(random_walk_weighted, nbrs, cdf)

    return {
        "metric": "graph_walk_steps_per_sec",
        "value": round(uniform_sps, 0),
        "unit": "walk steps/s",
        "vs_baseline": None,
        "weighted_walk_steps_per_sec": round(weighted_sps, 0),
        "build_edges_per_sec": round(GRAPH_EDGES / build_s, 0),
        "build_s": round(build_s, 3),
        "pad_s": round(pad_s, 3),
        "edges": GRAPH_EDGES,
        "nodes": GRAPH_NODES,
        "max_degree": GRAPH_MAX_DEGREE,
        "walk_len": GRAPH_WALK_LEN,
        "walk_batch": GRAPH_WALK_BATCH,
        "n_devices": ndev,
    }


# ---------------------------------------------------------------------------
# Online serving (SURVEY L12): xbox-style sparse model + jitted bf16
# predictor — the inference half of the CTR production loop, measured.
# ---------------------------------------------------------------------------

SERVING_KEYS = 2_000_000
SERVING_BATCH = 2048
SERVING_QUERY_BATCHES = 50
SERVE_REQ_ROWS = 64          # rows per client request in --clients mode
SERVE_CLIENT_SECONDS = 3.0   # timed window per client count
if _SMALL:
    SERVING_KEYS = 100_000
    SERVING_BATCH = 512
    SERVING_QUERY_BATCHES = 10
    SERVE_CLIENT_SECONDS = 1.0

# Parsed from --clients by main(): comma-separated client counts for the
# concurrent wire-mode serving bench ("" = skip the wire section).
# `bench.py deepfm --slot-auc[=s0,s1,...]` opt-in: run the AUC-runner
# slot-replacement eval on the trained model after the timed pass and
# record per-slot AUC degradation (None = off; [] = default first-4
# slots; a list = exactly those slots). Untimed — it runs after every
# perf number is captured.
SLOT_AUC = None
SERVE_CLIENTS = ""
# `bench.py serve --replicas 1,2` fleet axis ("" = skip): fresh fleet
# (R PredictServers + FleetRouter) per count over ONE shared predictor
# (the CPU-honest stand-in for R hosts: per-replica batchers/sockets/
# stats are real, the device table is shared so the axis measures the
# routing+coalescing overhead, not R copies of HBM).
SERVE_REPLICAS = ""
SERVE_FLEET_CLIENTS_PER_REPLICA = 4


def _serve_client_lines(rng, n_requests: int):
    """Vectorized svm-line assembly for the wire clients (per-line
    python f-strings would dominate the client threads' CPU budget and
    measure the bench, not the server)."""
    out = []
    for _ in range(n_requests):
        ids = rng.integers(1, SERVING_KEYS + 1,
                           (SERVE_REQ_ROWS, NUM_SLOTS))
        ids[:, 0] = rng.integers(1, 1001, SERVE_REQ_ROWS)
        line = np.full((SERVE_REQ_ROWS,), "0", dtype="U16")
        for j in range(NUM_SLOTS):
            line = np.char.add(line, f" s{j}:")
            line = np.char.add(line, ids[:, j].astype("U20"))
        out.append(line.tolist())
    return out


def _bench_serve_clients(pred, clients: list) -> dict:
    """Concurrent-client wire mode: N PredictClients hammer one
    PredictServer (micro-batcher on) for a fixed window; records
    throughput_rps / rows_per_s / p50/p99 predict latency /
    batch_fill_frac per client count. One fresh server per count so the
    latency digest and fill gauge belong to that run alone."""
    import threading

    from paddlebox_tpu.core import flags as flagmod, monitor
    from paddlebox_tpu.data.parser import parse_lines
    from paddlebox_tpu.serving.batcher import pack_bucketed, pow2_bucket
    from paddlebox_tpu.serving.service import PredictClient, PredictServer

    # Compile the pow2 row-bucket ladder BEFORE any timed window: a
    # coalesced batch of k requests lands in the pow2_bucket(k * rows)
    # trace, and an in-window XLA compile would be measured as a
    # multi-second p99.
    _tick("serving:bucket-warmup")
    wrng = np.random.default_rng(7)
    max_rows = min(max(clients) * SERVE_REQ_ROWS,
                   int(flagmod.flag("serving_batch_max_rows")))
    warm_lines = _serve_client_lines(wrng, 1)[0]
    b = pow2_bucket(SERVE_REQ_ROWS)
    while True:
        ins = parse_lines(warm_lines * (b // SERVE_REQ_ROWS), pred.feed)
        pred.predict(pack_bucketed(ins, pred.feed))
        if b >= pow2_bucket(max_rows):
            break
        b *= 2

    out = {}
    for n_cli in clients:
        _tick(f"serving:clients{n_cli}")
        monitor.reset()
        server = PredictServer("127.0.0.1:0", pred)
        rng = np.random.default_rng(1234 + n_cli)
        lines = [_serve_client_lines(rng, 8) for _ in range(n_cli)]
        done = [0] * n_cli
        stop = threading.Event()
        start = threading.Barrier(n_cli + 1)

        def run(i):
            cli = PredictClient(server.endpoint)
            ok = True
            try:
                cli.predict(lines[i][0])  # warm (compile outside window)
            except Exception as e:
                ok = False
                print(f"serve client {i} warmup failed: {e!r}",
                      file=sys.stderr)
            start.wait()  # always reached: a dead client must not
            try:          # wedge the barrier and stall the recording
                j = 0
                while ok and not stop.is_set():
                    cli.predict(lines[i][j % len(lines[i])])
                    done[i] += 1
                    j += 1
            finally:
                cli.close()

        threads = [threading.Thread(target=run, args=(i,), daemon=True)
                   for i in range(n_cli)]
        for t in threads:
            t.start()
        start.wait()
        t0 = time.perf_counter()
        time.sleep(SERVE_CLIENT_SECONDS)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        dt = time.perf_counter() - t0
        stats_cli = PredictClient(server.endpoint)
        st = stats_cli.stats()
        stats_cli.close()
        server.stop()
        n_req = sum(done)
        out[f"c{n_cli}"] = {
            "throughput_rps": round(n_req / dt, 1),
            "rows_per_s": round(n_req * SERVE_REQ_ROWS / dt, 1),
            "predict_p50_ms": st["latency_ms"]["p50"],
            "predict_p99_ms": st["latency_ms"]["p99"],
            "batch_fill_frac": round(st["batch_fill_frac"], 4),
            "batches": st["batches"],
            "requests": n_req,
        }
    return out


def _bench_serve_telemetry_overhead(pred, *, n_requests: int = 200
                                    ) -> dict:
    """Tracing + live-scrape overhead on the serving path: the same
    single-replica router loop timed with telemetry OFF, then with the
    span ring ON and a concurrent fleet_top-style scrape loop hitting
    metrics_snapshot on router+replica — `telemetry_overhead_frac` is
    the rps delta, gated lower-better by tools/perf_gate.py (the
    observability layer must stay ~free, or it gets turned off exactly
    when it is needed)."""
    import threading

    from paddlebox_tpu.core import telemetry_scrape, trace
    from paddlebox_tpu.serving.router import FleetRouter
    from paddlebox_tpu.serving.service import PredictClient, PredictServer

    server = PredictServer("127.0.0.1:0", pred, replica_id="bench-tel")
    router = FleetRouter("127.0.0.1:0", replicas=[server.endpoint],
                         start_health=False)
    rng = np.random.default_rng(999)
    lines = _serve_client_lines(rng, 8)
    cli = PredictClient(router.endpoint)
    cli.predict(lines[0])  # warm the forward + conns

    def timed_loop() -> float:
        t0 = time.perf_counter()
        for j in range(n_requests):
            cli.predict(lines[j % len(lines)])
        return n_requests / (time.perf_counter() - t0)

    trace.disable()
    rps_off = timed_loop()
    trace.enable()   # ring-only: no file unless FLAGS_trace_path is set
    targets = {"router": router.endpoint, "replica": server.endpoint}
    stop = threading.Event()
    scrapes = [0]

    def scrape_loop():
        while not stop.is_set():
            telemetry_scrape.scrape_cluster(targets, with_stats=False)
            scrapes[0] += 1
            stop.wait(0.1)

    t = threading.Thread(target=scrape_loop, daemon=True)
    t.start()
    try:
        try:
            rps_on = timed_loop()
        finally:
            stop.set()
            t.join(timeout=10)
            trace.disable()
            trace.clear()
        # Health plane (fleet health PR): history sampler + alert
        # engine ON at a deliberately hot 100ms cadence — every
        # registered registry (global + router + replica instance
        # rings) is sampled and the burn-rate rule pack evaluated per
        # tick. history_overhead_frac is the additional rps cost vs
        # telemetry-off; alerts_firing must be 0 on a healthy bench
        # (both gated by tools/perf_gate.py).
        from paddlebox_tpu.core import alerts, timeseries
        prev = {k: flags.flag(k)
                for k in ("history_interval_s", "alerts_enable")}
        flags.set_flags({"history_interval_s": 0.1,
                         "alerts_enable": True})
        try:
            timeseries.init_from_flags()
            alerts.init_from_flags()
            rps_health = timed_loop()
            firing = alerts.firing_count()
        finally:
            alerts.shutdown()
            timeseries.GLOBAL_SAMPLER.stop()
            flags.set_flags(prev)
    finally:
        cli.close()
        router.stop()
        server.stop()
    return {
        "trace_off_rps": round(rps_off, 1),
        "trace_on_rps": round(rps_on, 1),
        "telemetry_overhead_frac": round(
            max(0.0, 1.0 - rps_on / max(rps_off, 1e-9)), 4),
        "history_on_rps": round(rps_health, 1),
        "history_overhead_frac": round(
            max(0.0, 1.0 - rps_health / max(rps_off, 1e-9)), 4),
        "alerts_firing": int(firing),
        "scrapes": int(scrapes[0]),
    }


def _bench_serve_fleet(pred, replicas: list) -> dict:
    """Fleet axis: R replica servers behind one FleetRouter, hammered
    by 4 clients per replica for a fixed window. Fresh fleet per count
    (per-replica instance registries + a fresh router latency digest
    belong to that run alone); records aggregate throughput_rps,
    per-replica batch fill, router route_ms p50/p99, and the
    degraded-path share — the keys tools/perf_gate.py gates."""
    import threading

    from paddlebox_tpu.serving.router import FleetRouter
    from paddlebox_tpu.serving.service import PredictClient, PredictServer

    out = {}
    for n_rep in replicas:
        _tick(f"serving:replicas{n_rep}")
        n_cli = max(int(SERVE_FLEET_CLIENTS_PER_REPLICA) * n_rep, 1)
        servers = [PredictServer("127.0.0.1:0", pred,
                                 replica_id=f"bench-r{i}")
                   for i in range(n_rep)]
        router = FleetRouter("127.0.0.1:0",
                             replicas=[s.endpoint for s in servers],
                             start_health=False)
        rng = np.random.default_rng(4321 + n_rep)
        lines = [_serve_client_lines(rng, 8) for _ in range(n_cli)]
        done = [0] * n_cli
        stop = threading.Event()
        start = threading.Barrier(n_cli + 1)

        def run(i):
            cli = PredictClient(router.endpoint)
            ok = True
            try:
                cli.predict(lines[i][0])  # warm outside the window
            except Exception as e:
                ok = False
                print(f"fleet client {i} warmup failed: {e!r}",
                      file=sys.stderr)
            start.wait()
            try:
                j = 0
                while ok and not stop.is_set():
                    cli.predict(lines[i][j % len(lines[i])])
                    done[i] += 1
                    j += 1
            finally:
                cli.close()

        threads = [threading.Thread(target=run, args=(i,), daemon=True)
                   for i in range(n_cli)]
        for t in threads:
            t.start()
        start.wait()
        t0 = time.perf_counter()
        time.sleep(SERVE_CLIENT_SECONDS)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        dt = time.perf_counter() - t0
        stats_cli = PredictClient(router.endpoint)
        st = stats_cli.stats()
        stats_cli.close()
        router.stop()
        for s in servers:
            s.stop()
        n_req = sum(done)
        fills = [b["stats"]["batch_fill_frac"]
                 for b in st["replicas"].values()]
        out[f"r{n_rep}"] = {
            "throughput_rps": round(n_req / dt, 1),
            "rows_per_s": round(n_req * SERVE_REQ_ROWS / dt, 1),
            "route_ms_quantiles": {"p50": st["route_ms"]["p50"],
                                   "p99": st["route_ms"]["p99"]},
            "batch_fill_frac": round(
                sum(fills) / max(len(fills), 1), 4),
            "degraded_frac": round(
                st["degraded_rpcs"] / max(st["predict_rpcs"], 1), 4),
            "clients": n_cli,
            "requests": n_req,
        }
    _tick("serving:telemetry-overhead")
    out["telemetry"] = _bench_serve_telemetry_overhead(pred)
    return out


def bench_serving() -> dict:
    import jax

    from paddlebox_tpu.data.parser import parse_lines
    from paddlebox_tpu.data.slots import DataFeedConfig, SlotBatch, SlotConf
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.serving.predictor import CTRPredictor

    rng = np.random.default_rng(0)
    slots = tuple(SlotConf(f"s{i}", avg_len=1.0) for i in range(NUM_SLOTS))
    feed = DataFeedConfig(slots=slots, batch_size=SERVING_BATCH,
                          slot_capacity_slack=1.0)
    model = DeepFM(slot_names=tuple(f"s{i}" for i in range(NUM_SLOTS)),
                   emb_dim=EMB_DIM, hidden=(400, 400, 400))
    dense_params = model.init(jax.random.PRNGKey(0))

    # Trained-model stand-in: the serving table's cost profile depends on
    # key count and width, not the values.
    _tick("serving:table")
    keys = np.arange(1, SERVING_KEYS + 1, dtype=np.uint64)
    emb = rng.normal(size=(SERVING_KEYS, EMB_DIM)).astype(np.float32) * 0.01
    w = rng.normal(size=(SERVING_KEYS,)).astype(np.float32) * 0.01
    t0 = time.perf_counter()
    pred = CTRPredictor(model, feed, keys, emb, w, dense_params)
    # The table upload is part of the load: wait for it.
    jax.block_until_ready(pred._table)
    load_s = time.perf_counter() - t0

    # Query stream: hot head + uniform tail, same shape discipline as the
    # training benches. One batch shape -> one cached jitted forward.
    # Vectorized line assembly (np.char), as in _gen_pass_files.
    def query_batch():
        ids = rng.integers(1, SERVING_KEYS + 1,
                           (SERVING_BATCH, NUM_SLOTS))
        ids[:, 0] = rng.integers(1, 1001, SERVING_BATCH)
        line = np.full((SERVING_BATCH,), "0", dtype="U16")
        for j in range(NUM_SLOTS):
            line = np.char.add(line, f" s{j}:")
            line = np.char.add(line, ids[:, j].astype("U20"))
        return SlotBatch.pack(parse_lines(line.tolist(), feed), feed)

    batches = [query_batch() for _ in range(SERVING_QUERY_BATCHES)]
    _tick("serving:warmup")
    probs = pred.predict(batches[0])          # compile
    assert probs.shape == (SERVING_BATCH,)
    _tick("serving:timed")
    t0 = time.perf_counter()
    for b in batches:
        probs = pred.predict(b)
    float(probs[0])
    dt = time.perf_counter() - t0
    qps = SERVING_QUERY_BATCHES * SERVING_BATCH / dt

    # Per-request latency digest (the SLO view, recorded beside the
    # pipelined-throughput headline — NOT inside its timed loop, which
    # must stay async to remain comparable with prior rounds): each
    # predict here is synced so a sample is a real request latency.
    _tick("serving:latency")
    from paddlebox_tpu.core.quantiles import LogQuantileDigest
    lat = LogQuantileDigest()
    for b in batches:
        tq = time.perf_counter()
        float(pred.predict(b)[0])
        lat.observe((time.perf_counter() - tq) * 1e3)
    lat_q = {k: (round(v, 3) if v is not None else None)
             for k, v in lat.quantiles().items()}

    out = {
        "metric": "serving_predict_samples_per_sec",
        "value": round(qps, 1),
        "unit": "samples/s",
        "vs_baseline": None,
        "table_load_s": round(load_s, 3),
        "predict_ms_quantiles": lat_q,
        "serving_slo_p99_ms": float(flags.flag("serving_slo_p99_ms")),
        "serving_keys": SERVING_KEYS,
        "batch_size": SERVING_BATCH,
        "serving_batch_window_ms": float(
            flags.flag("serving_batch_window_ms")),
        "n_devices": len(jax.devices()),
    }
    if SERVE_CLIENTS:
        clients = [int(c) for c in SERVE_CLIENTS.split(",") if c.strip()]
        out["clients"] = _bench_serve_clients(pred, clients)
    if SERVE_REPLICAS:
        # The --clients warmup above (when present) already compiled
        # the pow2 ladder; compile it here if fleet mode runs alone.
        replicas = [int(r) for r in SERVE_REPLICAS.split(",")
                    if r.strip()]
        if not SERVE_CLIENTS:
            from paddlebox_tpu.core import flags as flagmod
            from paddlebox_tpu.data.parser import parse_lines as _pl
            from paddlebox_tpu.serving.batcher import (pack_bucketed,
                                                       pow2_bucket)
            wrng = np.random.default_rng(7)
            max_rows = min(
                max(replicas) * SERVE_FLEET_CLIENTS_PER_REPLICA
                * SERVE_REQ_ROWS,
                int(flagmod.flag("serving_batch_max_rows")))
            warm_lines = _serve_client_lines(wrng, 1)[0]
            b = pow2_bucket(SERVE_REQ_ROWS)
            while True:
                ins = _pl(warm_lines * (b // SERVE_REQ_ROWS), pred.feed)
                pred.predict(pack_bucketed(ins, pred.feed))
                if b >= pow2_bucket(max_rows):
                    break
                b *= 2
        out["replicas"] = _bench_serve_fleet(pred, replicas)
    return out


MULTIHOST_HOSTS = 2          # `bench.py multihost --hosts N` overrides
MULTIHOST_KEYS = 20_000 if _SMALL else 2_000_000
MULTIHOST_DIM = 16
MULTIHOST_ROUNDS = 3


def bench_multihost() -> dict:
    """Loopback-process mode of the multi-host embedding exchange tier
    (MULTIHOST.md): N shard servers on 127.0.0.1 — the sockets, wire
    codec, fan-out threading, and reshard machinery are all real; only
    the DCN propagation delay is absent. Records the cross-host
    exchange rate per wire dtype plus a grow-by-one reshard
    (minimal-transfer audit included), gated by tools/perf_gate.py."""
    from paddlebox_tpu.core import monitor
    from paddlebox_tpu.embedding.table import TableConfig
    from paddlebox_tpu.multihost import (MultiHostStore, ShardRangeTable,
                                         execute_reshard,
                                         rows_moved_minimal,
                                         start_local_shards, stop_shards)

    hosts = MULTIHOST_HOSTS
    cfg = TableConfig(name="emb", dim=MULTIHOST_DIM, learning_rate=0.1)
    rng = np.random.default_rng(0)
    keys = np.unique(rng.integers(
        1, 1 << 50, size=int(MULTIHOST_KEYS * 1.01) + 64,
        dtype=np.uint64))[:MULTIHOST_KEYS]

    _tick("multihost:cluster")
    servers, eps = start_local_shards(hosts, cfg)
    store = MultiHostStore(cfg, eps)
    # Populate: one untimed pull+push round inserts every key.
    rows = store.pull_for_pass(keys)
    store.push_from_pass(keys, rows)

    def timed_round():
        t0 = time.perf_counter()
        r = store.pull_for_pass(keys)
        pull_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        store.push_from_pass(keys, r)
        return pull_s, time.perf_counter() - t1

    out_wire = {}
    prev = flags.flag("multihost_wire_dtype")
    try:
        for wire in ("f32", "int8"):
            _tick(f"multihost:wire-{wire}")
            flags.set_flags({"multihost_wire_dtype": wire})
            timed_round()  # warm the plan cache + connections
            b0 = (monitor.GLOBAL.get("multihost/pull_bytes")
                  + monitor.GLOBAL.get("multihost/push_bytes"))
            t0 = time.perf_counter()
            pull_s = push_s = 0.0
            for _ in range(MULTIHOST_ROUNDS):
                p, q = timed_round()
                pull_s += p
                push_s += q
            dt = time.perf_counter() - t0
            moved = (monitor.GLOBAL.get("multihost/pull_bytes")
                     + monitor.GLOBAL.get("multihost/push_bytes") - b0)
            out_wire[wire] = {
                "cross_host_exchange_bytes_per_s": round(moved / dt, 1),
                "exchange_keys_per_s": round(
                    MULTIHOST_ROUNDS * keys.size * 2 / dt, 1),
                "pull_ms": round(pull_s / MULTIHOST_ROUNDS * 1e3, 2),
                "push_ms": round(push_s / MULTIHOST_ROUNDS * 1e3, 2),
                "wire_bytes_per_round": int(moved // MULTIHOST_ROUNDS),
                # One pass boundary = one pull + one push of the pass's
                # working set: the DCN byte bill the quantized wire
                # shrinks. Gated lower-better ("_bytes_").
                "cross_host_bytes_per_pass": int(
                    moved // MULTIHOST_ROUNDS),
            }
    finally:
        flags.set_flags({"multihost_wire_dtype": prev})
    assert (out_wire["int8"]["cross_host_bytes_per_pass"] * 2
            <= out_wire["f32"]["cross_host_bytes_per_pass"]), out_wire

    # Overlapped boundary exchange (the split-build early pulls + this
    # round's background exchange worker): each round writes the pass
    # back with push_from_pass_async — the 50% shared window pushes
    # synchronously, the bulk drains on the worker while the "trainer"
    # computes — then the next pass pulls its shared window
    # barrier-free at the boundary. exchange_overlap_frac = 1 -
    # wait/busy over the phase; gated higher-better ("overlap_frac").
    _tick("multihost:overlap")
    from paddlebox_tpu.embedding.table import shared_key_mask
    half = np.zeros(keys.size, bool)
    half[::2] = True
    rows = store.pull_for_pass(keys, pass_id=1000)
    xs0 = store.exchange_stats()
    ov_t0 = time.perf_counter()
    for r in range(MULTIHOST_ROUNDS):
        pid = 1000 + r
        job = store.push_from_pass_async(keys, rows,
                                         priority_select=half,
                                         pass_id=pid)
        while not job.done:          # the pass's training compute
            np.multiply(rows["emb"], np.float32(1.0))
        store.pull_for_pass(keys, half, pass_id=pid + 1,
                            barrier=False, boundary=True)
        rows = store.pull_for_pass(keys, pass_id=pid + 1)
    ov_s = time.perf_counter() - ov_t0
    xs1 = store.exchange_stats()
    xbusy = xs1["exchange_busy_ms"] - xs0["exchange_busy_ms"]
    xwait = xs1["exchange_wait_ms"] - xs0["exchange_wait_ms"]
    overlap = {
        "exchange_overlap_frac": round(
            max(0.0, min(1.0, 1.0 - xwait / max(xbusy, 1e-9))), 4),
        "exchange_busy_ms": round(xbusy, 2),
        "exchange_wait_ms": round(xwait, 2),
        "overlap_round_ms": round(ov_s / MULTIHOST_ROUNDS * 1e3, 2),
    }

    # Tracing + scrape overhead on the exchange path (f32 wire): the
    # same pull+push rounds with the span ring ON — every RPC then
    # carries a trace context and client/server spans — plus one
    # metrics_snapshot scrape of every shard per round. The keys/s
    # delta is `telemetry_overhead_frac`, gated lower-better by
    # tools/perf_gate.py.
    _tick("multihost:telemetry-overhead")
    from paddlebox_tpu.core import telemetry_scrape, trace
    off_t0 = time.perf_counter()
    for _ in range(MULTIHOST_ROUNDS):
        timed_round()
    off_s = time.perf_counter() - off_t0
    trace.enable()
    try:
        targets = {f"shard{i}": ep for i, ep in enumerate(eps)}
        on_t0 = time.perf_counter()
        for _ in range(MULTIHOST_ROUNDS):
            timed_round()
            telemetry_scrape.scrape_cluster(targets, with_stats=False)
        on_s = time.perf_counter() - on_t0
    finally:
        trace.disable()
        trace.clear()
    keys_off = MULTIHOST_ROUNDS * keys.size * 2 / off_s
    keys_on = MULTIHOST_ROUNDS * keys.size * 2 / on_s
    # Health plane: history sampler + alert engine ON (100ms cadence
    # over the global + per-shard instance rings, burn-rate pack
    # evaluated per tick) for the same rounds — the additional keys/s
    # cost is history_overhead_frac; alerts_firing must be 0 on a
    # healthy bench. Both gated by tools/perf_gate.py.
    from paddlebox_tpu.core import alerts as _alerts
    from paddlebox_tpu.core import timeseries as _timeseries
    _prev_hp = {k: flags.flag(k)
                for k in ("history_interval_s", "alerts_enable")}
    flags.set_flags({"history_interval_s": 0.1, "alerts_enable": True})
    try:
        _timeseries.init_from_flags()
        _alerts.init_from_flags()
        hp_t0 = time.perf_counter()
        for _ in range(MULTIHOST_ROUNDS):
            timed_round()
        hp_s = time.perf_counter() - hp_t0
        hp_firing = _alerts.firing_count()
    finally:
        _alerts.shutdown()
        _timeseries.GLOBAL_SAMPLER.stop()
        flags.set_flags(_prev_hp)
    keys_health = MULTIHOST_ROUNDS * keys.size * 2 / hp_s
    telemetry = {
        "trace_off_keys_per_s": round(keys_off, 1),
        "trace_on_keys_per_s": round(keys_on, 1),
        "telemetry_overhead_frac": round(
            max(0.0, 1.0 - keys_on / max(keys_off, 1e-9)), 4),
        "history_on_keys_per_s": round(keys_health, 1),
        "history_overhead_frac": round(
            max(0.0, 1.0 - keys_health / max(keys_off, 1e-9)), 4),
        "alerts_firing": int(hp_firing),
    }

    # Grow-by-one reshard at the measured table size, audited against
    # the minimal-transfer bound.
    _tick("multihost:reshard")
    grown, geps = start_local_shards(hosts + 1, cfg)
    joiner, jep = grown[hosts], geps[hosts]
    stop_shards(grown[:hosts])
    rec = execute_reshard(eps, eps + [jep])
    minimal = rows_moved_minimal(ShardRangeTable.for_world(hosts),
                                 ShardRangeTable.for_world(hosts + 1),
                                 keys)
    assert rec["moved_rows"] == minimal, (rec["moved_rows"], minimal)
    stop_shards(servers)
    joiner.stop()

    # Replicated-tier failover: a replicas=2 cluster under a pull loop
    # takes a scripted primary kill — pull p99 across the kill is the
    # failover blip (reads fail over to the surviving backup), the
    # promote+re-replicate repair restores R, and the journal catch-up
    # rate is measured by re-syncing a lagged backup.
    _tick("multihost:failover")
    fo = _bench_multihost_failover(cfg, keys)

    f32 = out_wire["f32"]
    return {
        "metric": f"multihost_{hosts}host_exchange_keys_per_sec",
        "value": f32["exchange_keys_per_s"],
        "unit": "keys/s",
        "hosts": hosts,
        "pass_keys": int(keys.size),
        "dim": MULTIHOST_DIM,
        "wire": out_wire,
        "reshard_ms": round(rec["reshard_ms"], 2),
        "reshard_moved_rows": int(rec["moved_rows"]),
        "reshard_rows_per_s": round(
            rec["moved_rows"] / max(rec["reshard_ms"], 1e-6) * 1e3, 1),
        "reshard_minimal_frac": round(
            rec["moved_rows"] / max(minimal, 1), 4),
        "failover_blip_ms": fo["failover_blip_ms"],
        "failover_pull_p50_ms": fo["pull_p50_ms"],
        "repair_ms": fo["repair_ms"],
        "journal_catchup_rows_per_s": fo["journal_catchup_rows_per_s"],
        "failover_failed_pulls": fo["failed_pulls"],  # provenance: 0
        "overlap": overlap,
        "telemetry": telemetry,
        "embedding_quant_block": int(flags.flag("embedding_quant_block")),
    }


def _bench_multihost_failover(cfg, keys) -> dict:
    """Scripted primary kill under a pull loop (MULTIHOST.md
    "replicated tier"): records the pull p99 across the kill
    (failover_blip_ms — the read-failover cost of losing a shard
    host), the promote + re-replicate repair wall time (repair_ms),
    and the journal catch-up throughput for a briefly-lagged backup
    (journal_catchup_rows_per_s)."""
    import numpy as np

    from paddlebox_tpu.core import monitor
    from paddlebox_tpu.multihost import (MultiHostStore, ReplicaMap,
                                         start_local_shards, stop_shards)
    from paddlebox_tpu.multihost.shard_service import ShardServer

    sub = keys[: max(1, keys.size // 8)]   # a serving-sized working set
    servers, eps = start_local_shards(2, cfg, replicas=2)
    store = MultiHostStore(cfg, eps, replicas=2)
    rows = store.pull_for_pass(sub)
    store.push_from_pass(sub, rows)

    # Journal catch-up rate: sever the backup's conns so one push lags,
    # then time the forced re-sync (delta replay of the missed rows).
    servers[1].close_connections()
    rows["show"] += 1.0
    t0 = time.perf_counter()
    store.push_from_pass(sub, rows)        # in-line catch-up fires here
    store.sync_replicas()
    catchup_s = time.perf_counter() - t0
    catchup_rows_per_s = sub.size / max(catchup_s, 1e-9)

    # The scripted kill under a pull loop.
    lat_ms, failed = [], 0
    kill_at = 10
    fresh = None
    try:
        for i in range(30):
            if i == kill_at:
                servers[1].kill()          # the primary of ~half the keys
            t1 = time.perf_counter()
            try:
                store.pull_for_pass(sub)
            except Exception:
                failed += 1
                continue
            lat_ms.append((time.perf_counter() - t1) * 1e3)
        lat = np.sort(np.asarray(lat_ms))
        blip_ms = float(lat[min(len(lat) - 1,
                                int(0.99 * len(lat)))])
        p50_ms = float(lat[len(lat) // 2])

        # Repair: promote the survivor, re-replicate to a fresh host.
        from paddlebox_tpu.multihost.reshard import \
            ElasticReshardController
        ctl = ElasticReshardController(store, None)
        t2 = time.perf_counter()
        rec = ctl.repair(reason="bench scripted kill")
        assert rec is not None
        fresh = ShardServer("127.0.0.1:0", 0, store.ranges, cfg)
        new_map = store.replica_map
        for slot in range(new_map.world):
            new_map = new_map.add_backup(slot, fresh.endpoint)
        ctl._adopt_map(new_map)
        store.sync_replicas()
        repair_ms = (time.perf_counter() - t2) * 1e3
        assert store.replica_map.replication == 2
        monitor.set_gauge("multihost/repair_ms", repair_ms)
    finally:
        store.close()
        stop_shards(servers + ([fresh] if fresh else []))
    return {"failover_blip_ms": round(blip_ms, 2),
            "pull_p50_ms": round(p50_ms, 2),
            "repair_ms": round(repair_ms, 2),
            "journal_catchup_rows_per_s": round(catchup_rows_per_s, 1),
            "failed_pulls": failed}


ONLINE_DAYS = 3                  # replayed log days (TTL needs >= 3)
ONLINE_PASS_FILES = 2            # files per carved incremental pass
# ---------------------------------------------------------------------------
# RPC plane microbench (`bench.py rpc`): the event-loop/mux wire (RPC.md)
# ---------------------------------------------------------------------------

RPC_DEPTHS = (1, 4, 16)
RPC_PAYLOAD_F32 = ({"64b": 16, "64kb": 16384} if _SMALL
                   else {"64b": 16, "64kb": 16384, "1mb": 262144})
RPC_WINDOWS = 60 if _SMALL else 400


def bench_rpc() -> dict:
    """Echo RTT ladder over one loopback FramedRPCServer: payload size
    × outstanding depth × wire plane ({legacy: v1 frames, one call per
    RTT (depth > 1 = the old thread-per-call fan-out); mux: v2
    request-id multiplexing, ``call_async`` pipelining on ONE socket;
    sg: mux + zero-copy scatter/gather array frames}). Per cell:
    calls_per_s, the window-completion p50/p99, and payload bytes/s —
    all pinned by tools/perf_gate.py. The headline is mux calls_per_s
    at depth ≥ 2; ``mux_over_legacy_at_o4`` records the pipelining win
    that motivated the mux wire (provenance, not gated)."""
    from paddlebox_tpu.core import monitor
    from paddlebox_tpu.distributed import rpc

    class _EchoServer(rpc.FramedRPCServer):
        service_name = "rpc-bench"

        def handle_echo(self, req):
            return {"a": req["a"]}

    modes = {
        "legacy": {"rpc_mux": False, "rpc_sg_min_bytes": -1},
        "mux": {"rpc_mux": True, "rpc_sg_min_bytes": -1},
        "sg": {"rpc_mux": True, "rpc_sg_min_bytes": 4096},
    }
    prev = {k: flags.flag(k) for k in ("rpc_mux", "rpc_sg_min_bytes")}
    out_modes = {}
    sg0 = monitor.GLOBAL.get("rpc/sg_frames")
    try:
        for mode, fl in modes.items():
            _tick(f"rpc:{mode}")
            flags.set_flags(fl)
            srv = _EchoServer("127.0.0.1:0")
            conn = rpc.FramedRPCConn(srv.endpoint, timeout=60.0,
                                     service_name="rpc-bench",
                                     idempotent=("echo",))
            cells = {}
            try:
                for pname, n in RPC_PAYLOAD_F32.items():
                    a = np.arange(n, dtype=np.float32)
                    per_call = a.nbytes * 2  # request + echoed reply
                    windows = max(20, RPC_WINDOWS // max(1, n // 4096))
                    conn.call("echo", a=a)  # warm connect + caps
                    for depth in RPC_DEPTHS:
                        walls = []
                        t0 = time.perf_counter()
                        for _ in range(windows):
                            w0 = time.perf_counter()
                            if depth == 1:
                                conn.call("echo", a=a)
                            else:
                                futs = [conn.call_async("echo", a=a)
                                        for _ in range(depth)]
                                for f in futs:
                                    f.result()
                            walls.append(time.perf_counter() - w0)
                        dt = time.perf_counter() - t0
                        calls = windows * depth
                        cells[f"{pname}_o{depth}"] = {
                            "calls_per_s": round(calls / dt, 1),
                            "p50_ms": round(float(
                                np.percentile(walls, 50)) * 1e3, 3),
                            "p99_ms": round(float(
                                np.percentile(walls, 99)) * 1e3, 3),
                            "bytes_per_s": round(
                                calls * per_call / dt, 1),
                        }
            finally:
                conn.close()
                srv.stop()
                srv.close_connections()
            out_modes[mode] = cells
    finally:
        flags.set_flags(prev)
    mux_r = out_modes["mux"]["64b_o4"]["calls_per_s"]
    leg_r = out_modes["legacy"]["64b_o4"]["calls_per_s"]
    return {
        "metric": "rpc_echo_mux_calls_per_sec",
        "value": mux_r,
        "unit": "calls/s",
        "windows": RPC_WINDOWS,                       # provenance
        "mux_over_legacy_at_o4": round(
            mux_r / max(leg_r, 1e-9), 3),             # provenance
        "sg_frames": int(monitor.GLOBAL.get("rpc/sg_frames") - sg0),
        "modes": out_modes,
    }


ONLINE_FILES_PER_DAY = 4 if _SMALL else 8
ONLINE_BATCH = 128 if _SMALL else 512
ONLINE_ROWS_PER_FILE = ONLINE_BATCH * (2 if _SMALL else 4)
ONLINE_SLOTS = 4
ONLINE_KEYS_PER_DAY = 2_000 if _SMALL else 20_000


def bench_online() -> dict:
    """Streaming online-learning mode (ONLINE.md): replay a fixed
    multi-day event log as a stream through StreamRunner — every carved
    incremental pass trains and publishes a delta through the donefile
    path serving tails — and record the freshness/lifecycle numbers the
    roadmap asked for: event→servable latency quantiles, passes/hour,
    and the post-shrink store row count that proves TTL/decay bounds
    the table under infinite traffic (each day's keys churn, so without
    the lifecycle the store would grow ~linearly in days)."""
    import jax

    from paddlebox_tpu.data.slots import DataFeedConfig, SlotConf
    from paddlebox_tpu.embedding import TableConfig
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.parallel import HybridTopology, build_mesh
    from paddlebox_tpu.stream import StreamRunner
    from paddlebox_tpu.train import CTRTrainer, TrainerConfig

    rng = np.random.default_rng(0)
    slot_names = tuple(f"s{i}" for i in range(ONLINE_SLOTS))
    feed = DataFeedConfig(
        slots=tuple(SlotConf(s, avg_len=1.0) for s in slot_names),
        batch_size=ONLINE_BATCH)
    model = DeepFM(slot_names=slot_names, emb_dim=8, hidden=(32,))
    mesh = build_mesh(HybridTopology(dp=len(jax.devices())))
    trainer = CTRTrainer(model, feed,
                         TableConfig(name="emb", dim=8,
                                     learning_rate=0.05),
                         mesh=mesh,
                         config=TrainerConfig(auc_num_buckets=1 << 10))
    trainer.init(seed=0)

    def write_day_files(log_dir, day_idx):
        """One day of events: keys drawn from a per-day sliding window
        (half the window carries over, half churns) so TTL has real
        unseen traffic to expire."""
        lo = 1 + day_idx * ONLINE_KEYS_PER_DAY // 2
        keys = np.arange(lo, lo + ONLINE_KEYS_PER_DAY, dtype=np.uint64)
        files = []
        for i in range(ONLINE_FILES_PER_DAY):
            ids = rng.choice(keys, (ONLINE_ROWS_PER_FILE, ONLINE_SLOTS))
            labels = _planted_labels(rng, ids[:, 0])
            line = labels.astype("U1")
            for j in range(ONLINE_SLOTS):
                line = np.char.add(line, f" s{j}:")
                line = np.char.add(line, ids[:, j].astype("U20"))
            # Atomic appearance (write-tmp-then-rename), the tailer's
            # documented arrival convention.
            name = f"day{day_idx}-{i:04d}.log"
            tmp = os.path.join(log_dir, "." + name + ".tmp")
            with open(tmp, "w") as f:
                f.write("\n".join(line.tolist()) + "\n")
            final = os.path.join(log_dir, name)
            os.replace(tmp, final)
            files.append(final)
        return files

    from paddlebox_tpu.core import flags as flagmod
    prev = {k: flagmod.flag(k) for k in
            ("stream_pass_events", "table_ttl_days", "quality_collect")}
    out_rows = {}
    with tempfile.TemporaryDirectory() as tmpdir:
        log_dir = os.path.join(tmpdir, "events")
        os.makedirs(log_dir)
        runner = StreamRunner(
            trainer, feed, os.path.join(tmpdir, "out"), log_dir=log_dir,
            day_of=lambda p: os.path.basename(p).split("-")[0],
            shuffle=False, num_reader_threads=2)
        try:
            flagmod.set_flags({
                "stream_pass_events":
                    ONLINE_PASS_FILES * ONLINE_ROWS_PER_FILE,
                "table_ttl_days": 1,
                # Model-quality plane ON for the streamed run: per-pass
                # COPC/calibration + slot health + drift alarms ride
                # the same replay (the "quality" record block below).
                "quality_collect": True})
            _tick("online:stream")
            t0 = time.perf_counter()
            passes = 0
            for d in range(ONLINE_DAYS):
                write_day_files(log_dir, d)
                passes += runner.poll_once(flush=True)
                runner.end_day()
                out_rows[f"day{d}"] = int(
                    trainer.engine.store.num_features)
                _tick(f"online:day{d}")
            wall = time.perf_counter() - t0
        finally:
            flagmod.set_flags(prev)
        store_rows = int(trainer.engine.store.num_features)

    events = ONLINE_DAYS * ONLINE_FILES_PER_DAY * ONLINE_ROWS_PER_FILE
    fresh = runner.freshness_quantiles() or {}
    eps = events / wall
    # Model-quality record (core/quality.py, collected per carved
    # pass): headline COPC + the per-pass calibration-error p99 from
    # the registry digest, total drift alarms, the worst slot's
    # example coverage, and the data-shape provenance (skew/churn —
    # recorded, never gated).
    from paddlebox_tpu.core import monitor as _mon
    snap = _mon.snapshot()
    cal_d = _mon.GLOBAL.quantile_digest("quality/calibration_error")
    slot_covs = [v for k, v in snap.items()
                 if k.startswith("quality/slot_coverage/")]
    quality_block = {
        "copc": round(float(snap.get("quality/copc", float("nan"))), 4),
        "calibration_error": (
            {"p99": round(cal_d.quantile(0.99), 5)}
            if cal_d is not None and cal_d.count else None),
        "quality_alarms": int(sum(
            v for k, v in snap.items()
            if k.startswith("quality/alarms/"))),
        "slot_coverage": (round(min(slot_covs), 4) if slot_covs
                          else None),
        "skew_top_share": round(float(
            snap.get("quality/skew_top_share", 0.0)), 4),
        "key_churn": round(float(
            snap.get("quality/key_churn", 0.0)), 4),
    }
    return {
        "metric": "online_stream_events_per_sec",
        "value": round(eps, 1),
        "unit": "events/s",
        "vs_baseline": None,
        "event_to_servable_ms": {
            k: (round(v, 1) if v is not None else None)
            for k, v in fresh.items() if k in ("p50", "p99")},
        "passes_per_hour": round(passes / wall * 3600.0, 1),
        "post_shrink_store_rows": store_rows,
        "day1_rows": out_rows.get("day0"),
        "day3_over_day1_rows": (
            round(out_rows["day%d" % (ONLINE_DAYS - 1)]
                  / max(out_rows["day0"], 1), 4)
            if "day0" in out_rows else None),
        "stream_passes": passes,
        "events": events,
        "table_ttl_days": 1,
        "quality": quality_block,
        "n_devices": len(jax.devices()),
    }


FLEET_TRACE = ""   # `bench.py fleet --trace seed[,duration_s[,rps]]`


def bench_fleet() -> dict:
    """Autopilot soak: replay a seeded, diurnal, hot-set-skewed trace
    (serving/traceload.py — replay-pure, so two runs of one spec are
    the same trace) against a small in-process fleet with the full
    control loop armed: history sampler + alert engine (PR 18 plane),
    FleetAutopilot scaling on the merged stats, and the COPC-gated
    canary controller watching a live donefile. The chaos script rides
    the trace: a 10x spike, a replica kill, and a calibration-poisoned
    BASE publish that must be confined to the canary subset and rolled
    back on the real sampled-label join. Records the soak/* keys
    tools/perf_gate.py gates: failed_rpcs and predict_p99_ms lower-
    better, action counts as provenance."""
    import dataclasses
    import shutil

    import jax

    from paddlebox_tpu.checkpoint.protocol import CheckpointProtocol
    from paddlebox_tpu.core import (alerts, flags as flagmod, monitor,
                                    telemetry_scrape, timeseries)
    from paddlebox_tpu.data.slots import DataFeedConfig, SlotConf
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.serving import traceload
    from paddlebox_tpu.serving.autopilot import FleetAutopilot
    from paddlebox_tpu.serving.predictor import (CTRPredictor,
                                                 load_xbox_model)
    from paddlebox_tpu.serving.router import FleetRouter
    from paddlebox_tpu.serving.service import PredictClient, PredictServer

    spec = [s for s in FLEET_TRACE.split(",") if s.strip()]
    seed = int(spec[0]) if len(spec) > 0 else 0
    duration = float(spec[1]) if len(spec) > 1 else (6.0 if _SMALL
                                                    else 20.0)
    rps = float(spec[2]) if len(spec) > 2 else 30.0

    slots = ("u", "i")
    dim = 8
    n_keys = 2000
    feed = DataFeedConfig(
        slots=tuple(SlotConf(s, avg_len=1.0) for s in slots),
        batch_size=64)
    model = DeepFM(slot_names=slots, emb_dim=dim, hidden=())
    dense = model.init(jax.random.PRNGKey(0))
    mrng = np.random.default_rng(3)
    keys = np.arange(1, n_keys + 1, dtype=np.uint64)
    emb = mrng.normal(size=(n_keys, dim)).astype(np.float32) * 0.02
    w = mrng.normal(size=(n_keys,)).astype(np.float32) * 0.02

    tmp = tempfile.mkdtemp(prefix="bench_fleet_")
    root = os.path.join(tmp, "publish")
    proto = CheckpointProtocol(root)

    def write_base(day, e, ww):
        d = proto.model_dir(day, 0)
        os.makedirs(d, exist_ok=True)
        np.savez(os.path.join(d, "embedding.xbox.npz"),
                 keys=keys, emb=e, w=ww)
        return d

    base_dir = write_base("20260801", emb, w)
    proto.publish("20260801")
    # The poisoned base: weights shifted so every prediction saturates
    # toward 1.0 — served COPC (label_sum/pred_sum) collapses to ~0.5
    # against the alternating labels below, a textbook calibration
    # break the canary gate must catch.
    write_base("20260802", emb + 5.0, w + 5.0)

    prev = {k: flagmod.flag(k) for k in (
        "quality_sample_rate", "quality_min_events",
        "serving_slo_p99_ms", "autopilot_cooldown_s",
        "autopilot_min_replicas", "autopilot_max_replicas",
        "autopilot_poll_s", "autopilot_canary_replicas",
        "autopilot_canary_min_labels", "autopilot_canary_copc_margin",
        "autopilot_canary_timeout_s", "history_interval_s",
        "alerts_enable", "fleet_health_interval_s")}
    flagmod.set_flags({
        "quality_sample_rate": 1.0, "quality_min_events": 8,
        "serving_slo_p99_ms": 2000.0,   # generous CPU bound: the soak
        # asserts p99 stays UNDER it, scale-out triggers on the kill
        "autopilot_cooldown_s": 1.0, "autopilot_min_replicas": 2,
        "autopilot_max_replicas": 4, "autopilot_poll_s": 0.2,
        "autopilot_canary_replicas": 1,
        "autopilot_canary_min_labels": 24,
        "autopilot_canary_copc_margin": 0.2,
        "autopilot_canary_timeout_s": 30.0,
        "history_interval_s": 0.2, "alerts_enable": True,
        "fleet_health_interval_s": 0.2})
    monitor.reset()
    _tick("fleet:setup")

    def make_server(rid):
        k, e, ww = load_xbox_model(base_dir, "embedding")
        pred = CTRPredictor(model, feed, k, e, ww, dense,
                            compute_dtype="float32")
        return PredictServer("127.0.0.1:0", pred, replica_id=rid)

    servers = {f"replica-{i}": make_server(f"replica-{i}")
               for i in range(2)}
    router = FleetRouter("127.0.0.1:0",
                         replicas=[s.endpoint
                                   for s in servers.values()])
    timeseries.init_from_flags()
    alerts.init_from_flags()

    spawn_n = [0]

    def spawn():
        rid = f"auto-{spawn_n[0]}"
        spawn_n[0] += 1
        s = make_server(rid)
        servers[rid] = s
        router.fleet.add_replica(rid, s.endpoint, ready=True)
        return rid

    def retire(rid):
        s = servers.pop(rid, None)
        if s is not None:
            s.stop()

    # registry=router.metrics: action counters land in the router's
    # instance registry too, so ONE telemetry_scrape sweep over the
    # fleet shows every action the autopilot took.
    autopilot = FleetAutopilot(
        router.fleet, lambda: router.handle_stats({}),
        donefile_root=root, spawn=spawn, retire=retire,
        registry=router.metrics,
        state_path=os.path.join(tmp, "autopilot.json"))
    autopilot.start()

    # Trace skew calibrated from the live observatory when it has
    # reported (quality/slot_top_share gauges in a replica snapshot);
    # falls back to the config default on a cold start.
    snap = next(iter(servers.values())).metrics.snapshot_all()
    cfg = traceload.TraceConfig.from_quality(
        snap.get("gauges") or {}, seed=seed, duration_s=duration,
        base_rps=rps, n_keys=n_keys, slots=slots, rows_per_request=2,
        chaos=(
            traceload.ChaosEvent(at_s=0.30 * duration, kind="spike",
                                 duration_s=0.15 * duration,
                                 factor=10.0),
            traceload.ChaosEvent(at_s=0.40 * duration,
                                 kind="kill_replica", arg="replica-1"),
            traceload.ChaosEvent(at_s=0.50 * duration,
                                 kind="poison_delta", arg="20260802"),
        ))
    gen = traceload.TraceGenerator(cfg)

    cli = PredictClient(router.endpoint)
    failed = [0]
    lines0 = next(iter(gen.requests())).lines
    cli.predict(list(lines0))  # compile outside the soak window

    def send(req):
        try:
            cli.predict(list(req.lines), rid=req.rid)
            cli.send_labels(
                req.rid,
                [(int(req.rid.rsplit("-", 1)[1]) + r) % 2
                 for r in range(len(req.lines))])
        except Exception as e:  # noqa: BLE001 - every failure counts
            failed[0] += 1
            print(f"[bench fleet] rpc failed: {e!r}", file=sys.stderr)

    def kill_replica(ev):
        s = servers.pop(ev.arg, None)
        if s is not None:
            # Kill-like teardown: refuse new connects AND sever the
            # router's pooled conns (a graceful stop would keep
            # draining them and the fleet would never notice).
            s.stop()
            s.close_connections()

    def poison(ev):
        proto.publish(ev.arg)

    _tick("fleet:replay")
    t0 = time.perf_counter()
    replayed = traceload.replay(
        gen, send, handlers={"kill_replica": kill_replica,
                             "poison_delta": poison})
    replay_wall = time.perf_counter() - t0
    # Drain the canary: the verdict needs joined labels on BOTH sides
    # after the poisoned base staged — keep the labeled trace flowing
    # (fresh seed: content no longer asserted) until it resolves.
    _tick("fleet:canary-drain")
    t_end = time.perf_counter() + 30.0
    extra = 1
    while autopilot.canary.state.data.get("canary") is not None \
            and time.perf_counter() < t_end:
        drain = traceload.TraceGenerator(dataclasses.replace(
            cfg, seed=seed + extra, chaos=()))
        extra += 1
        for req in drain.requests():
            if autopilot.canary.state.data.get("canary") is None \
                    or time.perf_counter() > t_end:
                break
            send(req)

    st = router.handle_stats({})
    snap_all = monitor.snapshot()
    # One cluster sweep must show every action the autopilot took.
    targets = {"router": router.endpoint}
    targets.update({rid: s.endpoint for rid, s in servers.items()})
    sweep = telemetry_scrape.scrape_cluster(targets, with_stats=False)
    sweep_counters = (sweep.get("merged") or {}).get("counters") or {}
    reports = list(autopilot.canary.reports)

    autopilot.stop()
    alerts.shutdown()
    timeseries.GLOBAL_SAMPLER.stop()
    cli.close()
    router.stop()
    for s in servers.values():
        s.stop()
    flagmod.set_flags(prev)
    shutil.rmtree(tmp, ignore_errors=True)

    scale_out = int(snap_all.get("autopilot/actions/scale_out", 0))
    scale_in = int(snap_all.get("autopilot/actions/scale_in", 0))
    rollbacks = [r for r in reports if r.get("verdict") == "rollback"]
    return {
        # Headline follows the bench convention (value = throughput,
        # higher-better): replayed requests per wall second THROUGH the
        # chaos. The robustness keys gate under soak/*.
        "metric": "fleet_soak_requests_per_s",
        "value": round(replayed["sent"] / max(replay_wall, 1e-9), 1),
        "unit": "req/s",
        "soak": {
            "failed_rpcs": int(failed[0]),
            "predict_p99_ms": (st.get("latency_ms") or {}).get("p99"),
            "degraded_frac": round(
                st.get("degraded_rpcs", 0)
                / max(st.get("predict_rpcs", 1), 1), 4),
            "scale_actions": scale_out + scale_in,
            "canary_blocked": len(rollbacks),
        },
        "trace": {"seed": seed, "duration_s": duration,
                  "base_rps": rps, "hot_share": cfg.hot_share,
                  "requests": int(replayed["sent"]),
                  "events_fired": int(replayed["events_fired"])},
        "actions": {k.rsplit("/", 1)[1]: int(v)
                    for k, v in snap_all.items()
                    if k.startswith("autopilot/actions/")},
        "canary_reports": reports,
        "scrape_shows_actions": any(
            k.startswith("autopilot/actions/")
            for k in sweep_counters),
        "slo_p99_ms_flag": 2000.0,
        "n_devices": len(jax.devices()),
    }


CONFIGS = {
    "deepfm": bench_deepfm,
    "resnet50": bench_resnet50,
    "bert_dp": bench_bert_dp,
    "gpt": bench_gpt,
    "wide_deep": bench_wide_deep,
    "graph": bench_graph,
    "serving": bench_serving,
    "serve": bench_serving,  # alias: `bench.py serve --clients 1,8,32`
    "multihost": bench_multihost,  # `bench.py multihost --hosts N`
    "online": bench_online,        # streaming freshness/lifecycle mode
    "rpc": bench_rpc,              # event-loop/mux wire echo ladder
    "fleet": bench_fleet,  # autopilot soak: `bench.py fleet --trace`
}


def _preflight_scatter_kernel(n: int, aw: int, pass_keys: int) -> None:
    """Run the push scatter-accumulate once on the real backend at the
    EXACT shape the selected bench will compile — same update count,
    payload width, and pass-table block (jit/Mosaic treat each shape as
    a fresh compile, so any other shape would not predict the real one)
    — through the same ``_accumulate`` wrapper the jitted step uses, and
    compare it with the XLA scatter. A kernel that does not compile,
    does not run, or disagrees fails the bench here: there is no
    fallback to another path."""
    from paddlebox_tpu.core import flags as flagmod
    if flagmod.flag("sparse_scatter_kernel") == "xla":
        return  # operator pinned the XLA path; nothing to check
    import jax.numpy as jnp

    from paddlebox_tpu.embedding.lookup import _accumulate
    from paddlebox_tpu.embedding.table import plan_shards
    # Mirror make_push_fn at the bench's actual device count: the jitted
    # step compiles PER-SHARD shapes (block = rows_per_shard + 1, n/ndev
    # updates inside shard_map) — a single-shard probe on a multi-chip
    # bench would validate a shape the step never compiles.
    ndev = len(jax.devices())
    block = plan_shards(pass_keys, ndev) + 1
    n = n // ndev
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.integers(0, block - 1, n).astype(np.int32))
    pay = jnp.asarray(rng.standard_normal((n, aw)).astype(np.float32))
    out = _accumulate(rows, pay, block)
    ref = jnp.zeros((block, aw), jnp.float32).at[rows].add(pay)
    err = float(jnp.max(jnp.abs(out - ref)))
    if not err < 1e-3:
        raise RuntimeError(
            f"sparse scatter kernel disagrees with the XLA scatter at "
            f"[{n} x {aw}] into {block} rows: max err {err}")


def _preflight_gather_kernel(n: int, dim: int, pass_keys: int) -> None:
    """The pull-side twin of _preflight_scatter_kernel: the sorted-stream
    gather at the EXACT per-shard shape the selected bench will compile
    (fused record width from the table config's optimizer, pull width
    dim+3), through the same ``_gather_rows`` wrapper the jitted step
    uses, against the XLA gather. Must be exact; any failure fails the
    bench."""
    from paddlebox_tpu.core import flags as flagmod
    if flagmod.flag("sparse_gather_kernel") == "xla":
        return
    import jax.numpy as jnp

    from paddlebox_tpu.embedding import TableConfig, make_sparse_optimizer
    from paddlebox_tpu.embedding.lookup import _gather_rows
    from paddlebox_tpu.embedding.table import plan_shards
    opt = make_sparse_optimizer(TableConfig(dim=dim))
    w = dim + 3 + opt.emb_state_width(dim) + opt.w_state_width()
    pw = dim + 3
    ndev = len(jax.devices())
    block = plan_shards(pass_keys, ndev) + 1
    n = n // ndev
    rng = np.random.default_rng(1)
    # block - 1 is the trash row: the kernel path DROPS it to zeros by
    # contract, so the probe keys stay below it.
    rows = jnp.asarray(rng.integers(0, block - 1, n).astype(np.int32))
    vals = jnp.asarray(rng.standard_normal((block, w)).astype(np.float32))
    out = _gather_rows(vals, rows, pw, block)
    err = float(jnp.max(jnp.abs(out - vals[rows, :pw])))
    if err != 0.0:
        raise RuntimeError(
            f"sparse gather kernel disagrees with the XLA gather at "
            f"[{n}] from [{block} x {w}]: max err {err}")


def main() -> None:
    global SERVE_CLIENTS, SERVE_REPLICAS, MULTIHOST_HOSTS, SLOT_AUC
    global FLEET_TRACE
    argv = list(sys.argv[1:])
    if "--slot-auc" in argv:
        i = argv.index("--slot-auc")
        SLOT_AUC = []
        del argv[i]
    for i, a in enumerate(argv):
        if a.startswith("--slot-auc="):
            SLOT_AUC = [s for s in a.split("=", 1)[1].split(",") if s]
            del argv[i]
            break
    if "--clients" in argv:
        i = argv.index("--clients")
        SERVE_CLIENTS = argv[i + 1] if i + 1 < len(argv) else "1,8,32"
        del argv[i:i + 2]
    if "--replicas" in argv:
        i = argv.index("--replicas")
        SERVE_REPLICAS = argv[i + 1] if i + 1 < len(argv) else "1,2"
        del argv[i:i + 2]
    if "--hosts" in argv:
        i = argv.index("--hosts")
        MULTIHOST_HOSTS = int(argv[i + 1]) if i + 1 < len(argv) else 2
        del argv[i:i + 2]
    if "--trace" in argv:
        # `bench.py fleet --trace [seed[,duration_s[,rps]]]` — the spec
        # is optional (defaults in bench_fleet); a bare --trace keeps
        # the seeded defaults.
        i = argv.index("--trace")
        if i + 1 < len(argv) and not argv[i + 1].startswith("-") \
                and argv[i + 1] not in CONFIGS:
            FLEET_TRACE = argv[i + 1]
            del argv[i:i + 2]
        else:
            FLEET_TRACE = ""
            del argv[i]
    name = argv[0] if argv else "deepfm"
    _tick("device-probe")
    import jax.numpy as jnp
    _sync(jnp.ones((8,), jnp.float32).sum())
    if not _SMALL and name in ("resnet50", "bert_dp", "gpt"):
        _peak_flops()       # unknown device_kind: fail before the run
    if not _SMALL and not _native_available():
        # Without the C++ host library every host-side stage (parse,
        # keymap, store index) silently runs its numpy reference.
        raise SystemExit(
            "bench: the native host library did not build (g++ missing "
            "or failing — see the warning above); host-side numbers "
            "would measure the numpy fallbacks")
    if name in ("deepfm", "wide_deep") and not _SMALL:
        # (updates/step, payload width, pass keys) of the selected CTR
        # config — aw = emb_dim + 4 ([g_emb | g_w | show | click |
        # count]). Small/CPU mode never selects the Pallas path (flag
        # "auto" gates on the tpu backend), so no preflight.
        _tick("preflight")
        if name == "deepfm":
            _preflight_scatter_kernel(BATCH * NUM_SLOTS, EMB_DIM + 4,
                                      PASS_KEYS)
            _preflight_gather_kernel(BATCH * NUM_SLOTS, EMB_DIM,
                                     PASS_KEYS)
        else:
            _preflight_scatter_kernel(WIDE_DEEP_BATCH * WIDE_DEEP_SLOTS,
                                      WIDE_DEEP_EMB_DIM + 4,
                                      WIDE_DEEP_PASS_KEYS)
            _preflight_gather_kernel(WIDE_DEEP_BATCH * WIDE_DEEP_SLOTS,
                                     WIDE_DEEP_EMB_DIM,
                                     WIDE_DEEP_PASS_KEYS)
    _tick(f"bench:{name}")
    out = CONFIGS[name]()
    # Every record names the device it ran on, and what each kernel
    # dispatch site resolved to (not the flag values).
    dev = jax.devices()
    out["platform"] = dev[0].platform
    out["device_kind"] = dev[0].device_kind
    out["device_count"] = len(dev)
    out["resolved_kernels"] = flags.resolved_kernels()
    print(json.dumps(out))
    floor = out.get("auc_floor")
    if isinstance(floor, dict) and not floor["passed"]:
        raise SystemExit(
            f"bench: AUC {out.get('auc')} <= floor {floor['floor']} on "
            f"planted-signal labels — the sparse path is NOT learning")


if __name__ == "__main__":
    main()
