"""Runner ``ctr_day``: the program's own pipelined day loop on a resident store.

What runs is ``train/day_runner.py`` (``pipeline_passes=True``): pass k+1's
files load and its table early-builds while pass k trains, the fused
end/begin program runs at the boundary. The runner only builds the
trainer, fills the store, hands the loop its pass files and watches the
pass boundaries through ``pass_boundary_hook``:

- set-up: the store's resident keys are inserted (rows are initialised on
  the device from the key hash), the pass files are written by a process
  pool meanwhile, and ``warmup_passes`` passes train so that every program
  of a steady pass is compiled or loaded;
- window: opens at the boundary that ends the warm-up and closes at the
  boundary that completes ``window_passes`` more passes, whatever their
  wall time (``--seconds`` is not read here): the same work in every run,
  so a faster program shortens the window and never trains a set of files
  twice inside it (``plan`` refuses a mix whose warm-up and window need
  more than ``distinct_passes``);
- after the window the hook stops the day (the day-end base dump is not
  part of a pass), and one held-out batch is evaluated by the program
  (``eval_pass``) and by the plain reference on the rows read back from
  the store.

The loop runs as a rank that does not write model files
(``is_rank0=False``). The per-pass delta publish is left out on purpose:
``save_delta`` compresses every key dirtied since the last base on one
thread, which is tens of seconds for a pass that was cut to ~15 s of
traffic (PERF.md, Findings and Open questions).
"""

from __future__ import annotations

import importlib
import multiprocessing
import os
import time
from typing import Dict, List

import numpy as np


# The program's tower computes in bf16, the reference in float32, on one
# batch of 16,384 rows. Each logit carries bf16's 2^-8 relative rounding with
# either sign, so the batch means (loss, predicted CTR, MAE, RMSE) move by
# about 2^-8 / sqrt(16384) = 3e-5 relative: measured 6e-6 to 3e-5 at full
# width here. 2^-10 leaves thirty times that, and a dropped slot, rows
# shifted by one or a tower run at half its width land outside
# (tests/test_references.py). AUC is absolute: 2^16 buckets against exact
# ranks differ by about 2e-5.
EVAL_TOL = {"loss": 2.0 ** -10, "auc": 2.0 ** -10, "predicted_ctr": 2.0 ** -10,
            "mae": 2.0 ** -10, "rmse": 2.0 ** -10}
AUC_BAND = (-0.02, 0.005)


class _WindowClosed(Exception):
    """Raised from the boundary hook to end the day once the window and
    the traced span are complete."""


def _feed(config: Dict, batch: int):
    from paddlebox_tpu.data.slots import DataFeedConfig, SlotConf
    model = config["model"]
    slots = tuple(SlotConf(f"s{i}", avg_len=1.0)
                  for i in range(model["slots"]))
    slots += (SlotConf("d", is_dense=True, dim=model["dense_dim"]),)
    return DataFeedConfig(slots=slots, batch_size=batch,
                          slot_capacity_slack=1.0)


def _trainer(config: Dict, chips: int, seed: int):
    import jax

    from paddlebox_tpu.embedding import DeviceFeatureStore, TableConfig
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.parallel import HybridTopology, build_mesh
    from paddlebox_tpu.train import CTRTrainer, TrainerConfig
    model = config["model"]
    mesh = build_mesh(HybridTopology(dp=chips), devices=jax.devices()[:chips])
    feed = _feed(config, config["batch_per_chip"] * chips)
    store = config["store"]
    # the store is built at ``init_rows`` and grows to ``rows`` as it fills
    rows = (1 << store.get("init_rows_log2_per_chip",
                           store["rows_log2_per_chip"])) * chips
    trainer = CTRTrainer(
        DeepFM(slot_names=tuple(f"s{i}" for i in range(model["slots"])),
               emb_dim=model["emb_dim"], dense_dim=model["dense_dim"],
               hidden=tuple(model["hidden"])),
        feed,
        TableConfig(dim=model["emb_dim"],
                    learning_rate=config["sparse_learning_rate"]),
        mesh=mesh,
        config=TrainerConfig(auc_num_buckets=config["auc_num_buckets"],
                             compute_dtype=config["compute_dtype"]),
        store_factory=lambda c: DeviceFeatureStore(
            c, mesh=mesh, seed=seed, capacity_hint=rows))
    trainer.init(seed=seed)
    return trainer, feed


def _fill_store(store, config: Dict, chips: int) -> None:
    """Resident keys 1..N in chunks: ``ensure_rows`` builds each chunk's
    rows on the device inside a power-of-two window, so a chunk is a
    power of two and the last window still ends inside the store."""
    n = config["store"]["resident_keys_per_chip"] * chips
    chunk = (1 << config["store"]["fill_chunk_log2_per_chip"]) * chips
    for lo in range(1, n + 1, chunk):
        store.ensure_rows(np.arange(lo, min(lo + chunk, n + 1),
                                    dtype=np.uint64))


def _pass_index(split: str, interval_min: int) -> int:
    return (int(split[:2]) * 60 + int(split[2:])) // interval_min


def run(job) -> Dict:
    from paddlebox_tpu.core import flags, monitor, report, trace
    from paddlebox_tpu.train.day_runner import DayRunner

    config, traffic, chips, seed = (job.config, job.traffic, job.chips,
                                    job.seed)
    gen = importlib.import_module(
        "benchmarks.generators." + traffic["generator"])
    p = gen.plan(traffic, config, chips)
    flags.set_flags(job.workload.get("flags", {}))
    data_dir = os.path.join(job.work_dir, "data")
    out_dir = os.path.join(job.work_dir, "day_output")

    # Pass files: written by processes that never import jax, while this
    # process fills the store.
    ctx = multiprocessing.get_context("spawn")
    workers = max(1, min(len(os.sched_getaffinity(0)) - 2, 24))
    pool = ctx.Pool(workers)
    try:
        writing = pool.map_async(gen.write_file, gen.tasks(data_dir, p, seed),
                                 chunksize=4)
        with job.span("setup/trainer"):
            trainer, feed = _trainer(config, chips, seed)
            store = trainer.engine.groups[0].engine.store
        with job.span("setup/fill_store"):
            _fill_store(store, config, chips)
        with job.span("setup/wait_files"):
            writing.get()
    finally:
        pool.close()
        pool.join()

    if job.trace:
        trace.GLOBAL.enable(ring_events=1 << 18)
    interval = 15                   # 96 pass slots a day, far more than fit
    warmup = p["warmup_passes"]
    closing = warmup + p["window_passes"]  # the pass that closes the window
    boundaries: List[float] = []    # perf_counter at each pass boundary
    reports: List[Dict] = []        # the program's pass report, per pass
    built_keys: List[int] = []      # store/pass_keys counter, per boundary
    window = {"open": None, "close": None, "compiles": None}

    def hook(day: str, pass_id: int) -> None:
        now = time.perf_counter()
        boundaries.append(now)
        reports.append(dict(report.LAST_PASS_REPORT or {}))
        built_keys.append(int(monitor.get("store/pass_keys")))
        if job.tracing_now():       # the traced span is one whole pass
            job.stop_device_trace()
        if pass_id == warmup:
            window["compiles"] = job.compiles()
            window["open"] = time.perf_counter()
        elif pass_id == closing:
            window["close"] = now
            raise _WindowClosed()
        elif pass_id == warmup + 1 and job.trace:
            # the window's second pass: by then the preload runs at its
            # steady distance behind training
            job.start_device_trace()

    runner = DayRunner(
        trainer, feed, out_dir, split_interval=interval, split_per_pass=1,
        is_data_hourly_placed=False, shuffle=True, num_reader_threads=4,
        pipeline_passes=True, is_rank0=False, pass_boundary_hook=hook,
        filelist_fn=lambda day, splits: gen.pass_files(
            data_dir, p, _pass_index(splits[0], interval) % p["n_passes"]))
    try:
        with job.span("day_loop"):
            runner.train_day("20260101")
        raise RuntimeError("the day ran out of passes before the window "
                           "closed")
    except _WindowClosed:
        pass
    compiles_in_window = job.compiles() - window["compiles"]

    in_window = reports[warmup:closing]
    steps = sum(int(r["steps"]) for r in in_window)
    failed = sum(int(r["steps"]) for r in in_window
                 if not np.isfinite(r["loss"]) or r["lookup_overflow"])
    overflow = sum(int(r["lookup_overflow"]) for r in in_window)
    wall = window["close"] - window["open"]
    last = in_window[-1]

    # Outside the window: the planted signal's ceiling, and the program's
    # pull + forward against the plain reference on one held-out batch.
    ceiling = gen.auc_ceiling(p, seed)
    auc_ok = ceiling + AUC_BAND[0] <= last["auc"] <= ceiling + AUC_BAND[1]
    with job.span("check/reference"):
        held = _held_out_check(job, gen, trainer, store, feed, p,
                               closing - 1, data_dir)
    correct = bool(auc_ok and overflow == 0 and failed == 0 and held["ok"])
    band = [ceiling + AUC_BAND[0], ceiling + AUC_BAND[1]]
    compared = {"auc_last_pass": {"value": float(last["auc"]),
                                  "limit": band},
                "failed_steps": {"value": int(failed), "limit": 0},
                "lookup_overflow": {"value": int(overflow), "limit": 0}}
    compared.update({f"held_out_{k}_gap": {"value": held["diff"][k],
                                           "limit": held["tol"][k]}
                     for k in EVAL_TOL})
    return {
        "attempted": steps, "failed": failed, "correct": correct,
        "compared": compared,
        "window_open": window["open"],
        "end_to_end": {"ctr_samples_per_s_per_chip":
                       steps * feed.batch_size / wall / chips},
        "detail": {
            "passes": len(in_window), "wall_s": wall,
            "pass_walls_s": np.diff(
                boundaries[warmup - 1:closing]).tolist(),
            "auc_last_pass": last["auc"], "auc_ceiling": ceiling,
            "held_out": held, "lookup_overflow": overflow,
            "last_pass_report": last,
        },
        "observed": {
            "program_spans": job.program_spans() if job.trace else [],
            "window_unix_ns": (job.unix_ns(window["open"]),
                               job.unix_ns(window["close"])),
            "steps": steps, "passes": len(in_window), "chips": chips,
            "traced_steps": p["batches"],
            "counters": {
                "compiles_in_window": compiles_in_window,
                "kernel_fallback": sum(int(r["kernel_fallback"])
                                       for r in in_window),
                "lookup_exchange_bytes": int(last["lookup_exchange_bytes"]),
                "resolved_kernels": flags.resolved_kernels(),
                "boundary_fused": monitor.get("device_store/boundary_fused"),
            },
            "shapes": {
                "ids_per_step_per_chip": (config["batch_per_chip"]
                                          * config["model"]["slots"]),
                # keys of the pass table the last boundary built
                "pass_keys_per_chip":
                    (built_keys[-1] - built_keys[-2]) // chips,
                "emb_dim": config["model"]["emb_dim"],
            },
        },
    }


def _held_out_check(job, gen, trainer, store, feed, p, pass_idx, data_dir
                    ) -> Dict:
    """One batch the trainer has not seen, over the last pass's keys: the
    program's ``eval_pass`` (pull + forward + loss + AUC on the device)
    against ``reference/<config>.py`` fed the rows read back from the
    store and the same dense parameters."""
    import jax

    from paddlebox_tpu.data.dataset import Dataset
    reference = importlib.import_module(
        f"benchmarks.reference.{job.config_name}")
    n = feed.batch_size
    file_idx = 10 ** 6                      # no pass file has this index
    ids, labels, dense = gen.draw_block(p, job.seed, pass_idx, file_idx, n)
    path = os.path.join(data_dir, "held_out", "part-00000")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(gen.format_lines(ids, labels, dense))
    ds = Dataset(feed, num_reader_threads=1)
    ds.set_filelist([path])
    ds.load_into_memory()
    got = trainer.eval_pass(ds)
    ds.clear()

    keys = np.unique(ids)
    rows = store.pull_for_pass(keys)
    where = np.searchsorted(keys, ids)
    params = jax.device_get(trainer.params)
    dense_f = dense.astype(np.float32) / np.float32(10 ** gen.DENSE_DIGITS)
    want = reference.evaluate(params, rows["emb"][where], rows["w"][where],
                              dense_f, labels.astype(np.float32))
    diff = {k: abs(float(got[k]) - float(want[k])) for k in EVAL_TOL}
    # the rounding of a mean shrinks with the root of the rows under it
    fewer_rows = max(1.0, (16384 / n) ** 0.5)
    tol = {k: EVAL_TOL[k] * fewer_rows * max(
        abs(float(want[k])), 1.0 if k == "auc" else 0.0) for k in EVAL_TOL}
    ok = all(np.isfinite(diff[k]) and diff[k] <= tol[k] for k in EVAL_TOL)
    return {"ok": bool(ok), "diff": diff, "tol": tol,
            "program": {k: float(got[k]) for k in EVAL_TOL},
            "reference": {k: float(want[k]) for k in EVAL_TOL}}
