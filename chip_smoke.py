"""First proof that the system starts on the chip: one process, all visible
chips, the main path end to end through the normal entry points.

    python chip_smoke.py                 # needs a TPU; fails without one
    python chip_smoke.py --rehearse-cpu  # toy sizes on 2 virtual CPU devices

Phases (a failure in any is fatal; nothing is retried on another path):

1. identity  device facts, versions, compile-cache directory, the native
             host library must have built, host link rates
2. kernels   each Pallas kernel, compiled, at its full-width shape against its
             jax.numpy reference on the same chip
3. deepfm    DeepFM at the Criteo widths as a trainer: text files ->
             Dataset (ingest worker processes) -> two train passes with a
             pipelined split build and fused boundary between them; then
             the same pass on the XLA kernel path, twice, to bound the
             Pallas-vs-XLA difference by XLA's difference with itself
4. predict   export -> CTRPredictor -> PredictServer answers a
             PredictClient over the wire, bit-identical to direct predict
5. gpt       a GPT-2-medium-wide stack: two train steps on the flash path,
             step-1 loss against the ring (XLA) attention of the same model
6. nemotron  a three-layer M*E stack (Mamba-2, grouped-query attention,
             latent experts) at published widths: the scan kernels against
             the sequential recurrence, grouped-head flash against its
             reference, two train steps with no dropped assignment
7. facts     compile seconds / cache hits / peak memory per phase

The report goes to <out>/chip_smoke_report.json and to stdout; the last
line of stdout is {"ok": true, "device": {...}}. This script claims no
speed: every time in the report is set-up cost or a link fact.
"""

import argparse
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# DeepFM at the Criteo widths and a GPT-2-medium-wide stack (cf.
# benchmarks/configs/deepfm_criteo.json and gpt2_medium.json). Cut for
# the smoke: the resident store (8M keys), the batch count (10 per pass)
# and the GPT depth; no width.
FULL = {
    "slots": 26, "emb_dim": 16, "dense_dim": 13, "hidden": (400, 400, 400),
    "batch": 16384, "store_keys": 8_000_000, "pass_keys": 4_000_000,
    "new_keys": 100_000, "batches": 10, "hot": 1000,
    # Depth cut 24 -> 12: at 24 layers the f32 step needs 16.22 GB of a
    # v5e's 15.75 GB HBM (XLA's compile-time accounting, chip run of PR
    # 21: the layer scan saves f32[24, 4, 1024, 4096] residuals).
    "gpt": {"vocab_size": 50304, "d_model": 1024, "n_heads": 16,
            "n_layers": 12, "d_ff": 4096, "max_seq_len": 1024},
    "gpt_cut": "12 of 24 layers; no width cut",
    "gpt_batch_per_chip": 4, "flash": (4, 1024, 16, 64),
    "seqpool": (65536, 16, 16384), "serve_requests": 48, "link_mib": 256,
    "auc_floor": 0.7,
    # benchmarks/configs/nemotron3_super_120b.json cut to one layer of
    # each kind and 2,048 positions; every width is the published one
    # (NemotronHConfig's defaults).
    "nemotron": {"vocab_size": 16384, "pattern": "M*E",
                 "experts_held": (0, 8)},
    "nemotron_seq": 2048,
}
# Rehearsal: same code, toy sizes, Pallas kernels interpreted.
TOY = {
    "slots": 26, "emb_dim": 16, "dense_dim": 13, "hidden": (32, 32),
    "batch": 256, "store_keys": 40_000, "pass_keys": 20_000,
    "new_keys": 500, "batches": 4, "hot": 50,
    "gpt": {"vocab_size": 256, "d_model": 64, "n_heads": 2,
            "n_layers": 2, "d_ff": 128, "max_seq_len": 128},
    "gpt_cut": "toy", "gpt_batch_per_chip": 2, "flash": (1, 128, 2, 64),
    "seqpool": (1024, 16, 256), "serve_requests": 32, "link_mib": 4,
    "auc_floor": 0.6,
    "nemotron": {"vocab_size": 256, "hidden_size": 64, "pattern": "M*E",
                 "mamba_num_heads": 4, "mamba_head_dim": 32,
                 "ssm_state_size": 16, "n_groups": 2, "chunk_size": 16,
                 "num_attention_heads": 4, "num_key_value_heads": 2,
                 "head_dim": 16, "n_routed_experts": 8,
                 "experts_held": (0, 2), "num_experts_per_tok": 2,
                 "moe_latent_size": 32, "moe_intermediate_size": 48,
                 "moe_shared_expert_intermediate_size": 64,
                 "kernels": "interpret"},
    "nemotron_seq": 48,
}


class Smoke:
    """Runs the phases and keeps the report. Per phase it records wall
    seconds, jax's own compile seconds and persistent-cache hits/misses
    (jax.monitoring events), and each device's peak bytes in use."""

    def __init__(self, rehearsal: bool, out_dir: str, cache_dir):
        import jax
        import jax.monitoring
        self.rehearsal = rehearsal
        self.cfg = TOY if rehearsal else FULL
        self.out_dir = out_dir
        self.cache_dir = cache_dir
        self.devices = jax.devices()
        self.report = {"ok": False, "rehearsal": rehearsal, "phases": {}}
        self._acc = {"compile_s": 0.0, "hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self._acc["compile_s"] += duration_secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self._acc["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self._acc["misses"] += 1

    def cache_entries(self):
        if self.cache_dir is None or not os.path.isdir(self.cache_dir):
            return 0
        return len(os.listdir(self.cache_dir))

    def memory(self, stat):
        """One ``memory_stats()`` entry per device (None off the TPU)."""
        return [(d.memory_stats() or {}).get(stat) for d in self.devices]

    def phase(self, name, fn):
        print(f"[chip_smoke] phase {name}: start", file=sys.stderr,
              flush=True)
        self._acc = {"compile_s": 0.0, "hits": 0, "misses": 0}
        t0 = time.perf_counter()
        rec = {"ok": False}
        self.report["phases"][name] = rec
        fn(rec)     # fills the live record: a failure keeps what it had
        rec["seconds"] = round(time.perf_counter() - t0, 2)
        rec["compile_seconds"] = round(self._acc["compile_s"], 2)
        rec["cache_hits"] = self._acc["hits"]
        rec["cache_misses"] = self._acc["misses"]
        rec["peak_bytes_in_use"] = self.memory("peak_bytes_in_use")
        rec["ok"] = True
        print(f"[chip_smoke] phase {name}: ok in {rec['seconds']}s "
              f"(compile {rec['compile_seconds']}s)", file=sys.stderr,
              flush=True)

    def write_report(self):
        os.makedirs(self.out_dir, exist_ok=True)
        name = ("chip_smoke_rehearsal.json" if self.rehearsal else
                f"chip_smoke_report_{len(self.devices)}chip.json")
        path = os.path.join(self.out_dir, name)
        with open(path, "w") as f:
            json.dump(self.report, f, indent=1, default=str)
            f.write("\n")
        return path


def check(cond, msg):
    """A smoke assertion that survives ``python -O``."""
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# Data, generated from a seed (planted labels on a hot head of keys)
# ---------------------------------------------------------------------------

def planted_labels(rng, hot_ids, target_rate=0.25, strength=2.0):
    """Each hot key carries a latent +-1 weight (a hash bit); labels are
    Bernoulli in that weight's logit. A learner that recovers per-key
    weights pulls AUC well above 0.5; an embedding served to the wrong
    row cannot."""
    import numpy as np
    h = (hot_ids * np.uint64(0x9E3779B97F4A7C15)) >> np.uint64(32)
    sign = (h & np.uint64(1)).astype(np.float32) * 2.0 - 1.0
    logit = sign * strength + np.log(target_rate / (1.0 - target_rate))
    return (rng.random(hot_ids.shape[0]) < 1.0 / (1.0 + np.exp(-logit))
            ).astype(np.int32)


def gen_lines(rng, ids, cfg, hot):
    """svm-format lines for an [n, slots - 1] id block: slot 0 is drawn
    from the hot head and the label carries that key's planted signal;
    the block fills the other slots."""
    import numpy as np
    n = ids.shape[0]
    ids = np.concatenate([rng.choice(hot, n)[:, None], ids], axis=1)
    labels = planted_labels(rng, ids[:, 0])
    line = labels.astype("U1")
    for j in range(cfg["slots"]):
        line = np.char.add(line, f" s{j}:")
        line = np.char.add(line, ids[:, j].astype("U20"))
    dense = (rng.random((n, cfg["dense_dim"])) * 10000).astype(np.int32)
    line = np.char.add(line, " d:0.")
    line = np.char.add(line, dense[:, 0].astype("U5"))
    for j in range(1, cfg["dense_dim"]):
        line = np.char.add(line, ",0.")
        line = np.char.add(line, dense[:, j].astype("U5"))
    return line.tolist(), labels


def gen_pass_files(tmpdir, tag, rng, pass_keys, cfg, hot):
    """One part file per batch. Every key of ``pass_keys`` occurs at
    least once (whole permutations are dealt out), so the pass table is
    built for the full key set."""
    import numpy as np
    nb, batch, slots = cfg["batches"], cfg["batch"], cfg["slots"] - 1
    need = nb * batch * slots
    check(need >= pass_keys.size, "too few batches to cover the pass keys")
    deal = np.concatenate([rng.permutation(pass_keys) for _ in range(
        -(-need // pass_keys.size))])[:need].reshape(nb, batch, slots)
    files = []
    for b in range(nb):
        lines, _ = gen_lines(rng, deal[b], cfg, hot)
        path = os.path.join(tmpdir, f"{tag}-part-{b:05d}")
        with open(path, "w") as f:
            f.write("\n".join(lines) + "\n")
        files.append(path)
    return files


# ---------------------------------------------------------------------------
# Phase 1: identity, native build, host link
# ---------------------------------------------------------------------------

def phase_identity(sm: Smoke, out: dict):
    import importlib.metadata as md

    import jax
    import numpy as np

    from paddlebox_tpu.native.build import native_available
    d0 = sm.devices[0]
    device = {"platform": d0.platform, "device_kind": d0.device_kind,
              "device_count": len(sm.devices)}
    sm.report.update(device)
    out.update({
        **device,
        "versions": {p: md.version(p) for p in (
            "jax", "jaxlib", "libtpu", "flax", "optax", "numpy")},
        "python": sys.version.split()[0],
        "compile_cache_dir": sm.cache_dir,
        "cache_entries_before": sm.cache_entries(),
        "cpu_count": os.cpu_count(),
    })
    out["native_available"] = bool(native_available())
    check(out["native_available"],
          "the native host library did not build (g++ missing or "
          "failing): every host-side stage would run its numpy fallback")

    # Host link: one array each way, and the round trip of an empty call.
    nbytes = sm.cfg["link_mib"] << 20
    host = np.ones((nbytes // 4,), np.float32)
    t0 = time.perf_counter()
    dev = jax.block_until_ready(jax.device_put(host, d0))
    h2d = time.perf_counter() - t0
    t0 = time.perf_counter()
    back = np.asarray(dev)
    d2h = time.perf_counter() - t0
    check(back[-1] == 1.0, "D2H returned wrong data")
    empty = jax.jit(lambda x: x + 1.0)
    x = jax.block_until_ready(empty(jax.numpy.zeros((8,), np.float32)))
    rtts = []
    for _ in range(50):
        t0 = time.perf_counter()
        x = jax.block_until_ready(empty(x))
        rtts.append(time.perf_counter() - t0)
    out["host_link"] = {
        "array_mib": sm.cfg["link_mib"],
        "h2d_gb_per_s": round(nbytes / h2d / 1e9, 3),
        "d2h_gb_per_s": round(nbytes / d2h / 1e9, 3),
        "empty_call_round_trip_ms_median": round(
            sorted(rtts)[len(rtts) // 2] * 1e3, 4),
    }


# ---------------------------------------------------------------------------
# Phase 2: the four Pallas kernels, compiled, against jax.numpy
# ---------------------------------------------------------------------------

def phase_kernels(sm: Smoke, out: dict):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddlebox_tpu.embedding import TableConfig
    from paddlebox_tpu.embedding.lookup import bucket_capacity
    from paddlebox_tpu.embedding.table import plan_shards, table_widths
    from paddlebox_tpu.ops.pallas_kernels.flash_attention import (
        flash_attention, flash_attention_reference)
    from paddlebox_tpu.ops.pallas_kernels.seqpool_cvm import (
        seqpool_cvm_pallas)
    from paddlebox_tpu.ops.pallas_kernels.sorted_gather import (
        sorted_gather, sorted_stream_layout, stream_tier)
    from paddlebox_tpu.ops.pallas_kernels.sorted_scatter import (
        UCAP, sorted_scatter_accumulate)
    from paddlebox_tpu.ops.seqpool import fused_seqpool_cvm
    cfg, interp = sm.cfg, sm.rehearsal
    dim, ke, kw = table_widths(TableConfig(dim=cfg["emb_dim"]))
    w, pw, aw = dim + 3 + ke + kw, dim + 3, dim + 4
    n_ids = cfg["batch"] * cfg["slots"]
    out.update({"interpret": interp, "record_width": w, "pull_width": pw})

    def sparse_pair(tag, n, block, hot=0):
        """sorted_gather (exact) and sorted_scatter_accumulate at one
        (ids, table block) shape; rows past the block are the dropped
        sentinel both kernels must zero / ignore. ``hot`` requests go to
        one row (day_zipf's hottest key: 73K of a step's 426K ids), a
        run over the per-block budget that the kernels serve themselves:
        the gather once, the scatter in several staging windows."""
        k0, k1, k2, k3 = jax.random.split(jax.random.PRNGKey(block + hot), 4)
        rows = jax.random.randint(k0, (n,), 0, block + block // 64,
                                  jnp.int32)
        rows = jax.random.permutation(k3, rows.at[:hot].set(block // 3))
        layout = sorted_stream_layout(rows, block)
        tier = int(stream_tier(layout))
        rec = out[tag] = {"ids": n, "rows": block, "hot": hot,
                          "max_run": int(layout[3]),
                          "max_distinct_run": int(layout[4]), "tier": tier}
        check(tier == (1 if hot else 0),
              f"{tag}: max_run {rec['max_run']}, max_distinct_run "
              f"{rec['max_distinct_run']} against UCAP {UCAP} select "
              f"tier {tier}: the gather would not run the path this "
              f"check is for (2 = its XLA branch)")
        table = jax.random.normal(k1, (block, w), jnp.float32)
        got = sorted_gather(rows, table, width=pw, interpret=interp)
        keep = rows < block
        ref = jnp.where(keep[:, None],
                        table[jnp.where(keep, rows, 0), :pw], 0.0)
        g_err = rec["gather_max_err"] = float(jnp.max(jnp.abs(got - ref)))
        check(g_err == 0.0, f"{tag}: sorted_gather max err {g_err} != 0")
        del table, got, ref
        pay = jax.random.normal(k2, (n, aw), jnp.float32)
        acc = np.asarray(
            sorted_scatter_accumulate(rows, pay, block, interpret=interp))
        # float32 adds in request order on the host: the kernel's own
        # order within a row (the sort is stable).
        ref = np.zeros((block + 1, aw), np.float32)
        np.add.at(ref, np.minimum(np.asarray(rows), block), np.asarray(pay))
        ref = ref[:block]
        s_err = rec["scatter_max_err"] = float(np.max(np.abs(acc - ref)))
        s_tol = rec["scatter_tol"] = 1e-5 * float(np.max(np.abs(ref)))
        check(s_err <= s_tol,
              f"{tag}: sorted_scatter max err {s_err} > {s_tol}")

    # One chip: all ids into the whole pass-table block.
    one_chip = plan_shards(cfg["pass_keys"], 1) + 1
    sparse_pair("sparse_1chip", n_ids, one_chip)
    # The same under day_zipf's skew (one chip runs no dedup in front of
    # the kernels).
    sparse_pair("sparse_1chip_hot_row", n_ids, one_chip,
                hot=max(n_ids * 73 // 426, UCAP + 1))
    # What each of four chips compiles: its shard's block, serving the
    # 4 x cap bucket cells its peers send.
    sparse_pair("sparse_4chip_shard", 4 * bucket_capacity(n_ids // 4, 4),
                plan_shards(cfg["pass_keys"], 4) + 1)

    # flash attention forward + backward at [4, 1024, 16, 64], default
    # FLAGS_flash_block_q/k, against the XLA reference at full
    # precision. Errors are relative to each tensor's largest value. The
    # kernel's f32 dots may run as bf16 passes on the MXU, exactly as
    # XLA's own default-precision matmuls do, so the bound is four times
    # the XLA reference's default-precision error against its
    # full-precision self, floored at bf16's 2^-8.
    b, s, h, d = cfg["flash"]
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v, wgt = (jax.random.normal(kk, (b, s, h, d), jnp.float32)
                    for kk in ks)

    def out_and_grads(attn):
        def loss(q, k, v):
            o = attn(q, k, v)
            return jnp.sum(o * wgt), o
        (_, o), g = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return dict(zip(("out", "dq", "dk", "dv"), (o,) + g))

    def xla_ref(q, k, v):
        return flash_attention_reference(q, k, v, causal=True)

    got = out_and_grads(lambda q, k, v: flash_attention(
        q, k, v, causal=True, use_pallas=True, interpret=interp))
    ref_default = out_and_grads(xla_ref)
    with jax.default_matmul_precision("highest"):
        ref = out_and_grads(xla_ref)

    def rel(a, b):
        return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))

    fl = out["flash_attention"] = {
        "shape": [b, s, h, d], "causal": True, "rel_err": {},
        "xla_default_precision_rel_err": {}, "tol": {}}
    for name in got:
        fl["rel_err"][name] = rel(got[name], ref[name])
        fl["xla_default_precision_rel_err"][name] = rel(ref_default[name],
                                                        ref[name])
        fl["tol"][name] = max(
            2.0 ** -8, 4 * fl["xla_default_precision_rel_err"][name])
    for name in got:
        check(bool(jnp.all(jnp.isfinite(got[name]))),
              f"flash {name} not finite")
        check(fl["rel_err"][name] <= fl["tol"][name],
              f"flash {name} rel err {fl['rel_err'][name]} > "
              f"{fl['tol'][name]}")
    del got, ref, ref_default

    # seqpool_cvm: reached by no model today (models pool with the XLA
    # ops.seqpool path); checked here so the kernel is known to run.
    n, d, rows = cfg["seqpool"]
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    emb = jax.random.normal(ks[0], (n, d), jnp.float32)
    show = jax.random.uniform(ks[1], (n,), jnp.float32, 1.0, 9.0)
    click = jax.random.uniform(ks[2], (n,), jnp.float32, 0.0, 1.0)
    seg = jnp.sort(jax.random.randint(ks[3], (n,), 0, rows + 1, jnp.int32))
    got = seqpool_cvm_pallas(emb, show, click, seg, rows, use_pallas=True,
                             interpret=interp)
    ref = fused_seqpool_cvm(emb, show, click, seg, rows)
    err = rel(got, ref)
    out["seqpool_cvm"] = {"shape": [n, d, rows], "rel_err": err,
                          "note": "reached by no model today"}
    check(err <= 1e-5, f"seqpool_cvm rel err {err} > 1e-5")


# ---------------------------------------------------------------------------
# Phase 3: DeepFM at full width, as a trainer
# ---------------------------------------------------------------------------

def deepfm_feed(cfg, batch_size):
    from paddlebox_tpu.data.slots import DataFeedConfig, SlotConf
    slots = tuple(SlotConf(f"s{i}", avg_len=1.0)
                  for i in range(cfg["slots"]))
    slots += (SlotConf("d", is_dense=True, dim=cfg["dense_dim"]),)
    return DataFeedConfig(slots=slots, batch_size=batch_size,
                          slot_capacity_slack=1.0)


def deepfm_model(cfg):
    from paddlebox_tpu.models import DeepFM
    return DeepFM(slot_names=tuple(f"s{i}" for i in range(cfg["slots"])),
                  emb_dim=cfg["emb_dim"], dense_dim=cfg["dense_dim"],
                  hidden=cfg["hidden"])


def make_trainer(sm: Smoke, devices):
    """A DeepFM ``CTRTrainer`` over ``devices`` with a device-resident
    store prepopulated with the resident key set."""
    import numpy as np

    from paddlebox_tpu.embedding import DeviceFeatureStore, TableConfig
    from paddlebox_tpu.parallel import HybridTopology, build_mesh
    from paddlebox_tpu.train import CTRTrainer, TrainerConfig
    cfg = sm.cfg
    mesh = build_mesh(HybridTopology(dp=len(devices)), devices=devices)
    hint = cfg["store_keys"] + cfg["new_keys"]
    trainer = CTRTrainer(
        deepfm_model(cfg), deepfm_feed(cfg, cfg["batch"]),
        TableConfig(dim=cfg["emb_dim"], learning_rate=0.05), mesh=mesh,
        config=TrainerConfig(auc_num_buckets=1 << 16,
                             compute_dtype="bfloat16"),
        store_factory=lambda c: DeviceFeatureStore(
            c, mesh=mesh, capacity_hint=hint))
    trainer.init(seed=0)
    store = trainer.engine.groups[0].engine.store
    store.ensure_rows(np.arange(1, cfg["store_keys"] + 1, dtype=np.uint64))
    return trainer, store


def load_dataset(feed, files, cls=None):
    from paddlebox_tpu.data.dataset import Dataset
    ds = (cls or Dataset)(feed, num_reader_threads=4)
    ds.set_filelist(files)
    ds.load_into_memory()
    return ds


def pipelined_dataset_class():
    """A pass's Dataset that also plays the day loop's preloader
    (DayRunner._start_preload): before its last batch it feeds the NEXT
    pass's keys with an async build — the engine split-builds the
    not-shared rows while this pass still trains — and holds that batch
    until the early half is published, so end_pass finds it and runs the
    fused boundary program. A real day loop races here; the smoke must
    reach the fused program every time."""
    from paddlebox_tpu.core import monitor
    from paddlebox_tpu.data.dataset import Dataset

    class PipelinedPass(Dataset):
        next_keys = None        # [keys per width group] of the next pass;
        engine = None           # None: a plain Dataset

        def batches_sharded(self, num_shards, **kw):
            it = super().batches_sharded(num_shards, **kw)
            if self.next_keys is None:
                yield from it
                return
            prev = next(it)
            for batch in it:
                yield prev
                prev = batch
            before = monitor.get("pass/split_builds")
            self.engine.feed_pass(self.next_keys, async_build=True)
            deadline = time.monotonic() + 300.0
            while monitor.get("pass/split_builds") == before:
                check(time.monotonic() < deadline,
                      "the next pass's split build never published")
                time.sleep(0.005)
            yield prev

    return PipelinedPass


def phase_deepfm(sm: Smoke, out: dict, tmpdir):
    import gc

    import numpy as np

    from paddlebox_tpu.core import flags, monitor
    from paddlebox_tpu.embedding.table import plan_shards
    cfg, ndev = sm.cfg, len(sm.devices)
    want_mode = "interpret" if sm.rehearsal else "pallas"
    rng = np.random.default_rng(0)
    out.update({
        "cut": f"resident store {cfg['store_keys']:,} keys; "
               f"{cfg['batches']} batches per pass; widths, batch "
               f"{cfg['batch']} and the {cfg['pass_keys']:,}-key pass "
               f"table are the deepfm_criteo cells'",
        "n_devices": ndev})

    # Key sets: pass 2 shares half of pass 1's keys, draws most of the
    # rest from the resident store and brings some the store never saw.
    sk, pk, new = cfg["store_keys"], cfg["pass_keys"], cfg["new_keys"]
    perm = rng.permutation(sk).astype(np.uint64) + np.uint64(1)
    keys1 = perm[:pk]
    keys2 = np.concatenate([
        perm[pk // 2:pk // 2 + pk - new],
        np.arange(sk + 1, sk + 1 + new, dtype=np.uint64)])
    hot = perm[pk // 2:pk // 2 + cfg["hot"]]       # in both passes
    files1 = gen_pass_files(tmpdir, "p1", rng, keys1, cfg, hot)
    files2 = gen_pass_files(tmpdir, "p2", rng, keys2, cfg, hot)
    workers = max(1, min(8, (os.cpu_count() or 2) - 1))
    flags.set_flags({"ingest_workers": workers})
    out["ingest_workers"] = workers
    feed = deepfm_feed(cfg, cfg["batch"])

    def run_pass1(devices, label):
        """A fresh trainer from the same seed trains pass 1 again, from
        the SAME loaded Dataset: worker processes deliver chunks in
        arrival order, so a reload would reorder the batches."""
        flags.resolved_kernels(reset=True)
        trainer, store = make_trainer(sm, devices)
        stats = trainer.train_pass(ds1)
        rec = out[label] = {
            "loss": float(stats["loss"]), "auc": float(stats["auc"]),
            "lookup_overflow": int(stats["lookup_overflow"]),
            "kernel_fallback": int(stats["kernel_fallback"]),
            "resolved_kernels": flags.resolved_kernels()}
        check(np.isfinite(rec["loss"]), f"{label}: loss not finite")
        check(rec["lookup_overflow"] == 0, f"{label}: lookups overflowed")
        return trainer, store, rec

    # -- the Pallas run: two pipelined passes --------------------------
    spawned0 = monitor.get("ingest/workers_spawned")
    flags.resolved_kernels(reset=True)
    trainer, store = make_trainer(sm, sm.devices)
    ds1 = load_dataset(feed, files1, pipelined_dataset_class())
    ds2 = load_dataset(feed, files2)
    check(monitor.get("ingest/workers_spawned") > spawned0,
          "the Dataset loads did not run in ingest worker processes")
    eng = trainer.engine
    ds1.engine = eng
    ds1.next_keys = [ds2.pass_keys(slots=g.slots) for g in eng.groups]
    check(ds1.next_keys[0].size == pk and ds1.num_instances
          == cfg["batches"] * cfg["batch"], "generated data is short")
    fused0 = monitor.get("device_store/boundary_fused")
    early0 = monitor.get("device_store/early_rows")
    new0 = monitor.get("device_store/new_keys")
    stats1 = trainer.train_pass(ds1)
    trainer.reset_metrics()                 # pass 2's AUC is its own
    stats2 = trainer.train_pass(ds2, feed_keys=False)
    resolved = out["resolved_kernels"] = flags.resolved_kernels()
    passes = out["passes"] = []
    for st in (stats1, stats2):
        passes.append({
            "steps": int(st["steps"]), "loss": float(st["loss"]),
            "auc": float(st["auc"]),
            "lookup_overflow": int(st["lookup_overflow"]),
            "kernel_fallback": int(st["kernel_fallback"]),
            "lookup_exchange_bytes": int(st["lookup_exchange_bytes"]),
            "boundary": st["boundary"]})
        check(np.isfinite(st["loss"]), "loss not finite")
        check(st["steps"] == cfg["batches"], "pass lost batches")
        check(st["lookup_overflow"] == 0, "sparse lookups overflowed")
        check(st["kernel_fallback"] == 0,
              "sorted-stream kernels fell back to XLA at run time")
        check((st["lookup_exchange_bytes"] > 0) == (ndev > 1),
              "lookup_exchange_bytes does not match the device count")
    check(resolved.get("sparse_gather") == [want_mode]
          and resolved.get("sparse_scatter") == [want_mode],
          f"sparse kernels resolved to {resolved}, want {want_mode}")
    out["boundary_fused"] = monitor.get("device_store/boundary_fused") - fused0
    out["early_rows"] = monitor.get("device_store/early_rows") - early0
    out["new_keys_inserted"] = monitor.get("device_store/new_keys") - new0
    check(out["boundary_fused"] == 1,
          "the fused end/begin boundary program did not run")
    check(out["early_rows"] == pk - pk // 2,
          "the split early build did not gather the not-shared rows")
    check(out["new_keys_inserted"] == new,
          "the split build did not insert the unseen keys")
    check(passes[1]["auc"] > cfg["auc_floor"],
          f"pass-2 AUC {passes[1]['auc']} <= floor {cfg['auc_floor']}: "
          f"the sparse path is not learning the planted signal")
    out["auc_floor"] = cfg["auc_floor"]

    # Placement: the resident store and a pass table, shard by shard.
    eng.feed_pass(ds1.next_keys, readonly=True)
    table = eng.begin_pass()[0]
    block = plan_shards(pk, ndev) + 1
    check(table.vals.shape[0] == ndev * block,
          f"pass table has {table.vals.shape[0]} rows, not the "
          f"{ndev} x {block} the AOT checks pin")
    store_devs = {s.device for s in store._parts[0].addressable_shards}
    table_devs = {s.device for s in table.vals.addressable_shards}
    in_use = sm.memory("bytes_in_use")
    eng.abort_pass()
    out["pass_table_rows_per_device"] = block
    out["store_shard_devices"] = len(store_devs)
    out["table_shard_devices"] = len(table_devs)
    out["bytes_in_use"] = in_use
    check(len(store_devs) == ndev and len(table_devs) == ndev,
          "store / pass table shards do not cover every device")
    if all(b is not None for b in in_use):
        check(min(in_use) > 0 and max(in_use) <= 2 * min(in_use),
              f"per-device bytes_in_use uneven: {in_use}")
    export_dir = os.path.join(tmpdir, "export")
    out["export"] = {k: v for k, v in trainer.export_serving(
        export_dir).items() if k == "features"}
    # Free the trainer's device memory (its store alone is gigabytes)
    # before the next one is built.
    ds1.next_keys = ds1.engine = None       # from here a plain Dataset
    del trainer, store, eng, table, ds2, stats1, stats2
    gc.collect()

    # -- in-situ kernel check: XLA path, twice -------------------------
    kernel_flags = flags.get_flags(["sparse_gather_kernel",
                                    "sparse_scatter_kernel"])
    flags.set_flags({"sparse_gather_kernel": "xla",
                     "sparse_scatter_kernel": "xla"})
    recs = []
    for label in ("xla_run_a", "xla_run_b"):
        t, s, rec = run_pass1(sm.devices, label)
        check(rec["resolved_kernels"].get("sparse_gather") == ["xla"]
              and rec["resolved_kernels"].get("sparse_scatter") == ["xla"],
              f"{label}: did not run the XLA path")
        recs.append(rec)
        del t, s
        gc.collect()
    flags.set_flags(kernel_flags)
    # Tolerance: four times XLA's difference with itself, floored at
    # bf16's 2^-8 (the tower computes in bf16) relative for the mean
    # loss and absolute for AUC.
    eps = 2.0 ** -8
    tol_loss = max(4 * abs(recs[0]["loss"] - recs[1]["loss"]),
                   eps * abs(recs[0]["loss"]))
    tol_auc = max(4 * abs(recs[0]["auc"] - recs[1]["auc"]), eps)
    d_loss = abs(passes[0]["loss"] - recs[0]["loss"])
    d_auc = abs(passes[0]["auc"] - recs[0]["auc"])
    out["pallas_vs_xla"] = {
        "pass1_loss_diff": d_loss, "tol_loss": tol_loss,
        "pass1_auc_diff": d_auc, "tol_auc": tol_auc,
        "xla_self_loss_diff": abs(recs[0]["loss"] - recs[1]["loss"]),
        "xla_self_auc_diff": abs(recs[0]["auc"] - recs[1]["auc"])}
    check(d_loss <= tol_loss and d_auc <= tol_auc,
          f"Pallas and XLA kernel paths disagree: {out['pallas_vs_xla']}")

    if ndev > 1:
        # The same pass on ONE chip of this host: sharding the table
        # must not change what is learned.
        t, s, rec = run_pass1(sm.devices[:1], "one_chip_reference")
        del t, s
        gc.collect()
        d_loss = abs(passes[0]["loss"] - rec["loss"])
        d_auc = abs(passes[0]["auc"] - rec["auc"])
        out["sharded_vs_one_chip"] = {
            "pass1_loss_diff": d_loss, "tol_loss": tol_loss,
            "pass1_auc_diff": d_auc, "tol_auc": tol_auc}
        check(d_loss <= tol_loss and d_auc <= tol_auc,
              f"dp={ndev} and one-chip runs disagree: "
              f"{out['sharded_vs_one_chip']}")
    return export_dir, keys2, hot


# ---------------------------------------------------------------------------
# Phase 4: the predict tier answers requests
# ---------------------------------------------------------------------------

def phase_predict(sm: Smoke, out: dict, export_dir, keys, hot):
    import jax.numpy as jnp
    import numpy as np

    from paddlebox_tpu.data.parser import parse_lines
    from paddlebox_tpu.metrics import (auc_accumulate, auc_compute,
                                       auc_state_init)
    from paddlebox_tpu.serving.batcher import pack_bucketed
    from paddlebox_tpu.serving.predictor import load_serving_predictor
    from paddlebox_tpu.serving.service import PredictClient, PredictServer
    cfg = sm.cfg
    feed = deepfm_feed(cfg, 64)
    rng = np.random.default_rng(4)
    pred = load_serving_predictor(deepfm_model(cfg), feed, export_dir)
    server = PredictServer("127.0.0.1:0", pred)
    client = PredictClient(server.endpoint)
    try:
        sizes = [1, 2, 3, 8, 17, 31, 33, 64] + rng.integers(
            1, 65, cfg["serve_requests"] - 8).tolist()
        probs, labels = [], []
        for n in sizes:
            ids = rng.choice(keys, (n, cfg["slots"] - 1))
            lines, y = gen_lines(rng, ids, cfg, hot)
            got = np.asarray(client.predict(lines))
            want = np.asarray(pred.predict(pack_bucketed(
                parse_lines(lines, feed), feed))[:n])
            check(got.shape == (n,), f"reply shape {got.shape} for {n} rows")
            check(np.all(np.isfinite(got)) and np.all((got > 0) & (got < 1)),
                  "reply not a finite probability")
            check(np.array_equal(got, want),
                  "wire reply differs from the predictor's direct predict")
            probs.append(got)
            labels.append(y)
        # The repo's own AUC (metrics/auc.py), as the trainer computes it.
        auc = float(auc_compute(auc_accumulate(
            auc_state_init(1 << 16), jnp.asarray(np.concatenate(probs)),
            jnp.asarray(np.concatenate(labels), jnp.float32)))["auc"])
        stats = client.stats()
    finally:
        client.close()
        server.stop()
        pred.close()
    out.update({"requests": len(sizes), "rows": int(sum(sizes)),
                "row_counts": f"{min(sizes)}..{max(sizes)}",
                "bit_identical_to_direct_predict": True, "auc": auc,
                "auc_floor": cfg["auc_floor"],
                "server_batches": stats.get("batches")})
    check(auc > cfg["auc_floor"],
          f"served AUC {auc} <= floor {cfg['auc_floor']}")


# ---------------------------------------------------------------------------
# Phase 5: one dense model, two steps
# ---------------------------------------------------------------------------

def phase_gpt(sm: Smoke, out: dict):
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from paddlebox_tpu.core import flags
    from paddlebox_tpu.models.gpt import (GPTConfig, gpt_loss_fn, init_gpt,
                                          make_gpt_train_step)
    from paddlebox_tpu.parallel import HybridTopology, build_mesh
    ndev = len(sm.devices)
    cfg = GPTConfig(**sm.cfg["gpt"])
    mesh = build_mesh(HybridTopology(dp=ndev))
    params, specs = init_gpt(jax.random.PRNGKey(0), cfg, pp_stages=1)
    opt = optax.adafactor(1e-3)
    opt_state = opt.init(params)
    bs, seq = sm.cfg["gpt_batch_per_chip"] * ndev, cfg.max_seq_len
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (bs, seq)),
                         jnp.int32)
    targets = jnp.asarray(rng.integers(0, cfg.vocab_size, (bs, seq)),
                          jnp.int32)

    # The reference first (the step donates params): the same model's
    # loss with ring attention — the XLA path at sp=1. Forward only: the
    # loss a step reports is its forward's, and the ring backward saves
    # [B, S, H, S] f32 scores per layer.
    out.update({"config": sm.cfg["gpt"], "cut": sm.cfg["gpt_cut"],
                "batch": bs, "seq": seq,
                "optimizer": "adafactor",
                "n_params": sum(int(np.prod(p.shape)) for p in
                                jax.tree_util.tree_leaves(params))})
    flags.resolved_kernels(reset=True)
    ring = dataclasses.replace(cfg, attention="ring")
    loss_ring = out["ring_reference_loss"] = float(jax.jit(gpt_loss_fn(
        ring, mesh, specs))(params, tokens, targets))
    check(flags.resolved_kernels(reset=True).get("gpt_attention")
          == ["ring"], "the reference did not take ring attention")

    step = make_gpt_train_step(cfg, mesh, specs, opt, num_microbatches=1)
    losses = out["losses"] = []
    for _ in range(2):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(loss))
    resolved = out["resolved_kernels"] = flags.resolved_kernels()
    if sm.rehearsal:
        want = {"gpt_attention": ["ring"]}       # 'auto' off the TPU
    else:
        want = {"gpt_attention": ["flash"], "flash_attention": ["pallas"]}
    check(all(resolved.get(k) == v for k, v in want.items()),
          f"attention resolved to {resolved}, want {want}")
    check(all(np.isfinite(losses)), f"GPT losses not finite: {losses}")
    check(losses[1] < losses[0],
          f"GPT loss did not fall on the repeated batch: {losses}")
    # bf16-pass matmuls on both sides: 2^-8 of the loss.
    tol = out["tol"] = 2.0 ** -8 * abs(loss_ring)
    check(abs(losses[0] - loss_ring) <= tol,
          f"flash step-1 loss {losses[0]} vs ring {loss_ring}: > {tol}")


# ---------------------------------------------------------------------------
# Phase 6: the hybrid state-space / attention / expert stack
# ---------------------------------------------------------------------------

def phase_nemotron(sm: Smoke, out: dict):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from paddlebox_tpu.core import flags
    from paddlebox_tpu.models.nemotron_h import (
        NemotronHConfig, init_nemotron_h, make_nemotron_h_train_step)
    from paddlebox_tpu.ops.pallas_kernels import (
        flash_attention, flash_attention_reference)
    from paddlebox_tpu.ops.pallas_kernels.ssd_scan import (
        ssd_scan, ssd_scan_reference)
    from paddlebox_tpu.parallel import HybridTopology, build_mesh
    ndev = len(sm.devices)
    cfg = NemotronHConfig(**sm.cfg["nemotron"])
    seq, interp = sm.cfg["nemotron_seq"], sm.rehearsal
    out.update({"config": {k: getattr(cfg, k) for k in (
        "pattern", "hidden_size", "mamba_num_heads", "mamba_head_dim",
        "ssm_state_size", "n_groups", "chunk_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "n_routed_experts",
        "experts_held", "num_experts_per_tok", "moe_latent_size",
        "moe_intermediate_size", "moe_shared_expert_intermediate_size",
        "vocab_size")}, "seq": seq})

    def rel(a, b):
        return float(jnp.linalg.norm((a - b).ravel())
                     / jnp.linalg.norm(b.ravel()))

    def out_and_grads(fn, args, wgt):
        def loss(*a):
            o = fn(*a)
            return jnp.sum(o * wgt), o
        (_, o), g = jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(len(args))), has_aux=True))(*args)
        return (o,) + g

    # The scan kernels at the layer's shapes against the recurrence run
    # one position at a time. The kernels round their matmul operands to
    # bfloat16 (state and decay stay float32): 2^-7 of each tensor's norm;
    # 2^-6 for the step sizes' gradient and 2^-4 for the per-head decay
    # rates', which are small sums over every position of large terms of
    # both signs.
    h, p = cfg.mamba_num_heads, cfg.mamba_head_dim
    g, n = cfg.n_groups, cfg.ssm_state_size
    ks = jax.random.split(jax.random.PRNGKey(21), 7)
    args = (jax.random.normal(ks[0], (1, seq, h, p)),
            jax.nn.softplus(jax.random.normal(ks[1], (1, seq, h)) - 3.0),
            -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0,
                                        maxval=2.7)),
            jax.random.normal(ks[3], (1, seq, g, n)) * n ** -0.5,
            jax.random.normal(ks[4], (1, seq, g, n)),
            jax.random.normal(ks[5], (h,)))
    wgt = jax.random.normal(ks[6], (1, seq, h, p))
    got = out_and_grads(lambda *a: ssd_scan(
        *a, chunk=cfg.chunk_size, use_pallas=True, interpret=interp),
        args, wgt)
    with jax.default_matmul_precision("highest"):
        want = out_and_grads(ssd_scan_reference, args, wgt)
    sc = out["ssd_scan"] = {"shape": [1, seq, h, p, g, n], "rel_err": {},
                            "tol": {}}
    for name, a, b in zip(("y", "dx", "ddt", "da", "db", "dc", "dd"),
                          got, want):
        check(bool(jnp.all(jnp.isfinite(a))), f"ssd_scan {name} not finite")
        sc["rel_err"][name] = rel(a, b)
        sc["tol"][name] = {"ddt": 2.0 ** -6, "da": 2.0 ** -4}.get(
            name, 2.0 ** -7)
        check(sc["rel_err"][name] <= sc["tol"][name],
              f"ssd_scan {name} rel err {sc['rel_err'][name]} > "
              f"{sc['tol'][name]}")
    del got, want, args

    # Grouped-head flash at the layer's shapes against the XLA reference
    # at full precision; the bound as in the kernels phase.
    hq, hkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    ks = jax.random.split(jax.random.PRNGKey(22), 4)
    qkv = (jax.random.normal(ks[0], (1, seq, hq, hd)),
           jax.random.normal(ks[1], (1, seq, hkv, hd)),
           jax.random.normal(ks[2], (1, seq, hkv, hd)))
    wgt = jax.random.normal(ks[3], (1, seq, hq, hd))

    def xla_ref(q, k, v):
        return flash_attention_reference(q, k, v, causal=True)
    got = out_and_grads(lambda q, k, v: flash_attention(
        q, k, v, causal=True, use_pallas=True, interpret=interp), qkv, wgt)
    ref_default = out_and_grads(xla_ref, qkv, wgt)
    with jax.default_matmul_precision("highest"):
        ref = out_and_grads(xla_ref, qkv, wgt)
    fl = out["flash_attention_gqa"] = {"shape": [1, seq, hq, hkv, hd],
                                       "rel_err": {}, "tol": {}}
    for name, a, d, b in zip(("out", "dq", "dk", "dv"), got, ref_default,
                             ref):
        check(bool(jnp.all(jnp.isfinite(a))), f"gqa flash {name} not finite")
        fl["rel_err"][name] = rel(a, b)
        fl["tol"][name] = max(2.0 ** -8, 4 * rel(d, b))
        check(fl["rel_err"][name] <= fl["tol"][name],
              f"gqa flash {name} rel err {fl['rel_err'][name]} > "
              f"{fl['tol'][name]}")
    del got, ref, ref_default, qkv

    # Two train steps through the normal path.
    mesh = build_mesh(HybridTopology(dp=ndev))
    params, specs = init_nemotron_h(jax.random.PRNGKey(0), cfg)
    opt = optax.adafactor(1e-3)
    opt_state = opt.init(params)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (ndev, seq + 1)),
                       jnp.int32)
    out["n_params"] = sum(int(np.prod(p.shape))
                          for p in jax.tree_util.tree_leaves(params))
    flags.resolved_kernels(reset=True)
    step = make_nemotron_h_train_step(cfg, mesh, specs, opt)
    losses = out["losses"] = []
    load = None
    for _ in range(2):
        params, opt_state, loss, aux = step(params, opt_state, toks[:, :-1],
                                            toks[:, 1:])
        losses.append(float(loss))
        load = np.asarray(aux["load"])
        check(int(np.asarray(aux["dropped"]).sum()) == 0,
              f"dropped assignments: {np.asarray(aux['dropped'])}")
    out["load_last_step"] = load.tolist()
    resolved = out["resolved_kernels"] = flags.resolved_kernels()
    mode = "interpret" if sm.rehearsal else "pallas"
    want = {"nemotron_ssd": [mode], "nemotron_attention": [mode],
            "ssd_scan": [mode], "flash_attention": [mode],
            "nemotron_moe_dispatch": [
                "interpret" if sm.rehearsal else "sort_pallas_grouped"]}
    check(all(resolved.get(k) == v for k, v in want.items()),
          f"kernels resolved to {resolved}, want {want}")
    check(load.sum() > 0, "no held expert served any assignment")
    check(all(np.isfinite(losses)), f"losses not finite: {losses}")
    check(losses[1] < losses[0],
          f"loss did not fall on the repeated batch: {losses}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy sizes on 2 virtual CPU devices, Pallas "
                         "kernels interpreted; the report says so")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out"),
                    help="directory for the JSON report")
    args = ap.parse_args(argv)

    from paddlebox_tpu.core import flags
    cache_dir = None
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=2").strip()
    else:
        cache_dir = flags.compilation_cache_dir()
    import jax
    dev = jax.devices()
    if not args.rehearse_cpu and dev[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, jax found {dev[0].platform!r} "
              f"({dev[0].device_kind}); nothing was run", file=sys.stderr)
        return 2
    if args.rehearse_cpu:
        flags.set_flags({"sparse_gather_kernel": "interpret",
                         "sparse_scatter_kernel": "interpret"})

    sm = Smoke(args.rehearse_cpu, args.out, cache_dir)
    t0 = time.perf_counter()
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmpdir:
            sm.phase("identity", lambda rec: phase_identity(sm, rec))
            sm.phase("kernels", lambda rec: phase_kernels(sm, rec))
            held = []
            sm.phase("deepfm", lambda rec: held.extend(
                phase_deepfm(sm, rec, tmpdir)))
            sm.phase("predict", lambda rec: phase_predict(sm, rec, *held))
            sm.phase("gpt", lambda rec: phase_gpt(sm, rec))
            sm.phase("nemotron", lambda rec: phase_nemotron(sm, rec))
        sm.report["facts"] = {
            "total_seconds": round(time.perf_counter() - t0, 1),
            "compile_seconds": round(sum(
                p["compile_seconds"] for p in sm.report["phases"].values()),
                1),
            "cache_hits": sum(p["cache_hits"]
                              for p in sm.report["phases"].values()),
            "cache_misses": sum(p["cache_misses"]
                                for p in sm.report["phases"].values()),
            "cache_entries_after": sm.cache_entries(),
        }
        sm.report["ok"] = True
    finally:
        path = sm.write_report()
        print(f"[chip_smoke] report: {path}", file=sys.stderr, flush=True)
    print(json.dumps(sm.report, default=str))
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
