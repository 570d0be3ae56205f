"""AOT-compile the FULL jitted CTR train step for TPU — no TPU needed.

The per-kernel AOT check (tools/aot_check_kernels.py) proves each Pallas
kernel compiles; this tool proves the whole bench device program does —
pull all-to-all, fwd/bwd, scatter-accumulate push (Pallas path active:
the flag's "auto" gate is forced on), dense update, AUC histograms —
through the real XLA:TPU + Mosaic pipeline via jax's compile-only PJRT
topology. Run after any change to the step, kernels, or models:

    python tools/aot_check_step.py

Shapes are a scaled-down bench config (full-scale kernel shapes are
covered by the per-kernel tests; program structure, not size, is what
this validates).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Append (last occurrence of a repeated flag wins) so an inherited
# 8-virtual-device setting from a test env doesn't leak in. 4 virtual
# CPU devices: the single-chip step builds on devices[:1]; the ZeRO
# dp=4 section needs a real 4-way mesh to learn its argument structure.
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4"
                           ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from paddlebox_tpu.core import flags as flagmod  # noqa: E402
from paddlebox_tpu.data.slots import DataFeedConfig, SlotConf  # noqa: E402
from paddlebox_tpu.embedding import TableConfig  # noqa: E402
from paddlebox_tpu.models import DeepFM  # noqa: E402
from paddlebox_tpu.parallel import HybridTopology, build_mesh  # noqa: E402
from paddlebox_tpu.train import CTRTrainer, TrainerConfig  # noqa: E402

from tools._aot_common import sds as sds_like  # noqa: E402
from tools._aot_common import tpu_topology  # noqa: E402


def main() -> None:
    n_slots, emb_dim, dense_dim, batch = 8, 16, 13, 1024
    pass_keys = 200_000

    slots = tuple(SlotConf(f"s{i}", avg_len=1.0) for i in range(n_slots))
    slots += (SlotConf("d", is_dense=True, dim=dense_dim),)
    feed = DataFeedConfig(slots=slots, batch_size=batch,
                          slot_capacity_slack=1.0)
    model = DeepFM(slot_names=tuple(f"s{i}" for i in range(n_slots)),
                   emb_dim=emb_dim, dense_dim=dense_dim,
                   hidden=(400, 400, 400))
    mesh_cpu = build_mesh(HybridTopology(dp=1), devices=jax.devices()[:1])
    tr = CTRTrainer(model, feed,
                    TableConfig(dim=emb_dim, learning_rate=0.05),
                    mesh=mesh_cpu,
                    config=TrainerConfig(auc_num_buckets=1 << 16,
                                         compute_dtype="bfloat16",
                                         data_norm=True))
    tr.init(seed=0)

    # Real pass state on CPU to learn the exact argument structure.
    rng = np.random.default_rng(0)
    keys = np.sort(rng.choice(np.arange(1, 10 * pass_keys, dtype=np.uint64),
                              pass_keys, replace=False))
    tr.engine.feed_pass([keys for _ in tr.engine.groups])
    tables = tr.engine.begin_pass()

    import ml_dtypes
    from paddlebox_tpu.data.slots import SlotBatch
    ids = {f"s{i}": rng.choice(keys, batch).astype(np.uint64)
           for i in range(n_slots)}
    segs = {n: np.arange(batch, dtype=np.int32) for n in ids}
    batch_obj = SlotBatch(
        labels=(rng.random((batch, 1)) < 0.2).astype(np.float32),
        valid=np.ones((batch,), bool),
        ids=ids, segments=segs,
        lengths={n: np.ones((batch,), np.int32) for n in ids},
        dense={"d": rng.normal(size=(batch, dense_dim)
                               ).astype(np.float32)})
    rows = tr._map_batch_rows(batch_obj)
    segs_j = {n: jnp.asarray(batch_obj.segments[n]) for n in ids}
    dense_j = jnp.asarray(batch_obj.dense["d"].astype(ml_dtypes.bfloat16))

    args = (tables, tr.params, tr.opt_state, tr.auc_state, rows, segs_j,
            jnp.asarray(batch_obj.labels), jnp.asarray(batch_obj.valid),
            dense_j, jnp.zeros((), jnp.int32))

    # Rebuild the step against a compile-only TPU device mesh and force
    # the Pallas scatter path (the "auto" gate keys off the default
    # backend, which is cpu here).
    topo = tpu_topology("v5e:2x2x1")
    if topo is None:
        return
    tr.mesh = Mesh(np.array([topo.devices[0]]), (tr.axis,))
    flagmod.set_flags({"sparse_scatter_kernel": "pallas",
                       "sparse_gather_kernel": "pallas"})
    step = tr._build_step()
    compiled = step.lower(*sds_like(args)).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):  # jax < 0.5 returns [dict]
        ca = ca[0] if ca else {}
    print("FULL-STEP TPU AOT COMPILE: OK "
          f"(flops={ca.get('flops', 0):.3e})")

    # int8 dense-sync variant (FLAGS_dense_allreduce_dtype=int8): the
    # quantize -> psum(int32) -> dequantize dense-grad wire is a
    # different device program than the verbatim-f32 step — it must
    # survive XLA:TPU on its own.
    flagmod.set_flags({"dense_allreduce_dtype": "int8"})
    try:
        tr._build_step().lower(*sds_like(args)).compile()
    finally:
        flagmod.set_flags({"dense_allreduce_dtype": "f32"})
    print("FULL-STEP(int8 dense sync) TPU AOT COMPILE: OK")

    eval_step = tr._build_eval_step()
    eval_args = (tables, tr.params, tr.auc_state, rows, segs_j,
                 jnp.asarray(batch_obj.labels),
                 jnp.asarray(batch_obj.valid), dense_j)
    eval_step.lower(*sds_like(eval_args)).compile()
    print("EVAL-STEP TPU AOT COMPILE: OK")

    # K-step scanned megastep (FLAGS_trainer_steps_per_dispatch=4):
    # the lax.scan wrapper + donation + both Pallas kernels INSIDE the
    # scan body must survive the real XLA:TPU + Mosaic pipeline —
    # compile-only shape stand-ins with the stacked [K, ...] leading
    # axis the prefetcher produces.
    K = 4

    def stk(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                (K,) + tuple(np.shape(x)), jnp.asarray(x).dtype), tree)

    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    mega = tr._build_step(k_steps=K)
    mega_args = (*sds_like((tables, tr.params, tr.opt_state,
                            tr.auc_state)), i32, i32,
                 stk(rows), stk(segs_j), stk(batch_obj.labels),
                 stk(batch_obj.valid), stk(dense_j))
    mega.lower(*mega_args).compile()
    print(f"MEGASTEP(K={K}) TPU AOT COMPILE: OK")

    mega_eval = tr._build_eval_step(k_steps=K)
    mega_eval_args = (*sds_like((tables, tr.params, tr.auc_state)), i32,
                      stk(rows), stk(segs_j), stk(batch_obj.labels),
                      stk(batch_obj.valid), stk(dense_j))
    mega_eval.lower(*mega_eval_args).compile()
    print(f"MEGASTEP-EVAL(K={K}) TPU AOT COMPILE: OK")

    # Fused pass-boundary program (FLAGS_pass_boundary_fuse): the
    # end_pass scatter + next-pass remainder gather in ONE dispatch —
    # both the single-chip program and the sharded all_to_all variant
    # must survive XLA:TPU (the boundary is pure-XLA scatter/gather, so
    # any regression here is an XLA-lowering one, caught without a chip).
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddlebox_tpu.embedding.device_store import (
        _fused_boundary_fn_local, _fused_boundary_fn_sharded)

    w_rec = 2 * emb_dim + 8          # bench-ish fused record width
    rps = 32768                      # 20K-key pass pow2 bucket
    m_cap = 16384                    # shared-remainder pow2 bucket
    store_rows = 1 << 20
    mesh1 = Mesh(np.array([topo.devices[0]]), (tr.axis,))
    rep = NamedSharding(mesh1, P())

    def sd(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=rep)

    fb = _fused_boundary_fn_local((w_rec,), rps, rps)
    fb.lower((sd((store_rows + 1, w_rec)),), sd((rps + 1, w_rec)),
             sd((rps,), jnp.int32), sd((rps + 1, w_rec)),
             sd((m_cap,), jnp.int32), sd((m_cap,), jnp.int32)).compile()
    print("FUSED-BOUNDARY(local) TPU AOT COMPILE: OK")

    s = min(4, len(topo.devices))
    mesh_s = Mesh(np.array(topo.devices[:s]), (tr.axis,))
    cap = 2048
    scap = 1 << 18
    fbs = _fused_boundary_fn_sharded(mesh_s, tr.axis, s, cap, cap,
                                     (w_rec,), rps, rps, scap)
    f32, i32t = jnp.float32, jnp.int32
    fbs.lower(
        (jax.ShapeDtypeStruct((s * (scap + 1), w_rec), f32),),
        jax.ShapeDtypeStruct((s * (rps + 1), w_rec), f32),
        jax.ShapeDtypeStruct((s, s * cap), i32t),
        jax.ShapeDtypeStruct((s, s * cap), i32t),
        jax.ShapeDtypeStruct((s * (rps + 1), w_rec), f32),
        jax.ShapeDtypeStruct((s, s * cap), i32t),
        jax.ShapeDtypeStruct((s, s * cap), i32t)).compile()
    print(f"FUSED-BOUNDARY(sharded S={s}) TPU AOT COMPILE: OK")

    # Split slot placement (FLAGS_table_slot_placement=split|host): the
    # resident store is a (hot [rows, D+3], slot [rows, Ke+Kw]) parts
    # tuple and the push writes BOTH parts inside one dispatch — the
    # column-split scatter and the two-part fused boundary are distinct
    # device programs from the 1-tuple fused layout and must survive
    # XLA:TPU on their own (same collective count: ONE request
    # all_to_all + ONE fused-width reply).
    from paddlebox_tpu.embedding.device_store import _scatter_fn_sharded
    hot_w = emb_dim + 3
    widths2 = (hot_w, w_rec - hot_w)
    parts2 = tuple(jax.ShapeDtypeStruct((s * (scap + 1), wp), f32)
                   for wp in widths2)
    _scatter_fn_sharded(mesh_s, tr.axis, s, cap, widths2).lower(
        parts2,
        jax.ShapeDtypeStruct((s * (rps + 1), w_rec), f32),
        jax.ShapeDtypeStruct((s, s * cap), i32t),
        jax.ShapeDtypeStruct((s, s * cap), i32t)).compile()
    fbs2 = _fused_boundary_fn_sharded(mesh_s, tr.axis, s, cap, cap,
                                      widths2, rps, rps, scap)
    fbs2.lower(
        parts2,
        jax.ShapeDtypeStruct((s * (rps + 1), w_rec), f32),
        jax.ShapeDtypeStruct((s, s * cap), i32t),
        jax.ShapeDtypeStruct((s, s * cap), i32t),
        jax.ShapeDtypeStruct((s * (rps + 1), w_rec), f32),
        jax.ShapeDtypeStruct((s, s * cap), i32t),
        jax.ShapeDtypeStruct((s, s * cap), i32t)).compile()
    print(f"SPLIT-SLOT-PUSH(sharded S={s}) TPU AOT COMPILE: OK")

    # ZeRO-sharded dense step (FLAGS_dense_zero=shard over dp=4): the
    # psum -> zero_slice -> shard update -> tiled all-gather schedule
    # plus the clip-decomposed optimizer, inside the full shard_map'd
    # CTR step with sharded opt_state in/out specs.
    check_zero_step(topo)


def check_zero_step(topo) -> None:
    from paddlebox_tpu.data.slots import SlotBatch

    flagmod.set_flags({"dense_zero": "shard", "dense_zero_min_size": 0})
    try:
        n_slots, emb_dim, batch = 4, 8, 256
        slots = tuple(SlotConf(f"s{i}", avg_len=1.0)
                      for i in range(n_slots))
        feed = DataFeedConfig(slots=slots, batch_size=batch,
                              slot_capacity_slack=1.0)
        model = DeepFM(slot_names=tuple(f"s{i}" for i in range(n_slots)),
                       emb_dim=emb_dim, hidden=(64,))
        tr = CTRTrainer(
            model, feed, TableConfig(dim=emb_dim),
            mesh=build_mesh(HybridTopology(dp=4)),
            config=TrainerConfig(auc_num_buckets=1 << 12,
                                 dense_optimizer="adam",
                                 grad_clip_norm=1.0))
        tr.init(seed=0)
        rng = np.random.default_rng(0)
        keys = np.sort(rng.choice(np.arange(1, 100_000, dtype=np.uint64),
                                  20_000, replace=False))
        tr.engine.feed_pass([keys for _ in tr.engine.groups])
        tables = tr.engine.begin_pass()
        ids = {f"s{i}": rng.choice(keys, batch).astype(np.uint64)
               for i in range(n_slots)}
        b = SlotBatch(
            labels=(rng.random((batch, 1)) < 0.2).astype(np.float32),
            valid=np.ones((batch,), bool), ids=ids,
            segments={n: np.arange(batch, dtype=np.int32) for n in ids},
            lengths={n: np.ones((batch,), np.int32) for n in ids},
            dense={})
        rows = tr._map_batch_rows(b)
        segs_j = {n: jnp.asarray(b.segments[n]) for n in ids}
        args = (tables, tr.params, tr.opt_state, tr.auc_state, rows,
                segs_j, jnp.asarray(b.labels), jnp.asarray(b.valid),
                jnp.zeros((batch, 0), jnp.float32),
                jnp.zeros((), jnp.int32))
        assert tr._dense_zero == "shard"
        tr.mesh = Mesh(np.array(topo.devices[:4]).reshape(4), (tr.axis,))
        tr._build_step().lower(*sds_like(args)).compile()
        print("ZERO-STEP(dp=4, adam+clip) TPU AOT COMPILE: OK")
    finally:
        flagmod.set_flags({"dense_zero": "off"})


if __name__ == "__main__":
    main()
