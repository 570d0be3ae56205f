"""AOT-compile MULTI-CHIP training steps for TPU — no TPU needed.

`dryrun_multichip` proves the sharded programs are semantically correct
on virtual CPU devices; this tool proves they also pass the real
XLA:TPU pipeline — ICI collective lowering (all_to_all, ppermute,
psum), 1F1B's scan-over-stages, ring attention, and the Pallas kernels
inside shard_map — against a 4-device v5e compile-only topology:

    python tools/aot_check_multichip.py

Covers: (1) GPT hybrid pp=2 x sp=2 with the 1F1B schedule and ring
attention; (2) the sparse CTR step over dp=4 (table sharded over dp,
bucket-by-shard all-to-all pull/push); (3) the device-resident store's
sharded gather/scatter/append programs (request/serve/reply
all_to_all).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4"
                           ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from paddlebox_tpu.parallel import HybridTopology, build_mesh  # noqa: E402


from tools._aot_common import sds, tpu_topology  # noqa: E402


def check_gpt_hybrid(topo) -> None:
    from paddlebox_tpu.models.gpt import (GPTConfig, init_gpt,
                                          make_gpt_train_step)
    cfg = GPTConfig(vocab_size=1024, d_model=128, n_heads=4, n_layers=4,
                    d_ff=256, max_seq_len=128, attention="ring")
    params, specs = init_gpt(jax.random.PRNGKey(0), cfg, pp_stages=2)
    opt = optax.adam(1e-3)
    mesh = build_mesh(HybridTopology(dp=1, pp=2, sp=2, mp=1),
                      devices=list(topo.devices))
    step = make_gpt_train_step(cfg, mesh, specs, opt, num_microbatches=2,
                               schedule="1f1b")
    opt_state = jax.eval_shape(opt.init, sds(params))
    tokens = jax.ShapeDtypeStruct((4, 128), jnp.int32)
    step.lower(sds(params), opt_state, tokens, tokens).compile()
    print("AOT gpt hybrid (pp=2 sp=2, 1f1b, ring attention): OK")


def check_ctr_dp4(topo) -> None:
    from jax.sharding import Mesh

    from paddlebox_tpu.core import flags as flagmod
    from paddlebox_tpu.data.slots import (DataFeedConfig, SlotBatch,
                                          SlotConf)
    from paddlebox_tpu.embedding import TableConfig
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.train import CTRTrainer, TrainerConfig

    n_slots, emb_dim, batch = 4, 8, 256
    slots = tuple(SlotConf(f"s{i}", avg_len=1.0) for i in range(n_slots))
    feed = DataFeedConfig(slots=slots, batch_size=batch,
                          slot_capacity_slack=1.0)
    model = DeepFM(slot_names=tuple(f"s{i}" for i in range(n_slots)),
                   emb_dim=emb_dim, hidden=(64,))
    mesh_cpu = build_mesh(HybridTopology(dp=4))
    tr = CTRTrainer(model, feed, TableConfig(dim=emb_dim),
                    mesh=mesh_cpu,
                    config=TrainerConfig(auc_num_buckets=1 << 12))
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
    dense_j = jnp.zeros((batch, 0), jnp.float32)
    args = (tables, tr.params, tr.opt_state, tr.auc_state, rows, segs_j,
            jnp.asarray(b.labels), jnp.asarray(b.valid), dense_j,
            jnp.zeros((), jnp.int32))
    tr.mesh = Mesh(np.array(topo.devices).reshape(4), (tr.axis,))
    flagmod.set_flags({"sparse_scatter_kernel": "pallas",
                       "sparse_gather_kernel": "pallas"})
    step = tr._build_step()
    step.lower(*sds(args)).compile()
    print("AOT ctr dp=4 (sharded table all-to-all pull/push): OK")


def check_device_store_sharded(topo) -> None:
    """The HBM-resident store's cross-chip programs: request/serve/reply
    all_to_all gather, write-back scatter, and on-device row append."""
    from jax.sharding import Mesh

    from paddlebox_tpu.embedding.device_store import (
        _append_fn_sharded, _gather_fn_sharded, _scatter_fn_sharded)

    mesh = Mesh(np.array(topo.devices).reshape(4), ("dp",))
    s, cap_store, w, rps, cap = 4, 1 << 18, 23, 1 << 16, 1 << 14

    # Resident values are a parts TUPLE since the slot-column split
    # (1-tuple under the fused layout; (hot, slot) under split/host).
    v = (jax.ShapeDtypeStruct((s * (cap_store + 1), w), jnp.float32),)
    rq = jax.ShapeDtypeStruct((s, s * cap), jnp.int32)
    ii = jax.ShapeDtypeStruct((s, 1), jnp.int32)
    iv = jax.ShapeDtypeStruct((s, w), jnp.float32)
    _gather_fn_sharded(mesh, "dp", s, cap, (w,), rps, cap_store).lower(
        v, rq, rq, ii, iv).compile()
    b = jax.ShapeDtypeStruct(((rps + 1) * s, w), jnp.float32)
    _scatter_fn_sharded(mesh, "dp", s, cap, (w,)).lower(
        v, b, rq, rq).compile()
    keys = jax.ShapeDtypeStruct((s * (1 << 12),), jnp.uint32)
    tmpl = jax.ShapeDtypeStruct((s, w), jnp.float32)
    st = jax.ShapeDtypeStruct((s,), jnp.int32)
    _append_fn_sharded(mesh, "dp", (w,), 1 << 12, 16, 0, 0.01).lower(
        v, keys, tmpl, st, st).compile()
    # Split placement variant: same collectives, two-part writes.
    hot = 16 + 3
    v2 = (jax.ShapeDtypeStruct((s * (cap_store + 1), hot), jnp.float32),
          jax.ShapeDtypeStruct((s * (cap_store + 1), w - hot),
                               jnp.float32))
    _gather_fn_sharded(mesh, "dp", s, cap, (hot, w - hot), rps,
                       cap_store).lower(v2, rq, rq, ii, iv).compile()
    _scatter_fn_sharded(mesh, "dp", s, cap, (hot, w - hot)).lower(
        v2, b, rq, rq).compile()
    print("AOT device store sharded gather/scatter/append: OK")


def main() -> None:
    topo = tpu_topology("v5e:2x2x1")
    if topo is None:
        return
    check_gpt_hybrid(topo)
    check_ctr_dp4(topo)
    check_device_store_sharded(topo)
    print("MULTICHIP TPU AOT COMPILE: OK")


if __name__ == "__main__":
    main()
