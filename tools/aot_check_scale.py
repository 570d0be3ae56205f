"""AOT-compile the training steps at SCALE topologies (64 and 256 chips)
— evidence for 8→256-chip scaling without 256 real chips: the real
XLA:TPU pipeline lowers the full multislice CTR step (slice-hierarchical dense sync, intra-slice
all-to-all pull/push with the DCN accumulator psum) and the hybrid GPT
step at production-shaped meshes.

    python tools/aot_check_scale.py            # 64-chip checks
    python tools/aot_check_scale.py --chips 256

Role of the reference's multi-node scale validation (its README's
hundreds-of-nodes claim rides gather_multi_node_grad + two-level NCCL,
heter_comm.h:156-172) — here the compiler is the witness: if XLA can
schedule the collectives over the 16x16 v5e topology, the program runs
when the chips exist.

Scope note: the compile-only topology is a SINGLE physical slice, so
the "slice" mesh axis here is logical (a device reshape) and its
collectives lower to ICI — this validates the program structure and
collective schedule at 256-chip scale, not the DCN transport itself.
The DCN hop's semantics are pinned by tests/test_multislice.py parity;
on real multi-slice hardware build_mesh routes the slice axis over DCN
via create_hybrid_device_mesh.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

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
from jax.sharding import Mesh  # noqa: E402


from tools._aot_common import sds, tpu_topology  # noqa: E402


def check_ctr_multislice(topo, n_slices: int, dp: int) -> None:
    """Full CTR train step on slice x dp chips: table sharded over dp
    (intra-slice), batch over slice x dp, hierarchical dense sync, DCN
    push psum. The step is compiled from ShapeDtypeStructs only — no
    arrays ever touch the (non-addressable) AOT topology devices; the
    trainer is built on a tiny CPU mesh and its replica geometry is then
    repointed at the scale mesh before ``_build_step``."""
    from paddlebox_tpu.data.slots import DataFeedConfig, SlotConf
    from paddlebox_tpu.embedding import TableConfig
    from paddlebox_tpu.embedding.table import PassTable
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.parallel import HybridTopology, build_mesh
    from paddlebox_tpu.train import CTRTrainer, TrainerConfig

    n = n_slices * dp
    n_slots, emb_dim = 4, 8
    batch = 8 * n
    slots = tuple(SlotConf(f"s{i}", avg_len=1.0) for i in range(n_slots))
    feed = DataFeedConfig(slots=slots, batch_size=batch,
                          slot_capacity_slack=1.0)
    model = DeepFM(slot_names=tuple(f"s{i}" for i in range(n_slots)),
                   emb_dim=emb_dim, hidden=(64,))
    mesh_cpu = build_mesh(HybridTopology(slice=2, dp=2))
    tr = CTRTrainer(model, feed, TableConfig(dim=emb_dim), mesh=mesh_cpu,
                    config=TrainerConfig(auc_num_buckets=1 << 12))
    # Repoint replica geometry at the scale topology BEFORE building the
    # step: ndev (replicas), per-slot capacities, and the mesh itself.
    tr.mesh = Mesh(np.array(topo.devices).reshape(n_slices, dp),
                   ("slice", "dp"))
    tr.ndev = n
    tr._slot_caps = {s.name: feed.sparse_capacity(s, num_shards=n)
                     for s in feed.sparse_slots}

    # Hand-built arg shapes (what _map_batch_rows/begin_pass would feed).
    from paddlebox_tpu.embedding.table import table_widths
    rps = 1 << 14                       # rows per table shard
    _, ke, kw = table_widths(TableConfig(dim=emb_dim))
    w = emb_dim + 3 + ke + kw
    tables = tuple(
        PassTable(vals=jax.ShapeDtypeStruct((dp * (rps + 1), w),
                                            jnp.float32),
                  rows_per_shard=rps, num_shards=dp, dim=emb_dim,
                  ke=ke, kw=kw)
        for _ in tr.engine.groups)
    total_cap = sum(tr._slot_caps.values())
    rows = tuple(jax.ShapeDtypeStruct((total_cap,), jnp.int32)
                 for _ in tr.engine.groups)
    segs = {s.name: jax.ShapeDtypeStruct((tr._slot_caps[s.name],),
                                         jnp.int32)
            for s in feed.sparse_slots}
    params = sds(model.init(jax.random.PRNGKey(0)))
    opt_state = jax.eval_shape(tr._optax.init, params)
    auc = sds(tr._auc_init())
    args = (tables, params, opt_state, auc, rows, segs,
            jax.ShapeDtypeStruct((batch, 1), jnp.float32),
            jax.ShapeDtypeStruct((batch,), jnp.bool_),
            jax.ShapeDtypeStruct((batch, 0), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.int32))
    t0 = time.time()
    step = tr._build_step()
    step.lower(*args).compile()
    print(f"AOT ctr multislice slice={n_slices} dp={dp} "
          f"({n} chips, batch {batch}): OK in {time.time()-t0:.0f}s")


def check_gpt_scale(topo, n_slices: int, dp: int, pp: int, sp: int,
                    mp: int, schedule: str = "1f1b",
                    num_chunks: int = 1) -> None:
    from paddlebox_tpu.models.gpt import (GPTConfig, init_gpt,
                                          make_gpt_train_step)
    from paddlebox_tpu.parallel.topology import AXIS_ORDER

    n = n_slices * dp * pp * sp * mp
    cfg = GPTConfig(vocab_size=2048, d_model=256, n_heads=8,
                    n_layers=2 * pp * max(num_chunks, 1), d_ff=512,
                    max_seq_len=256, attention="ring")
    params, specs = init_gpt(jax.random.PRNGKey(0), cfg, pp_stages=pp)
    shape = {"slice": n_slices, "dp": dp, "pp": pp, "sp": sp, "mp": mp}
    dims = [shape.get(a, 1) for a in AXIS_ORDER]
    mesh = Mesh(np.array(topo.devices).reshape(dims), tuple(AXIS_ORDER))
    opt = optax.adam(1e-3)
    step = make_gpt_train_step(cfg, mesh, specs, opt, num_microbatches=2,
                               schedule=schedule, num_chunks=num_chunks)
    opt_state = jax.eval_shape(opt.init, sds(params))
    tokens = jax.ShapeDtypeStruct((4 * n_slices * dp, 256), jnp.int32)
    t0 = time.time()
    step.lower(sds(params), opt_state, tokens, tokens).compile()
    print(f"AOT gpt hybrid slice={n_slices} dp={dp} pp={pp} sp={sp} "
          f"mp={mp} schedule={schedule} ({n} chips): OK in "
          f"{time.time()-t0:.0f}s")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, default=64, choices=(64, 256))
    args = ap.parse_args()
    name = {64: "v5e:8x8x1", 256: "v5e:16x16x1"}[args.chips]
    topo = tpu_topology(name)
    if topo is None:
        return
    if args.chips == 64:
        check_ctr_multislice(topo, n_slices=4, dp=16)
        check_gpt_scale(topo, n_slices=2, dp=4, pp=2, sp=2, mp=2)
        check_gpt_scale(topo, n_slices=2, dp=4, pp=2, sp=2, mp=2,
                        schedule="interleaved_1f1b", num_chunks=2)
    else:
        check_ctr_multislice(topo, n_slices=4, dp=64)
        check_gpt_scale(topo, n_slices=4, dp=8, pp=2, sp=2, mp=2)
        check_gpt_scale(topo, n_slices=4, dp=8, pp=2, sp=2, mp=2,
                        schedule="interleaved_1f1b", num_chunks=2)
    print(f"SCALE TPU AOT COMPILE ({args.chips} chips): OK")


if __name__ == "__main__":
    main()
