"""Structural regression net over the fused CTR train step.

The r02→r03 rework collapsed the push path from six scatter-adds +
three argsorts + six gathers per step to ONE owner-side
scatter-accumulate + a dense optimizer sweep (r02 chip run: XLA TPU
scatter costs ~7 ns/element, so scatter COUNT is the step's cost
model). These tests pin the op-level shape of the compiled program so a
refactor that quietly reintroduces per-field scatters (or a second
all_to_all round) fails loudly here instead of as a silent 3x
throughput regression the CPU tests can't see.
"""

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.data.parser import parse_lines
from paddlebox_tpu.data.slots import DataFeedConfig, SlotBatch, SlotConf
from paddlebox_tpu.embedding import DeviceFeatureStore, TableConfig
from paddlebox_tpu.models import DeepFM
from paddlebox_tpu.parallel import HybridTopology, build_mesh
from paddlebox_tpu.train import CTRTrainer, TrainerConfig
from paddlebox_tpu.train.ctr_trainer import _concat_dense_host
from paddlebox_tpu.utils import inspect as pbx_inspect


def _trainer_and_batch(ndev=4):
    mesh = build_mesh(HybridTopology(dp=ndev),
                      devices=jax.devices()[:ndev])
    slots = tuple(SlotConf(f"s{i}", avg_len=2.0) for i in range(3))
    feed = DataFeedConfig(slots=slots, batch_size=4 * ndev)
    model = DeepFM(slot_names=tuple(f"s{i}" for i in range(3)),
                   emb_dim=8, hidden=(16, 8))
    tr = CTRTrainer(model, feed, TableConfig(dim=8), mesh=mesh,
                    config=TrainerConfig(auc_num_buckets=1 << 10),
                    store_factory=lambda c: DeviceFeatureStore(
                        c, mesh=mesh))
    tr.init(seed=0)
    rng = np.random.default_rng(0)
    lines = [f"{rng.integers(0, 2)} "
             + " ".join(f"s{i}:{rng.integers(1, 40)}" for i in range(3))
             for _ in range(feed.batch_size)]
    batch = SlotBatch.pack_sharded(parse_lines(lines, feed), feed, ndev)
    tr.engine.feed_pass([
        np.unique(np.concatenate([batch.ids[n] for n in g.slots]))
        for g in tr.engine.groups])
    return tr, batch


def _step_op_counts(ndev=4):
    tr, batch = _trainer_and_batch(ndev)
    step = tr._build_step()
    tables = tr.engine.begin_pass()
    rows = tr._map_batch_rows(batch)
    segs = {n: jnp.asarray(batch.segments[n]) for n in batch.ids}
    args = (tables, tr.params, tr.opt_state, tr.auc_state, rows, segs,
            jnp.asarray(batch.labels), jnp.asarray(batch.valid),
            jnp.asarray(_concat_dense_host(batch)),
            jnp.zeros((), jnp.int32))
    return pbx_inspect.jaxpr_summary(lambda *a: step(*a), *args)


def test_ctr_step_collective_and_scatter_budget():
    c = _step_op_counts()
    # Exactly THREE all_to_alls for a single width group: the SHARED
    # rows exchange (compute_bucketing moves send_rows once for the
    # pull's requests AND the push's destinations — same array), the
    # pull reply, and the push payload. A fourth means the pull/push
    # stopped sharing the rows exchange (or a new collective round
    # crept into the hot path).
    assert c.get("all_to_all", 0) == 3, c
    # Scatter budget: ONE shared bucket-set (pull+push share the
    # bucket-by-shard layout), payload add, owner-side accumulate, AUC
    # histograms, and the gather-VJP scatter-adds from autodiff. The
    # six-field push layout this replaced would blow past the ceiling
    # (+5 per width group).
    assert (c.get("scatter-add", 0) + c.get("scatter", 0)) <= 12, c
    # Dedup-before-exchange (r05): representatives come from ONE
    # scatter-min over the row space per width group — a second one
    # means the layout stopped being shared between pull and push.
    assert c.get("scatter-min", 0) <= 1, c
    # ...and its routing costs at most two extra [n] gathers (first_idx,
    # representative cell) on top of the r04 budget of 10.
    assert c.get("gather", 0) <= 12, c
    # SORT-FREE bucketing, dedup included: positions come from a one-hot
    # cumsum and representatives from a scatter-min, so the step carries
    # ZERO sorts (the r02 layout carried 3 argsorts in the push alone;
    # the reference's dedup itself is 2x cub radix sort,
    # heter_comm.h:196-205; the Pallas accumulate's internal sort lives
    # behind the TPU-only flag and is not part of this CPU lowering).
    assert c.get("sort", 0) == 0, c
    assert c.get("cumsum", 0) >= 1, c


def test_ctr_megastep_one_scan_unchanged_per_step_budget():
    """The K-step megastep (FLAGS_trainer_steps_per_dispatch) must be
    ONE lax.scan wrapping the SAME per-step body: exactly one scan in
    the program, and the per-step collective / scatter / sort budgets
    of the K=1 pins above unchanged — jaxpr_summary counts the scan
    body ONCE, so any number here growing with K means ops leaked out
    of the scan (paid per block) or multiplied inside it."""
    K = 4
    tr, batch = _trainer_and_batch()
    mega = tr._build_step(k_steps=K)
    tables = tr.engine.begin_pass()
    rows = tuple(jnp.stack([r] * K) for r in tr._map_batch_rows(batch))
    segs = {n: jnp.stack([jnp.asarray(batch.segments[n])] * K)
            for n in batch.ids}
    stack = lambda x: jnp.stack([jnp.asarray(x)] * K)  # noqa: E731
    args = (tables, tr.params, tr.opt_state, tr.auc_state,
            jnp.zeros((), jnp.int32), jnp.asarray(K, jnp.int32),
            rows, segs, stack(batch.labels), stack(batch.valid),
            stack(_concat_dense_host(batch)))
    c = pbx_inspect.jaxpr_summary(lambda *a: mega(*a), *args)
    assert c.get("scan", 0) == 1, c
    # Per-step budgets identical to test_ctr_step_collective_and_
    # scatter_budget — the scan re-stages the body, it must not reshape
    # it (an extra all_to_all or scatter here costs K× per block).
    assert c.get("all_to_all", 0) == 3, c
    assert (c.get("scatter-add", 0) + c.get("scatter", 0)) <= 12, c
    assert c.get("scatter-min", 0) <= 1, c
    assert c.get("gather", 0) <= 12, c
    assert c.get("sort", 0) == 0, c
    assert c.get("cumsum", 0) >= 1, c


def _walk_eqns(jaxpr, in_cond=False):
    """Yield (primitive_name, eqn, inside_cond_branch) over the whole
    program. ``inside_cond_branch`` marks ops that exist only in a
    lax.cond / lax.switch arm — the sorted-stream gather keeps its
    exact XLA net there (more distinct rows asked of one block than the
    kernel budget), and the budget below must distinguish that arm's
    table-sized gather from one on the hot path."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, eqn, in_cond
        inner_cond = in_cond or eqn.primitive.name == "cond"
        for p in eqn.params.values():
            items = p if isinstance(p, (tuple, list)) else (p,)
            for item in items:
                if hasattr(item, "eqns"):
                    yield from _walk_eqns(item, inner_cond)


def test_ctr_step_pallas_mode_no_table_gather_scatter_one_sort():
    """The Pallas sorted-stream pair (sparse_gather_kernel +
    sparse_scatter_kernel = pallas) must leave ZERO XLA gathers reading
    the table and ZERO XLA scatters building the [block, aw] grad
    accumulator on the hot path (the gather's exact XLA net lives
    inside a lax.switch arm only; the scatter has no XLA arm at all),
    and the shared pull+push layout must pay exactly ONE sort per width
    group, arms included — the whole point of sharing
    compute_bucketing's stream layout; the gather's distinct tier
    compacts its stream without a second one."""
    import jax.tree_util as jtu

    from paddlebox_tpu.core import flags as flagmod
    from paddlebox_tpu.embedding.table import PassTable

    flagmod.set_flags({"sparse_gather_kernel": "pallas",
                       "sparse_scatter_kernel": "pallas"})
    try:
        mesh = build_mesh(HybridTopology(dp=4), devices=jax.devices()[:4])
        slots = tuple(SlotConf(f"s{i}", avg_len=2.0) for i in range(3))
        feed = DataFeedConfig(slots=slots, batch_size=16)
        model = DeepFM(slot_names=tuple(f"s{i}" for i in range(3)),
                       emb_dim=8, hidden=(16, 8))
        tr = CTRTrainer(model, feed, TableConfig(dim=8), mesh=mesh,
                        config=TrainerConfig(auc_num_buckets=1 << 10),
                        store_factory=lambda c: DeviceFeatureStore(
                            c, mesh=mesh))
        tr.init(seed=0)
        rng = np.random.default_rng(0)
        lines = [f"{rng.integers(0, 2)} "
                 + " ".join(f"s{i}:{rng.integers(1, 40)}" for i in range(3))
                 for _ in range(feed.batch_size)]
        batch = SlotBatch.pack_sharded(parse_lines(lines, feed), feed, 4)
        tr.engine.feed_pass([
            np.unique(np.concatenate([batch.ids[n] for n in g.slots]))
            for g in tr.engine.groups])
        step = tr._build_step()
        tables = tr.engine.begin_pass()
        rows = tr._map_batch_rows(batch)
        segs = {n: jnp.asarray(batch.segments[n]) for n in batch.ids}
        args = (tables, tr.params, tr.opt_state, tr.auc_state, rows, segs,
                jnp.asarray(batch.labels), jnp.asarray(batch.valid),
                jnp.asarray(_concat_dense_host(batch)),
                jnp.zeros((), jnp.int32))
        jaxpr = jax.make_jaxpr(lambda *a: step(*a))(*args)

        # Per-shard table/accumulator shapes as the shard_map body sees
        # them (gathers/scatters against these are the ~6-7 ns/element
        # ops the kernels exist to kill).
        t = tables[0]
        block, w = t.rows_per_shard + 1, t.vals.shape[-1]
        aw = t.dim + 4
        table_gathers, acc_scatters, sorts = [], [], 0
        for prim, eqn, in_cond in _walk_eqns(jaxpr.jaxpr):
            if prim == "sort":
                sorts += 1
            if in_cond or not eqn.invars:
                continue  # the gather's XLA net arm, by design
            shp = tuple(getattr(eqn.invars[0], "aval", None).shape
                        if hasattr(eqn.invars[0], "aval") else ())
            if prim == "gather" and shp == (block, w):
                table_gathers.append(eqn)
            if prim in ("scatter-add", "scatter") and shp == (block, aw):
                acc_scatters.append(eqn)
        assert not table_gathers, table_gathers
        assert not acc_scatters, acc_scatters
        # One width group -> exactly one argsort, shared by the pull
        # gather and the push scatter via compute_bucketing's layout.
        n_groups = len(tr.engine.groups)
        assert sorts == n_groups, (sorts, n_groups)
        assert jtu.tree_structure(args) is not None  # keep args alive
    finally:
        flagmod.set_flags({"sparse_gather_kernel": "auto",
                           "sparse_scatter_kernel": "auto"})


def test_jaxpr_summary_sees_inside_shard_map():
    """Guard for the introspection fix: shard_map carries a PLAIN Jaxpr
    param; the summary must recurse into it (a regression here silently
    turns the budget test above into {'jit': 1})."""
    from jax.sharding import PartitionSpec as P
    mesh = build_mesh(HybridTopology(dp=4), devices=jax.devices()[:4])

    def body(x):
        return jnp.zeros((8, 4)).at[jnp.array([1, 2])].add(x[:2])

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(),
                              out_specs=P(), check_vma=False))
    c = pbx_inspect.jaxpr_summary(f, jnp.ones((4, 4)))
    assert c.get("scatter-add", 0) >= 1, c
