"""sorted_gather (CopyForPull-class Pallas kernel) vs the XLA gather
reference — interpret mode on CPU; the same code compiles for TPU
(Mosaic AOT check in tools/aot_check_kernels.py). Covers the ISSUE's
parity matrix: uniform keys, skewed/hot rows (the distinct tier and the
XLA net behind it), trash rows, empty
blocks, widths 8/16/40, non-BLOCK-multiple row counts (the production
pow2+trash shape), the shared pull+push sort layout, and the lookup
wiring (pull_local single- and multi-shard) under the
``sparse_gather_kernel`` flag."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlebox_tpu.ops.pallas_kernels.sorted_gather import (
    sorted_gather, sorted_stream_layout, stream_tier)
from paddlebox_tpu.ops.pallas_kernels.sorted_scatter import (
    BLOCK, UCAP, sorted_scatter_accumulate)


def zipf_rows(rng, n, num_rows, a=1.2):
    """n Zipf(a)-ranked draws over num_rows rows, ranks scattered over
    the table the way hashed keys are (the day_zipf cell's id shape)."""
    return rng.permutation(num_rows)[
        (rng.zipf(a, n) - 1) % num_rows].astype(np.int32)


def _ref(rows, table, pw):
    keep = rows < table.shape[0]
    safe = np.where(keep, rows, 0)
    return np.where(keep[:, None], table[safe, :pw], 0.0).astype(np.float32)


@pytest.mark.parametrize("num_rows,n,w,pw", [
    (BLOCK, 1000, 16, 16),            # one block, full width
    (3 * BLOCK + 17, 20_000, 20, 16),  # non-multiple rows: tail block
    (BLOCK + 1, 9_000, 8, 8),          # the rows_per_shard+1 real shape
    (2 * BLOCK, 4_000, 40, 40),        # pull width 40 (wide mf)
])
def test_matches_xla_gather(num_rows, n, w, pw):
    rng = np.random.default_rng(0)
    rows = rng.integers(0, num_rows, n).astype(np.int32)
    table = rng.normal(size=(num_rows, w)).astype(np.float32)
    got = sorted_gather(jnp.asarray(rows), jnp.asarray(table), width=pw,
                        interpret=True)
    np.testing.assert_array_equal(np.asarray(got), _ref(rows, table, pw))


def test_trash_rows_dropped_to_zeros():
    rng = np.random.default_rng(1)
    num_rows = BLOCK + 1
    n = 6000
    rows = rng.integers(0, num_rows, n).astype(np.int32)
    # A third of entries carry the drop sentinel (padding/overflow), and
    # they CONCENTRATE — must count toward no block's run (else the
    # hot-row fallback would fire on every call).
    rows[::3] = num_rows
    table = rng.normal(size=(num_rows, 12)).astype(np.float32)
    got = sorted_gather(jnp.asarray(rows), jnp.asarray(table), width=12,
                        interpret=True)
    ref = _ref(rows, table, 12)
    assert (np.asarray(got)[::3] == 0.0).all()
    np.testing.assert_array_equal(np.asarray(got), ref)


def test_hot_row_served_by_distinct_tier():
    """More than UCAP requests for one row: the run is over the kernel's
    per-block budget, and the kernel serves it all the same — once, all
    requests sharing its staging slot (tier 1, not the XLA gather)."""
    rng = np.random.default_rng(2)
    num_rows = BLOCK
    n = UCAP + 2048
    rows = np.full((n,), 7, np.int32)
    rows[-5:] = num_rows              # plus a few dropped sentinels
    table = rng.normal(size=(num_rows, 16)).astype(np.float32)
    layout = sorted_stream_layout(jnp.asarray(rows), num_rows)
    assert int(layout[3]) == n - 5 and int(layout[4]) == 1
    assert int(stream_tier(layout)) == 1
    got = sorted_gather(jnp.asarray(rows), jnp.asarray(table), width=16,
                        interpret=True, layout=layout)
    np.testing.assert_array_equal(np.asarray(got), _ref(rows, table, 16))


def _skew_zipf(rng):
    return 3 * BLOCK + 17, zipf_rows(rng, 50_000, 3 * BLOCK + 17)


def _skew_one_row(rng):
    return 2 * BLOCK, np.full((3 * UCAP,), BLOCK + 5, np.int32)


def _skew_hot_sentinel(rng):
    # The hot "row" is the dropped sentinel: it counts toward no run.
    rows = rng.integers(0, BLOCK + 1, 3 * UCAP).astype(np.int32)
    rows[: 2 * UCAP + 100] = BLOCK + 1
    return BLOCK + 1, rng.permutation(rows)


def _skew_block_edge(rng):
    # Hot rows on the last row of a block and the first of the next.
    rows = rng.integers(0, 2 * BLOCK, 2 * UCAP + 900).astype(np.int32)
    rows[:UCAP + 300] = BLOCK - 1
    rows[UCAP + 300: 2 * UCAP + 700] = BLOCK
    return 2 * BLOCK, rng.permutation(rows)


def _skew_many_distinct(rng):
    # 5,000 distinct rows asked of one BLOCK-row table: past the budget
    # even when each is served once — the XLA net.
    return BLOCK, rng.permutation(BLOCK)[:5000].astype(np.int32)


@pytest.mark.parametrize("make,tier", [
    (_skew_zipf, 1), (_skew_one_row, 1), (_skew_hot_sentinel, 0),
    (_skew_block_edge, 1), (_skew_many_distinct, 2)],
    ids=["zipf", "one_row", "hot_sentinel", "block_edge", "many_distinct"])
def test_skewed_requests_exact_in_their_tier(make, tier):
    rng = np.random.default_rng(10)
    num_rows, rows = make(rng)
    table = rng.normal(size=(num_rows, 20)).astype(np.float32)
    layout = sorted_stream_layout(jnp.asarray(rows), num_rows)
    assert int(stream_tier(layout)) == tier
    got = sorted_gather(jnp.asarray(rows), jnp.asarray(table), width=16,
                        interpret=True, layout=layout)
    np.testing.assert_array_equal(np.asarray(got), _ref(rows, table, 16))


def test_distinct_run_counted_only_over_budget():
    """max_distinct_run is the most distinct rows any block is asked for
    when a run is over the budget; under it, max_run stands in."""
    rng = np.random.default_rng(11)
    num_rows = 2 * BLOCK
    hot = np.concatenate([np.full((UCAP + 1,), 3, np.int32),
                          np.arange(BLOCK, BLOCK + 700, dtype=np.int32),
                          np.arange(100, 140, dtype=np.int32)])
    layout = sorted_stream_layout(jnp.asarray(rng.permutation(hot)),
                                  num_rows)
    assert int(layout[3]) == UCAP + 41 and int(layout[4]) == 700
    spread = rng.integers(0, num_rows, 3000).astype(np.int32)
    layout = sorted_stream_layout(jnp.asarray(spread), num_rows)
    assert int(layout[4]) == int(layout[3]) <= UCAP


def test_hot_row_served_is_counted():
    """What the kernels did with an over-budget run is observable: the
    lookup counters read the shared layout's tier, so the trainer can
    sum them into the pass stats (``kernel_hot_served``,
    ``kernel_fallback``) next to ``lookup_overflow``."""
    from paddlebox_tpu.embedding.lookup import (kernel_fallback,
                                                kernel_hot_served)
    num_rows = 2 * BLOCK
    hot = jnp.full((UCAP + 1,), 7, jnp.int32)
    spread = jnp.arange(UCAP + 1, dtype=jnp.int32) * 4 % num_rows
    crowded = jnp.arange(UCAP + 1, dtype=jnp.int32)    # one block, distinct

    def shared(rows):        # compute_bucketing's one-shard tuple
        return (None, None, None, None, rows,
                sorted_stream_layout(rows, num_rows))

    def counted(bucketing):
        return (int(kernel_hot_served(bucketing)),
                int(kernel_fallback(bucketing)))

    assert counted(shared(hot)) == (1, 0)
    assert counted(shared(spread)) == (0, 0)
    assert counted(shared(crowded)) == (0, 1)   # the XLA net, counted
    assert counted(None) == (0, 0)              # no kernel layout in play


def test_empty_blocks_and_tail_rows():
    """All requests inside block 0 plus a handful in the tail partial
    block: interior blocks have zero-length runs (the kernel loop body
    must not execute), and tail rows past the last full block boundary
    are still served exactly."""
    rng = np.random.default_rng(3)
    num_rows = 3 * BLOCK + 5
    rows = np.concatenate([
        rng.integers(0, 64, 500),                    # block 0 only
        rng.integers(3 * BLOCK, num_rows, 40),        # tail block rows
    ]).astype(np.int32)
    table = rng.normal(size=(num_rows, 8)).astype(np.float32)
    got = sorted_gather(jnp.asarray(rows), jnp.asarray(table), width=8,
                        interpret=True)
    np.testing.assert_array_equal(np.asarray(got), _ref(rows, table, 8))


def test_width_slice_of_wider_record():
    """width < table width gathers the leading pull slice only — the
    lookup serves [emb | w | show | click] out of the fused record."""
    rng = np.random.default_rng(4)
    num_rows = BLOCK
    rows = rng.integers(0, num_rows, 300).astype(np.int32)
    table = rng.normal(size=(num_rows, 21)).astype(np.float32)
    got = sorted_gather(jnp.asarray(rows), jnp.asarray(table), width=11,
                        interpret=True)
    assert got.shape == (300, 11)
    np.testing.assert_array_equal(np.asarray(got), _ref(rows, table, 11))


def test_shared_layout_serves_gather_and_scatter():
    """ONE sorted_stream_layout drives both kernels (the step's shared
    argsort): results must be identical to each kernel computing its own
    sort."""
    rng = np.random.default_rng(5)
    num_rows = BLOCK + 1
    n = 4000
    rows = rng.integers(0, num_rows, n).astype(np.int32)
    rows[::6] = num_rows
    table = rng.normal(size=(num_rows, 12)).astype(np.float32)
    payload = rng.normal(size=(n, 12)).astype(np.float32)
    layout = sorted_stream_layout(jnp.asarray(rows), num_rows)

    g_shared = sorted_gather(jnp.asarray(rows), jnp.asarray(table),
                             width=12, interpret=True, layout=layout)
    g_own = sorted_gather(jnp.asarray(rows), jnp.asarray(table),
                          width=12, interpret=True)
    np.testing.assert_array_equal(np.asarray(g_shared), np.asarray(g_own))

    s_shared = sorted_scatter_accumulate(jnp.asarray(rows),
                                         jnp.asarray(payload), num_rows,
                                         interpret=True, layout=layout)
    s_own = sorted_scatter_accumulate(jnp.asarray(rows),
                                      jnp.asarray(payload), num_rows,
                                      interpret=True)
    np.testing.assert_array_equal(np.asarray(s_shared), np.asarray(s_own))


def test_layout_shape_mismatch_raises():
    rng = np.random.default_rng(6)
    rows = rng.integers(0, BLOCK, 100).astype(np.int32)
    table = rng.normal(size=(BLOCK, 8)).astype(np.float32)
    layout = sorted_stream_layout(jnp.asarray(rows), BLOCK)
    with pytest.raises(ValueError, match="shared layout"):
        sorted_gather(jnp.asarray(rows[:50]), jnp.asarray(table),
                      width=8, interpret=True, layout=layout)


def test_width_guards():
    rng = np.random.default_rng(7)
    rows = jnp.asarray(rng.integers(0, 64, 16).astype(np.int32))
    wide = jnp.asarray(rng.normal(size=(64, 130)).astype(np.float32))
    with pytest.raises(ValueError, match="table width"):
        sorted_gather(rows, wide, width=16, interpret=True)
    tbl = jnp.asarray(rng.normal(size=(64, 8)).astype(np.float32))
    with pytest.raises(ValueError, match="width"):
        sorted_gather(rows, tbl, width=9, interpret=True)


@pytest.mark.parametrize("skew", [False, True], ids=["uniform", "zipf"])
def test_pull_local_kernel_path_matches_xla(skew):
    """Full single-shard pull_local through the Pallas (interpret)
    gather equals the XLA-gather path — emb, w, show, click — with
    padding (trash-row) requests in the batch. Under Zipf rows (no dedup
    on one shard: the hottest row's run is over the kernel budget) the
    shared layout's distinct tier serves it, and says so."""
    from paddlebox_tpu.core import flags as flagmod
    from paddlebox_tpu.embedding.lookup import (compute_bucketing,
                                                kernel_fallback,
                                                kernel_hot_served,
                                                pull_local)
    from paddlebox_tpu.embedding.table import PassTable

    rng = np.random.default_rng(8)
    rps, d = 300, 4
    ke, kw = 1, 1
    w_width = d + 3 + ke + kw
    vals = rng.normal(size=(rps + 1, w_width)).astype(np.float32)
    vals[rps, :d + 3] = 0.0          # trash row pull columns zero
    if skew:
        rows = zipf_rows(rng, 30_000, rps)
        assert np.bincount(rows).max() > UCAP
    else:
        rows = rng.integers(0, rps, 256).astype(np.int32)
    rows[::5] = rps                  # padding entries -> trash row

    def run(mode):
        flagmod.set_flags({"sparse_gather_kernel": mode})
        try:
            table = PassTable(vals=jnp.asarray(vals), rows_per_shard=rps,
                              num_shards=1, dim=d, ke=ke, kw=kw)
            bk = compute_bucketing(table, jnp.asarray(rows), axis="dp")
            out = pull_local(table, jnp.asarray(rows), axis="dp",
                             bucketing=bk)
            out["served"] = jnp.stack([kernel_hot_served(bk),
                                       kernel_fallback(bk)])
            return {k: np.asarray(v) for k, v in out.items()}
        finally:
            flagmod.set_flags({"sparse_gather_kernel": "auto"})

    a = run("xla")
    b = run("interpret")
    for k in ("emb", "w", "show", "click", "overflow"):
        np.testing.assert_array_equal(b[k], a[k], err_msg=k)
    assert b["served"].tolist() == [int(skew), 0]


def test_sharded_pull_push_kernel_parity(devices8):
    """Multi-shard pull + push through compute_bucketing's SHARED
    layout (one rows exchange + one argsort) in interpret mode equal
    the XLA paths bit-for-bit — the serve-side gather and the owner-side
    scatter both consume the same sort."""
    from paddlebox_tpu.core import flags as flagmod
    from paddlebox_tpu.embedding.lookup import (compute_bucketing,
                                                pull_local, push_local)
    from paddlebox_tpu.embedding.optimizers import SparseAdagrad
    from paddlebox_tpu.embedding.table import PassTable
    from paddlebox_tpu.parallel import HybridTopology, build_mesh
    from jax.sharding import PartitionSpec as P
    import functools

    ndev = 4
    mesh = build_mesh(HybridTopology(dp=ndev), devices=devices8[:ndev])
    rng = np.random.default_rng(9)
    rps, d = 64, 4
    ke, kw = 1, 1
    block = rps + 1
    w_width = d + 3 + ke + kw
    vals = rng.normal(size=(ndev * block, w_width)).astype(np.float32)
    for s in range(ndev):
        vals[s * block + rps, :d + 3] = 0.0
    n_local = 40
    rows = rng.integers(0, ndev * block, ndev * n_local).astype(np.int32)
    rows[::7] = (rows[::7] // block) * block + rps     # padding -> trash
    g_emb = rng.normal(size=(ndev * n_local, d)).astype(np.float32)
    g_w = rng.normal(size=(ndev * n_local,)).astype(np.float32)
    shows = np.ones((ndev * n_local,), np.float32)
    clicks = (rng.random(ndev * n_local) < 0.4).astype(np.float32)

    def run(gmode, smode):
        flagmod.set_flags({"sparse_gather_kernel": gmode,
                           "sparse_scatter_kernel": smode})
        try:
            @jax.jit
            @functools.partial(
                jax.shard_map, mesh=mesh,
                in_specs=(P("dp"), P("dp"), P("dp"), P("dp"), P("dp"),
                          P("dp")),
                out_specs=(P("dp"), P("dp")),
                check_vma=False)
            def both(table, dev_rows, ge, gw, sh, ck):
                bk = compute_bucketing(table, dev_rows, axis="dp")
                pulled = pull_local(table, dev_rows, axis="dp",
                                    bucketing=bk)
                new = push_local(table, dev_rows, ge, gw, sh, ck,
                                 axis="dp", opt=SparseAdagrad(),
                                 bucketing=bk)
                return pulled["emb"], new.vals

            table = PassTable(vals=jnp.asarray(vals), rows_per_shard=rps,
                              num_shards=ndev, dim=d, ke=ke, kw=kw)
            emb, new_vals = both(table, jnp.asarray(rows),
                                 jnp.asarray(g_emb), jnp.asarray(g_w),
                                 jnp.asarray(shows), jnp.asarray(clicks))
            return np.asarray(emb), np.asarray(new_vals)
        finally:
            flagmod.set_flags({"sparse_gather_kernel": "auto",
                               "sparse_scatter_kernel": "auto"})

    emb_x, vals_x = run("xla", "xla")
    emb_k, vals_k = run("interpret", "interpret")
    np.testing.assert_allclose(emb_k, emb_x, rtol=1e-6, atol=1e-6)
    # Trash-row optimizer state may differ (kernel drops trash updates);
    # everything consumable must match.
    for s in range(ndev):
        np.testing.assert_allclose(
            vals_k[s * block:s * block + rps],
            vals_x[s * block:s * block + rps], rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(
            vals_k[s * block + rps, :d + 3],
            vals_x[s * block + rps, :d + 3])


@pytest.mark.parametrize("skew", [True, False], ids=["zipf", "uniform"])
def test_trainer_pass_reports_hot_rows_served(skew, tmp_path):
    """A 4-batch one-chip pass through the interpreted kernels: Zipf ids
    (one key takes ~18% of a step's 32K ids, a run over the budget in
    every step) are served by the kernels and counted so in the pass
    stats, the pass report's registry entry and the monitor counter;
    uniform ids move neither counter."""
    from paddlebox_tpu.core import flags as flagmod
    from paddlebox_tpu.core import monitor
    from paddlebox_tpu.data import Dataset, DataFeedConfig, SlotConf
    from paddlebox_tpu.embedding import TableConfig
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.parallel import HybridTopology, build_mesh
    from paddlebox_tpu.train import CTRTrainer, TrainerConfig

    slots = tuple(f"s{i}" for i in range(8))
    batch, batches, n_keys = 4096, 4, 100_003
    rng = np.random.default_rng(13)
    n = batch * batches
    # Ranks to keys by a bijection, so the popular keys scatter over the
    # pass table's blocks the way hashed feasigns do.
    ids = ((rng.zipf(1.2, (n, len(slots))) - 1) * 2654435761 % n_keys + 1
           if skew else rng.integers(1, n_keys + 1, (n, len(slots))))
    labels = rng.integers(0, 2, n)
    path = tmp_path / "part-0"
    with open(path, "w") as f:
        for lab, row in zip(labels, ids):
            f.write(f"{lab} " + " ".join(
                f"{s}:{v}" for s, v in zip(slots, row)) + "\n")
    feed = DataFeedConfig(slots=tuple(SlotConf(s, avg_len=1.0)
                                      for s in slots),
                          batch_size=batch, slot_capacity_slack=1.0)
    flagmod.set_flags({"sparse_gather_kernel": "interpret",
                       "sparse_scatter_kernel": "interpret"})
    served0 = monitor.get("embedding/kernel_hot_served")
    try:
        trainer = CTRTrainer(
            DeepFM(slot_names=slots, emb_dim=4, hidden=(8,)), feed,
            TableConfig(dim=4, learning_rate=0.05),
            mesh=build_mesh(HybridTopology(dp=1), devices=jax.devices()[:1]),
            config=TrainerConfig(auc_num_buckets=1 << 10))
        trainer.init(seed=0)
        ds = Dataset(feed, num_reader_threads=1)
        ds.set_filelist([str(path)])
        ds.load_into_memory()
        stats = trainer.train_pass(ds)
    finally:
        flagmod.set_flags({"sparse_gather_kernel": "auto",
                           "sparse_scatter_kernel": "auto"})
    assert stats["steps"] == batches and np.isfinite(stats["loss"])
    assert stats["lookup_overflow"] == 0 and stats["kernel_fallback"] == 0
    # one width group, one device: one cell a step
    assert stats["kernel_hot_served"] == (batches if skew else 0)
    assert (monitor.get("embedding/kernel_hot_served") - served0
            == stats["kernel_hot_served"])
    assert (monitor.get("pass/train_kernel_hot_served")
            == stats["kernel_hot_served"])
