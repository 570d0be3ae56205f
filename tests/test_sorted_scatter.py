"""sorted_scatter_accumulate (CopyForPush-class Pallas kernel) vs the XLA
scatter reference — interpret mode on CPU; same code compiles for TPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlebox_tpu.ops.pallas_kernels.sorted_scatter import (
    BLOCK, UCAP, WINDOW, sorted_scatter_accumulate)
from tests.test_sorted_gather import (_skew_block_edge, _skew_hot_sentinel,
                                      _skew_zipf, zipf_rows)


def _ref(rows, payload, num_rows):
    keep = rows < num_rows
    safe = np.where(keep, rows, 0)
    contrib = np.where(keep[:, None], payload, 0.0)
    out = np.zeros((num_rows, payload.shape[1]), np.float32)
    np.add.at(out, safe, contrib)
    out[~np.isin(np.arange(num_rows), rows[keep])] *= 1.0
    # np.add.at added dropped rows' zero contribs at row 0 — they're zero.
    return out


@pytest.mark.parametrize("num_rows,n", [(BLOCK, 1000),
                                        (3 * BLOCK + 17, 20_000)])
def test_matches_xla_scatter(num_rows, n):
    rng = np.random.default_rng(0)
    rows = rng.integers(0, num_rows, n).astype(np.int32)
    payload = rng.normal(size=(n, 12)).astype(np.float32)
    got = sorted_scatter_accumulate(jnp.asarray(rows),
                                    jnp.asarray(payload), num_rows,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(got), _ref(rows, payload,
                                                     num_rows),
                               rtol=1e-5, atol=1e-5)


def test_sentinel_rows_dropped():
    rng = np.random.default_rng(1)
    num_rows = BLOCK
    rows = rng.integers(0, num_rows, 500).astype(np.int32)
    # A third of entries carry the drop sentinel (trash/padding).
    rows[::3] = num_rows
    payload = rng.normal(size=(500, 8)).astype(np.float32)
    got = sorted_scatter_accumulate(jnp.asarray(rows),
                                    jnp.asarray(payload), num_rows,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(got), _ref(rows, payload,
                                                     num_rows),
                               rtol=1e-5, atol=1e-5)


def _sequential(rows, payload, num_rows):
    """float32 adds in request order: what the kernel must equal bit
    for bit (a stable sort keeps request order within a row)."""
    out = np.zeros((num_rows + 1, payload.shape[1]), np.float32)
    np.add.at(out, np.minimum(rows, num_rows), payload)
    return out[:num_rows]


def test_hot_row_run_walked_in_windows():
    """A run of 3 * UCAP + 17 updates on one row — several staging
    windows — is the sequential float32 sum in request order, bit for
    bit; there is no XLA branch to give way to. Against the float64 sum
    that sequence is good to 1e-5 relative (12K float32 adds of [0, 1)
    values: each rounds at the partial sum's ulp)."""
    rng = np.random.default_rng(2)
    num_rows = BLOCK
    n = 3 * UCAP + 17
    rows = np.full((n,), 7, np.int32)        # everything hits row 7
    payload = rng.random(size=(n, 4)).astype(np.float32)
    got = np.asarray(sorted_scatter_accumulate(
        jnp.asarray(rows), jnp.asarray(payload), num_rows, interpret=True))
    np.testing.assert_array_equal(got, _sequential(rows, payload, num_rows))
    np.testing.assert_allclose(got[7], payload.astype(np.float64).sum(0),
                               rtol=1e-5)
    assert not got[:7].any() and not got[8:].any()


def _one_row(rng):
    return 2 * BLOCK, np.full((2 * WINDOW + 5,), BLOCK + 5, np.int32)


def _run_at(off, cnt):
    """`off` updates in block 0 (so block 1's run starts `off` past an
    ALIGN boundary of the stream), then `cnt` on one row of block 1."""
    def make(rng):
        rows = np.concatenate([
            rng.integers(0, BLOCK, off), np.full((cnt,), BLOCK + 9),
            rng.integers(2 * BLOCK, 3 * BLOCK, 50)]).astype(np.int32)
        return 3 * BLOCK, rng.permutation(rows)
    return make


@pytest.mark.parametrize("make", [
    _skew_zipf, _one_row, _skew_hot_sentinel, _skew_block_edge,
    _run_at(0, UCAP), _run_at(0, 2 * UCAP), _run_at(0, WINDOW),
    _run_at(0, 2 * WINDOW), _run_at(1000, UCAP),
    _run_at(1000, WINDOW - 1000), _run_at(1000, WINDOW - 999),
    _run_at(1023, 3 * UCAP + 17)],
    ids=["zipf", "one_row", "hot_sentinel", "block_edge", "ends_at_ucap",
         "ends_at_2ucap", "ends_at_window", "ends_at_2windows",
         "offset_one_window", "offset_ends_at_window",
         "offset_straddles_window", "offset_many_windows"])
def test_skewed_updates_are_the_sequential_sum(make):
    rng = np.random.default_rng(12)
    num_rows, rows = make(rng)
    payload = rng.normal(size=(rows.shape[0], 12)).astype(np.float32)
    got = sorted_scatter_accumulate(jnp.asarray(rows),
                                    jnp.asarray(payload), num_rows,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  _sequential(rows, payload, num_rows))


@pytest.mark.parametrize("skew", [False, True], ids=["uniform", "zipf"])
def test_push_local_kernel_path_matches_xla(skew):
    """Full push_local through the Pallas (interpret) accumulate equals
    the XLA-scatter path — table values, states, and stats. Under Zipf
    rows (no dedup on one shard) the hottest row's run is over the
    staging budget and the kernel walks it in windows."""
    import jax.numpy as jnp
    from paddlebox_tpu.core import flags as flagmod
    from paddlebox_tpu.embedding.lookup import push_local
    from paddlebox_tpu.embedding.optimizers import SparseAdagrad
    from paddlebox_tpu.embedding.table import PassTable

    rng = np.random.default_rng(3)
    rps, d = 300, 4
    ke, kw = 1, 1
    w_width = d + 3 + ke + kw
    vals = rng.normal(size=(rps + 1, w_width)).astype(np.float32)
    vals[rps, :d + 3] = 0.0          # trash row pull columns zero
    n = 30_000 if skew else 256
    if skew:
        rows = zipf_rows(rng, n, rps)
        assert np.bincount(rows).max() > UCAP
    else:
        rows = rng.integers(0, rps, n).astype(np.int32)
    rows[::5] = rps                  # padding entries -> trash row
    g_emb = rng.normal(size=(n, d)).astype(np.float32)
    g_w = rng.normal(size=(n,)).astype(np.float32)
    shows = (rows != rps).astype(np.float32)
    clicks = shows * (rng.random(n) < 0.4)
    g_emb[rows == rps] = 0.0
    g_w[rows == rps] = 0.0

    def run(mode):
        flagmod.set_flags({"sparse_scatter_kernel": mode})
        try:
            table = PassTable(vals=jnp.asarray(vals), rows_per_shard=rps,
                              num_shards=1, dim=d, ke=ke, kw=kw)
            out = push_local(table, jnp.asarray(rows), jnp.asarray(g_emb),
                             jnp.asarray(g_w), jnp.asarray(shows),
                             jnp.asarray(clicks), axis="dp",
                             opt=SparseAdagrad())
            return np.asarray(out.vals)
        finally:
            flagmod.set_flags({"sparse_scatter_kernel": "auto"})

    a = run("xla")
    b = run("interpret")
    # Trash-row optimizer state may differ (kernel drops trash updates;
    # the XLA path counts them) — everything consumable must match.
    np.testing.assert_allclose(b[:rps], a[:rps], atol=1e-6,
                               rtol=1e-6 if skew else 1e-5)
    np.testing.assert_allclose(b[rps, :d + 3], a[rps, :d + 3], atol=0)


def test_sentinel_stays_off_the_books_at_non_multiple_num_rows():
    """num_rows NOT a multiple of BLOCK + thousands of concentrated
    sentinel entries: they must neither corrupt the result nor count
    toward any block's run (the last block would walk every padding
    lane's update for nothing)."""
    rng = np.random.default_rng(4)
    num_rows = BLOCK + 1           # rows_per_shard+1 shape, the real case
    n = 9000                       # > UCAP sentinels, clustered
    rows = rng.integers(0, num_rows, n).astype(np.int32)
    rows[::2] = num_rows           # half the entries are padding
    payload = rng.normal(size=(n, 6)).astype(np.float32)
    got = sorted_scatter_accumulate(jnp.asarray(rows),
                                    jnp.asarray(payload), num_rows,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               _ref(rows, payload, num_rows),
                               rtol=1e-5, atol=1e-5)
