"""Downpour async-PS trainer + program introspection tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlebox_tpu.distributed.ps import start_local_cluster
from paddlebox_tpu.embedding.table import TableConfig
from paddlebox_tpu.train.downpour import DownpourTrainer, PullDenseWorker
from paddlebox_tpu.utils import inspect as pbx_inspect


@pytest.fixture
def ps():
    cfg = TableConfig(name="emb", dim=4, optimizer="adagrad",
                      learning_rate=0.2)
    servers, client = start_local_cluster(2, {"emb": cfg})
    yield client
    client.stop_servers()
    client.close()
    for s in servers:
        s.stop()


def _make_batches(n_batches, cap=32, seed=0):
    """Synthetic CTR-ish data: label depends on whether any 'positive'
    feasign (odd id) is present."""
    rng = np.random.default_rng(seed)
    for _ in range(n_batches):
        ids = rng.integers(1, 200, cap).astype(np.uint64)
        label = (np.mean(ids % 2) > 0.5).astype(np.float32)
        yield {"ids": ids, "label": jnp.asarray([label])}


@pytest.mark.slow  # 10 s; a trainer no benchmark config runs (ROADMAP D5); tier-1 keeps its 870 s window (PR 21)
def test_downpour_learns_sparse_and_dense(ps):
    def loss_fn(dense, emb, w, batch):
        # score = mean(emb @ v) + sum(w)/cap + b
        s = jnp.mean(emb @ dense["v"]) + jnp.mean(w) + dense["b"][0]
        p = jax.nn.sigmoid(s)
        y = batch["label"][0]
        return -(y * jnp.log(p + 1e-7) + (1 - y) * jnp.log(1 - p + 1e-7))

    t = DownpourTrainer(ps, "emb", loss_fn,
                        {"v": np.zeros((4,), np.float32),
                         "b": np.zeros((1,), np.float32)},
                        pull_interval=0.01)
    try:
        out = t.fit(_make_batches(150), log_every=0)
        assert out["steps"] == 150
        assert out["loss_last"] < out["loss_first"]
        # sparse table actually trained: show counters accumulated
        stats = ps.stats()
        assert sum(s["emb"] for s in stats) > 0
        # dense was updated server-side (pushes applied by DenseTable)
        v = ps.pull_dense("b")
        assert np.abs(v).sum() > 0
    finally:
        t.stop()


def test_downpour_padding_rows_not_trained(ps):
    def loss_fn(dense, emb, w, batch):
        return jnp.sum(emb ** 2) + jnp.sum(w ** 2) + 0.0 * dense["z"][0]

    t = DownpourTrainer(ps, "emb", loss_fn,
                        {"z": np.zeros((1,), np.float32)})
    try:
        before = sum(s["emb"] for s in ps.stats())
        ids = np.asarray([5, 0, 7, 0], np.uint64)  # 0 = padding
        t.train_step({"ids": ids})
        # exactly the two real feasigns were created — a feasign-0 row
        # would make this 3 (padding keys must never touch the table)
        after = sum(s["emb"] for s in ps.stats())
        assert after - before == 2
    finally:
        t.stop()


def test_pull_dense_worker_versions(ps):
    ps.set_dense("w0", np.zeros(3, np.float32))
    pw = PullDenseWorker(ps, ["w0"], interval=0.01)
    pw.start()
    try:
        v0 = pw.version
        ps.set_dense("w0", np.ones(3, np.float32))
        import time
        deadline = time.time() + 5
        while time.time() < deadline:
            if pw.version > v0 and np.allclose(pw.latest()["w0"], 1.0):
                break
            time.sleep(0.01)
        np.testing.assert_allclose(pw.latest()["w0"], 1.0)
    finally:
        pw.stop()


# ---------------------------------------------------------------------------
# inspect
# ---------------------------------------------------------------------------

def test_jaxpr_summary_counts():
    def f(x):
        return jnp.sin(x) + jnp.cos(x) @ jnp.ones((4, 4))

    c = pbx_inspect.jaxpr_summary(f, jnp.ones((4, 4)))
    assert c.get("sin") == 1 and c.get("cos") == 1
    assert c.get("dot_general", 0) >= 1


def test_jaxpr_summary_recurses_into_cond_branches():
    def f(x):
        return jax.lax.cond(x.sum() > 0, jnp.sin, jnp.cos, x)

    c = pbx_inspect.jaxpr_summary(f, jnp.ones(3))
    assert c.get("sin", 0) >= 1 and c.get("cos", 0) >= 1


def test_jaxpr_summary_recurses_into_scan():
    def f(x):
        return jax.lax.scan(lambda c, t: (c + jnp.tanh(t), None), x,
                            jnp.arange(3.0))[0]

    c = pbx_inspect.jaxpr_summary(f, jnp.zeros(()))
    assert c.get("tanh", 0) >= 1  # found inside the scan body


def test_hlo_text_and_compiled_stats():
    def f(x):
        return (x @ x).sum()

    txt = pbx_inspect.hlo_text(f, jnp.ones((8, 8)))
    assert "dot" in txt.lower()
    stats = pbx_inspect.compiled_stats(f, jnp.ones((8, 8)))
    assert isinstance(stats, dict)  # backend-dependent contents


def test_print_tensor_summary():
    line = pbx_inspect.print_tensor(np.asarray([1.0, np.nan, 3.0]), "t")
    assert "nonfinite=1" in line and "shape=(3,)" in line
    assert "t:" in line
    assert "<empty>" in pbx_inspect.print_tensor(np.empty((0,)), "e")
    assert "dtype" in pbx_inspect.print_tensor(np.asarray(["a"]), "s")
