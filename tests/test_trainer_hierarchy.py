"""Trainer hierarchy, async dense table, and sanitizer tests."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from paddlebox_tpu.core import flags
from paddlebox_tpu.parallel import HybridTopology, build_mesh, pp
from paddlebox_tpu.train.async_dense import AsyncDenseTable
from paddlebox_tpu.train.trainer import (MultiTrainer, PipelineTrainer,
                                         TrainerDesc, create_trainer,
                                         register_trainer)
from paddlebox_tpu.utils import sanitizer


# ---------------------------------------------------------------------------
# MultiTrainer
# ---------------------------------------------------------------------------

def _linreg_batches(n_batches, bs=32, seed=0):
    rng = np.random.default_rng(seed)
    w = np.asarray([2.0, -1.0, 0.5, 3.0], np.float32)
    for _ in range(n_batches):
        x = rng.normal(size=(bs, 4)).astype(np.float32)
        yield {"x": x, "y": x @ w + 0.01 * rng.normal(size=bs).astype(
            np.float32)}


def test_multi_trainer_learns(devices8):
    mesh = build_mesh(HybridTopology(dp=8))

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"] + params["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    t = MultiTrainer(loss_fn, {"w": jnp.zeros(4), "b": jnp.zeros(())},
                     optax.sgd(0.1))
    out = t.fit(_linreg_batches(200), TrainerDesc(log_every=0), mesh)
    assert out["steps"] == 200
    assert out["loss_last"] < 0.01 < out["loss_first"]
    np.testing.assert_allclose(np.asarray(t.params["w"]),
                               [2, -1, 0.5, 3], atol=0.05)


def test_trainer_factory_registry():
    t = create_trainer("MultiTrainer",
                       lambda p, b: jnp.sum(p["w"] ** 2),
                       {"w": jnp.ones(2)}, optax.sgd(0.1))
    assert isinstance(t, MultiTrainer)
    with pytest.raises(KeyError):
        create_trainer("NoSuchTrainer")


def test_multi_trainer_max_steps_and_nan_check(devices8):
    mesh = build_mesh(HybridTopology(dp=8))

    def bad_loss(params, batch):
        # divergence by design: loss explodes to inf/nan quickly
        return jnp.exp(jnp.sum(params["w"] * 1e4)) * jnp.mean(batch["x"])

    t = MultiTrainer(bad_loss, {"w": jnp.ones(4)}, optax.sgd(1e6))
    with pytest.raises(FloatingPointError):
        t.fit(_linreg_batches(50),
              TrainerDesc(check_nan_inf=True, log_every=0), mesh)


# ---------------------------------------------------------------------------
# HeterTrainer
# ---------------------------------------------------------------------------

def test_heter_trainer_learns_with_host_stage(devices8):
    """Host normalization stage + device step pipelined through the
    interceptor runtime; parity with the plain trainer's convergence."""
    from paddlebox_tpu.train.trainer import HeterTrainer
    mesh = build_mesh(HybridTopology(dp=8))
    host_calls = []

    def host_fn(batch):
        # Fixed host-side transform (a per-batch normalization would make
        # the regression target batch-dependent and unlearnable).
        host_calls.append(1)
        return {"x": batch["x"] * 2.0, "y": batch["y"]}

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"] + params["b"]
                         - batch["y"]) ** 2)

    t = HeterTrainer(loss_fn, {"w": jnp.zeros(4), "b": jnp.zeros(())},
                     optax.adam(0.05), host_fn=host_fn)
    out = t.fit(list(_linreg_batches(150)), TrainerDesc(log_every=0), mesh)
    assert out["steps"] == 150
    assert len(host_calls) == 150
    assert out["loss_last"] < 0.05 < out["loss_first"]


def test_heter_trainer_short_dataset_under_max_steps(devices8):
    """max_steps beyond the dataset must end cleanly at the data's end,
    not hang waiting for batches that never come."""
    from paddlebox_tpu.train.trainer import HeterTrainer
    mesh = build_mesh(HybridTopology(dp=8))
    t = HeterTrainer(
        lambda p, b: jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2),
        {"w": jnp.zeros(4)}, optax.sgd(0.1), chunk_size=8)
    out = t.fit(_linreg_batches(10), TrainerDesc(max_steps=50, log_every=0),
                mesh)
    assert out["steps"] == 10


def test_heter_trainer_factory():
    from paddlebox_tpu.train.trainer import HeterTrainer
    t = create_trainer("HeterTrainer", lambda p, b: jnp.sum(p["w"] ** 2),
                       {"w": jnp.ones(2)}, optax.sgd(0.1))
    assert isinstance(t, HeterTrainer)


# ---------------------------------------------------------------------------
# PipelineTrainer
# ---------------------------------------------------------------------------

@pytest.mark.slow  # 4 s; a trainer no benchmark config runs (ROADMAP D5); tier-1 keeps its 870 s window (PR 21)
def test_pipeline_trainer_learns(devices8):
    mesh = build_mesh(HybridTopology(pp=8))
    rng = np.random.default_rng(0)
    dim = 8
    stage_params = [
        {"w": jnp.asarray(rng.normal(0, 0.5, (dim, dim)), jnp.float32)}
        for _ in range(8)]
    stacked = pp.stack_stage_params(stage_params)

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])

    def loss_head(y, batch):
        return jnp.mean((jnp.sum(y, -1) - batch["y"]) ** 2)

    t = PipelineTrainer(stage_fn, stacked, loss_head, optax.adam(3e-3))
    desc = TrainerDesc(num_micro_batches=8, log_every=0)

    def batches(n):
        r = np.random.default_rng(1)
        for _ in range(n):
            x = r.normal(size=(32, dim)).astype(np.float32)
            yield {"x": x, "y": np.tanh(x.sum(1)).astype(np.float32)}

    out = t.fit(batches(150), desc, mesh)
    assert out["loss_last"] < out["loss_first"] * 0.5


# ---------------------------------------------------------------------------
# AsyncDenseTable
# ---------------------------------------------------------------------------

def test_async_dense_applies_adam():
    params = {"w": np.ones((4,), np.float32)}
    table = AsyncDenseTable(params, learning_rate=0.1)
    for _ in range(10):
        table.push_dense({"w": np.ones((4,), np.float32)})
    table.flush()
    out = table.pull_dense()
    # positive grads -> params decreased
    assert (np.asarray(out["w"]) < 1.0).all()
    assert table.steps_applied >= 1
    table.stop()


def test_async_dense_converges_quadratic():
    """pull/push loop minimizes ||w - target||^2 through the async path."""
    target = np.asarray([1.0, -2.0, 0.5], np.float32)
    table = AsyncDenseTable({"w": np.zeros(3, np.float32)},
                            learning_rate=0.05, beta1=0.9, beta2=0.999)
    for _ in range(300):
        w = np.asarray(table.pull_dense()["w"])
        table.push_dense({"w": 2 * (w - target)})
        table.flush()
    w = np.asarray(table.pull_dense()["w"])
    np.testing.assert_allclose(w, target, atol=0.1)
    table.stop()


def test_async_dense_ring_drops_oldest_not_blocks():
    table = AsyncDenseTable({"w": np.zeros(2, np.float32)}, ring_capacity=2)
    # push far more than capacity quickly: must not block
    for i in range(100):
        table.push_dense({"w": np.full(2, float(i), np.float32)})
    table.stop()


def test_async_dense_shape_mismatch_raises():
    table = AsyncDenseTable({"w": np.zeros(2, np.float32)})
    with pytest.raises(ValueError):
        table.push_dense({"w": np.zeros(2), "extra": np.zeros(1)})
    # same leaf count, different structure -> refuse (would cross-apply)
    table2 = AsyncDenseTable({"a": np.zeros(2, np.float32),
                              "b": np.zeros(2, np.float32)})
    with pytest.raises(ValueError):
        table2.push_dense([np.zeros(2, np.float32),
                           np.zeros(2, np.float32)])
    # same structure, wrong leaf shape -> refuse
    with pytest.raises(ValueError):
        table2.push_dense({"a": np.zeros(3, np.float32),
                           "b": np.zeros(2, np.float32)})
    table.stop()
    table2.stop()


def test_dump_path_requires_eval_fn(devices8, tmp_path):
    t = MultiTrainer(lambda p, b: jnp.sum(p["w"] ** 2), {"w": jnp.ones(2)},
                     optax.sgd(0.1))
    with pytest.raises(ValueError):
        t.fit(iter([]), TrainerDesc(dump_path=str(tmp_path / "d.txt")))


def test_dump_path_writes_predictions(devices8, tmp_path):
    mesh = build_mesh(HybridTopology(dp=8))

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

    def eval_fn(params, batch):
        return batch["x"] @ params["w"], batch["y"]

    path = str(tmp_path / "preds.txt")
    t = MultiTrainer(loss_fn, {"w": jnp.zeros(4)}, optax.sgd(0.05),
                     eval_fn=eval_fn)
    t.fit(_linreg_batches(3), TrainerDesc(dump_path=path, log_every=0),
          mesh)
    lines = open(path).read().strip().splitlines()
    assert len(lines) == 3 * 32  # one line per instance


def test_pipeline_trainer_rejects_indivisible_batch(devices8):
    mesh = build_mesh(HybridTopology(pp=8))
    stacked = pp.stack_stage_params(
        [{"w": jnp.eye(4)} for _ in range(8)])
    t = PipelineTrainer(lambda p, x: x @ p["w"], stacked,
                        lambda y, b: jnp.mean(y ** 2), optax.sgd(0.1))
    desc = TrainerDesc(num_micro_batches=8, log_every=0)
    with pytest.raises(ValueError):
        t.fit(iter([{"x": np.ones((30, 4), np.float32)}]), desc, mesh)


# ---------------------------------------------------------------------------
# Sanitizer
# ---------------------------------------------------------------------------

def test_sanitizer_all_finite_and_report():
    clean = {"a": jnp.ones(3), "b": {"c": jnp.zeros((2, 2))}}
    assert bool(sanitizer.all_finite(clean))
    dirty = {"a": jnp.asarray([1.0, jnp.nan]),
             "b": {"c": jnp.asarray([jnp.inf, 1.0])}}
    assert not bool(sanitizer.all_finite(dirty))
    report = sanitizer.find_nonfinite(dirty)
    assert {k for _, k, _ in report} == {"nan", "inf"}
    assert all(count == 1 for _, _, count in report)
    assert any("a" in name for name, k, _ in report if k == "nan")
    assert any("c" in name for name, k, _ in report if k == "inf")


def test_sanitizer_check_batch_flag_gated():
    dirty = {"a": jnp.asarray([jnp.nan])}
    flags.set_flags({"check_nan_inf": False})
    assert sanitizer.check_batch(dirty) is True  # disabled -> no-op
    flags.set_flags({"check_nan_inf": True})
    try:
        with pytest.raises(FloatingPointError):
            sanitizer.check_batch(dirty, step=7)
        assert sanitizer.check_batch({"a": jnp.ones(2)}) is True
    finally:
        flags.set_flags({"check_nan_inf": False})


def test_sanitizer_ignores_integer_leaves():
    tree = {"ids": jnp.arange(5), "x": jnp.ones(2)}
    assert bool(sanitizer.all_finite(tree))
