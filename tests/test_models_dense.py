"""Dense model zoo + checkpoint + AMP + optimizer tests."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from paddlebox_tpu import amp
from paddlebox_tpu.checkpoint import (CheckpointProtocol,
                                      get_online_pass_interval, load_pytree,
                                      save_pytree)
from paddlebox_tpu.models.bert import BertConfig, bert_mlm_loss, init_bert
from paddlebox_tpu.models.resnet import ResNet
from paddlebox_tpu.optimizers import make_optimizer, warmup_cosine
from paddlebox_tpu.parallel import HybridTopology, build_mesh


# -- ResNet ------------------------------------------------------------------

@pytest.mark.slow  # 20 s conv compile; tier-1 keeps its 870 s window (PR 21)
def test_resnet18_forward_and_train_step():
    model = ResNet(depth=18, num_classes=10, width=8)
    params = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    logits, new_params = jax.jit(
        lambda p, x: model.apply(p, x, train=True))(params, x)
    assert logits.shape == (2, 10)
    assert np.isfinite(np.asarray(logits)).all()
    # BN stats updated
    assert not np.allclose(np.asarray(new_params["stem_bn"]["mean"]),
                           np.asarray(params["stem_bn"]["mean"]))
    # eval mode: stats unchanged
    logits_eval, p_eval = model.apply(params, x, train=False)
    np.testing.assert_array_equal(np.asarray(p_eval["stem_bn"]["mean"]),
                                  np.asarray(params["stem_bn"]["mean"]))


@pytest.mark.slow  # 10 s conv compile; tier-1 keeps its 870 s window (PR 21)
def test_resnet50_shapes():
    model = ResNet(depth=50, num_classes=10, width=8)
    params = model.init(jax.random.PRNGKey(0))
    x = jnp.zeros((1, 64, 64, 3))
    logits, _ = jax.jit(lambda p, x: model.apply(p, x, train=False))(params, x)
    assert logits.shape == (1, 10)


def test_resnet_learns():
    model = ResNet(depth=18, num_classes=2, width=8)
    params = model.init(jax.random.PRNGKey(0))
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)
    # Two classes separated by channel mean.
    rng = np.random.default_rng(0)
    x = rng.normal(size=(16, 16, 16, 3)).astype(np.float32)
    y = (x.mean(axis=(1, 2, 3)) > 0).astype(np.int32)
    x, y = jnp.asarray(x), jnp.asarray(y)

    @jax.jit
    def step(params, opt_state, x, y):
        def loss_fn(p):
            logits, newp = model.apply(p, x, train=True)
            logz = jax.nn.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
            return jnp.mean(logz - tgt), newp
        (loss, newp), g = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt_state = opt.update(g, opt_state)
        # newp carries the updated BN stats; apply the grad step on top.
        params = optax.apply_updates(newp, updates)
        return params, opt_state, loss

    losses = []
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state, x, y)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


# -- BERT --------------------------------------------------------------------

BCFG = BertConfig(vocab_size=100, d_model=32, n_heads=4, n_layers=2,
                  d_ff=64, max_seq_len=32)


@pytest.mark.slow  # 17 s compile-bound; tier-1 keeps its 870 s window
def test_bert_mlm_dp_parity(devices8):
    """dp-sharded MLM loss == single-device loss (role of the reference's
    dist parity tests, test_dist_base.py)."""
    params = init_bert(jax.random.PRNGKey(0), BCFG)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 100, (8, 16)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, 100, (8, 16)), jnp.int32)
    mask = jnp.asarray(rng.random((8, 16)) < 0.15, jnp.float32)

    single = bert_mlm_loss(params, BCFG, tokens, targets, mask)

    mesh = build_mesh(HybridTopology(dp=8))
    f = jax.shard_map(
        lambda p, t, tg, m: bert_mlm_loss(p, BCFG, t, tg, m,
                                          axis_name="dp"),
        mesh=mesh, in_specs=(P(), P("dp"), P("dp"), P("dp")),
        out_specs=P(), check_vma=False)
    dist = f(params, tokens, targets, mask)
    np.testing.assert_allclose(float(dist), float(single), rtol=1e-5)


def test_bert_train_step_learns():
    params = init_bert(jax.random.PRNGKey(0), BCFG)
    opt = optax.adam(3e-3)
    opt_state = opt.init(params)
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, 100, (8, 16)), jnp.int32)
    mask = jnp.asarray(np.ones((8, 16)), jnp.float32)

    @jax.jit
    def step(params, opt_state):
        loss, g = jax.value_and_grad(
            lambda p: bert_mlm_loss(p, BCFG, tokens, tokens, mask))(params)
        updates, opt_state = opt.update(g, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(20):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9, losses


# -- checkpoint --------------------------------------------------------------

def test_dense_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6.0).reshape(2, 3),
            "nested": {"b": jnp.ones((4,)), "c": [jnp.zeros((2,)),
                                                 jnp.full((1,), 7.0)]}}
    path = str(tmp_path / "ckpt" / "model.npz")
    save_pytree(tree, path, step=42)
    template = jax.tree.map(jnp.zeros_like, tree)
    restored, step = load_pytree(template, path)
    assert step == 42
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), tree, restored)


def test_checkpoint_missing_key_raises(tmp_path):
    path = str(tmp_path / "m.npz")
    save_pytree({"a": jnp.ones(2)}, path)
    with pytest.raises(KeyError):
        load_pytree({"a": jnp.zeros(2), "b": jnp.zeros(3)}, path)


def test_protocol_publish_and_recover(tmp_path):
    proto = CheckpointProtocol(str(tmp_path / "out"))
    assert proto.last_published() is None
    # Day base then two pass deltas, then next day's base.
    assert proto.publish("20260729", -1, key=111)
    assert proto.publish("20260729", 1)
    assert proto.publish("20260729", 2)
    # Duplicate publication is refused (donefile idempotence).
    assert not proto.publish("20260729", 2)
    last = proto.last_published()
    assert last.pass_id == 2 and last.day == "20260729"
    base, deltas = proto.recovery_chain()
    assert base.pass_id == 0
    assert [d.pass_id for d in deltas] == [1, 2]
    # New day base resets the chain.
    proto.publish("20260730", -1)
    base, deltas = proto.recovery_chain()
    assert base.day == "20260730" and deltas == []


def test_online_pass_interval():
    passes = get_online_pass_interval(list(range(24)), split_interval=60,
                                      split_per_pass=4)
    assert len(passes) == 6
    assert passes[0] == ["0000", "0100", "0200", "0300"]
    hourly = get_online_pass_interval([0, 1, 2, 3], split_interval=60,
                                      split_per_pass=2,
                                      is_data_hourly_placed=True)
    assert hourly[0] == ["00", "01"]


# -- AMP ---------------------------------------------------------------------

def test_amp_policy_cast():
    pol = amp.bf16_policy()
    tree = {"w": jnp.ones((2, 2), jnp.float32), "step": jnp.int32(3)}
    lo = pol.cast_to_compute(tree)
    assert lo["w"].dtype == jnp.bfloat16
    assert lo["step"].dtype == jnp.int32  # non-float untouched
    hi = pol.cast_to_param(lo)
    assert hi["w"].dtype == jnp.float32


def test_loss_scaling_dynamics():
    state = amp.loss_scale_init(1024.0, growth_interval=2)
    grads = {"g": jnp.ones((3,)) * 1024.0}
    # finite step: grads unscaled, tracker++
    g1, finite, state = amp.unscale_and_check(state, grads)
    assert bool(finite)
    np.testing.assert_allclose(np.asarray(g1["g"]), 1.0)
    assert float(state.scale) == 1024.0
    # second finite step hits growth_interval: scale doubles
    _, _, state = amp.unscale_and_check(state, grads)
    assert float(state.scale) == 2048.0
    # non-finite: backoff, skip
    bad = {"g": jnp.array([jnp.inf, 1.0, 1.0])}
    _, finite, state = amp.unscale_and_check(state, bad)
    assert not bool(finite)
    assert float(state.scale) == 1024.0
    # masked_update keeps old params on bad step
    old = {"w": jnp.zeros(2)}
    new = {"w": jnp.ones(2)}
    sel = amp.masked_update(finite, new, old)
    np.testing.assert_array_equal(np.asarray(sel["w"]), [0.0, 0.0])


# -- optimizers --------------------------------------------------------------

@pytest.mark.parametrize("name", ["sgd", "momentum", "adam", "adamw",
                                  "lars", "lamb"])
def test_optimizer_factory(name):
    tx = make_optimizer(name, 1e-2, weight_decay=0.01, clip_norm=1.0)
    params = {"w": jnp.ones((4, 4))}
    state = tx.init(params)
    grads = {"w": jnp.full((4, 4), 0.1)}
    updates, state = tx.update(grads, state, params)
    new = optax.apply_updates(params, updates)
    assert not np.allclose(np.asarray(new["w"]), np.asarray(params["w"]))


def test_unknown_optimizer_raises():
    with pytest.raises(ValueError):
        make_optimizer("adagrad2000", 1e-3)


def test_warmup_cosine_schedule():
    sched = warmup_cosine(1e-3, warmup_steps=10, total_steps=100)
    assert float(sched(0)) == 0.0
    np.testing.assert_allclose(float(sched(10)), 1e-3, rtol=1e-6)
    assert float(sched(100)) < 1e-4
