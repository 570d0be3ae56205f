"""Hybrid-parallel GPT tests: the reference's hybrid_parallel_pp_transformer
parity bar — hybrid (dp×pp×sp×mp) loss == single-device dense loss, and a
training step improves it."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from paddlebox_tpu.models.gpt import (GPTConfig, gpt_loss_fn, init_gpt,
                                      make_gpt_train_step)
from paddlebox_tpu.parallel import HybridTopology, build_mesh

CFG = GPTConfig(vocab_size=128, d_model=32, n_heads=4, n_layers=4, d_ff=64,
                max_seq_len=64)


def _dense_reference_loss(params, tokens, targets, cfg):
    """Single-device numpy/jnp reference of the same architecture."""
    x = params["embed"][tokens] + params["pos"][jnp.arange(tokens.shape[1])]

    def ln(x, g, b, eps=1e-5):
        mu = x.mean(-1, keepdims=True)
        var = x.var(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * g + b

    # layers stacked [pp, lps, ...] -> iterate in order
    layers = params["layers"]
    n_pp = jax.tree.leaves(layers)[0].shape[0]
    lps = jax.tree.leaves(layers)[0].shape[1]
    hd = cfg.d_model // cfg.n_heads
    for s in range(n_pp):
        for l in range(lps):
            p = jax.tree.map(lambda a: a[s, l], layers)
            h = ln(x, p["ln1_g"], p["ln1_b"])
            b, t, d = h.shape
            # head-major column layout (see _layer_init)
            qkv = (h @ p["wqkv"]).reshape(b, t, cfg.n_heads, 3, hd)
            q, k, v = qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]
            scores = jnp.einsum("bqhd,bkhd->bqhk", q, k) / np.sqrt(hd)
            mask = jnp.tril(jnp.ones((t, t), bool))
            scores = jnp.where(mask[None, :, None, :], scores, -jnp.inf)
            attn = jax.nn.softmax(scores, -1)
            o = jnp.einsum("bqhk,bkhd->bqhd", attn, v).reshape(b, t, d)
            x = x + o @ p["wo"]
            h2 = ln(x, p["ln2_g"], p["ln2_b"])
            x = x + jax.nn.gelu(h2 @ p["wi"] + p["bi"]) @ p["wo2"] + p["bo2"]
    x = ln(x, params["lnf_g"], params["lnf_b"])
    logits = x @ params["head"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - tgt)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, CFG.vocab_size, (8, 32)).astype(np.int32)
    targets = rng.integers(0, CFG.vocab_size, (8, 32)).astype(np.int32)
    return jnp.asarray(tokens), jnp.asarray(targets)


@pytest.mark.parametrize("topo", [
    dict(dp=1, pp=2, sp=2, mp=2),
    # The other topologies pin the same parity property; each is a full
    # compile (12-18 s), so they ride the slow tier and tier-1 keeps its
    # 870 s window with the one that crosses pp, sp (ring) and mp.
    pytest.param(dict(dp=2, pp=2, sp=1, mp=2), marks=pytest.mark.slow),
    pytest.param(dict(dp=4, sp=2), marks=pytest.mark.slow),
    pytest.param(dict(mp=4, sp=2), marks=pytest.mark.slow),
])
def test_hybrid_loss_matches_dense(devices8, data, topo):
    mesh = build_mesh(HybridTopology(**topo), devices8)
    pp_stages = topo.get("pp", 1)
    params, specs = init_gpt(jax.random.PRNGKey(0), CFG,
                             pp_stages=pp_stages)
    tokens, targets = data
    loss_fn = gpt_loss_fn(CFG, mesh, specs, num_microbatches=2)
    loss = loss_fn(params, tokens, targets)
    ref = _dense_reference_loss(params, tokens, targets, CFG)
    np.testing.assert_allclose(float(loss), float(ref), rtol=2e-4)


@pytest.mark.slow  # loss parity above is the tier-1 oracle; the
# 5-step learn loop compiles the full train step and rides tier-2
def test_hybrid_train_step_learns(devices8, data):
    mesh = build_mesh(HybridTopology(dp=2, pp=2, sp=1, mp=2), devices8)
    params, specs = init_gpt(jax.random.PRNGKey(1), CFG, pp_stages=2)
    tokens, targets = data
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)
    step = make_gpt_train_step(CFG, mesh, specs, opt, num_microbatches=2)
    losses = []
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], losses


@pytest.mark.slow  # three extra full-pipeline compiles; the plain
# 1f1b parity in test_1f1b_wired.py stays tier-1
def test_interleaved_1f1b_matches_tied_layer_loss(devices8, data):
    """Interleaved GPT wiring: with every layer's params TIED to the same
    values, the composed function is layer-order-invariant, so the
    interleaved schedule's loss must equal the plain 1F1B loss exactly —
    which isolates the schedule machinery from the (documented)
    layer-layout difference — and a training step must learn."""
    import optax

    mesh = build_mesh(HybridTopology(dp=1, pp=2, sp=1, mp=2),
                      devices8[:4])
    params, specs = init_gpt(jax.random.PRNGKey(2), CFG, pp_stages=2)
    # Tie all layer rows to layer 0's values.
    params = dict(params)
    params["layers"] = jax.tree.map(
        lambda a: jnp.broadcast_to(a[:1, :1], a.shape).copy(),
        params["layers"])
    tokens, targets = data
    opt = optax.adam(1e-3)

    from paddlebox_tpu.models.gpt import gpt_value_and_grad_1f1b
    vg_plain = gpt_value_and_grad_1f1b(CFG, mesh, specs,
                                       num_microbatches=4)
    vg_inter = gpt_value_and_grad_1f1b(CFG, mesh, specs,
                                       num_microbatches=4, num_chunks=2)
    loss_p, grads_p = jax.jit(vg_plain)(params, tokens, targets)
    loss_i, grads_i = jax.jit(vg_inter)(params, tokens, targets)
    np.testing.assert_allclose(float(loss_i), float(loss_p), rtol=1e-5)
    # Under tied layers the composed function is identical, so the
    # layout-independent leaves (embedding cotangent chain + loss_params
    # head channel) must agree — this gradient-checks the interleave's
    # dx0 and lgrads plumbing, not just the forward.
    for name in ("embed", "pos", "lnf_g", "lnf_b", "head"):
        np.testing.assert_allclose(
            np.asarray(grads_i[name]), np.asarray(grads_p[name]),
            rtol=5e-4, atol=1e-6, err_msg=name)

    # End-to-end: the wired step trains under the interleaved schedule.
    params2, specs2 = init_gpt(jax.random.PRNGKey(3), CFG, pp_stages=2)
    opt_state = opt.init(params2)
    step = make_gpt_train_step(CFG, mesh, specs2, opt,
                               num_microbatches=4,
                               schedule="interleaved_1f1b", num_chunks=2)
    losses = []
    for _ in range(5):
        params2, opt_state, loss = step(params2, opt_state, tokens,
                                        targets)
        losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], losses
