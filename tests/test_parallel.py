"""Parallelism suite tests: TP layers, ring/Ulysses attention, pipeline,
MoE, ZeRO specs — each verified against a single-device dense reference
(the reference's hybrid_parallel_mp_model.py-style parity tests run as
subprocess clusters; here the 8-device virtual mesh does it in-process).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from paddlebox_tpu.parallel import (HybridTopology, build_mesh, moe, pp, sp,
                                    tp, zero)


# ---------------------------------------------------------------------------
# TP layers
# ---------------------------------------------------------------------------

def test_vocab_parallel_embedding(devices8):
    mesh = build_mesh(HybridTopology(mp=8))
    vocab, dim = 64, 16
    params, specs = tp.vocab_parallel_embedding_init(
        jax.random.PRNGKey(0), vocab, dim)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, vocab, (4, 7)))

    f = jax.shard_map(
        functools.partial(tp.vocab_parallel_embedding, axis="mp"),
        mesh=mesh, in_specs=({"table": specs["table"]}, P()),
        out_specs=P(), check_vma=False)
    out = f(params, ids)
    ref = params["table"][ids]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


def test_column_row_parallel_linear_composition(devices8):
    """Column(gather=False) -> Row(parallel in) == dense two-layer."""
    mesh = build_mesh(HybridTopology(mp=8))
    rng = jax.random.PRNGKey(1)
    r1, r2 = jax.random.split(rng)
    cp, cspec = tp.column_parallel_linear_init(r1, 32, 64)
    rp, rspec = tp.row_parallel_linear_init(r2, 64, 16)
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 32))

    def f(cp, rp, x):
        h = tp.column_parallel_linear(cp, x, axis="mp")
        return tp.row_parallel_linear(rp, h, axis="mp")

    fm = jax.shard_map(f, mesh=mesh,
                       in_specs=(cspec, rspec, P()),
                       out_specs=P(), check_vma=False)
    out = fm(cp, rp, x)
    ref = (x @ cp["w"] + cp["b"]) @ rp["w"] + rp["b"]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_parallel_cross_entropy(devices8):
    mesh = build_mesh(HybridTopology(mp=8))
    t, v = 12, 64
    logits = jax.random.normal(jax.random.PRNGKey(3), (t, v))
    labels = jnp.asarray(np.random.default_rng(1).integers(0, v, (t,)))

    f = jax.shard_map(
        functools.partial(tp.parallel_cross_entropy, axis="mp"),
        mesh=mesh, in_specs=(P(None, "mp"), P()),
        out_specs=P(), check_vma=False)
    loss = f(logits, labels)
    # Dense reference.
    logz = jax.nn.logsumexp(logits, axis=-1)
    ref = logz - logits[jnp.arange(t), labels]
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Sequence parallelism
# ---------------------------------------------------------------------------

def _dense_attention(q, k, v, causal):
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bqhk", q, k) * scale
    if causal:
        n = q.shape[1]
        mask = jnp.tril(jnp.ones((n, n), bool))
        s = jnp.where(mask[None, :, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqhk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_exact(devices8, causal):
    mesh = build_mesh(HybridTopology(sp=8))
    b, s, h, d = 2, 64, 4, 8
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
               for _ in range(3))

    f = jax.shard_map(
        functools.partial(sp.ring_attention, axis="sp", causal=causal),
        mesh=mesh, in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"), check_vma=False)
    out = f(q, k, v)
    ref = _dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_exact(devices8, causal):
    mesh = build_mesh(HybridTopology(sp=8))
    b, s, h, d = 2, 64, 8, 4
    rng = np.random.default_rng(6)
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
               for _ in range(3))

    f = jax.shard_map(
        functools.partial(sp.ulysses_attention, axis="sp", causal=causal),
        mesh=mesh, in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"), check_vma=False)
    out = f(q, k, v)
    ref = _dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.slow  # 23 s sp=8 backward compile; ring fwd exactness and the sp=2 GPT loss parity stay tier-1
def test_ring_attention_grads_flow(devices8):
    """Autodiff through the ring (training usability)."""
    mesh = build_mesh(HybridTopology(sp=8))
    b, s, h, d = 1, 32, 2, 4
    q = jax.random.normal(jax.random.PRNGKey(7), (b, s, h, d))

    def loss(q):
        f = jax.shard_map(
            functools.partial(sp.ring_attention, axis="sp", causal=True),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False)
        return jnp.sum(f(q, q, q) ** 2)

    g = jax.grad(loss)(q)
    assert np.isfinite(np.asarray(g)).all()
    assert np.abs(np.asarray(g)).sum() > 0


# ---------------------------------------------------------------------------
# Pipeline
# ---------------------------------------------------------------------------

def test_gpipe_matches_sequential(devices8):
    mesh = build_mesh(HybridTopology(pp=8))
    f_dim = 16
    rng = jax.random.PRNGKey(8)
    stage_params = []
    for i in range(8):
        rng, sub = jax.random.split(rng)
        w = jax.random.normal(sub, (f_dim, f_dim)) * 0.3
        stage_params.append({"w": w})
    stacked = pp.stack_stage_params(stage_params)

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"])

    x_mb = jax.random.normal(jax.random.PRNGKey(9), (4, 8, f_dim))  # M=4

    run = pp.make_pipeline_fn(mesh, stage_fn, stacked)
    out = run(stacked, x_mb)

    ref = x_mb
    for p in stage_params:
        ref = jnp.tanh(ref @ p["w"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow  # 14 s pp=8 backward compile; test_1f1b_matches_gpipe_autodiff differentiates through gpipe in tier-1; tier-1 keeps its 870 s window (PR 21)
def test_gpipe_backward(devices8):
    mesh = build_mesh(HybridTopology(pp=8))
    f_dim = 8
    stage_params = [{"w": jax.random.normal(jax.random.PRNGKey(i),
                                            (f_dim, f_dim)) * 0.3}
                    for i in range(8)]
    stacked = pp.stack_stage_params(stage_params)
    x_mb = jax.random.normal(jax.random.PRNGKey(99), (2, 4, f_dim))

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"])

    pspecs = pp.stage_specs(stacked)

    def loss(stacked, x_mb):
        f = jax.shard_map(
            lambda sp_, x: pp.gpipe_apply(
                stage_fn, jax.tree.map(lambda a: a[0], sp_), x),
            mesh=mesh, in_specs=(pspecs, P()), out_specs=P(),
            check_vma=False)
        return jnp.sum(f(stacked, x_mb) ** 2)

    g = jax.grad(loss)(stacked, x_mb)
    g_flat = np.asarray(g["w"])
    assert np.isfinite(g_flat).all()
    # Every stage's params get gradient.
    assert (np.abs(g_flat).reshape(8, -1).sum(axis=1) > 0).all()


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def test_moe_dispatch_combine(devices8):
    mesh = build_mesh(HybridTopology(ep=8))
    f_dim, e_local = 16, 2  # 16 experts over 8 devices
    t_total = 8 * 32
    rng = jax.random.PRNGKey(11)
    k1, k2, k3 = jax.random.split(rng, 3)
    gate_w = jax.random.normal(k1, (f_dim, 16)) * 0.5
    # Identity-ish experts: expert e multiplies by (1 + e/10).
    expert_scale = (1.0 + jnp.arange(16) / 10.0)
    expert_params = {"scale": expert_scale.reshape(8, 2)}  # [dev, local]
    x = jax.random.normal(k3, (t_total, f_dim))

    def expert_fn(params_e, tokens):
        return tokens * params_e["scale"]

    def f(gate_w, expert_params, x):
        return moe.moe_layer(gate_w, expert_params, expert_fn, x,
                             axis="ep", capacity_factor=4.0)

    fm = jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(), {"scale": P("ep")}, P("ep")),
        out_specs=(P("ep"), P()), check_vma=False)
    y, aux = fm(gate_w, {"scale": expert_scale.reshape(16,)}, x)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    assert float(aux) > 0

    # Reference: dense top-2 mixture with ample capacity.
    logits = x @ gate_w
    gates = jax.nn.softmax(logits, axis=-1)
    top2 = jnp.argsort(gates, axis=-1)[:, -2:]
    ref = np.zeros_like(np.asarray(x))
    gn = np.asarray(gates)
    for t in range(t_total):
        e1, e2 = int(top2[t, 1]), int(top2[t, 0])
        w1, w2 = gn[t, e1], gn[t, e2]
        zn = w1 + w2
        ref[t] = (w1 / zn * np.asarray(x[t]) * (1 + e1 / 10.0) +
                  w2 / zn * np.asarray(x[t]) * (1 + e2 / 10.0))
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# ZeRO specs
# ---------------------------------------------------------------------------

def test_zero_specs_and_shard(devices8):
    mesh = build_mesh(HybridTopology(sharding=8))
    params = {
        "big": jnp.zeros((1024, 64)),     # sharded (dim 0 divisible)
        "small": jnp.zeros((4, 4)),       # replicated (too small)
        "odd": jnp.zeros((17, 131072)),   # dim1 not divisible... 131072%8==0
    }
    specs = zero.zero_specs(params, mesh)
    assert specs["big"] == P("sharding", None)
    assert specs["small"] == P()
    assert specs["odd"] == P(None, "sharding")

    sharded = zero.shard_tree(params, mesh)
    assert sharded["big"].sharding.spec == P("sharding", None)
    # addressable shard is 1/8 of rows
    assert sharded["big"].addressable_shards[0].data.shape == (128, 64)
