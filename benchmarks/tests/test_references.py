"""The plain references against the program's own model code on the CPU,
float32 on both sides, and the tolerance the chip run holds them to."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import deepfm_criteo, gpt2_medium
from benchmarks.runners.ctr_day import EVAL_TOL


def _deepfm_case(batch=2048, slots=26, dim=16, dense_dim=13, seed=0):
    from paddlebox_tpu.models import DeepFM
    rng = np.random.default_rng(seed)
    model = DeepFM(slot_names=tuple(f"s{i}" for i in range(slots)),
                   emb_dim=dim, dense_dim=dense_dim, hidden=(400, 400, 400))
    params = model.init(jax.random.PRNGKey(seed))
    # values the size trained rows have, not the 1e-2 of a fresh store
    emb = rng.normal(0, 0.1, (batch, slots, dim)).astype(np.float32)
    w = rng.normal(0, 0.5, (batch, slots)).astype(np.float32)
    dense = rng.random((batch, dense_dim)).astype(np.float32)
    labels = (rng.random(batch) < 0.3).astype(np.float32)
    return model, params, emb, w, dense, labels


def test_deepfm_reference_is_the_programs_model():
    model, params, emb, w, dense, _ = _deepfm_case()
    batch, slots, _ = emb.shape
    seg = jnp.arange(batch)
    with jax.default_matmul_precision("highest"):
        got = model.apply(
            params, {f"s{j}": jnp.asarray(emb[:, j]) for j in range(slots)},
            {f"s{j}": jnp.asarray(w[:, j]) for j in range(slots)},
            {f"s{j}": seg for j in range(slots)}, batch,
            dense_feats=jnp.asarray(dense))
    want = deepfm_criteo.logits(params, emb, w, dense)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def _outside(a, b):
    return any(abs(a[k] - b[k]) > EVAL_TOL[k] * max(
        abs(b[k]), 1.0 if k == "auc" else 0.0) for k in EVAL_TOL)


@pytest.mark.parametrize("fault", ["dropped_slot", "rows_shifted",
                                   "tower_half_width"])
def test_tolerance_catches_a_wrong_pull_or_forward(fault):
    _, params, emb, w, dense, labels = _deepfm_case(batch=16384)
    want = deepfm_criteo.evaluate(params, emb, w, dense, labels)
    if fault == "dropped_slot":
        keep = np.arange(emb.shape[1]) != 5
        bad = deepfm_criteo.evaluate(params, emb * keep[None, :, None],
                                     w * keep[None], dense, labels)
    elif fault == "rows_shifted":
        bad = deepfm_criteo.evaluate(params, np.roll(emb, 1, axis=0),
                                     np.roll(w, 1, axis=0), dense, labels)
    else:
        half = jax.tree.map(lambda x: x, params)
        half["mlp"][1] = dict(half["mlp"][1],
                              w=half["mlp"][1]["w"].at[200:].set(0.0))
        bad = deepfm_criteo.evaluate(half, emb, w, dense, labels)
    assert _outside(bad, want)
    assert not _outside(want, want)


def test_bf16_tower_stays_inside_the_tolerance():
    _, params, emb, w, dense, labels = _deepfm_case(batch=16384)
    want = deepfm_criteo.evaluate(params, emb, w, dense, labels)
    low = jax.tree.map(
        lambda x: np.asarray(jnp.asarray(x, jnp.bfloat16), np.float32),
        (params, emb, w, dense))
    assert not _outside(deepfm_criteo.evaluate(*low, labels), want)


def test_rank_auc_counts_ties_half():
    prob = np.array([0.2, 0.2, 0.9, 0.1])
    labels = np.array([1.0, 0.0, 1.0, 0.0])
    # pairs (pos, neg): (.2,.2) tie, (.2,.1) win, (.9,.2) win, (.9,.1) win
    assert deepfm_criteo.rank_auc(prob, labels) == pytest.approx(3.5 / 4)


def test_gpt_reference_is_the_programs_loss():
    from paddlebox_tpu.models.gpt import GPTConfig, gpt_loss_fn, init_gpt
    from paddlebox_tpu.parallel import HybridTopology, build_mesh
    cfg = GPTConfig(vocab_size=97, d_model=32, n_heads=4, n_layers=3,
                    d_ff=64, max_seq_len=16)
    params, specs = init_gpt(jax.random.PRNGKey(1), cfg, pp_stages=1)
    mesh = build_mesh(HybridTopology(dp=1), devices=jax.devices()[:1])
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 17), 0, 97)
    tokens, targets = toks[:, :-1], toks[:, 1:]
    with jax.default_matmul_precision("highest"):
        got = float(jax.jit(gpt_loss_fn(cfg, mesh, specs))(
            params, tokens, targets))
    want = float(gpt2_medium.loss(params, tokens, targets, n_head=4))
    assert got == pytest.approx(want, rel=1e-5)
    # the head-major QKV layout matters: q and k swapped is another model
    swapped = dict(params, layers=dict(
        params["layers"], wqkv=jnp.roll(params["layers"]["wqkv"], 8, -1)))
    assert abs(float(gpt2_medium.loss(swapped, tokens, targets, n_head=4))
               - want) > 1e-4
