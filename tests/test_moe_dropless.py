"""Top-k sigmoid routing and the dropless sort/segment dispatch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from paddlebox_tpu.parallel.moe import (dropless_dispatch,
                                        topk_sigmoid_router)

T, F, E, K, INNER = 64, 16, 16, 4, 24


def _layer(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (T, F)),
            jax.random.normal(ks[1], (F, E)),
            jax.random.normal(ks[2], (E, F, INNER)) * 0.3,
            jax.random.normal(ks[3], (E, INNER, F)) * 0.3)


def _expert(w1, w2):
    def rows_fn(rows, sizes):
        hidden = jnp.square(jax.nn.relu(lax.ragged_dot(rows, w1, sizes)))
        return lax.ragged_dot(hidden, w2, sizes)
    return rows_fn


def _uncut(x, gate, w1, w2, bias=None):
    """Every expert applied to every token, weighted by a one-hot."""
    bias = jnp.zeros(E) if bias is None else bias
    idx, w = topk_sigmoid_router(x, gate, bias, k=K, scaling=2.5)
    dense = jnp.einsum("tei,eif->tef", jnp.square(jax.nn.relu(
        jnp.einsum("tf,efi->tei", x, w1))), w2)
    return jnp.einsum("tke,tef->tf", jax.nn.one_hot(idx, E) * w[..., None],
                      dense)


def test_router_chooses_by_biased_score_and_weighs_by_score():
    x, gate, _, _ = _layer()
    bias = jnp.zeros(E).at[3].set(10.0)         # expert 3 always chosen
    idx, w = topk_sigmoid_router(x, gate, bias, k=K, scaling=5.0)
    assert idx.shape == (T, K) and w.dtype == jnp.float32
    assert bool(jnp.all(jnp.any(idx == 3, axis=1)))
    np.testing.assert_allclose(np.asarray(w.sum(axis=1)), 5.0, rtol=1e-5)
    scores = np.asarray(jax.nn.sigmoid(x @ gate))
    picked = np.take_along_axis(scores, np.asarray(idx), axis=1)
    np.testing.assert_allclose(
        np.asarray(w), 5.0 * picked / picked.sum(axis=1, keepdims=True),
        rtol=1e-4)
    # the bias steers the choice and takes no gradient
    grad = jax.grad(lambda b: jnp.sum(topk_sigmoid_router(
        x, gate, b, k=K)[1] ** 2))(bias)
    assert float(jnp.abs(grad).max()) == 0.0


@pytest.mark.parametrize("shares", [1, 4])
def test_shares_add_up_to_the_uncut_layer(shares):
    """What all the shares give (4 chips holding 4 experts each, or one
    holding all 16) adds up to the layer over all experts, in value and
    in every gradient."""
    x, gate, w1, w2 = _layer()
    count = E // shares

    def cut(x, gate, w1, w2):
        idx, w = topk_sigmoid_router(x, gate, jnp.zeros(E), k=K,
                                     scaling=2.5)
        return sum(dropless_dispatch(
            x, idx, w, (first, count),
            _expert(w1[first:first + count], w2[first:first + count]))[0]
            for first in range(0, E, count))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            np.asarray(jax.jit(cut)(x, gate, w1, w2)),
            np.asarray(_uncut(x, gate, w1, w2)), rtol=1e-5, atol=1e-5)
        want = jax.grad(lambda *a: jnp.sum(jnp.sin(_uncut(*a))),
                        argnums=(0, 1, 2, 3))(x, gate, w1, w2)
        got = jax.grad(lambda *a: jnp.sum(jnp.sin(cut(*a))),
                       argnums=(0, 1, 2, 3))(x, gate, w1, w2)
    for g, w in zip(got, want):
        assert float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)) < 1e-5


def test_nothing_is_dropped_when_one_expert_takes_half_the_tokens():
    x, gate, w1, w2 = _layer(1)
    # expert 5 is planted to win for the first half of the tokens
    bias = jnp.zeros(E)
    x = x.at[:T // 2].set(jnp.abs(x[:T // 2]))
    gate = gate.at[:, 5].set(3.0)
    idx, w = topk_sigmoid_router(x, gate, bias, k=K, scaling=2.5)
    out, counts = jax.jit(lambda x, idx, w: dropless_dispatch(
        x, idx, w, (4, 4), _expert(w1[4:8], w2[4:8])))(x, idx, w)
    load = np.asarray(counts.load)
    want = np.array([(np.asarray(idx) == e).sum() for e in range(4, 8)])
    np.testing.assert_array_equal(load, want)       # a numpy count
    assert load[1] >= T // 2 and int(counts.dropped) == 0
    # and the overloaded expert's tokens all got their part
    full = _uncut(x, gate, w1.at[:4].set(0).at[8:].set(0),
                  w2.at[:4].set(0).at[8:].set(0), bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               rtol=2e-2, atol=2e-2)


def test_no_held_expert_chosen_gives_zero():
    x, gate, w1, w2 = _layer(2)
    idx = jnp.zeros((T, K), jnp.int32)              # everyone picks 0
    w = jnp.ones((T, K))
    out, counts = dropless_dispatch(x, idx, w, (8, 4),
                                    _expert(w1[8:12], w2[8:12]))
    assert float(jnp.abs(out).max()) == 0.0
    assert int(counts.load.sum()) == 0 and int(counts.dropped) == 0


def test_a_skipped_block_is_counted_as_dropped(monkeypatch):
    """``dropped`` counts where the rows are added: a block the ``cond``
    wrongly skips leaves its assignments unserved, and the counter says
    how many."""
    import types
    from paddlebox_tpu.parallel import moe as moelib
    x, gate, w1, w2 = _layer(1)
    x = x.at[:T // 2].set(jnp.abs(x[:T // 2]))
    gate = gate.at[:, 5].set(3.0)
    idx, w = topk_sigmoid_router(x, gate, jnp.zeros(E), k=K, scaling=2.5)
    held = int(((np.asarray(idx) >= 4) & (np.asarray(idx) < 8)).sum())
    assert held > T                                 # a second block exists
    calls = []

    def first_block_only(pred, serve, skip, acc):
        calls.append(1)
        return serve(acc) if len(calls) == 1 else skip(acc)
    faulty = types.SimpleNamespace(**{n: getattr(lax, n) for n in dir(lax)
                                      if not n.startswith("_")})
    faulty.cond = first_block_only
    monkeypatch.setattr(moelib, "lax", faulty)
    _, counts = dropless_dispatch(x, idx, w, (4, 4),
                                  _expert(w1[4:8], w2[4:8]))
    assert int(counts.load.sum()) == held
    assert int(counts.dropped) == held - T
