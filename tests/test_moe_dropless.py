"""Top-k sigmoid routing and the dropless sort/segment dispatch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from paddlebox_tpu.core import flags
from paddlebox_tpu.models import nemotron_h as nh
from paddlebox_tpu.models.block_diffusion import _held_experts, _packed
from paddlebox_tpu.parallel.moe import (LoopedExperts, dropless_dispatch,
                                        topk_sigmoid_router,
                                        topk_softmax_router)

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


def _looped(rows_fn):
    """``rows_fn(params, rows, sizes)`` as the looped dispatch's experts:
    the backward pass from ``jax.vjp``, its gradient of the weights added
    to the sums."""
    def forward(p, rows, scale, sizes):
        return rows_fn(p, rows, sizes) * scale[:, None]

    def backward(p, rows, scale, sizes, dy, sums):
        _, back = jax.vjp(lambda p, r, s: forward(p, r, s, sizes), p, rows,
                          scale)
        own, drows, dscale = back(dy)
        return drows, dscale, jax.tree.map(jnp.add, sums, own)
    return LoopedExperts(forward, backward)


# ``_expert`` with the weights handed in: the looped dispatch's form
_expert_of = _looped(lambda p, rows, sizes: _expert(p["w1"], p["w2"])(
    rows, sizes))

# The squared-ReLU experts three ways: the backward pass from ``jax.vjp``
# of ``lax.ragged_dot``; the hybrid stack's written-out one over the XLA
# products; the same over the Pallas kernels in the interpreter, whose row
# tiles (128) the blocks must hold whole.
RELU2 = {
    "vjp": (lambda: _expert_of, 40),
    "xla": (lambda: nh._held_experts(flags.kernel_mode("xla"), jnp.float32),
            40),
    "interpret": (lambda: nh._held_experts(flags.kernel_mode("interpret"),
                                           jnp.float32), 128),
}


@pytest.mark.parametrize("shares,looped", [(1, False), (4, False),
                                           (4, True), (4, "xla"),
                                           (1, "interpret")])
def test_shares_add_up_to_the_uncut_layer(shares, looped):
    """What all the shares give (4 chips holding 4 experts each, or one
    holding all 16) adds up to the layer over all experts, in value and
    in every gradient; the blocks unrolled under ``cond`` or as a loop
    (``looped``: True the experts' backward from ``jax.vjp``, else the
    hybrid stack's experts of that ``RELU2`` name), in blocks that do not
    divide the T * K rows, or in two trips of 128 rows."""
    x, gate, w1, w2 = _layer()
    count = E // shares

    def cut(x, gate, w1, w2):
        idx, w = topk_sigmoid_router(x, gate, jnp.zeros(E), k=K,
                                     scaling=2.5)

        def share(first):
            held = {"w1": w1[first:first + count],
                    "w2": w2[first:first + count]}
            if looped:
                make, block_rows = RELU2["vjp" if looped is True else looped]
                return dropless_dispatch(x, idx, w, (first, count), make(),
                                         held, block_rows=block_rows)[0]
            return dropless_dispatch(x, idx, w, (first, count),
                                     _expert(held["w1"], held["w2"]))[0]
        return sum(share(first) for first in range(0, E, count))
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


def _swiglu_of(p, rows, sizes):
    gate = lax.ragged_dot(rows, p["w1"], sizes)
    return lax.ragged_dot(jax.nn.silu(gate) * lax.ragged_dot(
        rows, p["w3"], sizes), p["w2"], sizes)


def _uncut_swiglu(x, gate, w1, w3, w2):
    """Softmax top-k routing, every gated expert applied to every token."""
    idx, w = topk_softmax_router(x, gate, K)
    hidden = (jax.nn.silu(jnp.einsum("tf,efi->tei", x, w1))
              * jnp.einsum("tf,efi->tei", x, w3))
    return jnp.einsum("tke,tef->tf", jax.nn.one_hot(idx, E) * w[..., None],
                      jnp.einsum("tei,eif->tef", hidden, w2))


# The gated experts three ways, gate and up-projection side by side as the
# block-diffusion stack packs them: the backward pass from ``jax.vjp`` of
# ``lax.ragged_dot``; the stack's written-out one over the XLA products;
# the same over the Pallas kernels in the interpreter, whose row tiles
# (128) the blocks must hold whole.
SWIGLU = {
    "vjp": (lambda: _looped(lambda p, rows, sizes: _swiglu_of(
        {"w1": p["w13"][..., :INNER], "w3": p["w13"][..., INNER:],
         "w2": p["w2"]}, rows, sizes)), 0),
    "xla": (lambda: _held_experts(flags.kernel_mode("xla"), jnp.float32),
            40),
    "interpret": (lambda: _held_experts(flags.kernel_mode("interpret"),
                                        jnp.float32), 128),
}


@pytest.mark.parametrize("experts", sorted(SWIGLU))
def test_eight_shares_of_the_softmax_swiglu_layer_add_up_to_the_uncut_one(
        experts):
    """The block-diffusion stack's expert layer: softmax top-k routing
    renormalised over the chosen, gated experts, the looped dispatch. The
    eight shares ``(0, 2) .. (14, 2)`` add up to every expert applied to
    every token, in value and in every gradient."""
    make, block_rows = SWIGLU[experts]
    x, gate, w1, w2 = _layer(3)
    w3 = jax.random.normal(jax.random.PRNGKey(33), w1.shape) * 0.3

    def cut(x, gate, w1, w3, w2):
        idx, w = topk_softmax_router(x, gate, K)
        return sum(dropless_dispatch(
            x, idx, w, (first, 2), make(),
            _packed({"w1": w1[first:first + 2], "w3": w3[first:first + 2],
                     "w2": w2[first:first + 2]}), block_rows=block_rows)[0]
            for first in range(0, E, 2))
    args = (x, gate, w1, w3, w2)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            np.asarray(jax.jit(cut)(*args)),
            np.asarray(_uncut_swiglu(*args)),
            rtol=1e-5, atol=1e-5)
        want = jax.grad(lambda *a: jnp.sum(jnp.sin(_uncut_swiglu(*a))),
                        argnums=tuple(range(5)))(*args)
        got = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(cut(*a))),
                               argnums=tuple(range(5))))(*args)
    for g, w in zip(got, want):
        assert float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)) < 1e-5


def _both_layers(experts):
    """(the looped experts, how a device's weights are handed over, the
    router, the uncut layer, which of ``(x, gate, w1, w3, w2)`` it reads)
    of the block-diffusion stack's gated layer (``experts`` a ``SWIGLU``
    name) or the hybrid stack's squared-ReLU one (``relu2_`` and a
    ``RELU2`` name)."""
    if experts.startswith("relu2_"):
        make, _ = RELU2[experts[len("relu2_"):]]
        return (make(), lambda w1, w3, w2: {"w1": w1, "w2": w2},
                lambda x, gate: topk_sigmoid_router(
                    x, gate, jnp.zeros(E), k=K, scaling=2.5),
                lambda x, gate, w1, w3, w2: _uncut(x, gate, w1, w2),
                (0, 1, 2, 4))
    make, _ = SWIGLU[experts]
    return (make(), lambda w1, w3, w2: _packed({"w1": w1, "w3": w3,
                                                "w2": w2}),
            lambda x, gate: topk_softmax_router(x, gate, K), _uncut_swiglu,
            tuple(range(5)))


@pytest.mark.parametrize("experts", ["xla", "interpret", "relu2_xla",
                                     "relu2_interpret"])
def test_every_trip_of_the_loop_sums_into_one_gradient(experts):
    """One device holding all 16 experts (gated, or squared ReLU) serves
    the ``T * K`` = 256 assignments in two trips of 128 rows (one row
    tile each in the interpreter): both trips' gradients of the experts'
    weights land in the one carried sum, and the layer is the uncut
    one."""
    x, gate, w1, w2 = _layer(5)
    w3 = jax.random.normal(jax.random.PRNGKey(55), w1.shape) * 0.3
    looped, pack, route, uncut, read = _both_layers(experts)

    def cut(x, gate, w1, w3, w2):
        idx, w = route(x, gate)
        out, counts = dropless_dispatch(
            x, idx, w, (0, E), looped, pack(w1, w3, w2), block_rows=128)
        return out, counts
    args = (x, gate, w1, w3, w2)
    with jax.default_matmul_precision("highest"):
        out, counts = jax.jit(cut)(*args)
        assert int(counts.load.sum()) == T * K and int(counts.dropped) == 0
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(uncut(*args)),
                                   rtol=1e-5, atol=1e-5)
        want = jax.grad(lambda *a: jnp.sum(jnp.sin(uncut(*a))),
                        argnums=read)(*args)
        got = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(cut(*a)[0])),
                               argnums=read))(*args)
    for g, w in zip(got, want):
        assert float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)) < 1e-5


@pytest.mark.parametrize("experts", ["xla", "interpret", "relu2_xla",
                                     "relu2_interpret"])
def test_rows_past_the_last_segment_reach_nothing(experts):
    """The ``LoopedExperts`` contract: rows past the last segment may hold
    anything, in ``rows`` and in ``dy`` (on the TPU a grouped product
    leaves them unwritten); nothing of them reaches a live row, a live
    row's scale cotangent or the weights' sums."""
    looped, pack, _, _, _ = _both_layers(experts)
    ks = jax.random.split(jax.random.PRNGKey(6), 6)
    rows, held = 128, 4
    sizes = jnp.array([30, 0, 41, 27], jnp.int32)      # 98 of 128 live
    w1 = jax.random.normal(ks[0], (held, F, INNER)) * 0.3
    w3 = jax.random.normal(ks[1], (held, F, INNER)) * 0.3
    w2 = jax.random.normal(ks[2], (held, INNER, F)) * 0.3
    params = pack(w1, w3, w2)
    x = jax.random.normal(ks[3], (rows, F))
    dy = jax.random.normal(ks[4], (rows, F))
    scale = jax.random.uniform(ks[5], (rows,))
    live = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
    sums = jax.tree.map(lambda p: jnp.ones(p.shape, jnp.float32), params)

    def run(x, dy):
        with jax.default_matmul_precision("highest"):
            y = looped.forward(params, x, scale, sizes)
            return (y,) + tuple(looped.backward(params, x, scale, sizes, dy,
                                                sums))
    want = jax.jit(run)(jnp.where(live, x, 0.0), jnp.where(live, dy, 0.0))
    got = jax.jit(run)(jnp.where(live, x, jnp.nan),
                       jnp.where(live, dy, jnp.inf))
    for name, g, w in (("out", got[0], want[0]), ("drows", got[1], want[1]),
                       ("dscale", got[2][:, None], want[2][:, None])):
        np.testing.assert_allclose(np.asarray(jnp.where(live, g, 0.0)),
                                   np.asarray(jnp.where(live, w, 0.0)),
                                   rtol=1e-6, atol=1e-6, err_msg=name)
    for g, w in zip(jax.tree.leaves(got[3]), jax.tree.leaves(want[3])):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("kernels", ["xla", "interpret"])
def test_bfloat16_operands_round_the_products_and_nothing_else(kernels):
    """The hybrid stack's experts as the timed step runs them on the
    chip: bfloat16 operands, float32 sums and float32 between the
    products. The layer and the cotangents of the tokens, the router and
    both weights stay within a few 2^-8 of the float32 ones (read about
    one), and do differ from them."""
    x, gate, w1, w2 = _layer(7)

    def cut(mxu):
        def layer(x, gate, w1, w2):
            idx, w = topk_sigmoid_router(x, gate, jnp.zeros(E), k=K,
                                         scaling=2.5)
            return dropless_dispatch(
                x, idx, w, (0, E), nh._held_experts(
                    flags.kernel_mode(kernels), mxu), {"w1": w1, "w2": w2},
                block_rows=128)[0]
        return layer
    args = (x, gate, w1, w2)
    cot = jax.random.normal(jax.random.PRNGKey(77), x.shape)
    got, want = (jax.jit(jax.vjp, static_argnums=0)(cut(mxu), *args)
                 for mxu in (jnp.bfloat16, jnp.float32))
    for g, w in zip((got[0],) + got[1](cot), (want[0],) + want[1](cot)):
        err = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert 1e-5 < err < 4 * 2.0 ** -8


def test_softmax_router_is_the_sort_based_one_ties_included():
    x, gate, _, _ = _layer(4)
    # experts 2 and 9 score alike for every token, 4 and 11 as well: the
    # lower index of equals is chosen first
    gate = gate.at[:, 9].set(gate[:, 2]).at[:, 11].set(gate[:, 4])
    idx, w = jax.jit(lambda x, g: topk_softmax_router(x, g, K))(x, gate)
    assert idx.dtype == jnp.int32 and w.dtype == jnp.float32
    logits = np.asarray(x, np.float64) @ np.asarray(gate, np.float64)
    probs = np.exp(logits - logits.max(axis=1, keepdims=True))
    probs /= probs.sum(axis=1, keepdims=True)
    # exact ties in float64 as in float32: equal columns
    want = np.argsort(-probs, axis=1, kind="stable")[:, :K]
    np.testing.assert_array_equal(np.asarray(idx), want)
    picked = np.take_along_axis(probs, want, axis=1)
    np.testing.assert_allclose(
        np.asarray(w), picked / picked.sum(axis=1, keepdims=True), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(w.sum(axis=1)), 1.0, rtol=1e-6)
    assert bool(jnp.all(w[:, :-1] >= w[:, 1:]))
    both = (np.asarray(idx) == 2).any(axis=1) & (np.asarray(idx) == 9).any(
        axis=1)
    assert both.any()       # a tie inside the chosen
    rows = np.nonzero(both)[0]
    pos2 = np.argmax(np.asarray(idx)[rows] == 2, axis=1)
    pos9 = np.argmax(np.asarray(idx)[rows] == 9, axis=1)
    assert (pos2 < pos9).all()
    # the logits' gradient reaches the gate through the chosen alone
    grad = jax.grad(lambda g: jnp.sum(topk_softmax_router(x, g, K)[1]
                                      * jnp.arange(K)))(gate)
    assert float(jnp.abs(grad).max()) > 0.0


@pytest.mark.parametrize("looped", [False, True, "xla", "interpret"])
def test_nothing_is_dropped_when_one_expert_takes_half_the_tokens(looped):
    """``looped``: False the ``cond`` form; True the loop in blocks of T
    rows with the experts' backward from ``jax.vjp``; else the hybrid
    stack's experts of that ``RELU2`` name, in its blocks."""
    x, gate, w1, w2 = _layer(1)
    # expert 5 is planted to win for the first half of the tokens
    bias = jnp.zeros(E)
    x = x.at[:T // 2].set(jnp.abs(x[:T // 2]))
    gate = gate.at[:, 5].set(3.0)
    idx, w = topk_sigmoid_router(x, gate, bias, k=K, scaling=2.5)
    if looped is True:
        dispatch = lambda x, idx, w: dropless_dispatch(
            x, idx, w, (4, 4), _expert_of, {"w1": w1[4:8], "w2": w2[4:8]})
    elif looped:
        make, block_rows = RELU2[looped]
        dispatch = lambda x, idx, w: dropless_dispatch(
            x, idx, w, (4, 4), make(), {"w1": w1[4:8], "w2": w2[4:8]},
            block_rows=block_rows)
    else:
        dispatch = lambda x, idx, w: dropless_dispatch(
            x, idx, w, (4, 4), _expert(w1[4:8], w2[4:8]))
    out, counts = jax.jit(dispatch)(x, idx, w)
    load = np.asarray(counts.load)
    want = np.array([(np.asarray(idx) == e).sum() for e in range(4, 8)])
    np.testing.assert_array_equal(load, want)       # a numpy count
    assert load[1] >= T // 2 and int(counts.dropped) == 0
    # and the overloaded expert's tokens all got their part
    full = _uncut(x, gate, w1.at[:4].set(0).at[8:].set(0),
                  w2.at[:4].set(0).at[8:].set(0), bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("looped", [False, True, "xla", "interpret"])
def test_no_held_expert_chosen_gives_zero(looped):
    x, gate, w1, w2 = _layer(2)
    idx = jnp.zeros((T, K), jnp.int32)              # everyone picks 0
    w = jnp.ones((T, K))
    if looped:
        make, block_rows = ((lambda: _expert_of, 0) if looped is True
                            else RELU2[looped])
        out, counts = dropless_dispatch(
            x, idx, w, (8, 4), make(), {"w1": w1[8:12], "w2": w2[8:12]},
            block_rows=block_rows)
    else:
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
