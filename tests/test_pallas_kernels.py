"""Pallas kernel parity tests: interpreter-mode kernels vs XLA oracles.

Mirrors the reference's OpTest pattern (SURVEY.md §4: per-op numeric
parity harness, ``tests/unittests/op_test.py``) for the hand-written
kernels: forward values and grads must match the XLA reference
implementations that define the op semantics.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlebox_tpu.ops import fused_seqpool_cvm
from paddlebox_tpu.ops.pallas_kernels import (
    flash_attention,
    flash_attention_reference,
    seqpool_cvm_pallas,
)
from paddlebox_tpu.ops.pallas_kernels.flash_attention import (
    _schedule,
    tile_counts,
)


def _qkv(rng, b, s, h, d, sk=None):
    sk = s if sk is None else sk
    q = jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, sk, h, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, sk, h, d)).astype(np.float32))
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(2, 16, 2, 8), (1, 24, 1, 4)])
def test_flash_attention_forward(causal, shape):
    rng = np.random.default_rng(0)
    q, k, v = _qkv(rng, *shape)
    got = flash_attention(q, k, v, causal=causal, block_q=8, block_k=8,
                          interpret=True)
    want = flash_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_attention_unpadded_vs_padded():
    # Sq not a multiple of the block: wrapper pads and slices.
    rng = np.random.default_rng(1)
    q, k, v = _qkv(rng, 1, 13, 2, 8)
    got = flash_attention(q, k, v, causal=True, block_q=8, block_k=8,
                          interpret=True)
    want = flash_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grads(causal):
    rng = np.random.default_rng(2)
    q, k, v = _qkv(rng, 2, 16, 2, 8)

    def loss_pallas(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=8,
                              block_k=8, interpret=True)
        return jnp.sum(out * out)

    def loss_ref(q, k, v):
        out = flash_attention_reference(q, k, v, causal=causal)
        return jnp.sum(out * out)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_attention_offsets_match_global():
    # Ring-attention contract: per-block kernel with k_offset equals the
    # corresponding slice of full causal attention... exercised by
    # comparing a shifted-k block vs the reference with same offsets.
    rng = np.random.default_rng(3)
    q, k, v = _qkv(rng, 1, 8, 1, 4, sk=8)
    got = flash_attention(q, k, v, causal=True, q_offset=8, k_offset=0,
                          block_q=8, block_k=8, interpret=True)
    want = flash_attention_reference(q, k, v, causal=True, q_offset=8,
                                     k_offset=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def _out_and_grads(attn, q, k, v, w):
    def loss(q, k, v):
        out = attn(q, k, v)
        return jnp.sum(out * w), out
    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    return (out,) + grads


# (sq, sk, heads, kv heads, causal, q_offset, k_offset): what the 8 x 8
# tiles of each case are, from tile_counts (grid, live, edge).
_TILE_CASES = {
    # interior, edge (diagonal and k-padded), dead and a padded q block
    "all_kinds": ((29, 28, 2, 2, True, 0, 0), (16, 10, 4)),
    # a shard wholly in the past: every tile interior
    "past_shard": ((16, 16, 1, 1, True, 16, 0), (4, 4, 0)),
    # the diagonal shifted by half a tile: every live tile but one is an edge
    "shifted": ((16, 16, 1, 1, True, 4, 0), (4, 4, 3)),
    # keys ahead of the queries: rows of a live tile with no unmasked key
    "keys_ahead": ((16, 24, 1, 1, True, 0, 5), (6, 3, 3)),
    # grouped heads 4 / 2 through the transposed dk/dv body
    "grouped": ((24, 24, 4, 2, True, 0, 0), (9, 6, 3)),
    # not a tile multiple, no causal mask: k padding alone
    "ragged_full": ((13, 19, 2, 1, False, 0, 0), (6, 6, 2)),
}


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
@pytest.mark.parametrize("case", sorted(_TILE_CASES))
def test_flash_attention_tile_kinds(case, traced, precision):
    """Forward and all three gradients against the reference, on grids
    whose tiles are interior, edge, dead and k-padded, with the offsets
    known at trace time (the schedule lists the live tiles only) and
    traced (every tile is visited and the kernels skip the dead ones)."""
    (sq, sk, h, hkv, causal, qo, ko), counts = _TILE_CASES[case]
    assert tile_counts(sq, sk, 8, 8, causal, qo, ko) == counts
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(2, sq, h, 8)).astype(np.float32))
    k, v = (jnp.asarray(rng.normal(size=(2, sk, hkv, 8)).astype(np.float32))
            for _ in range(2))
    w = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))
    # A query with no key at or before it reads zero from the kernel
    # (ring attention merges such a row away by its lse) and the mean of
    # v from the reference's softmax: such rows are left out of the loss
    # and of the comparison.
    has_key = (qo + np.arange(sq) >= ko) | (not causal)
    w = w * has_key[None, :, None, None]

    def run(qo, ko):
        got = _out_and_grads(
            lambda q, k, v: flash_attention(
                q, k, v, causal=causal, q_offset=qo, k_offset=ko,
                block_q=8, block_k=8, interpret=True), q, k, v, w)
        want = _out_and_grads(
            lambda q, k, v: flash_attention_reference(
                q, k, v, causal=causal, q_offset=qo, k_offset=ko),
            q, k, v, w)
        return got, want

    with jax.default_matmul_precision(
            "highest" if precision == "highest" else "default"):
        got, want = jax.jit(run)(qo, ko) if traced else run(qo, ko)
    tol = 2e-5 if precision == "highest" else 2e-4
    np.testing.assert_array_equal(np.asarray(got[0])[:, ~has_key], 0.0)
    for a, b in zip(got, (want[0] * has_key[None, :, None, None],)
                    + want[1:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
def test_flash_attention_future_shard_is_empty(traced):
    # A shard wholly in the future: no tile is live, the output and every
    # gradient are zero (ring attention merges it away by its lse).
    assert tile_counts(16, 16, 8, 8, True, 0, 16) == (4, 0, 0)
    rng = np.random.default_rng(8)
    q, k, v = _qkv(rng, 1, 16, 2, 8)

    def run(ko):
        return _out_and_grads(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, k_offset=ko, block_q=8, block_k=8,
                interpret=True), q, k, v, jnp.ones_like(q))

    for a in (jax.jit(run)(16) if traced else run(16)):
        np.testing.assert_array_equal(np.asarray(a), 0.0)


# The three dense cells' grids at the default 512 x 512 tiles (PERF.md §5)
# and shapes that pad, shift and cut the grid.
@pytest.mark.parametrize("args, want", [
    ((4096, 4096, 512, 512, True), (64, 36, 8)),        # ouro_2_6b
    ((8192, 8192, 512, 512, True), (256, 136, 16)),     # nemotron3_super
    ((1024, 1024, 512, 512, True), (4, 3, 2)),          # gpt2_medium
    ((1024, 1024, 512, 512, False), (4, 4, 0)),
    ((1000, 1000, 512, 512, True), None),
    ((40, 24, 8, 16, True, 3, 11), None),
    ((24, 40, 16, 8, True, 17, 2), None),
    ((16, 16, 8, 8, True, 0, 40), None),
    ((13, 19, 8, 8, False), None),
])
def test_tile_counts_against_brute_force(args, want):
    sq, sk, block_q, block_k, causal = args[:5]
    qo, ko = args[5:] if len(args) > 5 else (0, 0)
    bq = block_q if sq >= block_q else -(-sq // 8) * 8
    bk = block_k if sk >= block_k else -(-sk // 8) * 8
    nq, nk = -(-sq // bq), -(-sk // bk)
    qpos = qo + np.arange(nq * bq)[:, None]
    kloc = np.arange(nk * bk)[None, :]
    valid = np.broadcast_to(kloc < sk, (nq * bq, nk * bk))
    if causal:
        valid = valid & (ko + kloc <= qpos)
    tiles = valid.reshape(nq, bq, nk, bk).transpose(0, 2, 1, 3)
    live = tiles.any(axis=(2, 3))
    edge = live & ~tiles.all(axis=(2, 3))
    got = tile_counts(*args)
    assert got == (nq * nk, int(live.sum()), int(edge.sum()))
    if want is not None:
        assert got == want
    # With the offsets known the schedule visits the live tiles and, in
    # a row without any, one tile; with traced offsets every tile.
    common = dict(sk=sk, causal=causal, block_q=bq, block_k=bk)
    for keys_outer, rows in ((False, live), (True, live.T)):
        tab = _schedule(nq, nk, 1, (qo, ko), keys_outer=keys_outer, **common)
        assert tab.shape == (3, int(np.maximum(rows.sum(axis=1), 1).sum()))
        assert int(live[tab[1], tab[2]].sum()) == int(live.sum())
        assert _schedule(nq, nk, 2, None, keys_outer=keys_outer,
                         **common).shape == (3, nq * nk * (1 + keys_outer))


def test_flash_attention_fallback_backend():
    # use_pallas=False returns the XLA path (non-TPU production default).
    rng = np.random.default_rng(4)
    q, k, v = _qkv(rng, 1, 8, 1, 4)
    got = flash_attention(q, k, v, causal=False, use_pallas=False)
    want = flash_attention_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def _seqpool_case(rng, n, num_rows, dim):
    emb = jnp.asarray(rng.normal(size=(n, dim)).astype(np.float32))
    show = jnp.asarray(
        rng.integers(0, 5, size=(n,)).astype(np.float32))
    click = jnp.asarray(
        rng.integers(0, 3, size=(n,)).astype(np.float32))
    # Sorted CSR segments, with some rows empty and trailing padding.
    seg = np.sort(rng.integers(0, num_rows, size=(n - 2,)))
    seg = np.concatenate([seg, [num_rows, num_rows]]).astype(np.int32)
    return emb, show, click, jnp.asarray(seg)


@pytest.mark.parametrize("use_cvm", [True, False])
def test_seqpool_cvm_pallas_forward(use_cvm):
    rng = np.random.default_rng(5)
    emb, show, click, seg = _seqpool_case(rng, 30, 7, 6)
    got = seqpool_cvm_pallas(emb, show, click, seg, 7, use_cvm=use_cvm,
                             block_b=8, block_n=8, interpret=True)
    want = fused_seqpool_cvm(emb, show, click, seg, 7, use_cvm=use_cvm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("use_cvm", [True, False])
def test_seqpool_cvm_pallas_grads(use_cvm):
    rng = np.random.default_rng(6)
    emb, show, click, seg = _seqpool_case(rng, 20, 5, 4)

    def loss_pallas(emb):
        out = seqpool_cvm_pallas(emb, show, click, seg, 5,
                                 use_cvm=use_cvm, block_b=8, block_n=8,
                                 interpret=True)
        return jnp.sum(out * jnp.arange(out.size).reshape(out.shape))

    def loss_ref(emb):
        out = fused_seqpool_cvm(emb, show, click, seg, 5,
                                use_cvm=use_cvm)
        return jnp.sum(out * jnp.arange(out.size).reshape(out.shape))

    gp = jax.grad(loss_pallas)(emb)
    gr = jax.grad(loss_ref)(emb)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                               rtol=1e-4, atol=1e-4)


def test_seqpool_cvm_clip():
    rng = np.random.default_rng(7)
    emb, show, click, seg = _seqpool_case(rng, 12, 3, 4)
    emb = emb * 100.0
    got = seqpool_cvm_pallas(emb, show, click, seg, 3, clip_value=5.0,
                             block_b=8, block_n=8, interpret=True)
    want = fused_seqpool_cvm(emb, show, click, seg, 3, clip_value=5.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
