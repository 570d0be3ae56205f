"""DIN-Rank model tests: rank_offset construction from pv group ids and
end-to-end learning of an in-pv context signal that a peer-blind model
cannot capture."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlebox_tpu.models import DINRank, build_rank_offset


def test_build_rank_offset_structure():
    gids = np.asarray([7, 7, 7, 9, 9, 3], np.uint64)
    ro = build_rank_offset(gids, max_rank=4)
    # ranks within each contiguous group
    np.testing.assert_array_equal(ro[:, 0], [1, 2, 3, 1, 2, 1])
    # row 0's peers: rows 1 (rank 2) and 2 (rank 3)
    assert (ro[0, 1], ro[0, 2]) == (2, 1)
    assert (ro[0, 3], ro[0, 4]) == (3, 2)
    assert ro[0, 5] == 0  # padding
    # singleton group: no peers
    assert (ro[5, 1:] == 0).all()


def test_build_rank_offset_respects_valid_and_cap():
    gids = np.asarray([1] * 6, np.uint64)
    valid = np.asarray([True, False, True, True, True, True])
    ro = build_rank_offset(gids, max_rank=3, valid=valid)
    assert ro[1, 0] == 0                 # invalid row gets no rank
    np.testing.assert_array_equal(ro[[0, 2, 3], 0], [1, 2, 3])
    assert ro[5, 0] == 0                 # beyond max_rank positions drop


@pytest.mark.slow  # 4 s learn loop; the op-level tests stay tier-1; tier-1 keeps its 870 s window (PR 21)
def test_din_rank_learns_peer_signal():
    """Label = 1 iff the instance's OWN feature is weaker than its pv
    peer's — only visible through rank attention."""
    rng = np.random.default_rng(0)
    model = DINRank(slot_names=("s",), emb_dim=4, max_rank=2,
                    att_dim=8, hidden=(16,))
    params = model.init(jax.random.PRNGKey(0))
    b = 32  # 16 pvs of 2

    def make_batch():
        strength = rng.normal(size=(b,)).astype(np.float32)
        emb = np.zeros((b, 4), np.float32)
        emb[:, 0] = strength
        segs = np.arange(b, dtype=np.int32)
        gids = np.repeat(np.arange(b // 2), 2).astype(np.uint64)
        labels = np.zeros((b,), np.float32)
        for i in range(0, b, 2):
            labels[i] = float(strength[i] < strength[i + 1])
            labels[i + 1] = float(strength[i + 1] < strength[i])
        ro = build_rank_offset(gids, max_rank=2)
        return (jnp.asarray(emb), jnp.asarray(segs), jnp.asarray(ro),
                jnp.asarray(labels))

    @jax.jit
    def step(params, emb, segs, ro, labels):
        def loss_fn(params):
            logits = model.apply(
                params, {"s": emb}, {"s": jnp.zeros(b)}, {"s": segs},
                batch_size=b, rank_offset=ro)
            return jnp.mean(
                jnp.maximum(logits, 0) - logits * labels
                + jnp.log1p(jnp.exp(-jnp.abs(logits))))
        loss, g = jax.value_and_grad(loss_fn)(params)
        return jax.tree.map(lambda p, gg: p - 0.1 * gg, params, g), loss

    losses = []
    for _ in range(300):
        emb, segs, ro, labels = make_batch()
        params, loss = step(params, emb, segs, ro, labels)
        losses.append(float(loss))
    assert losses[-1] < 0.4 < losses[0]

    # peer-blind ablation (no rank_offset) cannot separate the labels
    emb, segs, ro, labels = make_batch()
    logits_blind = model.apply(params, {"s": emb}, {"s": jnp.zeros(b)},
                               {"s": segs}, batch_size=b)
    pred_blind = (np.asarray(logits_blind) > 0)
    acc_blind = (pred_blind == np.asarray(labels)).mean()
    logits_att = model.apply(params, {"s": emb}, {"s": jnp.zeros(b)},
                             {"s": segs}, batch_size=b, rank_offset=ro)
    acc_att = ((np.asarray(logits_att) > 0) == np.asarray(labels)).mean()
    assert acc_att > 0.85
    assert acc_att > acc_blind + 0.2
