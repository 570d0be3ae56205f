"""``models/block_diffusion.py`` against the plain reference
(``benchmarks/reference/sdar_30b_a3b.py``) on seeded weights; the
block-diffusion mask rule of the flash kernels against a brute-force
boolean array, its tile counts, and the three kernels under it in
interpret mode; no leak from a block's clean copy into its noisy rows."""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.reference import sdar_30b_a3b as reference
from paddlebox_tpu.core import trace
from paddlebox_tpu.models import block_diffusion as bd
from paddlebox_tpu.models.block_diffusion import (
    BlockDiffusionConfig, block_diffusion_loss_fn, init_block_diffusion,
    make_block_diffusion_train_step)
from paddlebox_tpu.parallel import HybridTopology, build_mesh

# the package re-exports the function under the module's name
fa = importlib.import_module(
    "paddlebox_tpu.ops.pallas_kernels.flash_attention")
Mask = fa.BlockDiffusionMask

SMALL = BlockDiffusionConfig(
    vocab_size=256, hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, num_hidden_layers=4, model_layers=4,
    moe_intermediate_size=32, num_experts_per_tok=2, router_experts=8,
    experts_held=(2, 4), block_length=4, kernels="xla")


def _ref_config(cfg):
    return dict(
        rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        num_experts_per_tok=cfg.num_experts_per_tok,
        experts_held=list(cfg.experts_held), block_length=cfg.block_length,
        mask_token_id=cfg.mask_token_id)


def _noise(key, batch, seq, block, t_min=1e-3):
    k1, k2 = jax.random.split(key)
    levels = jax.random.uniform(k1, (batch, seq // block), jnp.float32,
                                t_min, 1.0)
    masked = jax.random.uniform(k2, (batch, seq)) < jnp.repeat(
        levels, block, axis=1)
    return levels, masked


def _seeded(cfg, seed=0, batch=2, seq=40):
    params, specs = init_block_diffusion(jax.random.PRNGKey(seed), cfg)
    # initial values are all of one size: spread them so that every term
    # carries weight (the gains too)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    params = jax.tree.unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, seq),
                                0, cfg.vocab_size - 1)
    levels, masked = _noise(jax.random.PRNGKey(seed + 2), batch, seq,
                            cfg.block_length)
    return params, specs, tokens, levels, masked


def _one_chip():
    return build_mesh(HybridTopology(dp=1), devices=jax.devices()[:1])


def _rel(got, want):
    return float(jnp.linalg.norm((got - want).ravel())
                 / jnp.maximum(jnp.linalg.norm(want.ravel()), 1e-30))


# -- the mask rule -----------------------------------------------------------

def _brute_force(seq, block):
    """The rule as the issue writes it, entry by entry."""
    want = np.zeros((2 * seq, 2 * seq), bool)
    for q in range(2 * seq):
        for k in range(2 * seq):
            nq, nk = q >= seq, k >= seq
            bq, bk = (q % seq) // block, (k % seq) // block
            want[q, k] = (not nk and bk < bq) or (nk == nq and bk == bq)
    return want


@pytest.mark.parametrize("seq,block,tile", [
    (16, 4, 8),         # two blocks a tile
    (16, 8, 8),         # a block a tile
    (48, 16, 8),        # a block larger than a tile
    (48, 6, 8),         # a block that does not divide a tile
    (24, 3, 8),         # no power of two: division, not a shift
    (32, 4, 16),
])
def test_mask_rule_is_the_brute_force_array(seq, block, tile):
    rule = Mask(seq, block)
    want = _brute_force(seq, block)
    np.testing.assert_array_equal(np.asarray(rule.everywhere()), want)
    # every row reads something, no clean row reads a noisy one
    assert want.any(axis=1).all() and not want[:seq, seq:].any()
    n = 2 * seq // tile
    traced = jax.jit(lambda qi, ki: rule.tile_kind(qi, ki, tile, tile))
    for qi in range(n):
        for ki in range(n):
            sub = want[qi * tile:(qi + 1) * tile, ki * tile:(ki + 1) * tile]
            live, interior = rule.tile_kind(qi, ki, tile, tile)
            assert (bool(live), bool(interior)) == (sub.any(), sub.all())
            live, interior = traced(qi, ki)     # as the kernels ask
            assert (bool(live), bool(interior)) == (sub.any(), sub.all())
            for q_axis in (0, 1):
                got = np.asarray(rule.allowed(qi * tile, ki * tile,
                                              (tile, tile), q_axis))
                np.testing.assert_array_equal(
                    got, sub if q_axis == 0 else sub.T)


def test_tile_counts_at_the_cells_shape():
    """4,096 positions in blocks of 4, 512 x 512 tiles: 80 of 256 tiles a
    head are live, the 8 diagonal tiles of each live quadrant edges; the
    causal counts are what they were."""
    assert fa.tile_counts(8192, 8192, 512, 512, False,
                          mask=Mask(4096, 4)) == (256, 80, 24)
    assert fa.tile_counts(8192, 8192, 512, 512, True) == (256, 136, 16)
    assert fa.tile_counts(4096, 4096, 512, 512, True) == (64, 36, 8)
    # a block as long as a tile: the noisy copy's own tiles are interior
    assert fa.tile_counts(8192, 8192, 512, 512, False,
                          mask=Mask(4096, 512)) == (256, 72, 0)
    # the schedule lists the live tiles and no other
    for keys_outer, group in ((False, 1), (True, 8)):
        tab = fa._schedule(16, 16, group, (0, 0), keys_outer=keys_outer,
                           sk=8192, causal=False, block_q=512, block_k=512,
                           mask=Mask(4096, 4))
        assert tab.shape == (3, 80 * group)


def test_mask_rule_refuses_what_it_cannot_tile():
    with pytest.raises(ValueError, match="blocks must divide"):
        Mask(10, 4)
    q = jnp.zeros((1, 40, 2, 16))
    with pytest.raises(ValueError, match="do not divide"):
        fa.flash_attention(q, q, q, mask=Mask(20, 4), interpret=True)
    with pytest.raises(ValueError, match="stands alone"):
        fa.flash_attention(q, q, q, mask=Mask(20, 4), causal=True,
                           use_pallas=False)
    with pytest.raises(ValueError, match="is over 32 rows"):
        fa.flash_attention(q, q, q, mask=Mask(16, 4), use_pallas=False)


@pytest.mark.parametrize("seq,block,block_q,block_k", [
    (32, 4, 8, 16), (32, 16, 16, 8), (24, 6, 8, 8)])
def test_kernels_under_the_rule_match_the_reference(seq, block, block_q,
                                                    block_k):
    """Forward, dq and dk/dv in interpret mode, 8 query heads over 2
    key/value heads, unequal tiles."""
    ks = jax.random.split(jax.random.PRNGKey(seq + block), 3)
    q = jax.random.normal(ks[0], (2, 2 * seq, 8, 16), jnp.float32)
    k, v = (jax.random.normal(kk, (2, 2 * seq, 2, 16), jnp.float32)
            for kk in ks[1:])
    rule = Mask(seq, block)

    def loss(attn):
        return lambda q, k, v: jnp.sum(jnp.sin(attn(q, k, v)))
    kernel = loss(lambda q, k, v: fa.flash_attention(
        q, k, v, mask=rule, block_q=block_q, block_k=block_k,
        interpret=True))
    plain = loss(lambda q, k, v: fa.flash_attention_reference(
        q, k, v, mask=rule))
    got, got_g = jax.value_and_grad(kernel, argnums=(0, 1, 2))(q, k, v)
    want, want_g = jax.value_and_grad(plain, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w in zip(got_g, want_g):
        np.testing.assert_allclose(g, w, atol=3e-5)


# -- against the plain reference ---------------------------------------------

@pytest.mark.parametrize("kernels", ["xla", "interpret"])
def test_loss_counts_and_every_gradient_are_the_references(kernels):
    cfg = dataclasses.replace(SMALL, kernels=kernels)
    params, specs, tokens, levels, masked = _seeded(cfg)
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            block_diffusion_loss_fn(cfg, _one_chip(), specs),
            has_aux=True))(params, tokens, levels, masked)
    (want, want_aux), want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss_and_aux(
            reference.unstack(p), tokens, levels, masked, _ref_config(cfg)),
        has_aux=True))(params)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    np.testing.assert_array_equal(aux["load"], want_aux["load"])
    assert int(aux["masked"]) == int(want_aux["masked"]) == int(masked.sum())
    assert float(aux["weight"]) == pytest.approx(float(want_aux["weight"]),
                                                 rel=1e-6)
    assert np.asarray(aux["dropped"]).tolist() == [0, 0, 0, 0]
    assert int(np.asarray(aux["load"]).sum()) > 0
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    want_flat = jax.tree.leaves(want_grads)
    assert len(flat) == len(want_flat) == 3 + 12 * cfg.pieces
    for (path, got), want_leaf in zip(flat, want_flat):
        assert _rel(got, want_leaf) < 2e-5, jax.tree_util.keystr(path)


def test_two_sequences_are_two_losses_of_one():
    params, specs, tokens, levels, masked = _seeded(SMALL)
    loss = jax.jit(block_diffusion_loss_fn(SMALL, _one_chip(), specs))
    both, aux = loss(params, tokens, levels, masked)
    ones = [loss(params, tokens[i:i + 1], levels[i:i + 1],
                 masked[i:i + 1]) for i in range(2)]
    assert float(both) == pytest.approx(
        np.mean([float(one[0]) for one in ones]), rel=1e-5)
    np.testing.assert_array_equal(
        aux["load"], sum(np.asarray(one[1]["load"]) for one in ones))


def _hidden_states(cfg, params, tokens, masked):
    """The stack's state after every layer, ``[2 L, d]``, for one
    sequence: the model's own layer function, outside the loss."""
    seq = tokens.shape[0]
    rule = Mask(seq, cfg.block_length)
    rows = jnp.concatenate([tokens, jnp.where(masked, cfg.mask_token_id,
                                              tokens)])
    h = params["embed"][rows][None]
    positions = jnp.tile(jnp.arange(seq), 2)
    for piece in params["layers"]:
        for i in range(piece["wq"].shape[0]):
            h, _ = bd._layer(jax.tree.map(lambda a: a[i], piece), h, cfg,
                             rule, positions)
    return h[0]


@pytest.mark.parametrize("kernels", ["xla", "interpret"])
def test_no_leak_between_the_copies(kernels):
    """A noisy row's output does not change when its own block's clean
    tokens change (it never reads them, nor anything that read them), and
    no clean row's output changes when any noisy row changes."""
    cfg = dataclasses.replace(SMALL, kernels=kernels, experts_held=(0, 8))
    params, _, tokens, _, masked = _seeded(cfg, seq=32)
    tokens, masked = tokens[0], masked[0]
    block, blk = cfg.block_length, 5            # positions 20 .. 23
    inside = slice(blk * block, (blk + 1) * block)
    # every position of the block masked in the noisy copy, so that the
    # noisy rows' inputs do not move with the clean tokens
    masked = masked.at[inside].set(True)
    base = _hidden_states(cfg, params, tokens, masked)
    changed = _hidden_states(
        cfg, params, tokens.at[inside].set((tokens[inside] + 7) % 200),
        masked)
    seq = tokens.shape[0]
    noisy_rows = slice(seq + blk * block, seq + (blk + 1) * block)
    np.testing.assert_array_equal(np.asarray(base[noisy_rows]),
                                  np.asarray(changed[noisy_rows]))
    # ... while the clean rows of the block, and the noisy rows of later
    # blocks (which read the block's clean copy), do move
    assert float(jnp.abs(base[inside] - changed[inside]).max()) > 1e-3
    later = slice(seq + (blk + 1) * block, 2 * seq)
    assert float(jnp.abs(base[later] - changed[later]).max()) > 1e-4
    # the noisy copy changes: no clean row moves
    flipped = _hidden_states(cfg, params, tokens, ~masked)
    np.testing.assert_array_equal(np.asarray(base[:seq]),
                                  np.asarray(flipped[:seq]))
    assert float(jnp.abs(base[seq:] - flipped[seq:]).max()) > 1e-3


# -- the step, the plan, the spans -------------------------------------------

def test_train_step_lowers_the_loss_and_counts_what_it_served():
    params, specs, tokens, levels, masked = _seeded(SMALL)
    opt = optax.adafactor(1e-2)
    step = make_block_diffusion_train_step(SMALL, _one_chip(), specs, opt)
    state = opt.init(params)
    trace.GLOBAL.enable(ring_events=1 << 10)
    try:
        losses = []
        for _ in range(4):
            params, state, loss, aux = step(params, state, tokens, levels,
                                            masked)
            losses.append(float(loss))
        spans = {e["name"]: e
                 for e in trace.GLOBAL.trace_object()["traceEvents"]
                 if e.get("ph") == "X"}
    finally:
        trace.GLOBAL.disable()
        trace.GLOBAL.clear()
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
    assert aux["load"].shape == (4, 4) and aux["dropped"].shape == (4,)
    assert int(aux["masked"]) == int(masked.sum())
    built = spans["block_diffusion/build_step"]["args"]
    assert built["layers"] == 4 and built["block"] == 4
    assert "layers_kept" in built and "tiles_live" not in built   # xla path
    # two sequences a device: 160 rows, 2 of 8 experts a row, 4 held, in
    # whole row tiles of the grouped products
    assert built["dispatch_block_rows"] == 256
    assert "dispatch_row_tile" not in built


def test_plan_counts_both_copies_rows_and_keeps_by_piece(monkeypatch):
    cfg = BlockDiffusionConfig(
        vocab_size=18992, num_hidden_layers=12, experts_held=(0, 16))
    assert cfg.pieces == 4 and cfg.mask_token_id == 18991
    mesh = _one_chip()
    params = jax.eval_shape(lambda k: init_block_diffusion(k, cfg)[0],
                            jax.random.PRNGKey(0))
    count = sum(x.size for x in jax.tree.leaves(params))
    # 12 x (19.14M outside the experts + 16 x 4.72M) + embedding and head
    assert count == 12 * (19_140_864 + 16 * 4_718_592) + 2048 + (
        2 * 18992 * 2048) == 1_213_453_312
    tokens = jax.ShapeDtypeStruct((1, 4096), jnp.int32)
    from paddlebox_tpu.models import residual_plan
    monkeypatch.setattr(residual_plan, "_device_bytes",
                        lambda mesh: residual_plan.DEFAULT_DEVICE_BYTES)
    plan = bd._plan_for(cfg, mesh, params, tokens)
    assert len(plan.names) == 4
    # the router's few values a row are kept in every piece, the flash
    # output in the last pieces first (their backward passes come first),
    # q / k / v after it
    kept = [set(names) for names in plan.names]
    assert all({"moe_logits", "moe_idx", "moe_order"} <= k for k in kept)
    assert "flash_out" in kept[-1] and "flash_q" not in kept[0]
    assert [("flash_out" in k) for k in kept] == sorted(
        ("flash_out" in k) for k in kept)
    experts = 12 * 3 * 16 * 2048 * 768 * 4
    assert 2.0 * plan.bytes <= int(
        0.83 * residual_plan.DEFAULT_DEVICE_BYTES) - (
        2 * 4 * count + 12 * 8192 * 2048 * 4 + 2 * 4096 * 18992 * 4
        + experts // 2)
    # a small device keeps nothing
    monkeypatch.setattr(residual_plan, "_device_bytes",
                        lambda mesh: 11 * 2 ** 30)
    assert bd._plan_for(cfg, mesh, params, tokens).names == ((),) * 4


def test_refuses_meshes_and_configs_it_cannot_run():
    with pytest.raises(ValueError, match="norm_topk_prob"):
        init_block_diffusion(jax.random.PRNGKey(0), dataclasses.replace(
            SMALL, norm_topk_prob=False))
    mesh = build_mesh(HybridTopology(dp=1, sp=2),
                      devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="sp=2"):
        block_diffusion_loss_fn(SMALL, mesh, {})
    params, specs, tokens, levels, masked = _seeded(SMALL, seq=40)
    with pytest.raises(ValueError, match="whole blocks"):
        block_diffusion_loss_fn(SMALL, _one_chip(), specs)(
            params, tokens[:, :38], levels, masked[:, :38])
