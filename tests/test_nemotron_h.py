"""``models/nemotron_h.py`` against the plain reference
(``tests/references/nemotron_h_reference.py``) on seeded weights: each
layer kind and the whole stack, in loss and in every gradient; the expert
layer's share of a deployment; the counts the step returns."""

import dataclasses
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from paddlebox_tpu.models import nemotron_h as nh
from paddlebox_tpu.models import residual_plan
from paddlebox_tpu.models.nemotron_h import (NemotronHConfig,
                                             init_nemotron_h,
                                             make_nemotron_h_train_step,
                                             nemotron_h_loss_fn)
from paddlebox_tpu.ops.pallas_kernels import (flash_attention,
                                              flash_attention_reference)
from paddlebox_tpu.parallel import HybridTopology, build_mesh
from tests.references import nemotron_h_reference as reference

SMALL = NemotronHConfig(
    vocab_size=256, hidden_size=64, pattern="M*E", num_hidden_layers=3,
    mamba_num_heads=4, mamba_head_dim=32, ssm_state_size=16, n_groups=2,
    chunk_size=16, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, n_routed_experts=8, experts_held=(2, 2),
    num_experts_per_tok=2, moe_latent_size=32, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=64, kernels="interpret")


def _ref_config(cfg):
    return dict(
        hybrid_override_pattern=cfg.pattern, norm_eps=cfg.norm_eps,
        mamba_num_heads=cfg.mamba_num_heads,
        mamba_head_dim=cfg.mamba_head_dim,
        ssm_state_size=cfg.ssm_state_size, n_groups=cfg.n_groups,
        conv_kernel=cfg.conv_kernel,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        num_experts_per_tok=cfg.num_experts_per_tok,
        routed_scaling_factor=cfg.routed_scaling_factor,
        experts_held=list(cfg.experts_held))


def _seeded(cfg, seed=0, batch=2, seq=40):
    params, specs = init_nemotron_h(jax.random.PRNGKey(seed), cfg)
    # initial values are all of one size; spread them so that every term
    # carries weight (gains off 1, a bias that steers the routing)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    params = jax.tree.unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1),
                              (batch, seq + 1), 0, cfg.vocab_size)
    return params, specs, toks[:, :-1], toks[:, 1:]


def _compare(cfg, topo, devices, seq=40, tol=1e-4):
    params, specs, tokens, targets = _seeded(cfg, seq=seq)
    mesh = build_mesh(topo, devices=devices)
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            nemotron_h_loss_fn(cfg, mesh, specs), has_aux=True))(
            params, tokens, targets)
        (want, load), want_grads = jax.value_and_grad(
            lambda p: reference.loss_and_load(p, tokens, targets,
                                              _ref_config(cfg)),
            has_aux=True)(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    np.testing.assert_array_equal(np.asarray(aux["load"]),
                                  np.asarray(load))
    assert not np.asarray(aux["dropped"]).any()
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        if name.endswith("['bias']"):
            assert not np.asarray(got).any()     # steers, takes no gradient
            continue
        err = float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
        assert err < tol, (name, err)


@pytest.mark.parametrize("pattern", ["M", "*", "E", "MEM*E"])
def test_xla_path_is_the_reference_in_loss_and_gradients(pattern):
    cfg = dataclasses.replace(SMALL, pattern=pattern, kernels="xla")
    _compare(cfg, HybridTopology(dp=1), jax.devices()[:1])


@pytest.mark.parametrize("pattern", ["M", "*", "M*E"])
def test_kernel_path_is_the_reference_in_loss_and_gradients(pattern):
    """Through the Pallas interpreter, at the reference's precision: the
    scan kernel takes float32 operands where the ambient matmul precision
    is "highest", as XLA's own matmuls do."""
    cfg = dataclasses.replace(SMALL, pattern=pattern)
    _compare(cfg, HybridTopology(dp=1), jax.devices()[:1])


def test_kernel_path_at_default_precision_rounds_operands_only():
    """The production setting: the scan's matmul operands are bfloat16,
    its state is not. Loss within 2^-8; the per-head parameters'
    gradients, small sums of large terms, within a few 2^-8."""
    cfg = dataclasses.replace(SMALL, pattern="M")
    params, specs, tokens, targets = _seeded(cfg)
    mesh = build_mesh(HybridTopology(dp=1), devices=jax.devices()[:1])
    (loss, _), grads = jax.jit(jax.value_and_grad(
        nemotron_h_loss_fn(cfg, mesh, specs), has_aux=True))(
        params, tokens, targets)
    want, want_grads = jax.value_and_grad(
        lambda p: reference.loss(p, tokens, targets, _ref_config(cfg)))(
        params)
    assert float(loss) == pytest.approx(float(want), rel=2.0 ** -8)
    got, ref = grads["layers"][0], want_grads["layers"][0]
    for name, limit in (("w_in", 0.01), ("w_out", 0.01), ("a_log", 0.05),
                        ("dt_bias", 0.05)):
        err = float(jnp.linalg.norm(got[name] - ref[name])
                    / jnp.linalg.norm(ref[name]))
        assert err < limit, (name, err)
    # and the operands were rounded: float32 operands agree to 1e-6
    err = float(jnp.linalg.norm(got["a_log"] - ref["a_log"])
                / jnp.linalg.norm(ref["a_log"]))
    assert err > 1e-5


def test_batch_and_vocabulary_sharded_mesh_gives_the_same(devices8):
    cfg = dataclasses.replace(SMALL, kernels="xla")
    _compare(cfg, HybridTopology(dp=2, mp=2), devices8[:4])


@pytest.mark.parametrize("axis", ["pp", "sp", "ep"])
def test_meshes_the_stack_cannot_run_on_are_refused(axis, devices8):
    mesh = build_mesh(HybridTopology(**{axis: 2}), devices=devices8[:2])
    _, specs = init_nemotron_h(jax.random.PRNGKey(0), SMALL)
    with pytest.raises(ValueError, match=axis):
        nemotron_h_loss_fn(SMALL, mesh, specs)


def test_unknown_pattern_letter_and_kernel_mode_are_refused():
    with pytest.raises(ValueError, match="pattern"):
        init_nemotron_h(jax.random.PRNGKey(0),
                        dataclasses.replace(SMALL, pattern="MX"))
    with pytest.raises(ValueError, match="kernels"):
        nh._kernel_mode(dataclasses.replace(SMALL, kernels="cuda"))


def test_expert_layer_shares_add_up_to_the_uncut_reference():
    """Four chips hold two of the eight experts each. What the four
    compute, the shared expert (which every chip computes alike) counted
    once, is the uncut layer."""
    cfg = dataclasses.replace(SMALL, pattern="E", experts_held=(0, 8))
    params, _, _, _ = _seeded(cfg)
    layer = params["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(7), (2, 24, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        want, load = reference._experts(
            layer, h.reshape(-1, cfg.hidden_size), _ref_config(cfg),
            reference.STATED)
        shared = nh._dot(nh._relu2(nh._dot(h, layer["ws1"])), layer["ws2"])
        total, served = 0.0, []
        for first in range(0, 8, 2):
            part = dict(layer, w1=layer["w1"][first:first + 2],
                        w2=layer["w2"][first:first + 2])
            y, counts = nh._experts(part, h, dataclasses.replace(
                cfg, experts_held=(first, 2)))
            total = total + (y - shared)
            served.append(np.asarray(counts.load))
        total = total + shared
    np.testing.assert_allclose(np.asarray(total).reshape(want.shape),
                               np.asarray(want), rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.concatenate(served), np.asarray(load))
    assert int(np.concatenate(served).sum()) == 48 * cfg.num_experts_per_tok


def test_dispatch_block_is_one_even_share_in_whole_row_tiles():
    """The cell's layer: 8,192 rows, 22 of 512 experts a row, 8 held:
    2,816 assignments, 22 row tiles of 128; a share under a tile takes
    one tile."""
    cell = NemotronHConfig(experts_held=(0, 8))
    assert nh.dispatch_block_rows(cell, 8192) == 2816 == 22 * 128
    assert nh.dispatch_block_rows(SMALL, 80) == 128


@pytest.mark.parametrize("kernels", ["xla", "interpret"])
def test_expert_dispatch_site_records_what_ran(kernels):
    """``nemotron_moe_dispatch`` says which grouped products the layer
    traced: the Pallas kernels read ``sort_pallas_grouped`` on the chip,
    elsewhere the mode's name."""
    from paddlebox_tpu.core import flags
    cfg = dataclasses.replace(SMALL, pattern="E", kernels=kernels)
    params, _, _, _ = _seeded(cfg)
    h = jnp.ones((1, 16, cfg.hidden_size))
    flags.resolved_kernels(reset=True)
    jax.eval_shape(lambda p, h: nh._experts(p, h, cfg),
                   params["layers"][0], h)
    assert flags.resolved_kernels()["nemotron_moe_dispatch"] == [kernels]


def test_step_trains_and_returns_the_routers_counts():
    cfg = dataclasses.replace(SMALL, kernels="xla")
    params, specs, tokens, targets = _seeded(cfg, seq=32)
    mesh = build_mesh(HybridTopology(dp=1), devices=jax.devices()[:1])
    opt = optax.adafactor(1e-2)
    step = make_nemotron_h_train_step(cfg, mesh, specs, opt)
    opt_state = opt.init(params)
    losses = []
    for _ in range(8):
        params, opt_state, loss, aux = step(params, opt_state, tokens,
                                            targets)
        losses.append(float(loss))
        load = np.asarray(aux["load"])
        assert load.shape == (1, 2) and load.dtype == np.int32
        assert 0 < load.sum() <= tokens.size * cfg.num_experts_per_tok
        assert not np.asarray(aux["dropped"]).any()
    assert losses[-1] < losses[0] - 0.1


# -- what a layer keeps for its backward pass --------------------------------

def _value_and_grad(cfg, monkeypatch, device_bytes=None, checkpoint=None):
    """Loss, aux and gradients of the seeded stack with the plan made for
    ``device_bytes`` (None: the default) and ``jax.checkpoint`` replaced
    by ``checkpoint`` (None: as it is)."""
    if device_bytes is not None:
        monkeypatch.setattr(residual_plan, "_device_bytes",
                            lambda mesh: device_bytes)
    if checkpoint is not None:
        monkeypatch.setattr(jax, "checkpoint", checkpoint)
    params, specs, tokens, targets = _seeded(cfg)
    mesh = build_mesh(HybridTopology(dp=1), devices=jax.devices()[:1])
    vg = jax.value_and_grad(nemotron_h_loss_fn(cfg, mesh, specs),
                            has_aux=True)
    return vg, (params, tokens, targets)


# a layer's input at _seeded's sizes: no candidate fits beside it
ONE_LAYER_INPUT = 2 * 40 * SMALL.hidden_size * 4


@pytest.mark.parametrize("other", ["rematerialised_whole", "nothing"])
@pytest.mark.parametrize("kernels", ["xla", "interpret"])
def test_kept_values_leave_loss_and_every_gradient_as_they_were(
        kernels, other, monkeypatch):
    """The kept values are the ones the second forward would compute: the
    loss and every gradient under the save policy are those of every
    layer rematerialised whole, and of no rematerialisation at all, bit
    for bit (exact equality, not 1 ulp: the same operations in the same
    order on the same operands)."""
    cfg = dataclasses.replace(SMALL, pattern="M*EM", kernels=kernels)
    vg, args = _value_and_grad(cfg, monkeypatch)
    (loss, aux), grads = jax.jit(vg)(*args)
    if other == "nothing":
        vg, _ = _value_and_grad(cfg, monkeypatch,
                                checkpoint=lambda f, policy=None: f)
    else:
        vg, _ = _value_and_grad(cfg, monkeypatch,
                                device_bytes=ONE_LAYER_INPUT)
    (want, want_aux), want_grads = jax.jit(vg)(*args)
    assert float(loss) == float(want)
    np.testing.assert_array_equal(np.asarray(aux["load"]),
                                  np.asarray(want_aux["load"]))
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(ref),
            err_msg=jax.tree_util.keystr(path))


def _count(jaxpr, found):
    """Occurrences of each primitive, and of each jitted helper by its
    name, in ``jaxpr`` and every jaxpr inside it."""
    for eqn in jaxpr.eqns:
        found[eqn.primitive.name] += 1
        if eqn.primitive.name == "jit":
            found[eqn.params["name"]] += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _count(sub, found)
    return found


def test_the_policy_engages_and_a_full_device_falls_back(monkeypatch):
    """In the whole gradient program of ``M*EM`` on the kernel path: one
    flash forward and one top-k, sort and scores product where the values
    are kept, two of each where the device has no room for them; two scan
    forwards a Mamba layer either way (the in-projection is what is
    kept)."""
    import collections
    cfg = dataclasses.replace(SMALL, pattern="M*EM")
    counts = {}
    for name, device_bytes in (("kept", None), ("full", ONE_LAYER_INPUT)):
        vg, args = _value_and_grad(cfg, monkeypatch, device_bytes)
        counts[name] = _count(jax.make_jaxpr(vg)(*args).jaxpr,
                              collections.Counter())
    kept, full = counts["kept"], counts["full"]
    assert (kept["_flash_fwd_call"], full["_flash_fwd_call"]) == (1, 2)
    assert kept["_flash_dq_call"] == full["_flash_dq_call"] == 1
    assert (kept["top_k"], full["top_k"]) == (1, 2)
    assert (kept["sort"], full["sort"]) == (1, 2)
    assert kept["_ssd_fwd_call"] == full["_ssd_fwd_call"] == 4
    assert kept["_ssd_bwd_call"] == full["_ssd_bwd_call"] == 2
    # wq, wk, wv; the scores, the latent and the shared expert's first
    # product; two in-projections
    assert full["dot_general"] - kept["dot_general"] >= 3 + 3 + 2
    assert full["ragged_dot_general"] == kept["ragged_dot_general"]


@pytest.mark.parametrize("device_bytes,layers_kept", [
    (None, "M:2,*:1,E:1"), (ONE_LAYER_INPUT, "M:0,*:0,E:0")])
def test_build_step_span_says_what_the_layers_keep(device_bytes,
                                                   layers_kept,
                                                   monkeypatch):
    from paddlebox_tpu.core import trace
    cfg = dataclasses.replace(SMALL, pattern="M*EM", kernels="xla")
    if device_bytes is not None:
        monkeypatch.setattr(residual_plan, "_device_bytes",
                            lambda mesh: device_bytes)
    params, specs, tokens, targets = _seeded(cfg)
    mesh = build_mesh(HybridTopology(dp=1), devices=jax.devices()[:1])
    opt = optax.adafactor(1e-2)
    trace.GLOBAL.enable(ring_events=256)
    try:
        trace.GLOBAL.clear()
        make_nemotron_h_train_step(cfg, mesh, specs, opt).lower(
            params, opt.init(params), tokens, targets)
        spans = [e for e in trace.GLOBAL.snapshot()
                 if e["name"] == "nemotron_h/build_step"]
    finally:
        trace.GLOBAL.disable()
        trace.GLOBAL.clear()
    plan = residual_plan.plan_residuals(
        cfg.pattern, nh._keepable(cfg, tokens.shape[1]), tokens.size,
        cfg.hidden_size,
        sum(leaf.nbytes for leaf in jax.tree.leaves(params)),
        device_bytes or residual_plan.DEFAULT_DEVICE_BYTES)
    assert [e["args"] for e in spans] == [
        dict(layers=4, **plan.attributes(cfg.pattern))]
    said = spans[0]["args"]
    assert said["layers_kept"] == layers_kept
    assert (said["planned_residual_bytes"] == 0) == (device_bytes is not None)
    assert ("moe_logits" in said["names_kept"]) == (device_bytes is None)


def test_candidates_rank_by_operations_a_byte():
    """The router's six-pass product first; the flash forward above the
    plain products at 8,192 positions (S / 2 operations a byte against
    hidden / 2) and below them at 1,024; the plain products tie and stay
    in the order attention, experts, Mamba."""
    cfg = NemotronHConfig()
    first = [c.names[0] for c in nh._keepable(cfg, 8192)]
    assert first == ["moe_logits", "flash_out", "flash_q", "moe_latent",
                     "moe_shared_hidden", "mamba_in_proj"]
    first = [c.names[0] for c in nh._keepable(cfg, 1024)]
    assert first == ["moe_logits", "flash_q", "moe_latent",
                     "moe_shared_hidden", "mamba_in_proj", "flash_out"]


def test_gpt_and_nemotron_steps_share_one_wrapper():
    from paddlebox_tpu.models import gpt, train_step
    assert gpt.make_train_step is train_step.make_train_step
    assert nh.make_train_step is train_step.make_train_step


@pytest.mark.parametrize("hq,hkv,s", [(4, 1, 40), (4, 2, 64), (6, 3, 33)])
def test_grouped_head_flash_is_the_reference(hq, hkv, s):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (2, s, hq, 16))
    k = jax.random.normal(ks[1], (2, s, hkv, 16))
    v = jax.random.normal(ks[2], (2, s, hkv, 16))
    weight = jax.random.normal(ks[3], q.shape)

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) * weight)
    kernel = loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=16, block_k=16, interpret=True))
    plain = loss(lambda q, k, v: flash_attention_reference(
        q, k, v, causal=True))
    with jax.default_matmul_precision("highest"):
        assert float(kernel(q, k, v)) == pytest.approx(
            float(plain(q, k, v)), rel=1e-5, abs=1e-4)
        got = jax.grad(kernel, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(plain, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        assert float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)) < 1e-5


def test_heads_that_do_not_group_are_refused():
    q = jnp.zeros((1, 8, 4, 16))
    kv = jnp.zeros((1, 8, 3, 16))
    with pytest.raises(ValueError, match="group"):
        flash_attention(q, kv, kv, interpret=True)


def test_the_two_reference_copies_agree():
    """The benchmark carries its own copy of the reference (it imports
    nothing from tests/): same text below the docstring, same numbers."""
    bench = importlib.import_module(
        "benchmarks.reference.nemotron3_super_120b")
    with open(reference.__file__) as f, open(bench.__file__) as g:
        assert f.read() == g.read()
    cfg = dataclasses.replace(SMALL, kernels="xla")
    params, _, tokens, targets = _seeded(cfg, seq=24)
    a = reference.loss_and_load(params, tokens, targets, _ref_config(cfg))
    b = bench.loss_and_load(params, tokens, targets, _ref_config(cfg))
    assert float(a[0]) == float(b[0])
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
    assert os.path.basename(bench.__file__) == "nemotron3_super_120b.py"
