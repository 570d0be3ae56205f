"""``models/looped.py`` against the plain reference
(``benchmarks/reference/ouro_2_6b.py``) on seeded weights; the loop
against a plain deep stack with the weights copied; the rotary embedding
against its formula; the exit distribution; the memory plan (the shared
``residual_plan`` rule, the hybrid stack's plan pinned)."""

import collections
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.reference import ouro_2_6b as reference
from paddlebox_tpu.models import looped, residual_plan
from paddlebox_tpu.models.looped import (LoopedConfig, exit_distribution,
                                         init_looped, looped_loss_fn,
                                         make_looped_train_step,
                                         rotary_embedding)
from paddlebox_tpu.parallel import HybridTopology, build_mesh
from tests.test_nemotron_h import _count

SMALL = LoopedConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    num_hidden_layers=4, total_ut_steps=4, kernels="xla")


def _ref_config(cfg):
    return dict(
        total_ut_steps=cfg.total_ut_steps, rms_norm_eps=cfg.rms_norm_eps,
        exit_entropy_weight=cfg.exit_entropy_weight,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        rope_theta=cfg.rope_theta)


def _seeded(cfg, seed=0, batch=2, seq=40):
    params, specs = init_looped(jax.random.PRNGKey(seed), cfg)
    # initial values are all of one size and the gate is shut at zero:
    # spread them so that every term carries weight
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    params = jax.tree.unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1),
                              (batch, seq + 1), 0, cfg.vocab_size)
    return params, specs, toks[:, :-1], toks[:, 1:]


def _one_chip():
    return build_mesh(HybridTopology(dp=1), devices=jax.devices()[:1])


def _rel(got, want):
    return float(jnp.linalg.norm((got - want).ravel())
                 / jnp.maximum(jnp.linalg.norm(want.ravel()), 1e-30))


# -- against the plain reference ---------------------------------------------

@pytest.mark.parametrize("kernels", ["xla", "interpret"])
def test_loss_passes_exit_and_every_gradient_are_the_references(kernels):
    cfg = dataclasses.replace(SMALL, kernels=kernels)
    params, specs, tokens, targets = _seeded(cfg)
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            looped_loss_fn(cfg, _one_chip(), specs), has_aux=True))(
            params, tokens, targets)
    (want, want_aux), want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss_and_aux(p, tokens, targets,
                                         _ref_config(cfg)),
        has_aux=True))(params)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    for name in ("pass_losses", "exit_p", "exit_entropy"):
        np.testing.assert_allclose(aux[name], want_aux[name], rtol=5e-6)
    assert int(aux["applications"]) == 16
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
        assert _rel(got, ref) < 2e-5, jax.tree_util.keystr(path)


def test_reference_at_three_passes_is_a_stack_run_three_times():
    """The control's switch: the third gate forced open gives the loss of
    ``total_ut_steps`` 3."""
    params, _, tokens, targets = _seeded(SMALL)
    config = _ref_config(SMALL)
    forced = reference.loss_and_aux(
        params, tokens, targets, config,
        dict(reference.STATED, three_passes=True))
    three = reference.loss_and_aux(
        params, tokens, targets, dict(config, total_ut_steps=3))
    assert float(forced[0]) == pytest.approx(float(three[0]), rel=1e-6)
    np.testing.assert_allclose(forced[1]["exit_p"][:3], three[1]["exit_p"],
                               rtol=1e-6)
    assert float(forced[1]["exit_p"][3]) == 0.0


# -- the loop against a plain deep stack -------------------------------------

def test_the_loop_is_a_deep_stack_with_the_weights_copied():
    """T passes over L shared layers give the loss of an unrolled T * L
    layer stack whose layers are copies, and a shared weight's gradient is
    the sum of its copies'."""
    cfg = SMALL
    t, n = cfg.total_ut_steps, cfg.num_hidden_layers
    params, specs, tokens, targets = _seeded(cfg)
    rows = [jax.tree.map(lambda a: a[i], piece)
            for piece in params["layers"]
            for i in range(n // cfg.pieces)]
    shared = {"layers": rows, "norm_f": params["norm_f"],
              "head": params["head"]}

    def unrolled(copies):
        """``copies[p]``: pass p's own copy of every shared weight."""
        h = params["embed"][tokens]
        ces, gates = [], []
        for own in copies:
            for lp in own["layers"]:
                h = looped._layer(lp, h, cfg)
            h = looped._rms(h, own["norm_f"], cfg.rms_norm_eps)
            logp = jax.nn.log_softmax(jnp.dot(h, own["head"]), axis=-1)
            ces.append(-jnp.take_along_axis(logp, targets[..., None],
                                            axis=-1)[..., 0])
            gates.append(jnp.sum(h * params["gate_w"], axis=-1)
                         + params["gate_b"])
        p = exit_distribution(jnp.stack(gates[:-1]))
        entropy = jnp.sum(jax.scipy.special.entr(p), axis=0)
        return jnp.mean(jnp.sum(p * jnp.stack(ces), axis=0)
                        - cfg.exit_entropy_weight * entropy)

    with jax.default_matmul_precision("highest"):
        want, by_copy = jax.jit(jax.value_and_grad(unrolled))(
            [shared] * t)
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            looped_loss_fn(cfg, _one_chip(), specs), has_aux=True))(
            params, tokens, targets)
    assert int(aux["applications"]) == t * n
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    summed = jax.tree.map(lambda *g: sum(g), *by_copy)
    per = n // cfg.pieces
    for layer in range(n):
        for name, want_grad in summed["layers"][layer].items():
            got = grads["layers"][layer // per][name][layer % per]
            assert _rel(got, want_grad) < 2e-5, (layer, name)
            # and no one copy's term is the whole of it
            assert _rel(by_copy[-1]["layers"][layer][name],
                        want_grad) > 1e-2, (layer, name)
    for name in ("norm_f", "head"):
        assert _rel(grads[name], summed[name]) < 2e-5, name


def test_program_size_does_not_grow_with_depth():
    """The lowered gradient program of 8 layers and of 24 has the same
    operations: the layers are scanned, not listed."""
    import re
    def census(layers):
        cfg = dataclasses.replace(SMALL, num_hidden_layers=layers)
        params, specs, tokens, targets = _seeded(cfg, seq=16)
        text = jax.jit(jax.value_and_grad(
            looped_loss_fn(cfg, _one_chip(), specs), has_aux=True)).lower(
            params, tokens, targets).as_text()
        return collections.Counter(re.findall(r"stablehlo\.\w+", text))
    shallow, deep = census(8), census(24)
    assert shallow == deep
    assert shallow["stablehlo.while"] >= 2 * SMALL.total_ut_steps


# -- rotary embedding --------------------------------------------------------

def test_rotary_is_the_complex_rotation():
    s, h, d = 37, 3, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (2, s, h, d))
    theta = 1e6
    got = np.asarray(rotary_embedding(x, jnp.arange(s), theta), np.float64)
    x = np.asarray(x, np.float64)
    pairs = x[..., :d // 2] + 1j * x[..., d // 2:]
    angle = (np.arange(s)[:, None]
             * theta ** (-2.0 * np.arange(d // 2) / d)[None, :])
    turned = pairs * np.exp(1j * angle)[None, :, None, :]
    want = np.concatenate([turned.real, turned.imag], axis=-1)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # position 0 is left as it is
    np.testing.assert_array_equal(got[:, 0], x[:, 0].astype(np.float32))


def test_rotary_scores_depend_on_the_distance_only():
    d = 32
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, d))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, d))

    def score(m, n):
        qm = rotary_embedding(q, jnp.asarray([m]), 1e4)
        kn = rotary_embedding(k, jnp.asarray([n]), 1e4)
        return float(jnp.sum(qm * kn))
    assert score(9, 4) == pytest.approx(score(105, 100), abs=1e-4)
    assert score(9, 4) == pytest.approx(score(5, 0), abs=1e-4)
    assert abs(score(9, 4) - score(9, 5)) > 1e-3


# -- the exit distribution and the loss --------------------------------------

def test_exit_distribution_sums_to_one():
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (3, 5, 7))
    p = exit_distribution(logits)
    assert p.shape == (4, 5, 7) and bool(jnp.all(p >= 0))
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, atol=1e-6)
    # shut gates leave everything to the last pass, open ones to the first
    np.testing.assert_allclose(
        exit_distribution(jnp.full((3, 2), -40.0))[-1], 1.0)
    np.testing.assert_allclose(
        exit_distribution(jnp.full((3, 2), 40.0))[0], 1.0)
    # the initial gate: 1/2, 1/4, 1/8 and what is left
    np.testing.assert_allclose(exit_distribution(jnp.zeros((3, 1)))[:, 0],
                               [0.5, 0.25, 0.125, 0.125])


def test_a_shut_gate_and_no_entropy_term_leave_the_last_passs_loss():
    cfg = dataclasses.replace(SMALL, exit_entropy_weight=0.0)
    params, specs, tokens, targets = _seeded(cfg)
    params = dict(params, gate_w=jnp.zeros_like(params["gate_w"]),
                  gate_b=jnp.asarray(-40.0))
    loss, aux = jax.jit(looped_loss_fn(cfg, _one_chip(), specs))(
        params, tokens, targets)
    assert float(loss) == pytest.approx(float(aux["pass_losses"][-1]),
                                        rel=1e-6)
    np.testing.assert_allclose(aux["exit_p"], [0, 0, 0, 1], atol=1e-12)
    assert float(aux["exit_entropy"]) == pytest.approx(0.0, abs=1e-12)


def test_initial_gate_expects_pass_1_875():
    params, specs = init_looped(jax.random.PRNGKey(0), SMALL)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 17), 0, 256)
    _, aux = jax.jit(looped_loss_fn(SMALL, _one_chip(), specs))(
        params, toks[:, :-1], toks[:, 1:])
    assert float(jnp.sum(jnp.arange(1, 5) * aux["exit_p"])) == \
        pytest.approx(1.875)


# -- what an application keeps for its backward pass -------------------------

def _value_and_grad(cfg, monkeypatch, device_bytes=None):
    if device_bytes is not None:
        monkeypatch.setattr(residual_plan, "_device_bytes",
                            lambda mesh: device_bytes)
    params, specs, tokens, targets = _seeded(cfg)
    mesh = _one_chip()
    plan = looped._plan_for(cfg, mesh, params, tokens)
    vg = jax.jit(jax.value_and_grad(looped_loss_fn(cfg, mesh, specs),
                                    has_aux=True))
    return plan, vg(params, tokens, targets)


def _device_with_room(cfg, room):
    """Device bytes that leave the plan ``room`` bytes for kept values at
    ``_seeded``'s sizes."""
    params, _, tokens, _ = _seeded(cfg)
    whole = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    piece = sum(leaf.nbytes for leaf in jax.tree.leaves(params["layers"][0]))
    planned = (2 * whole + cfg.total_ut_steps * cfg.num_hidden_layers
               * tokens.size * cfg.hidden_size * 4 + piece
               + 2 * tokens.size * cfg.vocab_size * 4)
    return int((planned + room * looped.KEPT_COST)
               / residual_plan.PLANNED_MEMORY_SHARE) + 1


@pytest.mark.parametrize("kernels", ["xla", "interpret"])
def test_every_plan_gives_the_same_loss_and_gradients(kernels, monkeypatch):
    """Keep nothing, keep everything and a plan that keeps some names in
    some scans: the kept values are the ones the second forward would
    compute, so the loss and every gradient are equal bit for bit."""
    cfg = dataclasses.replace(SMALL, kernels=kernels)
    first = looped._keepable(cfg, 40)[0]
    unit = 80 * first.bytes * cfg.num_hidden_layers // cfg.pieces
    results = {}
    for name, room in (("nothing", 0), ("everything", 1 << 30),
                       ("mixed", 5 * unit + 3 * unit // 4)):
        plan, results[name] = _value_and_grad(
            cfg, monkeypatch, _device_with_room(cfg, room))
        if name == "nothing":
            assert not any(plan.names) and plan.bytes == 0
        elif name == "everything":
            assert all(len(names) == 7 for names in plan.names)
        else:
            # the dearest candidate in the last five scans, a cheaper one
            # in some of them, nothing in the first scans
            assert [bool(n) for n in plan.names] == [False] * 11 + [True] * 5
            assert len(set(plan.names)) == 3
    (want, want_aux), want_grads = results["nothing"]
    for name in ("everything", "mixed"):
        (loss, aux), grads = results[name]
        assert float(loss) == float(want)
        np.testing.assert_array_equal(aux["pass_losses"],
                                      want_aux["pass_losses"])
        flat, _ = jax.tree_util.tree_flatten_with_path(grads)
        for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(ref),
                err_msg=name + jax.tree_util.keystr(path))


def test_the_policy_engages_and_a_full_device_falls_back(monkeypatch):
    """On the kernel path the gradient program holds one flash forward a
    scan body more where the device has no room for its output than where
    it is kept; the passes' gradients are summed behind a barrier, a scan's and
    a head's a pass."""
    cfg = dataclasses.replace(SMALL, kernels="interpret")
    params, specs, tokens, targets = _seeded(cfg)
    counts = {}
    for name, room in (("kept", 1 << 30), ("full", 0)):
        monkeypatch.setattr(residual_plan, "_device_bytes",
                            lambda mesh, room=room: _device_with_room(
                                cfg, room))
        vg = jax.value_and_grad(looped_loss_fn(cfg, _one_chip(), specs),
                                has_aux=True)
        counts[name] = _count(
            jax.make_jaxpr(vg)(params, tokens, targets).jaxpr,
            collections.Counter())
    scans = cfg.total_ut_steps * cfg.pieces
    # (a copy of the forward that nothing reads stays in the backward
    # scans' jaxpr either way; the compiler drops it)
    assert (counts["full"]["_flash_fwd_call"]
            - counts["kept"]["_flash_fwd_call"]) == scans
    assert counts["kept"]["_flash_dq_call"] == scans
    assert counts["full"]["_flash_dq_call"] == scans
    assert counts["full"]["dot_general"] > counts["kept"]["dot_general"]
    for found in counts.values():
        assert found["optimization_barrier"] == scans + cfg.total_ut_steps


@pytest.mark.parametrize("room_units", [0, 3, 7, 16, 40])
def test_planner_stays_in_its_room_and_counts_every_application(room_units):
    """``T * L`` inputs are planned before any kept value, whatever the
    cut into pieces, and the kept bytes (charged ``KEPT_COST``) never pass
    what is left."""
    cfg = SMALL
    tokens, seq = 80, 40
    cands = looped._keepable(cfg, seq)
    unit = tokens * cands[0].bytes
    inputs = (cfg.total_ut_steps * cfg.num_hidden_layers * tokens
              * cfg.hidden_size * 4)
    param_bytes, reserved = 1_000_000, 50_000
    device = int((2 * param_bytes + inputs + reserved + room_units * unit)
                 / residual_plan.PLANNED_MEMORY_SHARE) + 1
    for pieces in (1, 2, 4):
        per = cfg.num_hidden_layers // pieces
        plan = residual_plan.plan_residuals(
            "L" * (cfg.total_ut_steps * pieces),
            [c._replace(bytes=c.bytes * per, ops=c.ops * per)
             for c in cands],
            tokens, cfg.hidden_size * per, param_bytes, device, reserved,
            looped.KEPT_COST)
        room = (int(residual_plan.PLANNED_MEMORY_SHARE * device)
                - 2 * param_bytes - inputs - reserved)
        assert 0 <= room - room_units * unit < 2
        assert looped.KEPT_COST * plan.bytes <= room
        # last application first: what is kept is a suffix
        kept = [bool(n) for n in plan.names]
        assert kept == sorted(kept)
        if room_units == 0:
            assert plan.bytes == 0
    # one byte short of the inputs: nothing is kept, and nothing breaks
    none = residual_plan.plan_residuals(
        "L" * 16, cands, tokens, cfg.hidden_size, param_bytes,
        int((2 * param_bytes + inputs) / residual_plan.PLANNED_MEMORY_SHARE)
        - 8, 0)
    assert none.bytes == 0 and not any(none.names)


def test_looped_candidates_rank_by_operations_a_byte():
    """The flash forward above the plain products at 4,096 positions (S /
    2 operations a byte against hidden / 2), below them at 1,024; the
    products tie and stay as written: q / k / v, gate, up."""
    cfg = LoopedConfig()
    assert [c.names[0] for c in looped._keepable(cfg, 4096)] == [
        "flash_out", "flash_q", "looped_gate", "looped_up"]
    assert [c.names[0] for c in looped._keepable(cfg, 1024)] == [
        "flash_q", "looped_gate", "looped_up", "flash_out"]
    # 318 MB an application at the cell's shapes
    assert 4096 * sum(c.bytes for c in looped._keepable(cfg, 4096)) == \
        319_029_248


def test_the_hybrid_plan_is_pinned():
    """The lift of the planner out of ``models/nemotron_h.py`` cannot move
    the hybrid cell's plan: at the cell's shapes on 15.75 GiB it keeps
    ``M:2,E:5,*:1``, 2,645,884,928 bytes."""
    import json
    import os

    from benchmarks.runners.hybrid_train import program_config
    from paddlebox_tpu.models import nemotron_h as nh
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    with open(os.path.join(bench, "configs",
                           "nemotron3_super_120b.json")) as f:
        cfg = program_config(json.load(f))
    params = jax.eval_shape(lambda k: nh.init_nemotron_h(k, cfg)[0],
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32)
    plan = nh._plan_for(cfg, _one_chip(), params, tokens)
    assert residual_plan._device_bytes(_one_chip()) == int(15.75 * 2 ** 30)
    said = plan.attributes(cfg.pattern)
    assert said["layers_kept"] == "M:2,E:5,*:1"
    assert said["planned_residual_bytes"] == plan.bytes == 2_645_884_928
    assert [bool(n) for n in plan.names] == [
        False, True, False, True, False, True, True, True, True, True, True]


# -- the step ----------------------------------------------------------------

def test_build_step_span_says_what_the_scans_keep():
    from paddlebox_tpu.core import trace
    params, specs, tokens, targets = _seeded(SMALL)
    mesh = _one_chip()
    opt = optax.adafactor(1e-2)
    trace.GLOBAL.enable(ring_events=256)
    try:
        trace.GLOBAL.clear()
        make_looped_train_step(SMALL, mesh, specs, opt).lower(
            params, opt.init(params), tokens, targets)
        spans = [e for e in trace.GLOBAL.snapshot()
                 if e["name"] == "looped/build_step"]
    finally:
        trace.GLOBAL.disable()
        trace.GLOBAL.clear()
    plan = looped._plan_for(SMALL, mesh, params, tokens)
    assert [e["args"] for e in spans] == [
        dict(passes=4, layers=4, **looped.plan_attributes(SMALL, plan))]
    said = spans[0]["args"]
    assert said["layers_kept"] == "L:16"
    assert said["kept_by_pass"] == (
        "flash_out:4/4/4/4;flash_q:4/4/4/4;looped_gate:4/4/4/4;"
        "looped_up:4/4/4/4")
    assert said["planned_residual_bytes"] > 0


def test_train_step_learns_and_counts_its_applications():
    params, specs, tokens, targets = _seeded(SMALL, seq=32)
    opt = optax.adafactor(1e-2)
    step = make_looped_train_step(SMALL, _one_chip(), specs, opt)
    opt_state = opt.init(params)
    losses = []
    for _ in range(8):
        params, opt_state, loss, aux = step(params, opt_state, tokens,
                                            targets)
        losses.append(float(loss))
        assert int(aux["applications"]) == 16
        assert float(jnp.sum(aux["exit_p"])) == pytest.approx(1.0, abs=1e-5)
    assert losses[-1] < losses[0] - 0.1


def test_data_and_vocabulary_parallel_mesh_gives_the_one_chip_result(
        devices8):
    params, specs, tokens, targets = _seeded(SMALL, batch=4)
    vg = {}
    for name, mesh in (("one", _one_chip()), ("many", build_mesh(
            HybridTopology(dp=2, mp=2), devices=devices8[:4]))):
        vg[name] = jax.jit(jax.value_and_grad(
            looped_loss_fn(SMALL, mesh, specs), has_aux=True))(
            params, tokens, targets)
    (loss, aux), grads = vg["many"]
    (want, want_aux), want_grads = vg["one"]
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(aux["pass_losses"], want_aux["pass_losses"],
                               rtol=1e-6)
    assert int(aux["applications"]) == 16
    for got, ref in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert _rel(np.asarray(got), np.asarray(ref)) < 1e-5


@pytest.mark.parametrize("axis", ["pp", "sp"])
def test_pipeline_and_sequence_axes_are_refused(axis, devices8):
    _, specs = init_looped(jax.random.PRNGKey(0), SMALL)
    mesh = build_mesh(HybridTopology(**{axis: 2}), devices=devices8[:2])
    with pytest.raises(ValueError, match=f"{axis}=2"):
        looped_loss_fn(SMALL, mesh, specs)


def test_tied_embeddings_and_unknown_kernels_are_refused():
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        init_looped(jax.random.PRNGKey(0),
                    dataclasses.replace(SMALL, tie_word_embeddings=True))
    params, specs, tokens, targets = _seeded(SMALL)
    with pytest.raises(ValueError, match="kernels"):
        looped_loss_fn(dataclasses.replace(SMALL, kernels="cuda"),
                       _one_chip(), specs)(params, tokens, targets)


def test_pieces_divide_the_layers():
    assert [dataclasses.replace(SMALL, num_hidden_layers=n).pieces
            for n in (1, 2, 3, 7, 12, 48)] == [1, 2, 3, 1, 4, 4]
    assert looped.make_train_step.__module__ == \
        "paddlebox_tpu.models.train_step"
