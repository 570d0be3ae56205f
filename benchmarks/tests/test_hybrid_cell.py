"""The hybrid cell's pieces: operations and bytes against hand counts, the
reference against the program's model code on the CPU, the cell's limits
against planted faults, and the traced rehearsal."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import nemotron3_super_120b as reference
from benchmarks.runners import hybrid_train as runner
from benchmarks.trace.roofline import (flash_attention_gqa,
                                       nemotron_h_train, ssd_scan)

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "nemotron3_super_120b.train_s8192"
with open(os.path.join(BENCH, "trace", "peaks.json")) as f:
    V5E = json.load(f)["TPU v5 lite"]
with open(os.path.join(BENCH, "configs", "nemotron3_super_120b.json")) as f:
    CONFIG = json.load(f)

PUBLISHED = {
    "batch_per_chip": 1, "seq": 8192, "hidden_size": 4096,
    "pattern": "MEMEMEM*EME", "vocab_size": 16384, "dtype_bytes": 4,
    "n_head": 32, "n_kv_head": 2, "head_dim": 128, "mamba_num_heads": 128,
    "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 8,
    "conv_kernel": 4, "chunk_size": 128, "router_experts": 512,
    "moe_latent_size": 1024, "moe_intermediate_size": 2688,
    "moe_shared_expert_intermediate_size": 5376,
    "assignments_served_per_token": 0.34375}       # 22 * 8 / 512


# -- hand counts -------------------------------------------------------------

def test_flops_per_token_by_layer_kind():
    kind = nemotron_h_train.forward_flops_per_token(PUBLISHED)
    # in_proj 4096 x 18,560 and out_proj 8192 x 4096, 2 a weight; 4 conv
    # taps on 10,240 channels; C B^T over 64.5 positions in 8 groups of
    # 128; scores x over 64.5 positions, 128 heads of 64; C h and B (x) x
    # on 128 states of 128 x 64
    assert kind["M"] == (2 * (4096 * 18560 + 8192 * 4096) + 2 * 4 * 10240
                         + 2 * 8 * 128 * 64.5 + 2 * 128 * 64 * 64.5
                         + 4 * 128 * 128 * 64) == 224_617_472
    # q, o 4096 x 4096, k, v 4096 x 256; Q K^T and P V over 4096.5 keys
    assert kind["*"] == (2 * (2 * 4096 * 4096 + 2 * 4096 * 256)
                         + 4 * 32 * 128 * 4096.5) == 138_420_224
    # router 4096 x 512, latent 2 x 4096 x 1024, shared 2 x 4096 x 5376;
    # 0.34375 served assignments of 2 x 1024 x 2688 weights
    assert kind["E"] == (2 * (4096 * 512 + 2 * 4096 * 1024
                              + 2 * 4096 * 5376)
                         + 0.34375 * 4 * 1024 * 2688) == 112_836_608
    assert kind["head"] == 2 * 4096 * 16384
    total = nemotron_h_train.flops_per_token(PUBLISHED)
    assert total == 3 * (5 * 224_617_472 + 5 * 112_836_608 + 138_420_224
                         + 134_217_728)
    assert 5.8e9 < total < 6.0e9
    # the Mamba layers carry 57% of it
    assert 0.56 < 5 * kind["M"] * 3 / total < 0.58


def test_served_assignments_move_only_the_routed_part():
    none = dict(PUBLISHED, assignments_served_per_token=0.0)
    all22 = dict(PUBLISHED, assignments_served_per_token=22.0)
    got = (nemotron_h_train.flops_per_token(all22)
           - nemotron_h_train.flops_per_token(none))
    assert got == 3 * 5 * 22 * 4 * 1024 * 2688


def test_ssd_scan_parts():
    got = ssd_scan.parts(PUBLISHED, V5E, {})
    per_token = 2 * 8 * 128 * 64.5 + 2 * 128 * 64 * 64.5 + 4 * 128 * 128 * 64
    assert per_token == 5_383_168
    assert got["fwd"]["flops"] == 8192 * per_token
    assert got["bwd"]["flops"] == 2 * 8192 * per_token
    x, bc, head = 8192 * 8192 * 4, 8192 * 1024 * 4, 8192 * 128 * 4
    assert got["fwd"]["bytes"] == 2 * x + 2 * bc + 2 * head == 612_368_384
    assert got["bwd"]["bytes"] == 3 * x + 4 * bc + 4 * head
    # 72 operations a byte against the chip's 240: the tensors bound both
    assert got["fwd"]["bound"] == got["bwd"]["bound"] == "bytes"
    assert got["fwd"]["seconds"] == pytest.approx(612_368_384 / 819e9)


def test_flash_attention_gqa_parts():
    got = flash_attention_gqa.parts(PUBLISHED, V5E, {})
    product = 2 * 32 * 8192 * 8192 * 128 * 8193 / 16384
    assert got["fwd"]["flops"] == pytest.approx(2 * product)
    assert got["dq"]["flops"] + got["dkv"]["flops"] == pytest.approx(
        5 * product)
    tq, tkv, lse = 8192 * 32 * 128 * 4, 8192 * 2 * 128 * 4, 32 * 8192 * 4
    assert got["fwd"]["bytes"] == 2 * tq + 2 * tkv + lse
    assert got["dq"]["bytes"] == 4 * tq + 2 * tkv + lse
    assert got["dkv"]["bytes"] == 3 * tq + 4 * tkv + lse
    # K and V once per key/value head: 16 times fewer bytes than repeated
    assert tkv * 16 == tq
    # at S = 8,192 and D = 128 all three are bound by operations
    assert {v["bound"] for v in got.values()} == {"flops"}


def test_configuration_holds_the_published_widths():
    want = {"hidden_size": 4096, "mamba_num_heads": 128,
            "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 8,
            "conv_kernel": 4, "chunk_size": 128, "num_attention_heads": 32,
            "num_key_value_heads": 2, "head_dim": 128,
            "moe_latent_size": 1024, "moe_intermediate_size": 2688,
            "moe_shared_expert_intermediate_size": 5376,
            "router_experts": 512, "num_experts_per_tok": 22,
            "routed_scaling_factor": 5}
    assert {k: CONFIG[k] for k in want} == want
    assert CONFIG["hybrid_override_pattern"] == "MEMEMEM*EME"
    assert CONFIG["published"]["hybrid_override_pattern"].startswith(
        CONFIG["hybrid_override_pattern"])
    assert set(CONFIG["reduced"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    assert CONFIG["experts_held"] == [0, CONFIG["n_routed_experts"]]


# -- the limits against planted faults ---------------------------------------

def _small():
    from paddlebox_tpu.models.nemotron_h import NemotronHConfig
    cfg = NemotronHConfig(
        vocab_size=256, hidden_size=64, pattern="MEM*E",
        mamba_num_heads=4, mamba_head_dim=32, ssm_state_size=16, n_groups=2,
        chunk_size=32, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, n_routed_experts=16, experts_held=(0, 8),
        num_experts_per_tok=4, moe_latent_size=32, moe_intermediate_size=48,
        moe_shared_expert_intermediate_size=64, kernels="xla")
    config = dict(
        hybrid_override_pattern=cfg.pattern, norm_eps=1e-5,
        mamba_num_heads=4, mamba_head_dim=32, ssm_state_size=16, n_groups=2,
        conv_kernel=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, num_experts_per_tok=4, routed_scaling_factor=5.0,
        experts_held=[0, 8])
    return cfg, config


@pytest.fixture(scope="module")
def case():
    from paddlebox_tpu.models.nemotron_h import init_nemotron_h
    cfg, config = _small()
    params, specs = init_nemotron_h(jax.random.PRNGKey(0), cfg)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree.unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])
    # step sizes small enough that the state remembers hundreds of
    # positions, as the published initial values make it
    params["layers"][0]["dt_bias"] = params["layers"][0]["dt_bias"] - 2.0
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 385), 0, 256)
    paths = runner.checked_leaves(cfg.pattern)
    return cfg, config, params, specs, toks[:, :-1], toks[:, 1:], paths


def _reference_reading(case, params=None, config=None, **lower):
    cfg, config0, params0, _, tokens, targets, paths = case
    params = params0 if params is None else params
    config = config0 if config is None else config

    def f(picked):
        return reference.loss_and_load(
            runner.with_leaves(params, paths, picked), tokens, targets,
            config, dict(reference.STATED, **lower))
    (loss, load), grads = jax.value_and_grad(f, has_aux=True)(
        [runner.leaf_at(params, p) for p in paths])
    return float(loss), np.asarray(load), grads


def _outside(case, reading, want):
    paths = case[-1]
    return runner.outside(
        reading[0], want[0], runner.grad_errors(paths, reading[2], want[2]),
        runner.routing_shares(reading[1], want[1]), case[0].pattern)


def test_program_on_the_cpu_is_inside_every_limit(case):
    from paddlebox_tpu.models.nemotron_h import nemotron_h_loss_fn
    from paddlebox_tpu.parallel import HybridTopology, build_mesh
    cfg, _, params, specs, tokens, targets, paths = case
    mesh = build_mesh(HybridTopology(dp=1), devices=jax.devices()[:1])
    with jax.default_matmul_precision("highest"):       # as the runner
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            nemotron_h_loss_fn(cfg, mesh, specs), has_aux=True))(
            params, tokens, targets)
    got = (float(loss), np.asarray(aux["load"]),
           [runner.leaf_at(grads, p) for p in paths])
    want = _reference_reading(case)
    assert _outside(case, got, want) == []
    assert _outside(case, want, want) == []


def _without(params, layer, **zeroed):
    layers = list(params["layers"])
    layers[layer] = dict(layers[layer], **{
        k: jnp.zeros_like(layers[layer][k]) for k in zeroed})
    return dict(params, layers=layers)


@pytest.mark.parametrize("fault", [
    "bfloat16_scan_state", "bfloat16_router", "no_d_skip",
    "no_shared_expert", "scaling_1", "ungated"])
def test_limits_catch_a_lower_precision_or_a_missing_term(case, fault):
    cfg, config, params = case[:3]
    want = _reference_reading(case)
    if fault == "bfloat16_scan_state":
        bad = _reference_reading(case, state=True)
    elif fault == "bfloat16_router":
        bad = _reference_reading(case, router=True)
    elif fault == "no_d_skip":
        bad = _reference_reading(case, _without(params, 0, d=True))
    elif fault == "no_shared_expert":
        bad = _reference_reading(case, _without(params, 1, ws2=True))
    elif fault == "scaling_1":
        bad = _reference_reading(
            case, config=dict(config, routed_scaling_factor=1.0))
    else:
        # silu(z) taken for silu(0) = 0 would kill the layer; the fault
        # planted is the gate's input halved, a mis-sliced in_proj
        layers = list(params["layers"])
        di = cfg.mamba_inner
        layers[0] = dict(layers[0], w_in=layers[0]["w_in"].at[:, :di].mul(
            0.5))
        bad = _reference_reading(case, dict(params, layers=layers))
    failed = _outside(case, bad, want)
    assert failed, fault
    if fault == "bfloat16_router":
        assert "routing" in failed
    if fault == "bfloat16_scan_state":
        assert {"grad:layers.0.a_log", "grad:layers.0.dt_bias"} <= set(
            failed)
        assert "routing" not in failed


def test_a_flipped_assignment_widens_only_the_routed_leaves_limit():
    share = [0.0, 0.002]
    flipped = 2 * (2 * 0.002) ** 0.5
    plain = runner.grad_limit("layers.0.w_in", "MEM*E", share)
    assert plain == runner.GRAD_RTOL["matrix"]
    assert runner.grad_limit("layers.1.w1", "MEM*E", share) == plain
    assert runner.grad_limit("layers.4.w1", "MEM*E", share) == \
        pytest.approx(plain + flipped)
    assert runner.grad_limit("layers.1.gate", "MEM*E", share) == \
        runner.GRAD_RTOL["router"]
    assert runner.grad_limit("layers.4.gate", "MEM*E", share) == \
        pytest.approx(runner.GRAD_RTOL["router"] + flipped)
    assert runner.grad_limit("layers.0.a_log", "MEM*E", share) == \
        runner.GRAD_RTOL["scan_head"]


def test_routing_share_is_pooled_over_the_layers():
    want = np.array([[100, 300], [50, 50]])
    got = np.array([[101, 299], [50, 50]])          # one assignment moved
    by_layer, pooled = runner.routing_shares(got, want)
    assert by_layer == [2 / 400, 0.0] and pooled == 2 / 500
    assert runner.outside(1.0, 1.0, {}, (by_layer, pooled), "EE") == [
        "routing"]
    assert runner.outside(1.0, 1.0, {}, ([0.0, 0.0], 0.0), "EE") == []


# -- the timed step's own comparisons ----------------------------------------

@pytest.mark.parametrize("shape", [(256, 384), (8, 128, 200), (4, 300),
                                   (128,), (130, 128)])
def test_first_update_is_the_optimizers_first_step(shape):
    """Factored where the two largest axes reach 128, else by element."""
    import optax
    k = jax.random.PRNGKey(sum(shape))
    param = jax.random.normal(k, shape) * 0.02
    grad = jax.random.normal(jax.random.fold_in(k, 1), shape) * 1e-3
    opt = optax.adafactor(1e-3)
    want, _ = opt.update(grad, opt.init(param), param)
    got = reference.first_update(grad, param, 1e-3)
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 1e-6


def test_rounded_products_round_their_transposes_too():
    a = jax.random.normal(jax.random.PRNGKey(0), (5, 7))
    b = jax.random.normal(jax.random.PRNGKey(1), (7, 3))
    g = jax.random.normal(jax.random.PRNGKey(2), (5, 3))

    def r(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    out, vjp = jax.vjp(lambda a, b: reference._mm(a, b, True), a, b)
    da, db = vjp(g)
    np.testing.assert_array_equal(out, r(a) @ r(b))
    np.testing.assert_array_equal(da, r(g) @ r(b).T)
    np.testing.assert_array_equal(db, r(a).T @ r(g))
    exact = jax.vjp(lambda a, b: reference._mm(a, b, False), a, b)[1](g)
    np.testing.assert_array_equal(exact[0], g @ b.T)


def test_the_compiled_step_on_the_cpu_is_inside_the_timed_limits(case):
    """(e) and (f) through the step the runner times: its parameter
    change and counts against the reference's gradients through
    ``first_update`` (the CPU rounds no operand, so neither does the
    reference here)."""
    import optax
    from paddlebox_tpu.models.nemotron_h import make_nemotron_h_train_step
    from paddlebox_tpu.parallel import HybridTopology, build_mesh
    cfg, _, params, specs, tokens, targets, paths = case
    mesh = build_mesh(HybridTopology(dp=1), devices=jax.devices()[:1])
    opt = optax.adafactor(1e-3)
    want = _reference_reading(case)
    old = [np.asarray(runner.leaf_at(params, p)) for p in paths]
    want_update = runner.first_updates(reference, 1e-3)(want[2], old)
    new, _, _, aux = make_nemotron_h_train_step(cfg, mesh, specs, opt)(
        jax.tree.map(jnp.copy, params), opt.init(params), tokens, targets)
    err = runner.grad_errors(
        paths, [np.asarray(runner.leaf_at(new, p)) - o
                for p, o in zip(paths, old)],
        [np.asarray(u) for u in want_update])
    step_routing = runner.routing_shares(np.asarray(aux["load"]), want[1])
    assert runner.outside_timed(err, step_routing, cfg.pattern) == []
    assert int(np.asarray(aux["dropped"]).sum()) == 0


@pytest.mark.parametrize("fault", ["no_parameter_scale", "sgd", "ascent"])
def test_timed_limits_catch_another_optimizer(case, fault):
    import optax
    paths = case[-1]
    params = case[2]
    grads = _reference_reading(case)[2]
    picked = [runner.leaf_at(params, p) for p in paths]
    want = runner.first_updates(reference, 1e-3)(grads, picked)
    opt = {"no_parameter_scale": optax.adafactor(
               1e-3, multiply_by_parameter_scale=False),
           "sgd": optax.sgd(1e-3),
           "ascent": optax.chain(optax.adafactor(1e-3), optax.scale(-1.0))
           }[fault]
    got, _ = opt.update(grads, opt.init(picked), picked)
    failed = runner.outside_timed(
        runner.grad_errors(paths, got, want), ([0.0, 0.0], 0.0), "MEM*E")
    # all but ``d``, whose values are 1 and so is its scale
    assert len(failed) >= len(paths) - 1, fault


def test_timed_limits_by_leaf_kind():
    share = [0.0, 0.002]
    flipped = 2 * (2 * 0.002) ** 0.5
    assert runner.update_limit("layers.0.w_in", "MEM*E", share) == \
        runner.UPDATE_RTOL["matrix"]
    assert runner.update_limit("layers.0.conv_w", "MEM*E", share) == \
        runner.UPDATE_RTOL["sign"]
    assert runner.update_limit("layers.4.w2", "MEM*E", share) == \
        pytest.approx(runner.UPDATE_RTOL["matrix"] + flipped)
    assert runner.outside_timed({}, ([0.0], 1.0), "E") == ["step_routing"]
    assert runner.outside_timed({"head": 1.0}, ([0.0], 0.0), "E") == [
        "update:head"]


@pytest.mark.parametrize("fault", ["bfloat16_scan_state", "no_d_skip"])
def test_controls_read_every_comparison(fault, capsys):
    """``controls/<config>.py`` at the rehearsal sizes: one object, every
    fault through every comparison."""
    from benchmarks.controls import nemotron3_super_120b as controls
    if not hasattr(test_controls_read_every_comparison, "seen"):
        assert controls.main(["--seed", "5", "--rehearse"]) == 0
        test_controls_read_every_comparison.seen = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])
    seen = test_controls_read_every_comparison.seen
    assert set(seen) >= {"bfloat16_scan_state", "bfloat16_router",
                         "no_d_skip", "no_shared_expert"}
    assert seen[fault]["outside"]
    assert set(seen[fault]) >= {"grad_rel_err", "routing_share_pooled",
                                "update_rel_err",
                                "step_routing_share_pooled"}


# -- the traced rehearsal ----------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal(trace, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    detail = tmp_path / "detail.json"
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "2147483659", "--seconds", "1",
         "--trace", str(trace), "--rehearse", "--detail", str(detail)],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["metrics"] == {}
    assert line["attempted"] > 0 and line["failed"] == 0
    with open(detail) as f:
        seen = json.load(f)
    assert seen["detail"]["outside_limits"] == []
    assert seen["detail"]["dropped_assignments"] == 0
    assert len(seen["detail"]["step_update_rel_err"]) == len(
        seen["detail"]["grad_rel_err"])
    assert seen["detail"]["first_step_load"] == seen["detail"][
        "rounded_reference_load"]
    assert seen["counters"]["moe_assignments_served"] > 0
    assert seen["counters"]["kernel_fallback"] == 0
    names = {name for name, _ in seen["setup_spans"]}
    assert {"setup/init", "setup/reference", "setup/compile"} <= names
