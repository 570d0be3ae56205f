"""The looped cell's pieces: operations against a hand count, the
configuration against the published keys, the reference against the
program's model code on the CPU, the cell's limits against each control's
planted fault, and the rehearsal."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import ouro_2_6b as reference
from benchmarks.runners import looped_train as runner
from benchmarks.trace.roofline import flash_attention_gqa, looped_train

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "ouro_2_6b.train_s4096"
with open(os.path.join(BENCH, "trace", "peaks.json")) as f:
    V5E = json.load(f)["TPU v5 lite"]
with open(os.path.join(BENCH, "configs", "ouro_2_6b.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

CELL_SHAPES = {
    "batch_per_chip": 1, "seq": 4096, "hidden_size": 2048,
    "intermediate_size": 5632, "layers": 12, "passes": 4,
    "vocab_size": 49152, "dtype_bytes": 4, "n_head": 16, "n_kv_head": 16,
    "head_dim": 128}


# -- hand counts -------------------------------------------------------------

def test_flops_per_token_is_the_hand_count():
    part = looped_train.forward_flops_per_token(CELL_SHAPES)
    # q, k, v, o 2048 x 2048 and gate, up, down 2048 x 5632: 51,380,224
    # weights, 2 a weight; Q K^T and P V over 2048.5 keys, 16 heads of 128
    assert part["layer"] == (2 * (4 * 2048 * 2048 + 3 * 2048 * 5632)
                             + 4 * 2048 * 2048.5) == 119_541_760
    assert part["head"] == 2 * 2048 * 49152
    total = looped_train.flops_per_token(CELL_SHAPES)
    # 48 applications and 4 heads, forward + twice that backward
    assert total == 3 * (48 * 119_541_760 + 4 * 201_326_592)
    assert 19.6e9 < total < 19.7e9
    assert 80.3e12 < 4096 * total < 80.5e12       # 80.4 TFLOP a step
    # the four heads are 12% of it, attention 14%
    assert 0.12 < 3 * 4 * part["head"] / total < 0.13
    assert 0.12 < 3 * 48 * 4 * 2048 * 2048.5 / total < 0.14
    # at the published depth the heads are 3%
    whole = looped_train.flops_per_token(dict(CELL_SHAPES, layers=48))
    assert 0.03 < 3 * 4 * part["head"] / whole < 0.04


def test_flash_parts_at_the_cells_heads():
    got = flash_attention_gqa.parts(CELL_SHAPES, V5E, {})
    product = 2 * 16 * 4096 * 4096 * 128 * 4097 / 8192
    assert got["fwd"]["flops"] == pytest.approx(2 * product)
    tensor, lse = 4096 * 16 * 128 * 4, 16 * 4096 * 4
    assert got["fwd"]["bytes"] == 4 * tensor + lse      # K, V as large as Q
    assert got["dkv"]["bytes"] == 7 * tensor + lse
    assert {v["bound"] for v in got.values()} == {"flops"}


def test_mfu_reader_reads_only_a_looped_run():
    from benchmarks.readers import mfu_looped
    observed = {"tokens_per_s_per_chip": 3500.0, "shapes": CELL_SHAPES}
    got = mfu_looped.read({}, observed, None, V5E)
    assert got == pytest.approx(
        100 * 3500.0 * looped_train.flops_per_token(CELL_SHAPES) / 197e12)
    assert 34 < got < 36
    assert mfu_looped.read({}, {"tokens_per_s_per_chip": 1.0,
                                "shapes": {"seq": 8}}, None, V5E) is None
    assert mfu_looped.read({}, {}, None, V5E) is None


def test_configuration_holds_the_published_keys():
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152}
    assert {k: CONFIG[k] for k in published} == published
    assert CONFIG["num_hidden_layers"] == 12
    assert CONFIG["layer_types"] == ["full_attention"] * 12
    assert set(CONFIG["reduced"]) == {"num_hidden_layers", "layer_types"}
    assert CONFIG["published"]["num_hidden_layers"] == 48
    entry = [c for c in MANIFEST["configs"] if c["name"] == "ouro_2_6b"][0]
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"]
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    cfg = runner.program_config(CONFIG)
    assert (cfg.num_hidden_layers, cfg.total_ut_steps, cfg.pieces) == (
        12, 4, 4)
    # a layer 51,388,416 parameters, the whole cut 818.0M
    params = jax.eval_shape(
        lambda k: __import__("paddlebox_tpu.models.looped", fromlist=[
            "init_looped"]).init_looped(k, cfg)[0], jax.random.PRNGKey(0))
    per_piece = sum(x.size for x in jax.tree.leaves(params["layers"][0]))
    assert per_piece == 3 * 51_388_416
    assert sum(x.size for x in jax.tree.leaves(params)) == (
        12 * 51_388_416 + 201_326_592 + 2048 + 2049)


def test_the_cell_reports_its_metrics():
    listed = {e["name"] for e in MANIFEST["per_layer"]
              if CELL in e.get("workloads", [])}
    assert listed == {
        "dense.compiles_in_window", "dense.step_device_ms",
        "dense.device_idle_share", "dense.peak_hbm_bytes", "looped.mfu",
        "looped.flash_attention_roofline",
        "looped.flash_attention_device_ms_per_step",
        "looped.applications_per_step", "looped.exit_expected_pass",
        "looped.kernel_fallback",
        # the step's device time by named scope
        "dense.attention_ms_per_step", "dense.mlp_ms_per_step",
        "dense.stack_other_ms_per_step", "dense.head_ms_per_step",
        "dense.optimizer_ms_per_step", "dense.recompute_ms_per_step",
        "dense.unscoped_share"}
    for name in listed:
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".json"))


# -- the limits against the program and the planted faults -------------------

@pytest.fixture(scope="module")
def case():
    from paddlebox_tpu.models.looped import LoopedConfig, init_looped
    cfg = LoopedConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_attention_heads=4, num_key_value_heads=4, head_dim=16,
        num_hidden_layers=4, total_ut_steps=4, kernels="xla")
    config = dict(total_ut_steps=4, rms_norm_eps=1e-6,
                  exit_entropy_weight=0.05, num_attention_heads=4,
                  num_key_value_heads=4, head_dim=16, rope_theta=1e6)
    params, specs = init_looped(jax.random.PRNGKey(0), cfg)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree.unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 193), 0, 256)
    paths = runner.checked_leaves(cfg.pieces)
    return cfg, config, params, specs, toks[:, :-1], toks[:, 1:], paths


def _reference_reading(case, **lower):
    _, config, params, _, tokens, targets, paths = case
    read = runner.reference_reading(reference, config, paths)
    (loss, aux), grads = read(
        [runner.leaf_at(params, p) for p in paths], params, tokens, targets,
        dict(reference.STATED, **lower))
    return float(loss), runner.host_aux(aux), grads


def _outside(case, reading, want):
    return runner.outside(
        reading[0], want[0], reading[1], want[1],
        runner.grad_errors(case[-1], reading[2], want[2]))


def test_checked_leaves_hold_first_middle_and_last_piece():
    paths = runner.checked_leaves(4)
    assert {p[1] for p in paths if p[0] == "layers"} == {0, 2, 3}
    assert {p[2] for p in paths if p[0] == "layers"} == {
        "wq", "wo", "w_gate", "w_down", "n2"}
    assert [p for p in paths if p[0] != "layers"] == [
        ("head",), ("embed",), ("norm_f",), ("gate_w",), ("gate_b",)]
    assert len(runner.checked_leaves(1)) == 10


def test_program_on_the_cpu_is_inside_every_limit(case):
    from paddlebox_tpu.parallel import HybridTopology, build_mesh
    cfg, _, params, specs, tokens, targets, paths = case
    mesh = build_mesh(HybridTopology(dp=1), devices=jax.devices()[:1])
    with jax.default_matmul_precision("highest"):       # as the runner
        (loss, aux), grads = runner.program_reading(cfg, mesh, specs, paths)(
            params, tokens, targets)
    assert int(aux["applications"]) == 16
    got = (float(loss), runner.host_aux(aux), grads)
    want = _reference_reading(case)
    assert _outside(case, got, want) == []
    assert _outside(case, want, want) == []


def test_the_compiled_step_on_the_cpu_is_inside_the_timed_limits(case):
    import optax
    from paddlebox_tpu.models.looped import make_looped_train_step
    from paddlebox_tpu.parallel import HybridTopology, build_mesh
    cfg, _, params, specs, tokens, targets, paths = case
    mesh = build_mesh(HybridTopology(dp=1), devices=jax.devices()[:1])
    opt = optax.adafactor(1e-3)
    want = _reference_reading(case)
    old = [np.asarray(runner.leaf_at(params, p)) for p in paths]
    want_update = runner.first_updates(reference, 1e-3)(want[2], old)
    new, _, loss, aux = make_looped_train_step(cfg, mesh, specs, opt)(
        jax.tree.map(jnp.copy, params), opt.init(params), tokens, targets)
    err = runner.grad_errors(
        paths, [np.asarray(runner.leaf_at(new, p)) - o
                for p, o in zip(paths, old)],
        [np.asarray(u) for u in want_update])
    assert runner.outside_timed(err) == []
    assert runner.outside(float(loss), want[0], runner.host_aux(aux),
                          want[1], {}) == []
    # another optimizer's first step is outside on every matrix
    sgd = optax.sgd(1e-3)
    got, _ = sgd.update(want[2], sgd.init(old))
    failed = runner.outside_timed(runner.grad_errors(paths, got,
                                                     want_update))
    assert len(failed) >= sum(runner._kind(".".join(map(str, p)))
                              == "matrix" for p in paths)


def test_limits_by_leaf_kind():
    assert runner._kind("layers.0.wq") == runner._kind("head") == "matrix"
    assert runner._kind("layers.3.n2") == runner._kind("gate_b") == "gain"
    aux = {"pass_losses": [1.0, 1.0], "exit_p": [0.5, 0.5]}
    assert runner.outside(1.0, 1.0, aux, aux, {}) == []
    assert runner.outside(1.01, 1.0, aux, aux, {}) == ["loss"]
    assert runner.outside(
        1.0, 1.0, {"pass_losses": [1.0, 1.01], "exit_p": [0.5, 0.502]}, aux,
        {"head": 1e-3, "norm_f": 5e-5}) == [
        "pass_loss:2", "exit_p:2", "grad:head"]
    assert runner.outside_timed({"head": 0.1, "gate_w": 0.2}) == [
        "update:head"]


@pytest.fixture(scope="module")
def controls_seen():
    """``controls/<config>.py`` at the rehearsal sizes: one object, every
    fault through every comparison."""
    import contextlib
    import io

    from benchmarks.controls import ouro_2_6b as controls
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert controls.main(["--seed", "5", "--rehearse"]) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("fault,caught_by", [
    ("three_passes", "exit_p:4"),
    ("last_pass_weight_gradient_only", "grad:layers.0.wq"),
    ("no_norm_between_passes", "grad:layers.0.wq"),
    ("no_post_norms", "grad:layers.0.wq"),
    ("no_rotary", "grad:layers.0.wq"),
    ("rotary_theta_10000", "grad:layers.0.wq"),
    ("no_gate", "exit_p:1"),
    ("beta_0", "grad:gate_b"),
    ("bfloat16_state_between_passes", "grad:layers.0.wq"),
])
def test_limits_catch_each_controls_planted_fault(controls_seen, fault,
                                                  caught_by):
    from benchmarks.controls.ouro_2_6b import FAULTS
    assert set(FAULTS) <= set(controls_seen)
    seen = controls_seen[fault]
    assert caught_by in seen["outside"], seen["outside"]
    assert set(seen) >= {"loss", "aux", "grad_rel_err", "update_rel_err"}
    assert len(seen["grad_rel_err"]) == len(seen["update_rel_err"]) == len(
        runner.checked_leaves(2))


def test_last_pass_gradient_leaves_the_loss_and_moves_every_shared_weight(
        case):
    want = _reference_reading(case)
    got = _reference_reading(case, last_pass_grad=True)
    assert got[0] == want[0]
    err = runner.grad_errors(case[-1], got[2], want[2])
    assert all(e > 0.1 for name, e in err.items() if "layers" in name), err
    # the gate and the embedding are not held: the gate reads every pass
    assert err["gate_b"] == 0.0


# -- the rehearsal -----------------------------------------------------------

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
    assert seen["detail"]["applications_seen"] == [8]
    assert seen["detail"]["first_step_aux"]["exit_p"] == pytest.approx(
        [0.5, 0.25, 0.125, 0.125])
    assert len(seen["detail"]["step_update_rel_err"]) == len(
        seen["detail"]["grad_rel_err"])
    assert seen["counters"]["looped_applications"] == 8 * line["attempted"]
    assert seen["counters"]["kernel_fallback"] == 0
    assert 1.8 < seen["counters"]["looped_exit_expected_pass"] < 1.95
    names = {name for name, _ in seen["setup_spans"]}
    assert {"setup/init", "setup/program_grads", "setup/reference",
            "setup/compile"} <= names
