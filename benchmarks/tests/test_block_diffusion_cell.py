"""The block-diffusion cell's pieces: operations against a hand count and
a brute-force count over the boolean mask, the configuration against the
published keys, the reference against the program's model code on the CPU,
the cell's limits against each control's planted fault, and the
rehearsal."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import sdar_30b_a3b as reference
from benchmarks.runners import block_diffusion_train as runner
from benchmarks.trace.roofline import (block_diffusion_train,
                                       flash_attention_block_diffusion)

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "sdar_30b_a3b.train_bd_s4096"
with open(os.path.join(BENCH, "trace", "peaks.json")) as f:
    V5E = json.load(f)["TPU v5 lite"]
with open(os.path.join(BENCH, "configs", "sdar_30b_a3b.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

CELL_SHAPES = {
    "batch_per_chip": 1, "seq": 4096, "block_length": 4,
    "hidden_size": 2048, "layers": 12, "vocab_size": 18992,
    "dtype_bytes": 4, "n_head": 32, "n_kv_head": 4, "head_dim": 128,
    "router_experts": 128, "experts_held": 16, "num_experts_per_tok": 8,
    "moe_intermediate_size": 768}


# -- hand counts -------------------------------------------------------------

def test_unmasked_entries_are_the_boolean_masks():
    """L^2 + L B, by brute force over the reference's boolean mask."""
    for seq, block in ((8, 4), (8, 2), (12, 3), (16, 16)):
        seen = np.asarray(reference.may_read(jnp.arange(2 * seq), seq, block))
        assert flash_attention_block_diffusion.unmasked_entries(
            seq, block) == int(seen.sum()) == seq * seq + seq * block
    # clean L (L + B) / 2, noisy-to-clean L (L - B) / 2, noisy-to-noisy L B
    seen = np.asarray(reference.may_read(jnp.arange(16), 8, 4))
    assert (int(seen[:8, :8].sum()), int(seen[8:, :8].sum()),
            int(seen[8:, 8:].sum()), int(seen[:8, 8:].sum())) == (
        48, 16, 32, 0)


def test_flash_parts_under_the_mask():
    got = flash_attention_block_diffusion.parts(CELL_SHAPES, V5E, {})
    # 16.79M entries a head (the 80 live tiles hold 20.97M), 32 heads of 128
    entries = 4096 * 4096 + 4096 * 4
    assert entries == 16_793_600 and 80 * 512 * 512 == 20_971_520
    product = 2 * 32 * 128 * entries
    assert got["fwd"]["flops"] == 2 * product
    assert got["dq"]["flops"] == 2 * product
    assert got["dkv"]["flops"] == 3 * product
    # 275 GFLOP a layer forward, as the issue counts them
    assert 274e9 < got["fwd"]["flops"] < 276e9
    tq, tkv, lse = 8192 * 32 * 128 * 4, 8192 * 4 * 128 * 4, 32 * 8192 * 4
    assert got["fwd"]["bytes"] == 2 * tq + 2 * tkv + lse
    assert got["dq"]["bytes"] == 4 * tq + 2 * tkv + lse
    assert got["dkv"]["bytes"] == 3 * tq + 4 * tkv + lse
    assert {v["bound"] for v in got.values()} == {"flops"}
    # a small case against the brute-force count
    small = dict(CELL_SHAPES, seq=8, n_head=2, n_kv_head=1, head_dim=16)
    seen = int(np.asarray(reference.may_read(jnp.arange(16), 8, 4)).sum())
    assert flash_attention_block_diffusion.parts(small, V5E, {})[
        "fwd"]["flops"] == 2 * 2 * 2 * 16 * seen


def test_flops_per_trained_token_is_the_hand_count():
    part = block_diffusion_train.forward_flops_per_token(CELL_SHAPES)
    # two rows: q, o 2048 x 4096, k, v 2048 x 512 (18.87M weights), the
    # router 2048 x 128, one expected assignment of 3 x 2048 x 768
    rows = 2 * (2 * 18_874_368 + 2 * 262_144 + 2 * 4_718_592)
    attention = 4 * 32 * 128 * (4096 + 4)
    assert part["layer"] == rows + attention
    assert part["attention"] == attention == 67_174_400
    assert part["head"] == 2 * 2048 * 18992
    total = block_diffusion_train.flops_per_token(CELL_SHAPES)
    assert total == 3 * (12 * part["layer"] + part["head"])
    # ~25 TFLOP a step of 4,096 trained tokens; attention ~40% of it, the
    # routed products 12%, the head 1%
    assert 24e12 < 4096 * total < 25e12
    assert 0.39 < 3 * 12 * attention / total < 0.41
    assert 0.11 < 3 * 12 * 2 * 2 * 4_718_592 / total < 0.12
    assert 0.03 < 3 * part["head"] / total < 0.045
    # at L = 8, B = 4 the attention term is the brute-force count over
    # the boolean mask, a trained token
    small = dict(CELL_SHAPES, seq=8, n_head=2, head_dim=16)
    seen = int(np.asarray(reference.may_read(jnp.arange(16), 8, 4)).sum())
    assert block_diffusion_train.forward_flops_per_token(small)[
        "attention"] == 2 * 2 * 2 * 16 * seen / 8


def test_mfu_reader_reads_only_a_block_diffusion_run():
    from benchmarks.readers import mfu_blockdiff
    observed = {"tokens_per_s_per_chip": 10000.0, "shapes": CELL_SHAPES}
    got = mfu_blockdiff.read({}, observed, None, V5E)
    assert got == pytest.approx(
        100 * 10000.0 * block_diffusion_train.flops_per_token(CELL_SHAPES)
        / 197e12)
    assert 29 < got < 32
    assert mfu_blockdiff.read({}, {"tokens_per_s_per_chip": 1.0,
                                   "shapes": {"seq": 8}}, None, V5E) is None
    assert mfu_blockdiff.read({}, {}, None, V5E) is None


# -- the configuration -------------------------------------------------------

def test_configuration_holds_the_published_keys():
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "sdar_moe", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False}
    assert {k: CONFIG[k] for k in published} == published
    cut = {"num_hidden_layers": (12, 48), "num_experts": (16, 128),
           "vocab_size": (18992, 151936)}
    assert set(CONFIG["reduced"]) == set(CONFIG["published"]) == set(cut)
    for key, (here, whole) in cut.items():
        assert (CONFIG[key], CONFIG["published"][key]) == (here, whole)
    assert CONFIG["router_experts"] == 128
    assert CONFIG["experts_held"] == [0, 16]
    assert 8 * CONFIG["vocab_size"] == 151936           # an eighth, whole
    assert (CONFIG["block_length"], CONFIG["t_min"],
            CONFIG["mask_token_id"]) == (4, 1e-3, 18991)
    assert {"block_length", "noise_schedule", "t_min", "loss_weight",
            "no_shift", "mask_token_id", "qk_norm",
            "no_router_auxiliary_loss", "initial_weights", "optimizer",
            "dtype", "rematerialisation", "sequences_per_chip",
            "weights_seed", "keys_read_by_nothing"} <= set(CONFIG["assumed"])
    assert isinstance(CONFIG["weights_seed"], int)
    assert "8 chips" in CONFIG["deployment"] and "4 pipeline stages" in (
        CONFIG["deployment"])
    entry = [c for c in MANIFEST["configs"]
             if c["name"] == "sdar_30b_a3b"][0]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    cfg = runner.program_config(CONFIG)
    assert (cfg.num_hidden_layers, cfg.model_layers, cfg.pieces) == (
        12, 48, 4)
    assert (cfg.router_experts, cfg.experts_held, cfg.mask_token_id) == (
        128, (0, 16), 18991)
    with pytest.raises(ValueError, match="experts_held disagree"):
        runner.program_config(dict(CONFIG, num_experts=8))


def test_the_cell_reports_its_metrics():
    listed = {e["name"] for e in MANIFEST["per_layer"]
              if CELL in e.get("workloads", [])}
    assert listed == {
        "dense.compiles_in_window", "dense.step_device_ms",
        "dense.device_idle_share", "dense.peak_hbm_bytes",
        "moe.assignments_served_per_step", "moe.load_max_over_mean",
        "moe.dropped_assignments", "blockdiff.mfu",
        "blockdiff.flash_attention_roofline",
        "blockdiff.flash_attention_device_ms_per_step",
        "blockdiff.kernel_fallback", "blockdiff.masked_positions_per_step",
        # the step's device time by named scope
        "dense.attention_ms_per_step", "moe.layer_ms_per_step",
        "dense.stack_other_ms_per_step", "dense.head_ms_per_step",
        "dense.optimizer_ms_per_step", "dense.recompute_ms_per_step",
        "dense.unscoped_share"}
    for name in listed:
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".json"))
    end_to_end = {e["name"]: e for e in MANIFEST["end_to_end"]}
    assert CELL in end_to_end["dense_tokens_per_s_per_chip"]["workloads"]
    cell = [w for w in MANIFEST["workloads"] if w["name"] == CELL][0]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200


# -- the limits against the program and the planted faults -------------------

@pytest.fixture(scope="module")
def case():
    from paddlebox_tpu.models.block_diffusion import (BlockDiffusionConfig,
                                                      init_block_diffusion)
    cfg = BlockDiffusionConfig(
        vocab_size=256, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, num_hidden_layers=4,
        model_layers=4, moe_intermediate_size=32, num_experts_per_tok=2,
        router_experts=8, experts_held=(0, 4), kernels="xla")
    config = dict(
        rms_norm_eps=1e-6, rope_theta=1e6, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, num_experts_per_tok=2,
        experts_held=[0, 4], block_length=4, mask_token_id=255,
        t_min=1e-3)
    params, specs = init_block_diffusion(jax.random.PRNGKey(0), cfg)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree.unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])
    batch = runner.batch_draw(jax.random.PRNGKey(2), config, 1.0, 1, 96,
                              _data())(0)
    return cfg, config, params, specs, batch


def _one_chip():
    from paddlebox_tpu.parallel import HybridTopology, build_mesh
    return build_mesh(HybridTopology(dp=1), devices=jax.devices()[:1])


def _data():
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(_one_chip(), P("dp"))


@pytest.fixture(scope="module")
def program_seen(case):
    """The program's reading at ``highest`` and the paths it chose."""
    cfg, _, params, specs, batch = case
    with jax.default_matmul_precision("highest"):       # as the runner
        (loss, aux), grads, experts = runner.program_reading(
            cfg, _one_chip(), specs, runner.checked_leaves(4, 1))(
            params, *batch)
    paths = runner.checked_leaves(4, 1, [int(e) for e in experts])
    return paths, (float(loss), runner.host_aux(aux), grads)


def _reference_reading(case, paths, **lower):
    _, config, params, _, batch = case
    read = runner.reference_reading(reference, config, paths)
    (loss, aux), grads = read(
        [runner.leaf_at(params, p) for p in paths], params, *batch,
        dict(reference.STATED, **lower))
    return float(loss), runner.host_aux(aux), grads


def _outside(paths, reading, want):
    return runner.outside(
        reading[0], want[0], reading[1], want[1],
        runner.grad_errors(paths, reading[2], want[2]),
        runner.routing_shares(reading[1]["load"], want[1]["load"]), 1)


def test_checked_leaves_hold_first_middle_and_last_layer():
    paths = runner.checked_leaves(4, 3, [5, 6, 7])
    assert {p[1] for p in paths if p[0] == "layers"} == {0, 2, 3}
    assert {p[2] for p in paths if p[0] == "layers" and len(p) == 3} == {
        "wq", "wk", "wv", "wo", "gq", "gk", "router"}
    # one expert of the first, a middle and the last layer of the stack
    assert [p[1:] for p in paths if len(p) > 3 and p[2] == "w1"] == [
        (0, "w1", 0, 5), (2, "w1", 1, 6), (3, "w1", 2, 7)]
    assert [p for p in paths if p[0] != "layers"] == [
        ("embed",), ("head",), ("norm_f",)]
    assert len(runner.checked_leaves(1, 12)) == 13
    assert runner.checked_leaves(4, 3)[7] == ("layers", 0, "w1", 0, None)


def test_draw_is_the_traffics(case):
    _, config, _, _, (tokens, levels, masked) = case
    assert tokens.shape == (1, 96) and levels.shape == (1, 24)
    assert masked.dtype == jnp.bool_ and 0 < int(masked.sum()) < 96
    assert int(tokens.max()) < config["mask_token_id"]
    assert float(levels.min()) >= 1e-3 and float(levels.max()) <= 1.0
    again = runner.batch_draw(jax.random.PRNGKey(2), config, 1.0, 1, 96,
                              _data())
    np.testing.assert_array_equal(again(0)[0], tokens)
    assert not np.array_equal(again(1)[0], tokens)


def test_program_on_the_cpu_is_inside_every_limit(case, program_seen):
    paths, got = program_seen
    assert all(p[4] is not None for p in paths if len(p) > 3)
    want = _reference_reading(case, paths)
    assert _outside(paths, got, want) == []
    assert _outside(paths, want, want) == []
    # the chosen expert of the last layer has a gradient to compare
    last = [g for p, g in zip(paths, got[2]) if p[1:4] == (3, "w1", 0)][0]
    assert float(jnp.linalg.norm(last)) > 0


def test_the_compiled_step_on_the_cpu_is_inside_the_timed_limits(
        case, program_seen):
    import optax
    from paddlebox_tpu.models.block_diffusion import (
        make_block_diffusion_train_step)
    cfg, _, params, specs, batch = case
    paths, _ = program_seen
    opt = optax.adafactor(1e-3)
    want = _reference_reading(case, paths)
    old = [np.asarray(runner.leaf_at(params, p)) for p in paths]
    want_update = runner.first_updates(reference, 1e-3)(want[2], old)
    new, _, loss, aux = make_block_diffusion_train_step(
        cfg, _one_chip(), specs, opt)(
        jax.tree.map(jnp.copy, params), opt.init(params), *batch)
    err = runner.grad_errors(
        paths, [np.asarray(runner.leaf_at(new, p)) - o
                for p, o in zip(paths, old)],
        [np.asarray(u) for u in want_update])
    aux = runner.host_aux(aux)
    routing = runner.routing_shares(aux["load"], want[1]["load"])
    assert runner.outside_timed(err, routing, 1) == []
    assert runner.outside(float(loss), want[0], aux, want[1], {}, routing,
                          1) == []
    # another optimizer's first step is outside on every matrix
    sgd = optax.sgd(1e-3)
    got, _ = sgd.update(want[2], sgd.init(old))
    failed = runner.outside_timed(
        runner.grad_errors(paths, got, want_update), routing, 1)
    assert len(failed) >= sum(runner._kind(".".join(map(str, p)))
                              == "matrix" for p in paths)


def test_limits_by_leaf_kind():
    assert runner._kind("layers.0.wq") == runner._kind("head") == "matrix"
    assert runner._kind("layers.3.gq") == runner._kind("norm_f") == "gain"
    assert runner._kind("layers.2.router") == "router"
    assert runner._kind("layers.0.w3.0.5") == "expert"
    aux = {"masked": 10, "weight": 40.0}
    even = ([0.0] * 4, 0.0)
    assert runner.outside(1.0, 1.0, aux, aux, {}, even, 1) == []
    assert runner.outside(1.01, 1.0, dict(aux, masked=11),
                          dict(aux, weight=40.01),
                          {"head": 1e-3, "norm_f": 5e-5}, even, 1) == [
        "loss", "masked", "weight", "grad:head"]
    # a flipped assignment gives its own layer's expert and router room
    flipped = ([0.0, 0.0, 1e-3, 0.0], 2.5e-4)
    err = {"layers.2.w1.0.3": 0.05, "layers.1.w1.0.3": 0.05,
           "layers.2.router": 0.05, "layers.2.wq": 0.05}
    assert runner.outside(1.0, 1.0, aux, aux, err, flipped, 1) == [
        "grad:layers.1.w1.0.3", "grad:layers.2.wq"]
    assert runner.outside(1.0, 1.0, aux, aux, {}, ([0.0] * 4, 1e-3),
                          1) == ["routing"]
    # the last piece's router is read and not limited in (c)
    assert runner.outside_timed(
        {"layers.3.router": 1.2, "layers.0.router": 1.2, "head": 0.01},
        even, 1) == ["update:layers.0.router"]
    assert runner.outside_timed({}, ([0.0] * 4, 0.5), 1) == ["step_routing"]


def test_an_experts_slice_is_compared_by_direction():
    rng = np.random.default_rng(0)
    w = rng.normal(size=(8, 8))
    err = runner.grad_errors(
        [("layers", 0, "w1", 0, 2), ("layers", 0, "wq")], [3.0 * w, 3.0 * w],
        [w, w])
    assert err["layers.0.w1.0.2"] == pytest.approx(0.0, abs=1e-12)
    assert err["layers.0.wq"] == pytest.approx(2.0)
    zero = runner.grad_errors([("layers", 0, "w1", 0, 2)], [0 * w], [0 * w])
    assert zero["layers.0.w1.0.2"] == 0.0


@pytest.fixture(scope="module")
def controls_seen():
    """``controls/<config>.py`` at the rehearsal sizes: one object, every
    fault through every comparison."""
    import contextlib
    import io

    from benchmarks.controls import sdar_30b_a3b as controls
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert controls.main(["--seed", "5", "--rehearse"]) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("fault,caught_by", [
    ("plain_causal_mask", "grad:layers.0.wq"),
    ("noisy_rows_read_their_blocks_clean_copy", "grad:layers.0.wq"),
    ("clean_rows_strictly_causal", "grad:layers.0.wq"),
    ("noisy_rows_at_position_r", "grad:layers.0.wq"),
    ("no_loss_weight", "loss"),
    ("loss_over_all_noisy_positions", "loss"),
    ("no_qk_norm", "grad:layers.0.gq"),
    ("sigmoid_router", "grad:layers.0.router"),
    ("no_renormalisation", "grad:layers.0.router"),
    ("bfloat16_router", "grad:layers.0.router"),
    ("ungated_experts", "grad:layers.0.w3"),
])
def test_limits_catch_each_controls_planted_fault(controls_seen, fault,
                                                  caught_by):
    from benchmarks.controls.sdar_30b_a3b import FAULTS
    assert set(FAULTS) <= set(controls_seen)
    assert set(FAULTS.values()) == set(reference.FAULTS)
    seen = controls_seen[fault]
    assert any(name.startswith(caught_by) for name in seen["outside"]), (
        seen["outside"])
    assert set(seen) >= {"loss", "grad_rel_err", "update_rel_err",
                         "routing_share_pooled"}
    assert len(seen["grad_rel_err"]) == len(seen["update_rel_err"]) == len(
        controls_seen["leaves"]) == len(runner.checked_leaves(2, 1))


def test_wrong_masks_move_the_attention_and_leave_the_counts(case,
                                                             program_seen):
    paths, _ = program_seen
    want = _reference_reading(case, paths)
    for switch in ("causal_mask", "block_leak", "clean_strict",
                   "noisy_position"):
        got = _reference_reading(case, paths, **{switch: True})
        assert got[1]["masked"] == want[1]["masked"]
        err = runner.grad_errors(paths, got[2], want[2])
        assert err["layers.0.wq"] > 1e-3, (switch, err["layers.0.wq"])


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
    assert seen["detail"]["dropped_assignments"] == 0
    first = seen["detail"]["first_step_aux"]
    assert first["masked"] == seen["detail"]["reference_aux"]["masked"] > 0
    assert np.asarray(first["load"]).shape == (2, 2)
    assert len(seen["detail"]["step_update_rel_err"]) == len(
        seen["detail"]["grad_rel_err"]) == 23
    counters = seen["counters"]
    assert counters["kernel_fallback"] == 0
    assert counters["moe_dropped_assignments"] == 0
    assert counters["moe_assignments_served"] > 0
    assert counters["blockdiff_rows_per_token"] == 2
    assert 0 < counters["blockdiff_masked_positions"] < (
        128 * line["attempted"])
    names = {name for name, _ in seen["setup_spans"]}
    assert {"setup/init", "setup/program_grads", "setup/reference",
            "setup/compile"} <= names
