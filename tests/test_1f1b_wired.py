"""1F1B wired into the production paths (VERDICT r02 task 5):
- make_gpt_train_step(schedule="1f1b") parity vs the GPipe path
- PipelineTrainer with TrainerDesc.pipeline_schedule="1f1b" parity
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from paddlebox_tpu.models.gpt import GPTConfig, init_gpt, make_gpt_train_step
from paddlebox_tpu.parallel import HybridTopology, build_mesh, pp
from paddlebox_tpu.train.trainer import PipelineTrainer, TrainerDesc

CFG = GPTConfig(vocab_size=64, d_model=16, n_heads=2, n_layers=4, d_ff=32,
                max_seq_len=64, attention="ring")


@pytest.fixture
def devices8():
    d = jax.devices()
    assert len(d) >= 8
    return d[:8]


@pytest.fixture
def data():
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, CFG.vocab_size, (8, 32)).astype(np.int32)
    targets = rng.integers(0, CFG.vocab_size, (8, 32)).astype(np.int32)
    return jnp.asarray(tokens), jnp.asarray(targets)


@pytest.mark.parametrize("topo", [
    dict(dp=2, pp=2, sp=1, mp=2),
    # The alternate topologies pin the same parity property; they live
    # in the slow tier so tier-1 carries one compile of each schedule.
    pytest.param(dict(dp=1, pp=2, sp=2, mp=2), marks=pytest.mark.slow),
    pytest.param(dict(pp=4, dp=2), marks=pytest.mark.slow),
])
def test_gpt_1f1b_matches_gpipe(devices8, data, topo):
    """Same params/data: one 1F1B step produces the same loss and the
    same updated params as one GPipe step (both are exact schedules of
    the identical objective)."""
    mesh = build_mesh(HybridTopology(**topo), devices8)
    pp_stages = topo.get("pp", 1)
    tokens, targets = data
    out = {}
    for schedule in ("gpipe", "1f1b"):
        params, specs = init_gpt(jax.random.PRNGKey(0), CFG,
                                 pp_stages=pp_stages)
        opt = optax.sgd(1e-2)
        opt_state = opt.init(params)
        step = make_gpt_train_step(CFG, mesh, specs, opt,
                                   num_microbatches=4, schedule=schedule)
        p2, _, loss = step(params, opt_state, tokens, targets)
        out[schedule] = (float(loss), jax.device_get(p2))
    np.testing.assert_allclose(out["1f1b"][0], out["gpipe"][0], rtol=2e-5)
    ga, gb = out["gpipe"][1], out["1f1b"][1]
    for path, a in jax.tree_util.tree_leaves_with_path(ga):
        b = a  # placeholder; compare via tree below
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4,
                                                atol=2e-6),
        ga, gb)


@pytest.mark.slow  # 5 s learn loop; the 1F1B-vs-GPipe parity above stays tier-1; tier-1 keeps its 870 s window (PR 21)
def test_gpt_1f1b_learns(devices8, data):
    mesh = build_mesh(HybridTopology(dp=2, pp=2, sp=1, mp=2), devices8)
    params, specs = init_gpt(jax.random.PRNGKey(1), CFG, pp_stages=2)
    tokens, targets = data
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)
    step = make_gpt_train_step(CFG, mesh, specs, opt, num_microbatches=4,
                               schedule="1f1b")
    losses = []
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], losses


def _make_pipeline_trainer(schedule):
    rng = np.random.default_rng(0)
    dim = 8
    stage_params = [
        {"w": jnp.asarray(rng.normal(0, 0.5, (dim, dim)), jnp.float32)}
        for _ in range(8)]
    stacked = pp.stack_stage_params(stage_params)

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])

    def loss_head(y, batch):
        return jnp.mean((jnp.sum(y, -1) - batch["y"]) ** 2)

    t = PipelineTrainer(stage_fn, stacked, loss_head, optax.sgd(3e-3))
    t.initialize(TrainerDesc(num_micro_batches=8, log_every=0,
                             pipeline_schedule=schedule))
    return t


def test_pipeline_trainer_1f1b_matches_gpipe(devices8):
    mesh = build_mesh(HybridTopology(pp=8))
    rng = np.random.default_rng(1)
    batches = []
    for _ in range(4):
        x = rng.normal(0, 1, (16, 8)).astype(np.float32)
        batches.append({"x": jnp.asarray(x),
                        "y": jnp.asarray(np.sin(x.sum(-1)))})
    results = {}
    for schedule in ("gpipe", "1f1b"):
        t = _make_pipeline_trainer(schedule)
        t.init_trainer_env(mesh)
        stats = t.run(iter(batches))
        results[schedule] = (stats, jax.device_get(t.params))
    sa, sb = results["gpipe"][0], results["1f1b"][0]
    np.testing.assert_allclose(sb["loss_first"], sa["loss_first"],
                               rtol=2e-5)
    np.testing.assert_allclose(sb["loss_last"], sa["loss_last"], rtol=2e-4)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4,
                                                atol=2e-6),
        results["gpipe"][1], results["1f1b"][1])
