"""The flash-attention kernels carry names a device trace can find: each
``pallas_call`` is the whole result of a jitted helper, so the compiled
custom-call is named after the helper (PERF.md, dense_kernels). The
helpers are otherwise invisible: same numbers, same gradients."""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from paddlebox_tpu.models.gpt import (GPTConfig, init_gpt,
                                      make_gpt_train_step)
from paddlebox_tpu.parallel import HybridTopology, build_mesh

# the package re-exports the function under the module's name
fa = importlib.import_module(
    "paddlebox_tpu.ops.pallas_kernels.flash_attention")
HELPERS = ("_flash_fwd_call", "_flash_dq_call", "_flash_dkv_call")
STATIC = dict(scale=0.25, causal=True, block_q=8, block_k=8, interpret=True)


def _operands(seed=0, bh=4, s=16, d=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q3, k3, v3, do3 = (jax.random.normal(k, (bh, s, d), jnp.float32)
                       for k in ks[:4])
    lse3 = jax.random.normal(ks[4], (bh, 1, s), jnp.float32) + 3.0
    delta3 = jax.random.normal(ks[5], (bh, 1, s), jnp.float32)
    scalars = (jnp.zeros((1, 1), jnp.int32), jnp.zeros((1, 1), jnp.int32),
               jnp.full((1, 1), s, jnp.int32))
    return scalars, (q3, k3, v3), (do3, lse3, delta3)


@pytest.mark.parametrize("helper", HELPERS)
def test_helper_returns_exactly_what_its_pallas_call_returns(helper):
    scalars, qkv, bwd = _operands()
    args = scalars + qkv + (() if helper == "_flash_fwd_call" else bwd)
    fn = getattr(fa, helper)
    got = fn(*args, **STATIC)
    want = fn.__wrapped__(*args, **STATIC)      # the bare pallas_call
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # reshapes stay outside: the forward's row stats leave as [BH, 1, Sq]
    if helper == "_flash_fwd_call":
        assert got[1].shape == (4, 1, 16)


@pytest.mark.parametrize("causal", [False, True])
def test_outputs_and_gradients_match_the_reference(causal):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (2, 24, 2, 16), jnp.float32)
               for kk in ks)

    def loss(attn):
        return lambda q, k, v: jnp.sum(jnp.sin(attn(q, k, v)))
    kernel = loss(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=causal, block_q=8, block_k=8, interpret=True))
    plain = loss(lambda q, k, v: fa.flash_attention_reference(
        q, k, v, causal=causal))
    got, got_g = jax.value_and_grad(kernel, argnums=(0, 1, 2))(q, k, v)
    want, want_g = jax.value_and_grad(plain, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w in zip(got_g, want_g):
        np.testing.assert_allclose(g, w, atol=2e-5)


@pytest.fixture(scope="module")
def one_layer_step_text():
    """A one-layer GPT train step lowered for the TPU platform (no chip
    and no libtpu needed: lowering stops at StableHLO). The kernel gate
    asks for a TPU backend; the test answers for it."""
    from paddlebox_tpu.core import flags
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(flags, "pallas_kernels_enabled", lambda: True)
        return _lower_one_layer_step()


def _lower_one_layer_step():
    cfg = GPTConfig(vocab_size=128, d_model=128, n_heads=2, n_layers=1,
                    d_ff=256, max_seq_len=128, attention="flash")
    mesh = build_mesh(HybridTopology(dp=1), devices=jax.devices()[:1])
    specs = {}

    def make(key):
        params, s = init_gpt(key, cfg, pp_stages=1)
        specs.update(s)
        return params
    params = jax.eval_shape(make, jax.random.PRNGKey(0))
    opt = optax.sgd(1e-3)
    opt_state = jax.eval_shape(opt.init, params)
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    step = make_gpt_train_step(cfg, mesh, specs, opt, num_microbatches=1)
    return step.trace(params, opt_state, tokens, tokens).lower(
        lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("helper", HELPERS)
def test_lowered_step_names_the_kernel(one_layer_step_text, helper):
    text = one_layer_step_text
    # the helper is a function of the module, called once a layer scan
    assert re.search(rf"func\.func private @{helper}\b", text)
    body = text[text.index(f"func.func private @{helper}"):]
    body = body[:body.index("\n  }")]
    # ... that holds the kernel's custom-call and returns its results
    assert body.count("tpu_custom_call") == 1
    assert "stablehlo.reshape" not in body
