"""Plain reference for ``gpt2_medium``: the GPT-2 decoder (Radford et al.
2019; ``openai-community/gpt2-medium`` ``config.json``) as the program's
block computes it, in ``jax.numpy``, float32, ``highest`` matmul precision:
no flash kernel, no scan, no sharding.

    x_0   = E[tokens] + P[positions]
    a     = x + Attn(LN1(x)) Wo            causal softmax(QK^T / sqrt(d_h)) V
    x'    = a + (gelu_tanh(LN2(a) Wi + bi) Wo2 + bo2)
    loss  = mean cross entropy of LN_f(x_L) H against the targets

Departures from the published model, all the program's
(``models/gpt.py``) and listed in the configuration file: the output head
H is its own matrix (GPT-2 ties it to E), and the QKV and attention-output
projections carry no bias. The fused QKV weight's columns are head-major
``[head][q|k|v][d_h]``.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def _ln(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def loss(params: Dict, tokens, targets, *, n_head: int,
         layer_norm_epsilon: float = 1e-5) -> jax.Array:
    """params as ``init_gpt`` lays them out (layers stacked
    ``[1, n_layer, ...]``); tokens, targets ``[B, S]`` -> mean loss."""
    with jax.default_matmul_precision("highest"):
        b, s = tokens.shape
        d = params["embed"].shape[1]
        dh = d // n_head
        x = params["embed"][tokens] + params["pos"][:s][None]
        layers = jax.tree.map(lambda a: a[0], params["layers"])
        n_layer = layers["wqkv"].shape[0]
        mask = jnp.tril(jnp.ones((s, s), bool))
        for i in range(n_layer):
            lp = jax.tree.map(lambda a: a[i], layers)
            h = _ln(x, lp["ln1_g"], lp["ln1_b"], layer_norm_epsilon)
            qkv = (h @ lp["wqkv"]).reshape(b, s, n_head, 3, dh)
            q, k, v = qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]
            score = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
                jnp.float32(dh))
            score = jnp.where(mask[None, None], score, -jnp.inf)
            attn = jnp.einsum("bhqk,bkhd->bqhd",
                              jax.nn.softmax(score, axis=-1), v)
            x = x + attn.reshape(b, s, d) @ lp["wo"]
            h = _ln(x, lp["ln2_g"], lp["ln2_b"], layer_norm_epsilon)
            u = jax.nn.gelu(h @ lp["wi"] + lp["bi"], approximate=True)
            x = x + u @ lp["wo2"] + lp["bo2"]
        x = _ln(x, params["lnf_g"], params["lnf_b"], layer_norm_epsilon)
        logits = x @ params["head"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return -jnp.mean(picked)
