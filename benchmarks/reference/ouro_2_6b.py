"""Plain reference for the looped decoder (ByteDance/Ouro-2.6B,
``config.json``, ``model_type`` ``ouro``; "Scaling Latent Reasoning via
Looped Language Models", arXiv:2510.25741): ``jax.numpy``, float32,
``highest`` matmul precision, a Python loop over passes and layers. No
kernel, no scan over layers, no sharding, nothing imported from the
program.

    h(0) = E[tokens]
    h(t) = RMSNorm_f( M_L o ... o M_1 ( h(t-1) ) )        t = 1 .. T
    M(h): a = h + N2(Attn(N1(h)));   M(h) = a + N4(SwiGLU(N3(a)))
    Attn(x): q, k, v = x Wq, x Wk, x Wv in heads of D; q, k turned by the
             rotary embedding: the pair (x_i, x_{i + D/2}) of position s
             by the angle s * theta^(-2i / D); causal
             softmax(q k^T / sqrt(D)) v; Wo. Query head h reads key/value
             head h // (H / H_kv).
    SwiGLU(x) = (silu(x Wg) * (x Wu)) Wd
    l(t)     = cross entropy of h(t) W_head against the targets, a token
    lambda_t = sigmoid(w_g . h(t) + b_g)                  t < T
    p_t      = lambda_t prod_{j<t} (1 - lambda_j),  p_T = prod_{j<T} (1 - lambda_j)
    loss     = mean over tokens of  sum_t p_t l(t) - beta H(p)

Departures from the published description, each also an ``assumed`` entry
of the configuration file: the final norm is applied after every pass and
the normed state is what the next pass reads (config.json does not say
where it sits relative to the loop); sandwich norms, four gains a layer;
one linear unit on the normed state as the exit gate; beta from the
configuration file; no biases, no q/k norm.

Every layer application and every pass's head are rematerialised, and the
attention's score rows exist one block of queries at a time, so that a
gradient at 4,096 positions fits a 16 GB chip; that changes what is
stored, not what is computed.

``lower`` names what is computed a precision below this or a term short,
each entry a bool that may be traced, so that one compiled function gives
every reading the benchmark and its controls compare with:

``operands``         the operands of every weight product rounded to
                     bfloat16, in the product and in both of its
                     transposes, the sums in float32: how the timed
                     program multiplies.
``three_passes``     the third pass's gate forced open: the loss of a
                     stack run three times.
``last_pass_grad``   the weights of every pass but the last held constant:
                     the weight gradient of the last pass only.
``no_pass_norm``     the next pass reads the state before the final norm.
``no_post_norm``     N2 and N4 left out.
``no_rotary``        no rotary embedding.
``theta_10k``        rotary theta 10,000.
``no_gate``          the exit distribution uniform over the passes.
``beta_0``           no entropy term.
``bfloat16_state``   the state between passes rounded to bfloat16.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# the rounded product and the optimizer's first step are the hybrid
# reference's: plain functions of arrays, nothing of its model
from benchmarks.reference.nemotron3_super_120b import (  # noqa: F401
    _bf16, _mm, first_update)

QUERY_BLOCK = 512       # queries whose score rows exist at one time
# what the configuration states
STATED = {"operands": False, "three_passes": False, "last_pass_grad": False,
          "no_pass_norm": False, "no_post_norm": False, "no_rotary": False,
          "theta_10k": False, "no_gate": False, "beta_0": False,
          "bfloat16_state": False}


def _rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rotary(x, theta, lower):
    """x [S, H, D]: the pair (x_i, x_{i + D/2}) of position s turned by
    s * theta^(-2i / D). The frequencies in float64 on the host, the angle
    and the turn in float32."""
    s, _, d = x.shape
    i = np.arange(d // 2, dtype=np.float64)
    freq = jnp.where(lower["theta_10k"],
                     jnp.asarray(10000.0 ** (-2.0 * i / d), jnp.float32),
                     jnp.asarray(float(theta) ** (-2.0 * i / d), jnp.float32))
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    angle = jnp.where(lower["no_rotary"], 0.0, angle)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1)


def _attention(p, x, c, lower):
    s = x.shape[0]
    hq, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    rounded = lower["operands"]
    q = _rotary(_mm(x, p["wq"], rounded).reshape(s, hq, hd),
                c["rope_theta"], lower)
    k = _rotary(_mm(x, p["wk"], rounded).reshape(s, hkv, hd),
                c["rope_theta"], lower)
    v = _mm(x, p["wv"], rounded).reshape(s, hkv, hd)
    k, v = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v))
    block = min(QUERY_BLOCK, s)
    pad = (-s) % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, hq, hd)
    starts = jnp.arange(qb.shape[0]) * block

    @jax.checkpoint
    def rows(inp):
        q_blk, start = inp
        score = jnp.einsum("qhd,khd->hqk", q_blk, k) / jnp.sqrt(
            jnp.float32(hd))
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(s)[None]
        score = jnp.where(seen[None], score, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(score, axis=-1), v)

    out = lax.map(rows, (qb, starts)).reshape(-1, hq * hd)[:s]
    return _mm(out, p["wo"], rounded)


def _layer(p, h, c, lower):
    eps, rounded = c["rms_norm_eps"], lower["operands"]

    def post(y, gain):
        return jnp.where(lower["no_post_norm"], y, _rms(y, gain, eps))
    a = h + post(_attention(p, _rms(h, p["n1"], eps), c, lower), p["n2"])
    x = _rms(a, p["n3"], eps)
    y = _mm(jax.nn.silu(_mm(x, p["w_gate"], rounded))
            * _mm(x, p["w_up"], rounded), p["w_down"], rounded)
    return a + post(y, p["n4"])


def _read(h, head, gate_w, gate_b, targets, rounded):
    """Per token: cross entropy of ``h W_head`` and the gate's logit."""
    logp = jax.nn.log_softmax(_mm(h, head, rounded), axis=-1)
    ce = -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
    return ce, jnp.sum(h * gate_w, axis=-1) + gate_b


def _held(tree, on):
    """``tree`` as a constant where ``on``."""
    return jax.tree.map(
        lambda w: jnp.where(on, lax.stop_gradient(w), w), tree)


def loss_and_aux(params: Dict, tokens, targets, config: Dict,
                 lower: Dict = STATED):
    """params as ``init_looped`` lays them out (``params["layers"]`` a
    list of pieces whose leaves stack the piece's layers); tokens, targets
    ``[B, S]``; ``config`` the configuration file's keys; ``lower`` as the
    module's text says. Returns (loss, aux): ``pass_losses`` ``[T]`` the
    mean cross entropy of each pass, ``exit_p`` ``[T]`` the mean exit
    distribution, ``exit_entropy`` its mean entropy."""
    passes = config["total_ut_steps"]
    eps = config["rms_norm_eps"]
    beta = jnp.where(lower["beta_0"], 0.0, config["exit_entropy_weight"])
    layers = [jax.tree.map(lambda a: a[i], piece)
              for piece in params["layers"]
              for i in range(jax.tree.leaves(piece)[0].shape[0])]
    shared = {"layers": layers, "norm_f": params["norm_f"],
              "head": params["head"]}
    with jax.default_matmul_precision("highest"):
        def sequence(toks, tgts):
            h = params["embed"][toks]
            ces, gates = [], []
            for t in range(passes):
                w = _held(shared, jnp.logical_and(lower["last_pass_grad"],
                                                  t < passes - 1))
                for lp in w["layers"]:
                    h = jax.checkpoint(
                        lambda lp, h: _layer(lp, h, config, lower))(lp, h)
                normed = _rms(h, w["norm_f"], eps)
                ce, gate = jax.checkpoint(_read)(
                    normed, w["head"], params["gate_w"], params["gate_b"],
                    tgts, lower["operands"])
                ces.append(ce)
                gates.append(gate)
                h = _bf16(jnp.where(lower["no_pass_norm"], h, normed),
                          lower["bfloat16_state"])
            lam = jax.nn.sigmoid(jnp.stack(gates[:-1]))         # [T-1, S]
            if passes > 2:
                lam = lam.at[2].set(jnp.where(lower["three_passes"], 1.0,
                                              lam[2]))
            p, left = [], jnp.ones_like(lam[0])
            for t in range(passes - 1):
                p.append(lam[t] * left)
                left = left * (1.0 - lam[t])
            p = jnp.where(lower["no_gate"], 1.0 / passes,
                          jnp.stack(p + [left]))
            entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(
                jnp.where(p > 0, p, 1.0)), 0.0), axis=0)
            ces = jnp.stack(ces)
            return (jnp.sum(jnp.sum(p * ces, axis=0) - beta * entropy),
                    jnp.sum(ces, axis=1), jnp.sum(p, axis=1),
                    jnp.sum(entropy))
        parts = [sequence(tokens[b], targets[b])
                 for b in range(tokens.shape[0])]
        total, ces, p, entropy = (sum(x) / tokens.size for x in zip(*parts))
        return total, {"pass_losses": ces, "exit_p": p,
                       "exit_entropy": entropy}


def loss(params: Dict, tokens, targets, config: Dict) -> jax.Array:
    return loss_and_aux(params, tokens, targets, config)[0]
