"""Plain reference for ``nemotron_h`` (NVIDIA-Nemotron-3-Super-120B-A12B,
``config.json``): the stack of single-mixer blocks in ``jax.numpy``,
float32, ``highest`` matmul precision. No kernel, no chunked scan, no
sort, no sharding, nothing imported from the program.

    x_0   = E[tokens]
    x    <- x + mixer(RMSNorm(x))             one mixer per pattern letter
    loss  = mean cross entropy of RMSNorm(x_L) H against the targets

``M``  [z | xBC | dt] = x W_in; xBC <- silu(conv_k(xBC) + b) (causal,
       depthwise); xBC -> x_h [H, P], B, C [G, N]; dt <- softplus(dt +
       dt_bias); A = -exp(A_log);
           h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x) x_t
           y_t = C_t . h_t + D x_t
       one position at a time; y <- RMSNorm(y * silu(z)) in G groups with
       a gain; y W_out.
``*``  q, k, v = x W_q, x W_k, x W_v; query head h reads key/value head
       h // (H_q / H_kv); causal softmax(q k^T / sqrt(d)) v; W_o. No
       rotary embedding.
``E``  s = sigmoid(x W_g); the k largest of s + bias; w = scaling * s /
       (sum of the chosen s + 1e-20); u = x W_down;
       r = sum over chosen AND held experts of w_e W2_e relu(W1_e u)^2;
       r W_up + W2_s relu(W1_s x)^2. Every held expert is applied to
       every token and weighted by w_e or 0.

Departures from the published model (listed in the configuration file):
the multi-token-prediction module is left out, and the layer computes only
the part of the routed sum its held experts give.

The recurrence and the attention are rematerialised in blocks (one chunk
of positions, one block of queries) so that a gradient at 8,192 positions
fits a 16 GB chip; that changes what is stored, not what is computed.

``lower`` names what is computed a precision below this, each entry a
bool that may be traced, so that one compiled function gives every
reading the benchmark compares with:

``operands``  the operands of every weight product rounded to bfloat16,
              in the product and in both of its transposes, the sums in
              float32: how one pass of the MXU reads float32 operands,
              which is how the timed program multiplies. What the timed
              step is compared with.
``state``     the scan's carried state rounded to bfloat16 at every
              position, and
``router``    the router's product in bfloat16: the nearest
              precision below the one the configuration states. Both
              must read outside the benchmark's limits.

``first_update`` is the optimizer's first step on one leaf, so that the
timed step's own parameter change has something plain to be held to.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

SCAN_BLOCK = 128        # positions whose states are recomputed together
QUERY_BLOCK = 512       # queries whose score rows exist at one time
# what the configuration states: nothing below float32
STATED = {"operands": False, "state": False, "router": False}


def _bf16(x, on):
    """``x`` rounded to what bfloat16 holds, where ``on``. An explicit
    rounding: a cast there and back is one the compiler may drop."""
    return jnp.where(on, lax.reduce_precision(x, 8, 7), x)


def _exact(a, b):
    # said here and not left to the ambient precision: a gradient's
    # products are traced after ``loss_and_load`` has returned
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


@jax.custom_vjp
def _mm(a, b, rounded):
    """``a @ b`` for 2-D ``a``, ``b``; where ``rounded``, every product
    (this one and the two its gradient takes) reads its operands through
    bfloat16."""
    return _exact(_bf16(a, rounded), _bf16(b, rounded))


def _mm_fwd(a, b, rounded):
    return _mm(a, b, rounded), (a, b, rounded)


def _mm_bwd(res, g):
    a, b, rounded = res
    g = _bf16(g, rounded)
    return (_exact(g, _bf16(b, rounded).T), _exact(_bf16(a, rounded).T, g),
            None)


_mm.defvjp(_mm_fwd, _mm_bwd)


def _rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _relu2(x):
    return jnp.maximum(x, 0.0) ** 2


def _recurrence(xs, dt, a, bm, cm, d, bf16_state):
    """xs [S, H, P], dt [S, H], a, d [H], bm, cm [S, H, N] -> y [S, H, P]."""
    s, h, p = xs.shape
    n = bm.shape[-1]
    pad = (-s) % SCAN_BLOCK

    def blocks(v):
        v = jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
        return v.reshape((-1, SCAN_BLOCK) + v.shape[1:])

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * b_t)[:, :, None] * x_t[:, None, :])
        state = _bf16(state, bf16_state)
        return state, (jnp.sum(c_t[:, :, None] * state, axis=1)
                       + d[:, None] * x_t)

    @jax.checkpoint
    def block(state, inp):
        return lax.scan(step, state, inp)

    _, y = lax.scan(block, jnp.zeros((h, n, p), jnp.float32),
                    (blocks(xs), blocks(dt), blocks(bm), blocks(cm)))
    return y.reshape(-1, h, p)[:s]


def _mamba(p, x, c, lower):
    s = x.shape[0]
    heads, hd, n, g, k = (c["mamba_num_heads"], c["mamba_head_dim"],
                          c["ssm_state_size"], c["n_groups"],
                          c["conv_kernel"])
    di, gn = heads * hd, g * n
    z, xbc, dt = jnp.split(_mm(x, p["w_in"], lower["operands"]),
                           [di, 2 * di + 2 * gn], axis=-1)
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    conv = p["conv_b"]
    for i in range(k):
        conv = conv + padded[i:i + s] * p["conv_w"][i]
    xbc = jax.nn.silu(conv)
    xs, bm, cm = jnp.split(xbc, [di, di + gn], axis=-1)
    rep = heads // g
    y = _recurrence(
        xs.reshape(s, heads, hd), jax.nn.softplus(dt + p["dt_bias"]),
        -jnp.exp(p["a_log"]),
        jnp.repeat(bm.reshape(s, g, n), rep, axis=1),
        jnp.repeat(cm.reshape(s, g, n), rep, axis=1), p["d"],
        lower["state"])
    y = y.reshape(s, di) * jax.nn.silu(z)
    y = _rms(y.reshape(s, g, di // g), p["gnorm"].reshape(g, di // g),
             c["norm_eps"])
    return _mm(y.reshape(s, di), p["w_out"], lower["operands"]), None


def _attention(p, x, c, lower):
    s = x.shape[0]
    hq, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    rounded = lower["operands"]
    q = _mm(x, p["wq"], rounded).reshape(s, hq, hd)
    k = jnp.repeat(_mm(x, p["wk"], rounded).reshape(s, hkv, hd),
                   hq // hkv, axis=1)
    v = jnp.repeat(_mm(x, p["wv"], rounded).reshape(s, hkv, hd),
                   hq // hkv, axis=1)
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
    return _mm(out, p["wo"], rounded), None


def _experts(p, x, c, lower):
    first, count = c["experts_held"]
    rounded = lower["operands"]
    bf16 = jnp.bfloat16
    scores = jax.nn.sigmoid(jnp.where(
        lower["router"],
        (x.astype(bf16) @ p["gate"].astype(bf16)).astype(jnp.float32),
        x @ p["gate"]))
    _, idx = lax.top_k(scores + p["bias"], c["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weights = c["routed_scaling_factor"] * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    u = _mm(x, p["w_down"], rounded)
    routed = jnp.zeros_like(u)
    load = []
    for e in range(count):
        mine = idx == first + e                               # [T, k]
        w_e = jnp.sum(jnp.where(mine, weights, 0.0), axis=-1)
        routed = routed + w_e[:, None] * _mm(
            _relu2(_mm(u, p["w1"][e], rounded)), p["w2"][e], rounded)
        load.append(jnp.sum(mine))
    y = (_mm(routed, p["w_up"], rounded)
         + _mm(_relu2(_mm(x, p["ws1"], rounded)), p["ws2"], rounded))
    return y, jnp.stack(load)


_MIXER = {"M": _mamba, "*": _attention, "E": _experts}


def loss_and_load(params: Dict, tokens, targets, config: Dict,
                  lower: Dict = STATED):
    """params as ``init_nemotron_h`` lays them out; tokens, targets
    ``[B, S]``; ``config`` the configuration file's keys; ``lower`` as the
    module's text says. Returns (mean loss, load ``[E layers, held]``:
    assignments per held expert, summed over the batch)."""
    with jax.default_matmul_precision("highest"):
        def sequence(toks):
            x = params["embed"][toks]
            load = []
            for letter, lp in zip(config["hybrid_override_pattern"],
                                  params["layers"]):
                def apply(lp, x, letter=letter):
                    y, served = _MIXER[letter](
                        lp, _rms(x, lp["norm"], config["norm_eps"]), config,
                        lower)
                    return x + y, served
                x, served = jax.checkpoint(apply)(lp, x)
                if served is not None:
                    load.append(served)
            x = _rms(x, params["norm_f"], config["norm_eps"])
            return jax.nn.log_softmax(
                _mm(x, params["head"], lower["operands"]), axis=-1), load
        total, loads = 0.0, []
        for b in range(tokens.shape[0]):
            logp, load = sequence(tokens[b])
            total = total - jnp.sum(jnp.take_along_axis(
                logp, targets[b][:, None], axis=-1))
            loads.append(jnp.stack(load) if load else jnp.zeros(
                (0, config["experts_held"][1]), jnp.int32))
        return total / tokens.size, sum(loads)


def loss(params: Dict, tokens, targets, config: Dict) -> jax.Array:
    return loss_and_load(params, tokens, targets, config)[0]


def first_update(grad, param, learning_rate: float):
    """What Adafactor (Shazeer & Stern 2018, with the defaults the
    configuration's ``optimizer`` names) adds to ``param`` in its first
    step, whose second-moment estimates are that step's alone: ``grad``
    over the root of its squares' mean by row times by column over the
    mean of all (for a leaf whose two largest axes are both at least 128
    long; element by element for any other, which leaves the sign), cut
    to a root mean square of 1, times the learning rate and the
    parameter's own root mean square (at least 1e-3)."""
    sq = grad * grad + 1e-30
    axes = sorted(range(grad.ndim), key=lambda i: grad.shape[i])[-2:]
    if grad.ndim >= 2 and grad.shape[axes[0]] >= 128:
        small, large = axes
        rows = jnp.mean(sq, axis=large, keepdims=True)
        cols = jnp.mean(sq, axis=small, keepdims=True)
        u = (grad / jnp.sqrt(rows / jnp.mean(rows, axis=small, keepdims=True))
             / jnp.sqrt(cols))
    else:
        u = grad / jnp.sqrt(sq)
    u = u / jnp.maximum(1.0, jnp.sqrt(jnp.mean(u * u)))
    scale = jnp.maximum(jnp.sqrt(jnp.mean(param * param)), 1e-3)
    return -learning_rate * scale * u
