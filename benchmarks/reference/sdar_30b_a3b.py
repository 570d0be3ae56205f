"""Plain reference for block-diffusion training of the SDAR expert decoder
(JetLM/SDAR-30B-A3B-Chat, ``config.json``, ``model_type`` ``sdar_moe``;
the objective of "Block Diffusion: Interpolating Between Autoregressive
and Diffusion Language Models", arXiv:2503.09573): ``jax.numpy``, float32,
``highest`` matmul precision, a Python loop over layers and a loop over held
experts (``lax.scan``: one body, compiled once). No kernel, no scan over
layers, no sort, no sharding, nothing imported from the program.

    x [L] tokens in blocks of B; block j has a level t_j; position i of
    the noisy copy is MASK where ``masked[i]`` (drawn with probability
    t_{b(i)} by the caller), else x_i.
    rows r in [0, 2L): clean rows 0 .. L-1, noisy rows L .. 2L-1;
    noisy(r) = r >= L, p(r) = r mod L, b(r) = p(r) // B.
    h = E[x || x_noisy]; per layer:
      a = RMSNorm(h; g1); q, k, v = a Wq, a Wk, a Wv in heads of D;
      q <- RMSNorm_D(q; gq), k <- RMSNorm_D(k; gk) (one gain of D each);
      rotary (rotate-half, theta) on q, k at position p(r);
      row q reads row k iff (not noisy(k) and b(k) < b(q))
                          or (noisy(k) == noisy(q) and b(k) == b(q));
      o = softmax(q k^T / sqrt(D) + M) v, query head j reading key/value
      head j // (H / H_kv); h <- h + o Wo.
      u = RMSNorm(h; g2); s = softmax(u Wr) over all routed experts; E(u)
      the k largest (lower index first among equals); w_e = s_e / sum of
      the chosen s; h <- h + sum over chosen AND held e of
      w_e (silu(u W1_e) * (u W3_e)) W2_e. Every held expert is applied to
      every row and weighted by w_e or 0.
    loss = 1 / L  sum over masked i of
           (1 / t_{b(i)}) CE(head(RMSNorm(h_{L+i})), x_i)

Departures from the published model, each an ``assumed`` or ``reduced``
entry of the configuration file: block length, noise schedule and loss
weight (the config gives none), no shift between a masked position and its
label, the mask token is the last id of the held vocabulary slice, q/k
norms as in ``qwen3_moe``, no router auxiliary loss, and the layer computes
only the part its held experts give.

Every layer is rematerialised and the attention's score rows exist one
block of queries at a time, so that a gradient at 8,192 rows fits a 16 GB
chip; that changes what is stored, not what is computed.

``lower`` names what is computed a precision below this or a term wrong,
each entry a bool that may be traced, so that one compiled function gives
every reading the benchmark and its controls compare with:

``operands``        the operands of every weight product rounded to
                    bfloat16, in the product and in both of its
                    transposes, the sums in float32: how the timed program
                    multiplies.
``causal_mask``     a plain causal mask over the 2L rows.
``block_leak``      a noisy row also reads its own block's clean copy.
``clean_strict``    a clean row reads clean rows up to itself, not to the
                    end of its block.
``noisy_position``  rotary position r, not r mod L.
``no_weight``       weight 1 in place of 1 / t.
``all_positions``   the loss over every noisy position, masked or not.
``no_qk_norm``      no RMSNorm on q and k.
``sigmoid_router``  sigmoid scores in place of the softmax.
``no_renorm``       the chosen experts' probabilities as they are.
``router_bf16``     the router's product in bfloat16.
``ungated``         experts without W3: silu(u W1) W2.
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
    _mm, first_update)

QUERY_BLOCK = 256       # queries whose score rows exist at one time
FAULTS = ("causal_mask", "block_leak", "clean_strict", "noisy_position",
          "no_weight", "all_positions", "no_qk_norm", "sigmoid_router",
          "no_renorm", "router_bf16", "ungated")
# what the configuration states
STATED = dict({"operands": False}, **{name: False for name in FAULTS})


def unstack(params: Dict) -> Dict:
    """``params`` as the program's ``init_block_diffusion`` lays them out
    (``layers`` a list of pieces whose leaves stack the piece's layers)
    with ``layers`` a list of one entry a layer, which is what the
    equations below are written over: a function that cuts the layer's
    dict of leaves out of its piece. ``loss_and_aux`` calls it inside the
    layer's rematerialised region, so that the cut is made again in the
    backward pass and no second copy of the weights is held between."""
    return dict(params, layers=[
        (lambda piece=piece, i=i: jax.tree.map(lambda a: a[i], piece))
        for piece in params["layers"]
        for i in range(jax.tree.leaves(piece)[0].shape[0])])


def _rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rotary(x, positions, theta):
    """x [S, H, D]: the pair (x_i, x_{i + D/2}) of row s turned by
    positions[s] * theta^(-2i / D). The frequencies in float64 on the
    host, the angle and the turn in float32."""
    d = x.shape[-1]
    i = np.arange(d // 2, dtype=np.float64)
    freq = jnp.asarray(float(theta) ** (-2.0 * i / d), jnp.float32)
    angle = (positions.astype(jnp.float32)[:, None] * freq[None, :])[:, None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1)


def may_read(q_rows, seq: int, block: int, lower: Dict = STATED):
    """Boolean ``[len(q_rows), 2 seq]``: may row q read row k? The rule of
    the module's text by broadcasting, with ``lower``'s wrong masks."""
    k_rows = jnp.arange(2 * seq)
    nq, nk = (q_rows >= seq)[:, None], (k_rows >= seq)[None, :]
    pq, pk = (q_rows % seq)[:, None], (k_rows % seq)[None, :]
    bq, bk = pq // block, pk // block
    rule = (~nk & (bk < bq)) | ((nk == nq) & (bk == bq))
    rule = rule | (lower["block_leak"] & nq & ~nk & (bk == bq))
    rule = jnp.where(lower["clean_strict"] & ~nq & ~nk, pk <= pq, rule)
    return jnp.where(lower["causal_mask"],
                     k_rows[None, :] <= q_rows[:, None], rule)


def _attention(p, x, c, lower):
    rows, seq = x.shape[0], x.shape[0] // 2
    hq, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    rounded, eps = lower["operands"], c["rms_norm_eps"]
    r = jnp.arange(rows)
    positions = jnp.where(lower["noisy_position"], r, r % seq)

    def head_norm(t, gain):
        return jnp.where(lower["no_qk_norm"], t, _rms(t, gain, eps))
    q = _rotary(head_norm(_mm(x, p["wq"], rounded).reshape(rows, hq, hd),
                          p["gq"]), positions, c["rope_theta"])
    k = _rotary(head_norm(_mm(x, p["wk"], rounded).reshape(rows, hkv, hd),
                          p["gk"]), positions, c["rope_theta"])
    v = _mm(x, p["wv"], rounded).reshape(rows, hkv, hd)
    k, v = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v))
    block = min(QUERY_BLOCK, rows)
    pad = (-rows) % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, hq, hd)
    starts = jnp.arange(qb.shape[0]) * block

    @jax.checkpoint
    def some_rows(inp):
        q_blk, start = inp
        score = jnp.einsum("qhd,khd->hqk", q_blk, k) / jnp.sqrt(
            jnp.float32(hd))
        # a padded query row reads row 0, and is cut off below
        seen = may_read(jnp.minimum(start + jnp.arange(block), rows - 1),
                        seq, c["block_length"], lower)
        score = jnp.where(seen[None], score, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(score, axis=-1), v)

    out = lax.map(some_rows, (qb, starts)).reshape(-1, hq * hd)[:rows]
    return _mm(out, p["wo"], rounded)


def _experts(p, u, c, lower):
    first, count = c["experts_held"]
    rounded = lower["operands"]
    bf16 = jnp.bfloat16
    logits = jnp.where(
        lower["router_bf16"],
        (u.astype(bf16) @ p["router"].astype(bf16)).astype(jnp.float32),
        u @ p["router"])
    scores = jnp.where(lower["sigmoid_router"], jax.nn.sigmoid(logits),
                       jax.nn.softmax(logits, axis=-1))
    _, idx = lax.top_k(scores, c["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weights = jnp.where(lower["no_renorm"], chosen,
                        chosen / jnp.sum(chosen, axis=-1, keepdims=True))

    @jax.checkpoint
    def add_expert(y, expert):
        e, w1, w3, w2 = expert
        mine = idx == first + e                               # [rows, k]
        w_e = jnp.sum(jnp.where(mine, weights, 0.0), axis=-1)
        gate = jax.nn.silu(_mm(u, w1, rounded))
        hidden = jnp.where(lower["ungated"], gate,
                           gate * _mm(u, w3, rounded))
        return y + w_e[:, None] * _mm(hidden, w2, rounded), jnp.sum(mine)
    # one held expert after the other, each applied to every row
    return lax.scan(add_expert, jnp.zeros_like(u),
                    (jnp.arange(count), p["w1"], p["w3"], p["w2"]))


def _layer(p, h, c, lower):
    eps = c["rms_norm_eps"]
    h = h + _attention(p, _rms(h, p["n1"], eps), c, lower)
    y, load = _experts(p, _rms(h, p["n2"], eps), c, lower)
    return h + y, load


def loss_and_aux(params: Dict, tokens, levels, masked, config: Dict,
                 lower: Dict = STATED):
    """``params``: ``embed``, ``head``, ``norm_f`` and ``layers``, a list
    with one entry a layer: a dict of its leaves (``w1[e]`` / ``w3[e]`` /
    ``w2[e]`` the e-th held expert's, stacked) or a function that returns
    it (``unstack``); tokens ``[B, L]``, levels ``[B, L / block]``,
    masked ``[B, L]`` bool; ``config`` the configuration file's keys;
    ``lower`` as the module's text says. Returns (loss, aux): ``load``
    ``[layers, held]`` assignments per held expert, ``masked`` the masked
    positions, ``weight`` the sum of their weights."""
    block, eps = config["block_length"], config["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        def sequence(toks, level, hidden):
            seq = toks.shape[0]
            rows = jnp.concatenate(
                [toks, jnp.where(hidden, config["mask_token_id"], toks)])
            h = params["embed"][rows]
            loads = []
            for lp in params["layers"]:
                h, load = jax.checkpoint(lambda h, lp=lp: _layer(
                    lp() if callable(lp) else lp, h, config, lower))(h)
                loads.append(load)
            logp = jax.nn.log_softmax(_mm(
                _rms(h[seq:], params["norm_f"], eps), params["head"],
                lower["operands"]), axis=-1)
            ce = -jnp.take_along_axis(logp, toks[:, None], axis=-1)[:, 0]
            weight = jnp.where(lower["no_weight"], 1.0,
                               1.0 / jnp.repeat(level, block))
            weight = jnp.where(hidden | lower["all_positions"], weight, 0.0)
            return jnp.sum(weight * ce), jnp.stack(loads), jnp.sum(weight)
        parts = [sequence(tokens[b], levels[b], masked[b])
                 for b in range(tokens.shape[0])]
        total, load, weight = (sum(x) for x in zip(*parts))
        return total / tokens.size, {
            "load": load, "masked": jnp.sum(masked, dtype=jnp.int32),
            "weight": weight}


def loss(params: Dict, tokens, levels, masked, config: Dict) -> jax.Array:
    return loss_and_aux(params, tokens, levels, masked, config)[0]
