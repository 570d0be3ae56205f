"""BERT encoder for masked-LM pretraining.

Role of the reference's Fleet data-parallel BERT path (static-graph program
+ per-grad ``c_allreduce_sum``; SURVEY.md §3.4). TPU-first: one jitted
data-parallel train step — batch sharded over dp, params replicated,
gradient reduction from differentiating the global-mean loss under
shard_map (or plain pjit sharding annotations).

Reuses the GPT block machinery with bidirectional attention and adds MLM
heads; the hybrid-parallel path (tp/sp axes) composes exactly as in
models/gpt.py.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    d_ff: int = 3072
    max_seq_len: int = 512
    type_vocab: int = 2


def _ln(x, g, b, eps=1e-12):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def init_bert(rng: jax.Array, cfg: BertConfig) -> Dict[str, Any]:
    d, f = cfg.d_model, cfg.d_ff
    keys = iter(jax.random.split(rng, 8 * cfg.n_layers + 8))
    s = 0.02

    def nrm(shape):
        return jax.random.normal(next(keys), shape) * s

    layers = []
    for _ in range(cfg.n_layers):
        layers.append({
            "wqkv": nrm((d, 3 * d)), "bqkv": jnp.zeros((3 * d,)),
            "wo": nrm((d, d)), "bo": jnp.zeros((d,)),
            "ln1_g": jnp.ones((d,)), "ln1_b": jnp.zeros((d,)),
            "wi": nrm((d, f)), "bi": jnp.zeros((f,)),
            "wo2": nrm((f, d)), "bo2": jnp.zeros((d,)),
            "ln2_g": jnp.ones((d,)), "ln2_b": jnp.zeros((d,)),
        })
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return {
        "tok": nrm((cfg.vocab_size, d)),
        "pos": nrm((cfg.max_seq_len, d)),
        "typ": nrm((cfg.type_vocab, d)),
        "emb_ln_g": jnp.ones((d,)), "emb_ln_b": jnp.zeros((d,)),
        "layers": stacked,
        "mlm_w": nrm((d, d)), "mlm_b": jnp.zeros((d,)),
        "mlm_ln_g": jnp.ones((d,)), "mlm_ln_b": jnp.zeros((d,)),
        "mlm_out_b": jnp.zeros((cfg.vocab_size,)),
    }


def bert_encode(params: Dict, cfg: BertConfig, tokens: jax.Array,
                type_ids: jax.Array | None = None,
                attn_mask: jax.Array | None = None) -> jax.Array:
    """tokens [B, S] → hidden [B, S, D]."""
    b, s = tokens.shape
    hd = cfg.d_model // cfg.n_heads
    x = params["tok"][tokens] + params["pos"][jnp.arange(s)][None]
    if type_ids is not None:
        x = x + params["typ"][type_ids]
    x = _ln(x, params["emb_ln_g"], params["emb_ln_b"])

    if attn_mask is not None:
        bias = jnp.where(attn_mask[:, None, None, :] > 0, 0.0, -1e30)
    else:
        bias = None

    def block(x, p):
        in_dtype = x.dtype
        qkv = (jnp.dot(x, p["wqkv"], preferred_element_type=jnp.float32)
               + p["bqkv"]).reshape(b, s, cfg.n_heads, 3, hd)
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * hd ** -0.5
        if bias is not None:
            sc = sc + bias.transpose(0, 2, 1, 3)
        a = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, v,
                       preferred_element_type=jnp.float32)
        o = o.reshape(b, s, cfg.d_model)
        o = jnp.dot(o, p["wo"], preferred_element_type=jnp.float32) + p["bo"]
        x = _ln(x + o, p["ln1_g"], p["ln1_b"])
        h = jax.nn.gelu(
            jnp.dot(x, p["wi"], preferred_element_type=jnp.float32)
            + p["bi"])
        h = jnp.dot(h, p["wo2"], preferred_element_type=jnp.float32) + p["bo2"]
        out = _ln(x + h, p["ln2_g"], p["ln2_b"])
        # Keep the residual stream in the policy dtype (bf16 under AMP):
        # the f32-accumulating dots must not widen the scan carry.
        return out.astype(in_dtype), None

    x, _ = lax.scan(block, x, params["layers"])
    return x


def bert_mlm_loss(params: Dict, cfg: BertConfig, tokens: jax.Array,
                  targets: jax.Array, mask: jax.Array,
                  axis_name: str | None = None) -> jax.Array:
    """Masked-LM loss. mask [B, S] — 1 where the token is predicted.
    Weight-tied output embedding (standard BERT)."""
    h = bert_encode(params, cfg, tokens)
    h = jax.nn.gelu(
        jnp.dot(h, params["mlm_w"], preferred_element_type=jnp.float32)
        + params["mlm_b"])
    h = _ln(h, params["mlm_ln_g"], params["mlm_ln_b"])
    logits = jnp.dot(h, params["tok"].T,
                     preferred_element_type=jnp.float32) + params["mlm_out_b"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    losses = (logz - tgt) * mask
    total = jnp.sum(losses)
    count = jnp.sum(mask)
    if axis_name is not None:
        total = lax.psum(total, axis_name)
        count = lax.psum(count, axis_name)
    return total / jnp.maximum(count, 1.0)
