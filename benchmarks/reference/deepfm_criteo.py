"""Plain reference for ``deepfm_criteo``: DeepFM (Guo et al.,
arXiv:1703.04247, section 2, eq. 1-4) in ``jax.numpy``, float32,
``highest`` matmul precision, no kernels, no sharding, no pass table.

One feasign per slot, so a sample is ``emb [S, D]`` and ``w [S]`` (the rows
of its S keys) and ``dense [Dd]``:

    y_fm   = bias + sum_s w_s + 1/2 * sum_d ((sum_s v_sd)^2 - sum_s v_sd^2)
    y_dnn  = MLP(concat(v_1 .. v_S, dense)),  ReLU between layers
    logit  = y_fm + y_dnn,   p = sigmoid(logit)

Departures from the paper, all the program's: the dense (numeric)
features enter the tower only, raw; no dropout; embedding width 16, not
10. The statistics are the ones the program's AUC state reports, computed
exactly here (rank AUC, not a 2^16-bucket histogram).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def logits(params: Dict, emb, w, dense) -> jax.Array:
    """emb [B, S, D], w [B, S], dense [B, Dd] -> logits [B]."""
    with jax.default_matmul_precision("highest"):
        emb = jnp.asarray(emb, jnp.float32)
        first = jnp.sum(jnp.asarray(w, jnp.float32), axis=1) + params["bias"]
        sum_v = jnp.sum(emb, axis=1)
        second = 0.5 * jnp.sum(sum_v * sum_v - jnp.sum(emb * emb, axis=1),
                               axis=-1)
        x = jnp.concatenate([emb.reshape(emb.shape[0], -1),
                             jnp.asarray(dense, jnp.float32)], axis=-1)
        layers = params["mlp"]
        for i, layer in enumerate(layers):
            x = x @ jnp.asarray(layer["w"], jnp.float32) + layer["b"]
            if i + 1 < len(layers):
                x = jnp.maximum(x, 0.0)
        return first + second + x[:, 0]


def rank_auc(prob: np.ndarray, labels: np.ndarray) -> float:
    """P(score of a positive > score of a negative), ties counting half."""
    order = np.argsort(prob, kind="stable")
    sorted_p = prob[order]
    # average rank over ties
    first = np.searchsorted(sorted_p, sorted_p, side="left")
    last = np.searchsorted(sorted_p, sorted_p, side="right")
    ranks = np.empty(prob.shape[0], np.float64)
    ranks[order] = (first + last + 1) / 2.0
    pos = labels > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2.0)
                 / (n_pos * n_neg))


def evaluate(params: Dict, emb, w, dense, labels) -> Dict[str, float]:
    z = np.asarray(logits(params, emb, w, dense), np.float64)
    y = np.asarray(labels, np.float64)
    prob = 1.0 / (1.0 + np.exp(-z))
    bce = np.maximum(z, 0.0) - z * y + np.log1p(np.exp(-np.abs(z)))
    return {"loss": float(bce.mean()), "auc": rank_auc(prob, y),
            "predicted_ctr": float(prob.mean()),
            "mae": float(np.abs(prob - y).mean()),
            "rmse": float(np.sqrt(((prob - y) ** 2).mean()))}
