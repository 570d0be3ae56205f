"""Exact bucketed AUC + calibration statistics.

Role of ``BasicAucCalculator`` (``fleet/metrics.h:46``, ``metrics.cc:33-355``):
- ``add_data``: bucket = pred * num_buckets; ``_table[label][bucket] += 1``
- distributed: allreduce-sum both histograms (metrics.cc:286-292)
- ``computeBucketAuc``: sweep buckets high→low accumulating trapezoid area
- side stats: actual ctr, predicted ctr, mae, rmse, bucket error

and ``WuAucMetricMsg`` per-user AUC (``metrics.h:306``).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.core import flags


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class AucState:
    """Device-side accumulator (all replicated across dp after psum).

    table [2, num_buckets] float32 — pos/neg prediction histograms;
    scalar sums for calibration stats.
    """

    table: jax.Array
    abserr: jax.Array
    sqrerr: jax.Array
    pred_sum: jax.Array
    label_sum: jax.Array
    count: jax.Array

    def tree_flatten(self):
        return ((self.table, self.abserr, self.sqrerr, self.pred_sum,
                 self.label_sum, self.count), None)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)


def auc_state_init(num_buckets: Optional[int] = None) -> AucState:
    nb = num_buckets or flags.flag("auc_num_buckets")

    def z():
        # Distinct buffers per field: a shared constant would break buffer
        # donation (same buffer donated N times).
        return jnp.zeros((), jnp.float32)

    return AucState(table=jnp.zeros((2, nb), jnp.float32),
                    abserr=z(), sqrerr=z(), pred_sum=z(), label_sum=z(),
                    count=z())


def auc_accumulate(state: AucState, preds: jax.Array, labels: jax.Array,
                   valid: Optional[jax.Array] = None,
                   axis: Optional[str] = None) -> AucState:
    """Accumulate a batch (device-side, jit/shard_map-safe).

    preds/labels [B] float32 in [0,1]/{0,1}; valid [B] bool masks padding
    rows. When ``axis`` is given (inside shard_map) the per-batch increment
    is psum'd over it so the state stays replicated — the role of the
    Gloo/MPI allreduce, paid incrementally.
    """
    nb = state.table.shape[1]
    w = jnp.ones_like(preds) if valid is None else valid.astype(preds.dtype)
    bucket = jnp.clip((preds * nb).astype(jnp.int32), 0, nb - 1)
    pos = (labels > 0.5).astype(preds.dtype) * w
    # ONE width-2 scatter-add builds BOTH histograms: each sample adds
    # its (neg_w, pos_w) column at its bucket. XLA TPU scatter pays a
    # ~5 ms fixed cost per OP (r02 chip run), so the
    # split show/click form — one scatter per label row, or the flat
    # segment_sum over [2*nb] whose index arithmetic defeats the
    # unique-window lowering — pays the overhead twice for the same
    # bytes. Column-major update ([:, bucket]) keeps the state layout
    # [2, nb] unchanged for checkpoints and compute_from_table.
    inc_table = jnp.zeros((2, nb), preds.dtype).at[:, bucket].add(
        jnp.stack([w - pos, pos], axis=0))
    err = (preds - labels) * w
    inc = (inc_table, jnp.sum(jnp.abs(err)), jnp.sum(err * err),
           jnp.sum(preds * w), jnp.sum(labels * w), jnp.sum(w))
    if axis is not None:
        inc = jax.lax.psum(inc, axis)
    return AucState(table=state.table + inc[0],
                    abserr=state.abserr + inc[1],
                    sqrerr=state.sqrerr + inc[2],
                    pred_sum=state.pred_sum + inc[3],
                    label_sum=state.label_sum + inc[4],
                    count=state.count + inc[5])


def auc_compute(state: AucState) -> Dict[str, float]:
    """Host-side final sweep (role of computeBucketAuc + calculate_bucket_error,
    metrics.cc:124-391). Returns auc, bucket_error, actual/predicted ctr,
    mae, rmse — via the sweep shared with the host calculator."""
    from paddlebox_tpu.metrics.registry import compute_from_table
    return compute_from_table(
        np.asarray(state.table, np.float64), float(state.abserr),
        float(state.sqrerr), float(state.pred_sum), float(state.label_sum),
        float(state.count))


def wuauc_accumulate(user_ids: np.ndarray, preds: np.ndarray,
                     labels: np.ndarray) -> Tuple[float, float, int]:
    """(wauc_sum, weight_sum, user_count) over one uid-complete partition
    of records — partitions (e.g. uid-hash spill buckets) sum, since each
    user's records live in exactly one partition."""
    order = np.argsort(user_ids, kind="stable")
    uids, preds, labels = user_ids[order], preds[order], labels[order]
    boundaries = np.flatnonzero(
        np.concatenate([[True], uids[1:] != uids[:-1], [True]]))
    wauc_sum = 0.0
    weight_sum = 0.0
    user_count = 0
    for lo, hi in zip(boundaries[:-1], boundaries[1:]):
        p, l = preds[lo:hi], labels[lo:hi]
        npos = float((l > 0.5).sum())
        nneg = float(len(l) - npos)
        if npos == 0 or nneg == 0:
            continue
        # rank-sum AUC within user
        ranks = np.argsort(np.argsort(p, kind="stable"), kind="stable") + 1
        auc_u = (ranks[l > 0.5].sum() - npos * (npos + 1) / 2) / (npos * nneg)
        w = hi - lo
        wauc_sum += auc_u * w
        weight_sum += w
        user_count += 1
    return wauc_sum, weight_sum, user_count


def wuauc_compute(user_ids: np.ndarray, preds: np.ndarray,
                  labels: np.ndarray) -> Dict[str, float]:
    """Per-user (weighted-user) AUC on host (role of WuAucMetricMsg,
    metrics.h:306 / ``computeWuAuc``): group records by user, compute AUC
    per user with >=1 pos and >=1 neg, average weighted by instance count."""
    wauc_sum, weight_sum, user_count = wuauc_accumulate(user_ids, preds,
                                                        labels)
    return {
        "wuauc": wauc_sum / weight_sum if weight_sum else float("nan"),
        "wuauc_users": float(user_count),
    }
