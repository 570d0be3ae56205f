"""Wide&Deep CTR model with CVM features.

The Wide&Deep 100B-feature HeterPS-style model.
Deep tower consumes ``fused_seqpool_cvm`` outputs — per-slot pooled
embeddings with leading [log(show+1), log(ctr)] channels, the PaddleBox
production pattern (fused_seqpool_cvm wrapper, contrib/layers/nn.py:1746);
wide tower is the pooled scalar-w linear term.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from paddlebox_tpu.nn import mlp_apply, mlp_init
from paddlebox_tpu.ops import fused_seqpool_cvm, seqpool


@dataclasses.dataclass(frozen=True)
class WideDeep:
    slot_names: Tuple[str, ...]
    emb_dim: int
    dense_dim: int = 0
    hidden: Tuple[int, ...] = (512, 256, 128)
    use_cvm: bool = True

    def init(self, rng: jax.Array) -> Dict:
        s = len(self.slot_names)
        per_slot = self.emb_dim + (2 if self.use_cvm else 0)
        in_dim = s * per_slot + self.dense_dim
        rng, sub = jax.random.split(rng)
        return {
            "mlp": mlp_init(sub, in_dim, list(self.hidden) + [1]),
            "bias": jnp.zeros((), jnp.float32),
        }

    def apply(self, params: Dict,
              emb: Dict[str, jax.Array],
              w: Dict[str, jax.Array],
              show: Dict[str, jax.Array],
              click: Dict[str, jax.Array],
              segments: Dict[str, jax.Array],
              batch_size: int,
              dense_feats: jax.Array | None = None) -> jax.Array:
        """Returns logits [B]."""
        pooled: List[jax.Array] = []
        wide = params["bias"]
        for name in self.slot_names:
            pooled.append(fused_seqpool_cvm(
                emb[name], show[name], click[name], segments[name],
                batch_size, use_cvm=self.use_cvm))
            wide = wide + seqpool(w[name], segments[name], batch_size)
        flat = jnp.concatenate(pooled, axis=-1)
        if dense_feats is not None and self.dense_dim:
            flat = jnp.concatenate([flat, dense_feats], axis=-1)
        deep = mlp_apply(params["mlp"], flat)[:, 0]
        return wide + deep
