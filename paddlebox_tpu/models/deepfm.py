"""DeepFM CTR model over pulled sparse embeddings.

DeepFM on Criteo (reference path
``pull_box_sparse`` + dense ops). Consumes the sparse pull outputs
(per-slot CSR embeddings) and produces logits:

  logit = wide(w) + FM2(v) + MLP(concat slot embeddings [, dense feats])

Functional: ``init`` returns the dense-param pytree; ``apply`` is pure so
the trainer can differentiate wrt (params, pulled_emb, pulled_w) and feed
the embedding grads straight into the sparse push.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from paddlebox_tpu.models.common import slot_dims
from paddlebox_tpu.nn import mlp_apply, mlp_init
from paddlebox_tpu.ops import seqpool


@dataclasses.dataclass(frozen=True)
class DeepFM:
    slot_names: Tuple[str, ...]
    # One width for every slot, or a per-slot mapping (dynamic mf, role of
    # CtrDymfAccessor per-slot mf dims). With mixed widths the FM term
    # zero-pads pooled vectors to the max width (missing dims contribute
    # nothing to the interaction); the deep tower concats true widths.
    emb_dim: Union[int, Mapping[str, int]]
    dense_dim: int = 0                    # width of concatenated dense slots
    hidden: Tuple[int, ...] = (400, 400, 400)

    def _dims(self) -> Dict[str, int]:
        return slot_dims(self.slot_names, self.emb_dim)

    def init(self, rng: jax.Array) -> Dict:
        in_dim = sum(self._dims().values()) + self.dense_dim
        rng, sub = jax.random.split(rng)
        return {
            "mlp": mlp_init(sub, in_dim, list(self.hidden) + [1]),
            "bias": jnp.zeros((), jnp.float32),
        }

    def apply(self, params: Dict,
              emb: Dict[str, jax.Array],       # slot -> [cap_s, D_s] pulled
              w: Dict[str, jax.Array],         # slot -> [cap_s] pulled
              segments: Dict[str, jax.Array],  # slot -> [cap_s] row ids
              batch_size: int,
              dense_feats: jax.Array | None = None) -> jax.Array:
        """Returns logits [B]."""
        dims = self._dims()
        dmax = max(dims.values())
        pooled_v: List[jax.Array] = []   # per-slot [B, D_s]
        wide_terms: List[jax.Array] = []  # per-slot [B]
        for name in self.slot_names:
            pooled_v.append(seqpool(emb[name], segments[name], batch_size))
            wide_terms.append(seqpool(w[name], segments[name], batch_size))

        # Wide (first-order) term.
        wide = sum(wide_terms) + params["bias"]           # [B]

        # FM second-order interaction: 0.5 * ((Σ_s v)^2 - Σ_s v^2), with
        # narrower slots zero-padded to the max width.
        padded = [jnp.pad(p, ((0, 0), (0, dmax - p.shape[-1])))
                  if p.shape[-1] < dmax else p for p in pooled_v]
        v = jnp.stack(padded, axis=1)                     # [B, S, Dmax]
        sum_v = jnp.sum(v, axis=1)                        # [B, Dmax]
        sum_sq = jnp.sum(v * v, axis=1)                   # [B, Dmax]
        fm = 0.5 * jnp.sum(sum_v * sum_v - sum_sq, axis=-1)  # [B]

        # Deep tower over true (unpadded) widths.
        flat = jnp.concatenate(pooled_v, axis=-1)         # [B, sum D_s]
        if dense_feats is not None and self.dense_dim:
            flat = jnp.concatenate([flat, dense_feats], axis=-1)
        deep = mlp_apply(params["mlp"], flat)[:, 0]       # [B]

        return wide + fm + deep
