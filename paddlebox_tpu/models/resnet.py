"""ResNet-50 for image classification.

Role of the reference's vision path (``paddle.vision.models.resnet50``).
TPU-first: NHWC layout (channels on the lane axis), bottleneck blocks as
fused conv+BN+relu chains XLA maps onto the MXU via implicit GEMM.
Functional params; BN running stats threaded explicitly (no mutable
module state to fight jit).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from paddlebox_tpu.nn.conv import (batchnorm_apply, batchnorm_init,
                                   conv2d_apply, conv2d_init)
from paddlebox_tpu.nn.layers import dense_apply, dense_init

BLOCKS = {18: (2, 2, 2, 2), 34: (3, 4, 6, 3), 50: (3, 4, 6, 3),
          101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}


@dataclasses.dataclass(frozen=True)
class ResNet:
    depth: int = 50
    num_classes: int = 1000
    width: int = 64

    @property
    def bottleneck(self) -> bool:
        return self.depth >= 50

    def init(self, rng: jax.Array) -> Dict[str, Any]:
        keys = iter(jax.random.split(rng, 256))
        w = self.width
        params: Dict[str, Any] = {
            "stem_conv": conv2d_init(next(keys), 3, w, 7),
            "stem_bn": batchnorm_init(w),
        }
        in_ch = w
        exp = 4 if self.bottleneck else 1
        for stage, nblocks in enumerate(BLOCKS[self.depth]):
            ch = w * (2 ** stage)
            for b in range(nblocks):
                name = f"s{stage}b{b}"
                stride = 2 if (b == 0 and stage > 0) else 1
                out_ch = ch * exp
                blk: Dict[str, Any] = {}
                if self.bottleneck:
                    blk["c1"] = conv2d_init(next(keys), in_ch, ch, 1)
                    blk["bn1"] = batchnorm_init(ch)
                    blk["c2"] = conv2d_init(next(keys), ch, ch, 3)
                    blk["bn2"] = batchnorm_init(ch)
                    blk["c3"] = conv2d_init(next(keys), ch, out_ch, 1)
                    blk["bn3"] = batchnorm_init(out_ch)
                else:
                    blk["c1"] = conv2d_init(next(keys), in_ch, ch, 3)
                    blk["bn1"] = batchnorm_init(ch)
                    blk["c2"] = conv2d_init(next(keys), ch, out_ch, 3)
                    blk["bn2"] = batchnorm_init(out_ch)
                if in_ch != out_ch or stride != 1:
                    blk["proj"] = conv2d_init(next(keys), in_ch, out_ch, 1)
                    blk["proj_bn"] = batchnorm_init(out_ch)
                params[name] = blk
                in_ch = out_ch
        params["head"] = dense_init(next(keys), in_ch, self.num_classes)
        return params

    def apply(self, params: Dict, x: jax.Array, *, train: bool = False,
              axis_name: str | None = None) -> Tuple[jax.Array, Dict]:
        """x [B, H, W, 3] → (logits [B, classes], updated params w/ BN
        stats)."""
        new_params = dict(params)

        def bn(name_or_blk, blk_name, key, h):
            p = new_params[blk_name][key] if blk_name else new_params[key]
            y, p2 = batchnorm_apply(p, h, train=train, axis_name=axis_name)
            if blk_name:
                new_params[blk_name] = {**new_params[blk_name], key: p2}
            else:
                new_params[key] = p2
            return y

        h = conv2d_apply(params["stem_conv"], x, stride=2)
        h = jax.nn.relu(bn(None, None, "stem_bn", h))
        h = jax.lax.reduce_window(
            h, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")

        for stage, nblocks in enumerate(BLOCKS[self.depth]):
            for b in range(nblocks):
                name = f"s{stage}b{b}"
                blk = params[name]
                stride = 2 if (b == 0 and stage > 0) else 1
                shortcut = h
                if self.bottleneck:
                    y = conv2d_apply(blk["c1"], h)
                    y = jax.nn.relu(bn(None, name, "bn1", y))
                    y = conv2d_apply(blk["c2"], y, stride=stride)
                    y = jax.nn.relu(bn(None, name, "bn2", y))
                    y = conv2d_apply(blk["c3"], y)
                    y = bn(None, name, "bn3", y)
                else:
                    y = conv2d_apply(blk["c1"], h, stride=stride)
                    y = jax.nn.relu(bn(None, name, "bn1", y))
                    y = conv2d_apply(blk["c2"], y)
                    y = bn(None, name, "bn2", y)
                if "proj" in blk:
                    shortcut = conv2d_apply(blk["proj"], h, stride=stride)
                    shortcut = bn(None, name, "proj_bn", shortcut)
                h = jax.nn.relu(y + shortcut)

        h = jnp.mean(h, axis=(1, 2))
        return dense_apply(params["head"], h), new_params
