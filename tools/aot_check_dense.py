"""AOT-compile the dense benchmark train steps (resnet50 bf16, BERT-base)
for TPU — no TPU needed (compile-only PJRT topology).

These two steps had never run on hardware before round 3 (both
carried calling-convention bugs), so their TPU-compile surface — notably
the bf16 conv forward/transpose path resnet now uses — is exactly the
kind of thing that would otherwise only fail inside the recorded run:

    python tools/aot_check_dense.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=1"
                           ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402
from jax.sharding import Mesh, NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402


from tools._aot_common import sds, tpu_topology  # noqa: E402


def check_resnet(sh) -> None:
    """The resnet50 AMP step: bf16 compute params (BN stats f32), f32
    master merge — the conv dtype-symmetry fix under autodiff, through
    the package's own amp helpers."""
    from paddlebox_tpu.amp import (cast_compute_except_stats as
                                   cast_compute)
    from paddlebox_tpu.amp import merge_bn_stats as merge_bn
    from paddlebox_tpu.models.resnet import ResNet
    model = ResNet(depth=50, num_classes=1000)
    params = model.init(jax.random.PRNGKey(0))
    opt = optax.sgd(0.1, momentum=0.9)

    def loss_fn(p, x, y):
        logits, p_new = model.apply(cast_compute(p), x, train=True)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), y).mean(), p_new

    def step(p, s, x, y):
        (loss, p_new), g = jax.value_and_grad(loss_fn, has_aux=True)(
            p, x, y)
        updates, s = opt.update(g, s, p)
        return merge_bn(optax.apply_updates(p, updates), p_new), s, loss

    opt_state = jax.eval_shape(opt.init, sds(params))
    x = jax.ShapeDtypeStruct((128, 224, 224, 3), jnp.bfloat16,
                             sharding=sh)
    y = jax.ShapeDtypeStruct((128,), jnp.int32, sharding=sh)
    jax.jit(step).lower(sds(params), opt_state, x, y).compile()
    print("AOT resnet50 bf16 train step: OK")


def check_bert(sh) -> None:
    from paddlebox_tpu.models.bert import (BertConfig, bert_mlm_loss,
                                           init_bert)
    cfg = BertConfig()
    params = init_bert(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(1e-4)

    def step(p, s, tokens, targets, mask):
        loss, g = jax.value_and_grad(
            lambda p: bert_mlm_loss(p, cfg, tokens, targets, mask))(p)
        updates, s = opt.update(g, s, p)
        return optax.apply_updates(p, updates), s, loss

    opt_state = jax.eval_shape(opt.init, sds(params))
    tok = jax.ShapeDtypeStruct((8, 128), jnp.int32, sharding=sh)
    msk = jax.ShapeDtypeStruct((8, 128), jnp.float32, sharding=sh)
    jax.jit(step).lower(sds(params), opt_state, tok, tok, msk).compile()
    print("AOT bert-base train step: OK")


def main() -> None:
    topo = tpu_topology("v5e:2x2x1")
    if topo is None:
        return
    sh = NamedSharding(Mesh([topo.devices[0]], ("d",)), P())
    check_bert(sh)
    check_resnet(sh)
    print("DENSE BENCH TPU AOT COMPILE: OK")


if __name__ == "__main__":
    main()
