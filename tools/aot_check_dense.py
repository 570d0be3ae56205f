"""AOT-compile the dense benchmark train steps (resnet50 bf16, BERT-base,
the hybrid, the looped and the block-diffusion cell's two programs each
with their memory) for TPU — no TPU needed
(compile-only PJRT topology).

These two steps had never run on hardware before round 3 (both
carried calling-convention bugs), so their TPU-compile surface — notably
the bf16 conv forward/transpose path resnet now uses — is exactly the
kind of thing that would otherwise only fail inside the recorded run:

    python tools/aot_check_dense.py [--gpt | --hybrid | --looped |
                                     --blockdiff] [--text DIR]

``--gpt`` checks the GPT-2 medium cell's timed step (flash attention, as
``auto`` resolves on the chip), ``--hybrid`` the hybrid cell in their place,
``--looped`` the looped
cell (``models/looped.py`` at ``benchmarks/configs/ouro_2_6b.json``, 4,096
positions: the timed step and the set-up's ``highest`` gradient function
as ``benchmarks/runners/looped_train.py`` builds it), ``--blockdiff`` the
block-diffusion cell likewise (``models/block_diffusion.py`` at
``benchmarks/configs/sdar_30b_a3b.json``, 4,096 positions, 8,192 rows;
``benchmarks/runners/block_diffusion_train.py``). The hybrid stack
(``models/nemotron_h.py``) plans what its layers keep for the backward
pass from the device's memory. ``check_hybrid`` compiles
what ``benchmarks/runners/hybrid_train.py`` builds from that plan at the
cell's sizes (``benchmarks/configs/nemotron3_super_120b.json``, 8,192
positions): the timed step, and the same loss's gradient at ``highest``
with every gradient an output, which the set-up runs first. Either above
``HYBRID_MEMORY_SHARE`` of the v5e's memory fails the check: on the chip
that is an out-of-memory in set-up, a failed cell.

``--text DIR`` also writes each compiled program's text to
``DIR/<cell>.<program>.hlo`` with its metadata (``op_name``, source lines),
the module's source-location tables and the Mosaic kernels' debug
locations taken out (``stripped``): what two checkouts compile can then be
compared character for character, and a change that only names things
(``jax.named_scope``) must leave it equal.
"""

import os
import re
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


# What one program may take of the chip: arguments + outputs that alias
# none of them + temporaries. The rest is what the runner holds beside a
# program (token batches, the optimizer state while the set-up's gradient
# runs) and the allocator's fragmentation.
HYBRID_MEMORY_SHARE = 0.93


# what a module's text says about where it came from, not what it computes
_METADATA = re.compile(r', metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')
_SOURCE_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                  "StackFrames")
_TABLE_ROW = re.compile(r"^\d+ ")
_KERNEL_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')


def _kernel_body(match) -> str:
    """A Mosaic kernel's serialized module (bytecode), which carries the
    source lines it was traced from, as the digest of its text without
    them."""
    import base64
    import hashlib

    from jax._src.lib.mlir import ir
    body = base64.b64decode(match.group(1))
    if not body.startswith(b"ML\xefR"):    # text XLA wrote, no locations
        return match.group(0)
    with ir.Context() as ctx:
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(body)
        asm = module.operation.get_asm(enable_debug_info=False)
    return '"body":"sha256:%s"' % hashlib.sha256(asm.encode()).hexdigest()


def stripped(text: str) -> str:
    """A compiled module's text without its instructions' metadata,
    without the source-location tables and with each Mosaic kernel's
    module read without its debug locations: what shifts whenever a line
    of the program is edited."""
    out, in_table = [], False
    for line in text.splitlines():
        if line in _SOURCE_TABLES:
            in_table = True
            continue
        if in_table and _TABLE_ROW.match(line):
            continue
        in_table = False
        out.append(_KERNEL_BODY.sub(_kernel_body, _METADATA.sub("", line)))
    return "\n".join(out) + "\n"


def _text_dir():
    if "--text" not in sys.argv:
        return None
    path = sys.argv[sys.argv.index("--text") + 1]
    os.makedirs(path, exist_ok=True)
    return path


def program_bytes(compiled) -> dict:
    m = compiled.memory_analysis()
    return {"arguments": m.argument_size_in_bytes,
            "outputs": m.output_size_in_bytes - m.alias_size_in_bytes,
            "temporaries": m.temp_size_in_bytes}


def _cell_files(config_name: str, traffic_name: str):
    import json
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           config_name + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmarks", "traffic",
                           traffic_name + ".json")) as f:
        return config, int(json.load(f)["sequence_length"])


def _check_programs(name: str, device, config, seq, init, said, programs):
    """Compiles for the described ``device`` what a dense cell's runner
    builds at the cell's sizes and fails if any program is over
    ``HYBRID_MEMORY_SHARE`` of the chip. ``init(key) -> (params, specs)``;
    ``said(mesh, params, tokens)`` the plan as its span reports it;
    ``programs(mesh, specs, opt)`` pairs of a name and a function ``(params,
    opt_state, tokens) -> lowered``."""
    import json

    from paddlebox_tpu.core import flags
    from paddlebox_tpu.models import residual_plan
    from paddlebox_tpu.parallel import HybridTopology, build_mesh
    # the device is described, the backend here is the CPU: say what the
    # chip's process would find
    flags.pallas_kernels_enabled = lambda: True
    mesh = build_mesh(HybridTopology(dp=1), devices=[device])
    rep = NamedSharding(mesh, P())
    specs = {}

    def make(key):
        params, s = init(key)
        specs.update(s)
        return params
    opt = optax.adafactor(config["learning_rate"])
    params = jax.eval_shape(make, jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(opt.init, params)

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=rep), tree)
    params, opt_state = placed(params), placed(opt_state)
    tok = jax.ShapeDtypeStruct((int(config["sequences_per_chip"]), seq),
                               jnp.int32, sharding=NamedSharding(
                                   mesh, P("dp")))
    print(f"{name} plan:", json.dumps(said(mesh, params, tok)), flush=True)
    # a described device reports no memory: the plan was made for the
    # stacks' stated default, which is the v5e's
    limit = int(HYBRID_MEMORY_SHARE * residual_plan.DEFAULT_DEVICE_BYTES)
    text_dir = _text_dir()
    for program, lower in programs(mesh, specs, opt):
        compiled = lower(params, opt_state, tok).compile()
        if text_dir:
            path = os.path.join(
                text_dir, f"{name}.{program.replace(' ', '_')}.hlo")
            with open(path, "w") as f:
                f.write(stripped(compiled.as_text()))
        parts = program_bytes(compiled)
        total = sum(parts.values())
        print(f"AOT {name} {program}: {json.dumps(parts)} total {total} "
              f"of {limit} allowed", flush=True)
        if total > limit:
            raise SystemExit(
                f"{name} {program} program needs {total} bytes, over "
                f"{HYBRID_MEMORY_SHARE:.0%} of the v5e's "
                f"{residual_plan.DEFAULT_DEVICE_BYTES}")
    print(f"AOT {name} step and setup gradient fit: OK")


def check_gpt(device) -> None:
    from paddlebox_tpu.models.gpt import (GPTConfig, init_gpt,
                                          make_gpt_train_step)
    config, seq = _cell_files("gpt2_medium", "train_s1024")
    cfg = GPTConfig(vocab_size=config["vocab_size"],
                    d_model=config["n_embd"], n_heads=config["n_head"],
                    n_layers=config["n_layer"], d_ff=config["n_inner"],
                    max_seq_len=config["n_positions"], attention="flash")

    def programs(mesh, specs, opt):
        def step(params, opt_state, tok):
            return make_gpt_train_step(cfg, mesh, specs, opt).lower(
                params, opt_state, tok, tok)
        return (("step", step),)
    _check_programs("gpt", device, config, seq,
                    lambda key: init_gpt(key, cfg, pp_stages=1),
                    lambda mesh, params, tok: {}, programs)


def check_hybrid(device) -> None:
    from benchmarks.runners.hybrid_train import program_config
    from paddlebox_tpu.models import nemotron_h as nh
    config, seq = _cell_files("nemotron3_super_120b", "train_s8192")
    cfg = program_config(config)

    def programs(mesh, specs, opt):
        def step(params, opt_state, tok):
            return nh.make_nemotron_h_train_step(cfg, mesh, specs, opt).lower(
                params, opt_state, tok, tok)

        def grads(params, opt_state, tok):
            with jax.default_matmul_precision("highest"):
                return jax.jit(jax.value_and_grad(
                    nh.nemotron_h_loss_fn(cfg, mesh, specs),
                    has_aux=True)).lower(params, tok, tok)
        return ("step", step), ("setup gradient", grads)
    _check_programs(
        "hybrid", device, config, seq,
        lambda key: nh.init_nemotron_h(key, cfg),
        lambda mesh, params, tok: nh._plan_for(
            cfg, mesh, params, tok).attributes(cfg.pattern), programs)


def check_looped(device) -> None:
    from benchmarks.runners import looped_train as runner
    from paddlebox_tpu.models import looped
    config, seq = _cell_files("ouro_2_6b", "train_s4096")
    cfg = runner.program_config(config)

    def programs(mesh, specs, opt):
        def step(params, opt_state, tok):
            return looped.make_looped_train_step(cfg, mesh, specs, opt).lower(
                params, opt_state, tok, tok)

        def grads(params, opt_state, tok):
            with jax.default_matmul_precision("highest"):
                return runner.program_reading(
                    cfg, mesh, specs, runner.checked_leaves(cfg.pieces)
                ).lower(params, tok, tok)
        return ("step", step), ("setup gradient", grads)
    _check_programs(
        "looped", device, config, seq,
        lambda key: looped.init_looped(key, cfg),
        lambda mesh, params, tok: looped.plan_attributes(
            cfg, looped._plan_for(cfg, mesh, params, tok)), programs)


def check_blockdiff(device) -> None:
    from benchmarks.runners import block_diffusion_train as runner
    from paddlebox_tpu.models import block_diffusion as bd
    config, seq = _cell_files("sdar_30b_a3b", "train_bd_s4096")
    cfg = runner.program_config(config)

    def noise(tok):
        """The step's other two inputs, laid out as the tokens are."""
        return (jax.ShapeDtypeStruct(
            (tok.shape[0], seq // cfg.block_length), jnp.float32,
            sharding=tok.sharding),
            jax.ShapeDtypeStruct(tok.shape, jnp.bool_,
                                 sharding=tok.sharding))

    def programs(mesh, specs, opt):
        def step(params, opt_state, tok):
            return bd.make_block_diffusion_train_step(
                cfg, mesh, specs, opt).lower(params, opt_state, tok,
                                             *noise(tok))

        def grads(params, opt_state, tok):
            with jax.default_matmul_precision("highest"):
                return runner.program_reading(
                    cfg, mesh, specs, runner.checked_leaves(
                        cfg.pieces, cfg.num_hidden_layers // cfg.pieces)
                ).lower(params, tok, *noise(tok))
        return ("step", step), ("setup gradient", grads)
    _check_programs(
        "blockdiff", device, config, seq,
        lambda key: bd.init_block_diffusion(key, cfg),
        lambda mesh, params, tok: bd.plan_attributes(
            cfg, bd._plan_for(cfg, mesh, params, tok), seq, tok.shape[0]),
        programs)


def main() -> None:
    topo = tpu_topology("v5e:2x2x1")
    if topo is None:
        return
    sh = NamedSharding(Mesh([topo.devices[0]], ("d",)), P())
    if "--gpt" in sys.argv:
        check_gpt(topo.devices[0])
        return
    if "--hybrid" in sys.argv:      # two minutes and a half of its own
        check_hybrid(topo.devices[0])
        return
    if "--looped" in sys.argv:      # two minutes of its own
        check_looped(topo.devices[0])
        return
    if "--blockdiff" in sys.argv:
        check_blockdiff(topo.devices[0])
        return
    check_bert(sh)
    check_resnet(sh)
    print("DENSE BENCH TPU AOT COMPILE: OK")


if __name__ == "__main__":
    main()
