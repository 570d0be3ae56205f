"""GPT-style transformer with full hybrid parallelism (dp×pp×sp×mp).

A GPT hybrid-parallel model (reference path
``fleet/meta_parallel/`` TP+PP+sharding). Composes the whole parallelism
suite in one train step:

- mp: vocab-parallel embedding + column/row-parallel attention & FFN +
  vocab-parallel cross entropy (roles of mp_layers.py / c_embedding /
  c_softmax_with_cross_entropy)
- pp: transformer blocks partitioned into stages streamed with the
  scan+ppermute pipeline (role of PipelineParallel.forward_backward_pipeline)
- sp: ring attention over the sequence axis (NEW capability, absent in the
  reference — SURVEY.md §5)
- dp: batch sharding; gradient reduction falls out of autodiff through the
  global-mean loss (role of EagerReducer/c_allreduce_sum)

Everything runs inside ONE ``shard_map`` over the hybrid mesh; jax.grad
through it yields the full hybrid backward (pipelined, ring-reversed,
TP-transposed) with XLA scheduling all collectives.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from paddlebox_tpu.core import flags
from paddlebox_tpu.models.train_step import make_train_step
from paddlebox_tpu.parallel import pp as pplib
from paddlebox_tpu.parallel import sp as splib
from paddlebox_tpu.parallel import tp as tplib


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 8
    d_ff: int = 2048
    max_seq_len: int = 1024
    dtype: Any = jnp.float32
    # "auto": Pallas flash attention on TPU when the sequence is not
    # sharded (sp axis size 1), ring attention otherwise; "ring"/"flash"
    # force a path (role of the reference's fused_attention_op.cu choice).
    attention: str = "auto"


def _layer_init(rng, cfg: GPTConfig):
    d, f = cfg.d_model, cfg.d_ff
    k = iter(jax.random.split(rng, 6))
    s = d ** -0.5
    return {
        "ln1_g": jnp.ones((d,)), "ln1_b": jnp.zeros((d,)),
        # Column order is HEAD-MAJOR [head0(q,k,v) | head1(q,k,v) | ...] so
        # the mp sharding splits whole heads, not q/k/v mid-tensor.
        "wqkv": jax.random.normal(next(k), (d, 3 * d)) * s,
        "wo": jax.random.normal(next(k), (d, d)) * s,
        "ln2_g": jnp.ones((d,)), "ln2_b": jnp.zeros((d,)),
        "wi": jax.random.normal(next(k), (d, f)) * s,
        "bi": jnp.zeros((f,)),
        "wo2": jax.random.normal(next(k), (f, d)) * (f ** -0.5),
        "bo2": jnp.zeros((d,)),
    }


def _layer_specs():
    """TP shardings per layer leaf (with the stacked [pp, layer] dims
    prepended by the caller)."""
    return {
        "ln1_g": P(), "ln1_b": P(),
        "wqkv": P(None, "mp"),   # column-parallel: heads split over mp
        "wo": P("mp", None),     # row-parallel
        "ln2_g": P(), "ln2_b": P(),
        "wi": P(None, "mp"),     # column-parallel FFN in
        "bi": P("mp"),
        "wo2": P("mp", None),    # row-parallel FFN out
        "bo2": P(),
    }


def init_gpt(rng: jax.Array, cfg: GPTConfig, *, pp_stages: int = 1
             ) -> Tuple[Dict, Dict]:
    """Returns (params, partition_specs). Layer params are stacked
    [pp_stages, layers_per_stage, ...]."""
    if cfg.n_layers % pp_stages:
        raise ValueError(f"{cfg.n_layers} layers not divisible into "
                         f"{pp_stages} stages")
    lps = cfg.n_layers // pp_stages
    keys = jax.random.split(rng, cfg.n_layers + 3)
    layers = [_layer_init(keys[i], cfg) for i in range(cfg.n_layers)]
    # Stack [pp, layers_per_stage, ...].
    stages = [jax.tree.map(lambda *xs: jnp.stack(xs),
                           *layers[s * lps:(s + 1) * lps])
              for s in range(pp_stages)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *stages)

    params = {
        "embed": jax.random.normal(keys[-3], (cfg.vocab_size, cfg.d_model))
        * 0.02,
        "pos": jax.random.normal(keys[-2], (cfg.max_seq_len, cfg.d_model))
        * 0.02,
        "layers": stacked,
        "lnf_g": jnp.ones((cfg.d_model,)), "lnf_b": jnp.zeros((cfg.d_model,)),
        "head": jax.random.normal(keys[-1], (cfg.d_model, cfg.vocab_size))
        * cfg.d_model ** -0.5,
    }
    lspecs = _layer_specs()
    specs = {
        "embed": P("mp", None),        # vocab-parallel
        "pos": P(None, None),
        "layers": jax.tree.map(
            lambda s: P("pp", None, *s), lspecs,
            is_leaf=lambda x: isinstance(x, P)),
        "lnf_g": P(), "lnf_b": P(),
        "head": P(None, "mp"),         # vocab-parallel head
    }
    return params, specs


def _ln(x, g, b, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def _block(p, x, cfg: GPTConfig, heads_local: int):
    """One transformer block on local shards: x [mb, S_local, D];
    wqkv local [D, 3*D/mp]."""
    b, s, d = x.shape
    in_dtype = x.dtype
    hd = cfg.d_model // cfg.n_heads
    with jax.named_scope("attention"):
        h = _ln(x, p["ln1_g"], p["ln1_b"])
        qkv = jnp.dot(h, p["wqkv"], preferred_element_type=jnp.float32)
        qkv = qkv.reshape(b, s, heads_local, 3, hd)
        q, k, v = qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]
        sp_n = lax.axis_size("sp")
        if cfg.attention not in ("auto", "ring", "flash"):
            raise ValueError(f"unknown attention mode {cfg.attention!r}; "
                             "choose from 'auto', 'ring', 'flash'")
        if cfg.attention == "flash" and sp_n > 1:
            # The flash kernel sees only the local K/V shard; with a
            # sharded sequence only ring attention is exact.
            raise ValueError("attention='flash' requires sp axis size 1; "
                             "use 'ring' or 'auto' with a sharded sequence")
        use_flash = cfg.attention == "flash" or (
            cfg.attention == "auto" and sp_n == 1
            and jax.default_backend() == "tpu")
        flags.note_kernel("gpt_attention", "flash" if use_flash else "ring")
        if use_flash:
            from paddlebox_tpu.ops.pallas_kernels import flash_attention
            attn = flash_attention(q, k, v, causal=True)
        else:
            attn = splib.ring_attention(q, k, v, axis="sp", causal=True)
        attn = attn.reshape(b, s, heads_local * hd)
        o = jnp.dot(attn, p["wo"], preferred_element_type=jnp.float32)
        o = lax.psum(o, "mp")                       # row-parallel combine
        x = x + o
    with jax.named_scope("mlp"):
        h2 = _ln(x, p["ln2_g"], p["ln2_b"])
        u = jnp.dot(h2, p["wi"], preferred_element_type=jnp.float32) + p["bi"]
        u = jax.nn.gelu(u)
        y = jnp.dot(u, p["wo2"], preferred_element_type=jnp.float32)
        y = lax.psum(y, "mp") + p["bo2"]
        # Residual stream stays in the input dtype (bf16-safe scan carry);
        # note x is rebound above, so use the dtype captured at entry.
        return (x + y).astype(in_dtype)


def _data_axes(mesh: Mesh) -> tuple:
    """Batch-dim axes: ("slice", "dp") on a multi-slice mesh (batch
    splits across DCN slices too; XLA decomposes the loss/grad psums
    hierarchically over the physical topology), else ("dp",)."""
    if "slice" in mesh.axis_names and int(mesh.shape["slice"]) > 1:
        return ("slice", "dp")
    return ("dp",)


def gpt_loss_fn(cfg: GPTConfig, mesh: Mesh, specs: Dict, *,
                num_microbatches: int = 1):
    """Builds loss(params, tokens, targets) -> scalar, shard_mapped over
    the hybrid mesh. tokens/targets [B, S] int32; B sharded over the data
    axes (dp, plus the DCN slice axis on multi-slice meshes), S over sp."""
    heads_local = cfg.n_heads // int(mesh.shape["mp"])
    daxes = _data_axes(mesh)
    raxes = daxes + ("sp",)

    def stage_fn(stage_params, x):
        # stage_params leaves [layers_per_stage, ...]; scan over layers.
        def body(h, lp):
            return _block(lp, h, cfg, heads_local), None
        out, _ = lax.scan(body, x, stage_params)
        return out

    def body(params, tokens, targets):
        # tokens local [B_local, S_local]
        s_local = tokens.shape[1]
        with jax.named_scope("embed"):
            x = tplib.vocab_parallel_embedding(
                {"table": params["embed"]}, tokens, axis="mp")
            rank_sp = lax.axis_index("sp")
            pos_ids = rank_sp * s_local + jnp.arange(s_local)
            x = x + params["pos"][pos_ids][None, :, :]

        with jax.named_scope("stack"):
            # Microbatch the local batch for the pipeline.
            bl = x.shape[0]
            m = num_microbatches
            x_mb = x.reshape(m, bl // m, s_local, cfg.d_model)
            stage_params_local = jax.tree.map(lambda a: a[0],
                                              params["layers"])
            h_mb = pplib.gpipe_apply(stage_fn, stage_params_local, x_mb,
                                     axis="pp")
            h = h_mb.reshape(bl, s_local, cfg.d_model)

        with jax.named_scope("head"):
            h = _ln(h, params["lnf_g"], params["lnf_b"])
            logits_local = jnp.dot(h, params["head"],
                                   preferred_element_type=jnp.float32)
            losses = tplib.parallel_cross_entropy(logits_local, targets,
                                                  axis="mp")
            # Global mean over all tokens (replica × sp shards).
            total = lax.psum(jnp.sum(losses), raxes)
            count = lax.psum(jnp.asarray(losses.size, jnp.float32), raxes)
            return total / count

    in_specs = (specs, P(daxes, "sp"), P(daxes, "sp"))
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=P(),
                         check_vma=False)


def gpt_value_and_grad_1f1b(cfg: GPTConfig, mesh: Mesh, specs: Dict, *,
                            num_microbatches: int = 1,
                            num_chunks: int = 1):
    """(params, tokens, targets) -> (loss, grads) using the 1F1B pipeline
    schedule (role of the reference's default train_batch path,
    ``meta_parallel/pipeline_parallel.py:82``): bounded activation memory
    — each stage holds O(pp) stage inputs instead of the
    GPipe-through-autodiff O(M) residuals. The embedding runs outside the
    pipeline (cotangents returned by the schedule), the final-LN/head pair
    rides the schedule's ``loss_params`` channel.

    ``num_chunks > 1`` selects the INTERLEAVED schedule (virtual pipeline
    stages, role of virtual_pp_degree): each rank's resident layer rows
    split into ``num_chunks`` chunks whose virtual depth is CYCLIC over
    ranks (chunk c on rank r sits at depth c*pp + r) — about half the
    fill/drain bubble time. Note the depth meaning of a given physical
    layer row therefore differs from the plain schedule; layers are
    iid-initialized so training from scratch is equivalent, but
    checkpoints are not interchangeable between num_chunks settings."""
    heads_local = cfg.n_heads // int(mesh.shape["mp"])

    def stage_fn(stage_params, x):
        def body(h, lp):
            return _block(lp, h, cfg, heads_local), None
        out, _ = lax.scan(body, x, stage_params)
        return out

    mp_n = int(mesh.shape["mp"])
    daxes = _data_axes(mesh)
    raxes = daxes + ("sp",)

    def loss_head(lp, y, tgt):
        h = _ln(y, lp["lnf_g"], lp["lnf_b"])
        logits = jnp.dot(h, lp["head"], preferred_element_type=jnp.float32)
        losses = tplib.parallel_cross_entropy(logits, tgt, axis="mp")
        # The schedule seeds this (mp-replicated) value on EVERY mp rank,
        # and psum's transpose under shard_map sums seeded cotangents —
        # so the seeded objective is mp * L unless scaled down here; the
        # reported loss is scaled back up by the caller.
        return jnp.mean(losses) / mp_n

    def body(params, tokens, targets):
        s_local = tokens.shape[1]

        def embed_fn(ep):
            x = tplib.vocab_parallel_embedding(
                {"table": ep["embed"]}, tokens, axis="mp")
            rank_sp = lax.axis_index("sp")
            pos_ids = rank_sp * s_local + jnp.arange(s_local)
            return x + ep["pos"][pos_ids][None, :, :]

        ep = {"embed": params["embed"], "pos": params["pos"]}
        x, vjp_embed = jax.vjp(embed_fn, ep)
        bl = x.shape[0]
        m = num_microbatches
        x_mb = x.reshape(m, bl // m, s_local, cfg.d_model)
        tgt_mb = targets.reshape(m, bl // m, s_local)
        lp = {"lnf_g": params["lnf_g"], "lnf_b": params["lnf_b"],
              "head": params["head"]}
        stage_params_local = jax.tree.map(lambda a: a[0], params["layers"])
        if num_chunks > 1:
            lps = jax.tree.leaves(stage_params_local)[0].shape[0]
            if lps % num_chunks:
                raise ValueError(
                    f"{lps} layers per pp stage do not split into "
                    f"num_chunks={num_chunks} equal chunks")
            chunked = jax.tree.map(
                lambda a: a.reshape((num_chunks, a.shape[0] // num_chunks)
                                    + a.shape[1:]), stage_params_local)
            loss, cgrads, lpgrads, dx0 = \
                pplib.interleaved_one_f_one_b_value_and_grad(
                    stage_fn, loss_head, chunked, x_mb, tgt_mb,
                    num_chunks=num_chunks, axis="pp", loss_params=lp,
                    return_input_grads=True)
            sgrads = jax.tree.map(
                lambda g: g.reshape((g.shape[0] * g.shape[1],)
                                    + g.shape[2:]), cgrads)
        else:
            loss, sgrads, lpgrads, dx0 = pplib.one_f_one_b_value_and_grad(
                stage_fn, loss_head, stage_params_local, x_mb, tgt_mb,
                axis="pp", loss_params=lp, return_input_grads=True)
        (dep,) = vjp_embed(
            dx0.reshape(bl, s_local, cfg.d_model).astype(x.dtype))

        grads = {
            "embed": dep["embed"],
            "pos": dep["pos"],
            "layers": jax.tree.map(lambda g: g[None], sgrads),
            "lnf_g": lpgrads["lnf_g"],
            "lnf_b": lpgrads["lnf_b"],
            "head": lpgrads["head"],
        }

        # Reductions mirroring what autodiff-through-shard_map gives the
        # GPipe path implicitly: a param replicated over an axis gets the
        # SUM of per-rank partials over that axis (broadcast transpose) —
        # pp (grads exist only on the first/last rank) and mp (each rank
        # contributes through its own heads/vocab shard) — while dp/sp
        # average, because each shard's loss is normalized by its LOCAL
        # token count (mean of local means == global mean for equal
        # shards).
        def reduce_leaf(g, spec):
            sharded = set()
            for entry in spec:
                if entry is None:
                    continue
                if isinstance(entry, (tuple, list)):
                    sharded.update(entry)
                else:
                    sharded.add(entry)
            axes = [a for a in ("pp", "mp") if a not in sharded]
            if axes:
                g = lax.psum(g, tuple(axes))
            return lax.pmean(g, raxes)

        grads = jax.tree.map(reduce_leaf, grads, specs)
        return lax.pmean(loss * mp_n, raxes), grads

    in_specs = (specs, P(daxes, "sp"), P(daxes, "sp"))
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=(P(), specs), check_vma=False)


def make_gpt_train_step(cfg: GPTConfig, mesh: Mesh, specs: Dict,
                        optimizer, *, num_microbatches: int = 1,
                        schedule: str = "gpipe", num_chunks: int = 1,
                        out_shardings=None):
    """Jitted (params, opt_state, tokens, targets) -> (params, opt_state,
    loss) with donation. Gradient reduction across dp/pp/sp/mp falls out
    of differentiating through the shard_map (``schedule="gpipe"``) or is
    explicit in the 1F1B path (``schedule="1f1b"`` — the reference's
    default pipeline schedule, pipeline_parallel.py:82, with bounded
    activation memory; pick it when microbatch count × activation size
    would blow HBM under GPipe). ``schedule="interleaved_1f1b"`` with
    ``num_chunks=V`` runs the virtual-stage interleave (~half the
    pipeline bubble; see gpt_value_and_grad_1f1b for the layer-layout
    note)."""
    if schedule in ("gpipe", "1f1b") and num_chunks != 1:
        # Silently training the plain schedule while the caller believes
        # they got the interleave would also bake in the wrong layer
        # layout (checkpoints differ between num_chunks settings).
        raise ValueError(
            f"num_chunks={num_chunks} requires "
            f"schedule='interleaved_1f1b' (got {schedule!r})")
    if schedule == "gpipe":
        loss_fn = gpt_loss_fn(cfg, mesh, specs,
                              num_microbatches=num_microbatches)
        vg = jax.value_and_grad(loss_fn)
    elif schedule == "1f1b":
        vg = gpt_value_and_grad_1f1b(cfg, mesh, specs,
                                     num_microbatches=num_microbatches)
    elif schedule == "interleaved_1f1b":
        if num_chunks < 2:
            raise ValueError("interleaved_1f1b needs num_chunks >= 2 — "
                             "at 1 chunk it IS the plain 1f1b schedule")
        vg = gpt_value_and_grad_1f1b(cfg, mesh, specs,
                                     num_microbatches=num_microbatches,
                                     num_chunks=num_chunks)
    else:
        raise ValueError(f"unknown pipeline schedule {schedule!r}; "
                         "choose 'gpipe', '1f1b', or 'interleaved_1f1b'")

    return make_train_step(vg, optimizer, out_shardings=out_shardings)
