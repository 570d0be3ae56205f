"""Block-diffusion training of an expert decoder (``model_type``
``sdar_moe``; the objective of "Block Diffusion: Interpolating Between
Autoregressive and Diffusion Language Models", arXiv:2503.09573).

A sequence ``x`` of ``L`` tokens is cut into blocks of ``B``. Each block
``j`` draws a noise level ``t_j``, and position ``i`` of a *noisy copy* is
the mask token with probability ``t_{b(i)}``, else ``x_i``. The stack runs
over ``2 L`` rows, the clean sequence followed by its noisy copy, a noisy
row at its clean twin's position:

    h      = E[x || x_noisy]
    h     <- h + Attn(RMSNorm(h)) ; h <- h + Experts(RMSNorm(h))    per layer
    loss   = 1 / L  sum over masked i of (1 / t_{b(i)}) CE(head(h_{L+i}), x_i)

``Attn``: q, k, v projections, a per-head RMSNorm on q and on k (one gain
of ``head_dim`` each), rotary embedding at ``r mod L``, grouped-query
softmax attention under ``BlockDiffusionMask`` (a clean row reads the clean
rows of its own and earlier blocks; a noisy row the clean rows of strictly
earlier blocks and the noisy rows of its own block; the flash kernels skip
the dead tiles), no biases. ``Experts``: softmax over all routed experts,
the top k renormalised (``parallel/moe.py`` ``topk_softmax_router``), each
a SwiGLU ``(silu(u W1) * (u W3)) W2``; the layer computes the part that the
experts it holds (``experts_held``) give (``dropless_dispatch``). Final
norm and head read the noisy rows only. A trained token is a position of
``x``: L tokens a step, 2 L rows through the stack.

Built like ``models/looped.py``: one ``shard_map`` over the hybrid mesh,
vocabulary-parallel embedding and cross entropy over ``mp``, batch over
the data axes, every other weight whole on every device. The layers are
equal: their parameters are stacked in ``cfg.pieces`` equal pieces and a
piece is one ``lax.scan``; every layer is rematerialised and keeps its
input and what ``residual_plan`` finds room for (a scan has one save
policy, so the plan's unit is a piece). The noise (levels, which positions
are masked) is an input of the step: whoever draws it sees what the
program saw.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from paddlebox_tpu.core import flags, trace
from paddlebox_tpu.models import residual_plan
from paddlebox_tpu.models.gpt import _data_axes
from paddlebox_tpu.models.looped import _dot, _rms, rotary_embedding
from paddlebox_tpu.models.residual_plan import Keepable, product, ranked
from paddlebox_tpu.models.train_step import make_train_step
from paddlebox_tpu.ops.pallas_kernels.flash_attention import (
    RESIDUAL_NAMES as FLASH_RESIDUAL_NAMES, BlockDiffusionMask,
    flash_attention, tile_counts)
from paddlebox_tpu.ops.pallas_kernels.grouped_matmul import (
    ROW_TILE, grouped_matmul, grouped_weight_grad, row_tile_schedule,
    scatter_add_rows)
from paddlebox_tpu.ops.pallas_kernels.ssd_scan import ambient_mxu_dtype
from paddlebox_tpu.parallel import moe as moelib
from paddlebox_tpu.parallel import tp as tplib


@dataclasses.dataclass(frozen=True)
class BlockDiffusionConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_hidden_layers: int = 48         # the layers built here
    # depth of the published model: scales the out-projections' initial
    # values whatever part of it is built
    model_layers: int = 48
    moe_intermediate_size: int = 768
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    router_experts: int = 128           # what the router scores
    experts_held: Tuple[int, int] = (0, 128)    # (first, count) built here
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    block_length: int = 4
    # "auto": the Pallas kernel on a TPU, its XLA reference elsewhere;
    # "interpret": the kernel through the Pallas interpreter (tests);
    # "xla": the reference
    kernels: str = "auto"

    @property
    def mask_token_id(self) -> int:
        """The last id of the vocabulary held here."""
        return self.vocab_size - 1

    @property
    def pieces(self) -> int:
        """Into how many equal pieces the layers are stacked: the most,
        up to ``PLAN_PIECES``, that divide them."""
        return max(k for k in range(1, PLAN_PIECES + 1)
                   if self.num_hidden_layers % k == 0)


# The embedding's initial scale. At 0.02 a token's row (norm 0.9) is
# outweighed after one layer by the mean value vector that near-uniform
# initial attention adds to every row alike (norm ~2 a layer): all rows of
# a sequence look the same to every router, go to the same 8 of 128
# experts, and what a chip's 16 held experts serve is 0 to 3 times the
# rows, by layer and by step (measured on the chip at the published
# widths: 56k to 131k assignments a step within single runs, PERF.md).
EMBED_STD = 1.0
# A scan has one save policy: the layers are stacked in up to this many
# pieces so that the plan can keep a value in some of them.
PLAN_PIECES = 4


# -- parameters --------------------------------------------------------------

def init_block_diffusion(rng: jax.Array, cfg: BlockDiffusionConfig
                         ) -> Tuple[Dict, Dict]:
    """Returns (params, partition_specs); ``params["layers"]`` is a list
    of ``cfg.pieces`` dicts whose leaves are stacked ``[num_hidden_layers
    / pieces, ...]``: layer l is row ``l % (L / pieces)`` of piece ``l //
    (L / pieces)``, and ``w1`` / ``w3`` / ``w2`` stack the held experts
    behind that. normal(0, 0.02) weights, out-projections (``wo``, ``w2``)
    scaled by 1 / sqrt(model_layers), gains 1, the embedding normal(0, 1):
    a token's own row then outweighs what attention averages into every
    row alike, and rows of different tokens go to different experts (at
    0.02 every row of a sequence goes to the same eight: ``EMBED_STD``)."""
    if cfg.tie_word_embeddings or not cfg.norm_topk_prob:
        raise ValueError("the block-diffusion stack keeps an embedding and "
                         "a head of their own and renormalises the chosen "
                         "experts' weights (tie_word_embeddings false, "
                         "norm_topk_prob true)")
    d, f, hd = cfg.hidden_size, cfg.moe_intermediate_size, cfg.head_dim
    hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    held = cfg.experts_held[1]
    n = cfg.num_hidden_layers // cfg.pieces
    layer = (d * hd * 2 * (hq + hkv) + 2 * hd + 2 * d
             + d * cfg.router_experts + 3 * held * d * f)
    with trace.span("block_diffusion/init", layers=cfg.num_hidden_layers,
                    parameters=(cfg.num_hidden_layers * layer + d
                                + 2 * cfg.vocab_size * d)):
        keys = jax.random.split(rng, 2 + cfg.pieces)
        out_scale = 0.02 / math.sqrt(cfg.model_layers)

        def normal(key, *shape, scale=0.02):
            return jax.random.normal(key, shape, jnp.float32) * scale

        def piece(key):
            k = jax.random.split(key, 8)
            return {
                "n1": jnp.ones((n, d)), "n2": jnp.ones((n, d)),
                "gq": jnp.ones((n, hd)), "gk": jnp.ones((n, hd)),
                "wq": normal(k[0], n, d, hq * hd),
                "wk": normal(k[1], n, d, hkv * hd),
                "wv": normal(k[2], n, d, hkv * hd),
                "wo": normal(k[3], n, hq * hd, d, scale=out_scale),
                "router": normal(k[4], n, d, cfg.router_experts),
                "w1": normal(k[5], n, held, d, f),
                "w3": normal(k[6], n, held, d, f),
                "w2": normal(k[7], n, held, f, d, scale=out_scale),
            }
        params = {
            "embed": normal(keys[0], cfg.vocab_size, d, scale=EMBED_STD),
            "layers": [piece(key) for key in keys[2:]],
            "norm_f": jnp.ones((d,)),
            "head": normal(keys[1], d, cfg.vocab_size),
        }
        specs = jax.tree.map(lambda _: P(), params)
        specs["embed"] = P("mp", None)      # vocabulary-parallel
        specs["head"] = P(None, "mp")
    return params, specs


# -- the layer ---------------------------------------------------------------

# The looped dispatch's block, in even router's shares of a layer's
# assignments (a layer's held load is 0.7 to 2.4 shares): read on the chip
# at one, two and three shares over six seeds (PERF.md section 5).
DISPATCH_SHARES = 1


def dispatch_block_rows(cfg: BlockDiffusionConfig, rows: int) -> int:
    """Assignments a trip of the expert dispatch's loop holds, for
    ``rows`` rows through a layer: ``DISPATCH_SHARES`` times what the
    held experts get of an even router, in whole row tiles of the grouped
    products."""
    share = -(-rows * cfg.num_experts_per_tok * cfg.experts_held[1]
              // cfg.router_experts)
    return -(-DISPATCH_SHARES * share // ROW_TILE) * ROW_TILE


def _packed(lp) -> Dict:
    """A layer's held experts as ``_held_experts`` takes them: the gate's
    and the up-projection's matrices side by side (``w13`` ``[held, d, 2
    inner]``), so that one grouped product reads a row once for both and
    one gives the rows' cotangent through both. The gradient comes back
    through the concatenation as two halves."""
    return {"w13": jnp.concatenate([lp["w1"], lp["w3"]], axis=-1),
            "w2": lp["w2"]}


def _held_experts(mode: Dict, mxu) -> moelib.LoopedExperts:
    """SwiGLU experts (``_packed``) over contiguous row segments,
    ``sizes[e]`` rows for held expert e, each row's result times its
    ``scale``: a grouped product in (gate | up) and one out, forward;
    backward the one in again and four more (the rows' cotangent through
    ``w2`` and through ``w13`` transposed; the two weights' gradients,
    each added to the sum it is handed). Every product's operands are
    ``mxu``, every sum float32; what lies between the products is
    float32. ``mode``: ``flags.kernel_mode``'s."""
    def products(sizes, rows):
        kernels = dict(use_pallas=mode["use_pallas"],
                       interpret=mode["interpret"], sizes=sizes)
        if mode["use_pallas"]:      # one table of visits for all of them
            kernels["schedule"] = row_tile_schedule(sizes, rows, ROW_TILE)
        return (functools.partial(grouped_matmul, **kernels),
                functools.partial(grouped_weight_grad, **kernels))

    def forward(p, rows, scale, sizes):
        rows_by, _ = products(sizes, rows.shape[0])
        gate, up = jnp.split(rows_by(rows, p["w13"].astype(mxu)), 2, axis=-1)
        hidden = jax.nn.silu(gate) * up * scale[:, None]
        return rows_by(hidden.astype(mxu), p["w2"].astype(mxu))

    def backward(p, rows, scale, sizes, dy, sums):
        rows_by, weights_by = products(sizes, rows.shape[0])
        w13, w2 = p["w13"].astype(mxu), p["w2"].astype(mxu)
        gate, up = jnp.split(rows_by(rows, w13), 2, axis=-1)
        sig = jax.nn.sigmoid(gate)
        act = gate * sig
        hidden = act * up
        # y = scale * (hidden @ w2): the cotangent of hidden is scale *
        # (dy @ w2.T) and that of scale is <dy, hidden @ w2> = <dy @
        # w2.T, hidden>, so the out-product is not computed again
        back = rows_by(dy, w2, transpose_w=True)
        dhidden = back * scale[:, None]
        din = jnp.concatenate(
            [dhidden * up * (sig * (1.0 + gate * (1.0 - sig))),
             dhidden * act], axis=-1).astype(mxu)
        sums = {"w13": weights_by(rows, din, into=sums["w13"]),
                "w2": weights_by((hidden * scale[:, None]).astype(mxu), dy,
                                 into=sums["w2"])}
        return (rows_by(din, w13, transpose_w=True),
                jnp.sum(back * hidden, axis=-1), sums)
    return moelib.LoopedExperts(
        forward, backward, mxu, functools.partial(
            scatter_add_rows, use_pallas=mode["use_pallas"],
            interpret=mode["interpret"]))


def _layer(lp, h, cfg: BlockDiffusionConfig, rule: BlockDiffusionMask,
           positions):
    """``h`` ``[b, 2 L, d]`` -> (``h'``, what the expert layer served)."""
    b, s, d = h.shape
    hd, eps = cfg.head_dim, cfg.rms_norm_eps
    hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    mode = flags.kernel_mode(cfg.kernels)
    with jax.named_scope("attention"):
        x = _rms(h, lp["n1"], eps)
        q = rotary_embedding(
            _rms(_dot(x, lp["wq"]).reshape(b, s, hq, hd), lp["gq"], eps),
            positions, cfg.rope_theta)
        k = rotary_embedding(
            _rms(_dot(x, lp["wk"]).reshape(b, s, hkv, hd), lp["gk"], eps),
            positions, cfg.rope_theta)
        v = _dot(x, lp["wv"]).reshape(b, s, hkv, hd)
        flags.note_kernel("block_diffusion_attention", mode["name"])
        attn = flash_attention(q, k, v, mask=rule,
                               use_pallas=mode["use_pallas"],
                               interpret=mode["interpret"])
        h = h + _dot(attn.reshape(b, s, -1), lp["wo"])
    with jax.named_scope("moe"):
        u = _rms(h, lp["n2"], eps).reshape(b * s, d)
        idx, weights = moelib.topk_softmax_router(u, lp["router"],
                                                  cfg.num_experts_per_tok)

        # the looped form (8 static blocks of 2 L rows, 2048 wide, over 16
        # experts' matrices would be 6 GB of the step at the published
        # sizes) over grouped products that visit the live row tiles
        flags.note_kernel(
            "block_diffusion_moe_dispatch",
            "sort_pallas_grouped" if mode["name"] == "pallas"
            else mode["name"])
        # The products take their operands as they come: they are cast to
        # what XLA makes of a float32 product under the ambient precision,
        # as the stack's other products are (bfloat16; float32 under
        # "highest"). Elsewhere XLA's own product, and the interpreter's,
        # at float32.
        mxu = (ambient_mxu_dtype() if mode["name"] == "pallas"
               else jnp.float32)
        y, counts = moelib.dropless_dispatch(
            u, idx, weights, cfg.experts_held, _held_experts(mode, mxu),
            _packed(lp), block_rows=dispatch_block_rows(cfg, b * s))
        return h + y.reshape(b, s, d), counts


# -- what a layer keeps for its backward pass --------------------------------

def _keepable(cfg: BlockDiffusionConfig, seq: int):
    """The candidates of ``residual_plan`` for one layer (kind ``L``), a
    row of the ``2 seq`` the stack sees, dearest to recompute per byte
    first. The experts' products are no candidates: the looped dispatch
    keeps nothing of a block's forward (``parallel/moe.py``)."""
    d, hd = cfg.hidden_size, cfg.head_dim
    hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    routed, k = cfg.router_experts, cfg.num_experts_per_tok
    return ranked([
        product("L", FLASH_RESIDUAL_NAMES[:3], d, (hq + 2 * hkv) * hd),
        # two products over the (seq + block) / 2 keys a row reads on
        # average under the mask
        Keepable("L", FLASH_RESIDUAL_NAMES[3:], 4 * hq * (hd + 1),
                 2.0 * (seq + cfg.block_length) * hq * hd),
        # the router's product runs at Precision.HIGHEST, six passes; the
        # top-k and the sort come on top and are not counted
        Keepable("L", moelib.ROUTING_RESIDUAL_NAMES, 4 * (routed + 3 * k),
                 6 * 2.0 * d * routed),
    ])


# What a kept byte is charged against the plan's room: the compiled step
# grows by 1.8 to 1.85 bytes for each (12 layers at the published widths,
# compiled for the v5e, tools/aot_check_dense.py --blockdiff: kept 0 ->
# 7.16 GB of temporaries, 1.28 -> 9.46, 2.69 -> 12.12), as in the looped
# stack, whose scans keep their values the same way.
KEPT_COST = 2.0


def _plan_for(cfg: BlockDiffusionConfig, mesh: Mesh, params, tokens):
    """What the stack keeps for one call's shapes (``residual_plan``): one
    entry a piece, standing for the ``L / pieces`` layers its scan runs,
    over the ``2 seq`` rows a sequence puts through the stack. Reserved
    beside parameters, gradients and inputs: the noisy rows' logits with
    their cotangent, and the held experts' weights once more at half
    their bytes (XLA casts every piece's to bfloat16 for the grouped
    products once a step, ahead of the scans: 1.5 GB of the compiled step
    at the published sizes)."""
    per_scan = cfg.num_hidden_layers // cfg.pieces
    shards = math.prod(int(mesh.shape[a]) for a in _data_axes(mesh))
    logits = (tokens.size // shards
              * (cfg.vocab_size // int(mesh.shape["mp"])) * 4)
    experts = sum(piece[n].size * piece[n].dtype.itemsize
                  for piece in params["layers"] for n in ("w1", "w3", "w2"))
    rows = jax.ShapeDtypeStruct((tokens.shape[0], 2 * tokens.shape[1]),
                                jnp.int32)
    candidates = [c._replace(bytes=c.bytes * per_scan, ops=c.ops * per_scan)
                  for c in _keepable(cfg, tokens.shape[1])]
    return residual_plan._plan_for(
        mesh, params, rows, "L" * cfg.pieces, candidates,
        cfg.hidden_size * per_scan,
        reserved_bytes=2 * logits + experts // 2, kept_cost=KEPT_COST)


def plan_attributes(cfg: BlockDiffusionConfig, plan, seq: int,
                    batch: int) -> Dict:
    """The plan as the ``block_diffusion/build_step`` span reports it
    (``layers_kept`` counts pieces), with the expert dispatch's block for
    ``batch`` sequences a device (``dispatch_block_rows``) and, where the
    kernels run, the grouped products' row tile and the mask's tiles a
    head in the flash kernels: grid / live / edge (``tile_counts``)."""
    out = dict(plan.attributes("L" * cfg.pieces), block=cfg.block_length,
               dispatch_block_rows=dispatch_block_rows(cfg, batch * 2 * seq))
    if flags.kernel_mode(cfg.kernels)["use_pallas"]:
        grid, live, edge = tile_counts(
            2 * seq, 2 * seq, int(flags.flag("flash_block_q")),
            int(flags.flag("flash_block_k")), False,
            mask=BlockDiffusionMask(seq, cfg.block_length))
        out.update(tiles_grid=grid, tiles_live=live, tiles_edge=edge,
                   dispatch_row_tile=ROW_TILE)
    return out


# -- the loss ----------------------------------------------------------------

def block_diffusion_loss_fn(cfg: BlockDiffusionConfig, mesh: Mesh,
                            specs: Dict):
    """Builds ``loss(params, tokens, levels, masked) -> (loss, aux)``,
    shard_mapped over the hybrid mesh. ``tokens`` ``[B, L]`` int32,
    ``levels`` ``[B, L / block_length]`` float32 in (0, 1] (a block's noise
    level t), ``masked`` ``[B, L]`` bool (the positions of the noisy copy
    that are the mask token); B sharded over the data axes. ``loss`` is
    the sum over masked positions of CE / t, over all ``B L`` tokens.
    ``aux``, summed over the data axes: ``load`` ``[layers, held]``
    assignments per held expert, ``dropped`` ``[layers]`` (0: the dispatch
    has no capacity), ``masked`` the masked positions and ``weight`` the
    sum of their 1 / t."""
    for axis in ("pp", "sp", "ep"):
        if int(mesh.shape[axis]) > 1:
            raise ValueError(
                f"block diffusion on a mesh with {axis}={mesh.shape[axis]}: "
                "the stack is one pipeline stage, the mask needs both "
                "copies of its sequence whole and the expert layer has no "
                "exchange yet")
    daxes = _data_axes(mesh)

    def scan_layers(keep, rule, positions):
        """``(stacked, h) -> (h', counts stacked a layer)``: one scan over
        a piece's layers under one save policy."""
        apply = jax.checkpoint(
            functools.partial(_layer, cfg=cfg, rule=rule,
                              positions=positions), prevent_cse=False,
            policy=jax.checkpoint_policies.save_only_these_names(*keep)
            if keep else None)
        return lambda stacked, h: lax.scan(
            lambda x, lp: apply(lp, x), h, stacked)

    def body(plan, params, tokens, levels, masked):
        seq, block = tokens.shape[1], cfg.block_length
        rule = BlockDiffusionMask(seq, block)
        with jax.named_scope("embed"):
            rows = jnp.concatenate(
                [tokens, jnp.where(masked, cfg.mask_token_id, tokens)],
                axis=1)
            h = tplib.vocab_parallel_embedding(
                {"table": params["embed"]}, rows, axis="mp")
        served = []
        with jax.named_scope("stack"):
            positions = jnp.tile(jnp.arange(seq), 2)
            for keep, piece in zip(plan.names, params["layers"]):
                h, counts = scan_layers(keep, rule, positions)(piece, h)
                served.append(counts)
        with jax.named_scope("head"):
            logits = _dot(_rms(h[:, seq:], params["norm_f"],
                               cfg.rms_norm_eps), params["head"])
            ce = tplib.parallel_cross_entropy(logits, tokens, axis="mp")
            weight = jnp.where(
                masked, 1.0 / jnp.repeat(levels, block, axis=1), 0.0)
            count = lax.psum(jnp.asarray(tokens.size, jnp.float32), daxes)
            aux = {
                "load": lax.psum(jnp.concatenate(
                    [c.load for c in served]), daxes),
                "dropped": lax.psum(jnp.concatenate(
                    [c.dropped for c in served]), daxes),
                "masked": lax.psum(jnp.sum(masked, dtype=jnp.int32),
                                   daxes),
                "weight": lax.psum(jnp.sum(weight), daxes),
            }
            return lax.psum(jnp.sum(weight * ce), daxes) / count, aux

    def loss(params, tokens, levels, masked):
        if tokens.shape[1] % cfg.block_length:
            raise ValueError(f"{tokens.shape[1]} positions are not whole "
                             f"blocks of {cfg.block_length}")
        plan = _plan_for(cfg, mesh, params, tokens)
        data = P(daxes, None)
        return jax.shard_map(
            functools.partial(body, plan), mesh=mesh,
            in_specs=(specs, data, data, data), out_specs=(P(), P()),
            check_vma=False)(params, tokens, levels, masked)
    return loss


def make_block_diffusion_train_step(cfg: BlockDiffusionConfig, mesh: Mesh,
                                    specs: Dict, optimizer):
    """Jitted ``(params, opt_state, tokens, levels, masked) -> (params,
    opt_state, loss, aux)`` with donation; ``aux`` as
    ``block_diffusion_loss_fn`` returns it. The
    ``block_diffusion/build_step`` span covers the tracing of the loss and
    its gradient, once a compilation, and says what the layers keep, the
    expert dispatch's block and which tiles the mask leaves
    (``plan_attributes``)."""
    vg = jax.value_and_grad(block_diffusion_loss_fn(cfg, mesh, specs),
                            has_aux=True)

    shards = math.prod(int(mesh.shape[a]) for a in _data_axes(mesh))

    def traced(params, tokens, levels, masked):
        plan = _plan_for(cfg, mesh, params, tokens)
        with trace.span("block_diffusion/build_step",
                        layers=cfg.num_hidden_layers,
                        **plan_attributes(cfg, plan, tokens.shape[1],
                                          tokens.shape[0] // shards)):
            return vg(params, tokens, levels, masked)
    return make_train_step(traced, optimizer, has_aux=True)
