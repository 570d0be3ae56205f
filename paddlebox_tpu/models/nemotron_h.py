"""Hybrid state-space / attention / expert decoder (``nemotron_h``).

A stack of single-mixer blocks ``x <- x + mixer(RMSNorm(x))`` whose mixer
is chosen per layer by a pattern string: ``M`` Mamba-2, ``*`` grouped-query
attention, ``E`` a latent mixture of experts with one shared expert.

- ``M``: ``[z | xBC | dt] = x W_in``; ``xBC <- silu(causal depthwise
  conv(xBC) + b)``; ``xBC -> x_h, B, C``; ``dt <- softplus(dt + dt_bias)``;
  the selective scan (``ops/pallas_kernels/ssd_scan.py``); ``y <-
  RMSNorm_grouped(y * silu(z))``; ``y W_out``.
- ``*``: causal softmax attention, more query heads than key/value heads
  (``ops/pallas_kernels/flash_attention.py``), no biases, no rotary
  embedding.
- ``E``: sigmoid scores over all routed experts, the top k chosen
  (``parallel/moe.py``); the routed experts live in a latent space
  (``W_down``, ``W_up``) and the layer computes the part of the routed sum
  that the experts it holds (``experts_held``) give, through the looped
  dispatch over the grouped products of
  ``ops/pallas_kernels/grouped_matmul.py``; the shared expert sees the
  full-width input; squared ReLU.

Built like ``models/gpt.py``: one ``shard_map`` over the hybrid mesh,
vocabulary-parallel embedding and cross entropy over ``mp``, batch over
the data axes; every other weight is whole on every device. The layers
differ, so they are a Python list (no ``lax.scan`` over equal blocks), and
each is rematerialised (``jax.checkpoint``): a layer keeps its input for
its backward pass and, of what its forward computes, the values
``residual_plan.plan_residuals`` finds room for in the device's memory.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from paddlebox_tpu.core import flags, trace
from paddlebox_tpu.models import residual_plan
from paddlebox_tpu.models.gpt import _data_axes
from paddlebox_tpu.models.residual_plan import Keepable, product, ranked
from paddlebox_tpu.models.train_step import make_train_step
from paddlebox_tpu.ops.pallas_kernels.flash_attention import (
    RESIDUAL_NAMES as FLASH_RESIDUAL_NAMES, flash_attention)
from paddlebox_tpu.ops.pallas_kernels.grouped_matmul import (
    ROW_TILE, grouped_matmul, grouped_weight_grad, row_tile_schedule,
    scatter_add_rows)
from paddlebox_tpu.ops.pallas_kernels.ssd_scan import (ambient_mxu_dtype,
                                                       ssd_scan)
from paddlebox_tpu.parallel import moe as moelib
from paddlebox_tpu.parallel import tp as tplib


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    # one letter per layer: M Mamba-2, * attention, E experts
    pattern: str = "MEMEMEM*EME"
    # depth of the published model: scales the out-projections' initial
    # values (rescale_prenorm_residual) whatever part of it is built
    num_hidden_layers: int = 88
    norm_eps: float = 1e-5
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    n_routed_experts: int = 512         # what the router scores
    experts_held: Tuple[int, int] = (0, 512)    # (first, count) built here
    num_experts_per_tok: int = 22
    moe_latent_size: int = 1024
    moe_intermediate_size: int = 2688
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    # "auto": the Pallas kernels on a TPU, their XLA references elsewhere;
    # "interpret": the kernels through the Pallas interpreter (tests);
    # "xla": the references
    kernels: str = "auto"

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size


def _kernel_mode(cfg: NemotronHConfig) -> Dict:
    return flags.kernel_mode(cfg.kernels)


# -- parameters --------------------------------------------------------------

def _normal(key, shape, scale=0.02):
    return jax.random.normal(key, shape, jnp.float32) * scale


def _init_mamba(key, cfg: NemotronHConfig, out_scale):
    d, di, h = cfg.hidden_size, cfg.mamba_inner, cfg.mamba_num_heads
    k = jax.random.split(key, 5)
    # step sizes log-uniform in [time_step_min, time_step_max], stored
    # through the inverse of softplus
    dt = jnp.exp(jax.random.uniform(k[2], (h,)) * (
        math.log(cfg.time_step_max) - math.log(cfg.time_step_min))
        + math.log(cfg.time_step_min))
    dt = jnp.maximum(dt, cfg.time_step_floor)
    return {
        "norm": jnp.ones((d,)),
        "w_in": _normal(k[0], (d, di + cfg.conv_dim + h)),
        "conv_w": jax.random.uniform(
            k[1], (cfg.conv_kernel, cfg.conv_dim), jnp.float32, -1.0, 1.0)
        * cfg.conv_kernel ** -0.5,
        "conv_b": jnp.zeros((cfg.conv_dim,)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "a_log": jnp.log(jax.random.uniform(k[3], (h,), jnp.float32,
                                            1.0, 16.0)),
        "d": jnp.ones((h,)),
        "gnorm": jnp.ones((di,)),
        "w_out": _normal(k[4], (di, d), out_scale),
    }


def _init_attention(key, cfg: NemotronHConfig, out_scale):
    d, hd = cfg.hidden_size, cfg.head_dim
    k = jax.random.split(key, 4)
    return {
        "norm": jnp.ones((d,)),
        "wq": _normal(k[0], (d, cfg.num_attention_heads * hd)),
        "wk": _normal(k[1], (d, cfg.num_key_value_heads * hd)),
        "wv": _normal(k[2], (d, cfg.num_key_value_heads * hd)),
        "wo": _normal(k[3], (cfg.num_attention_heads * hd, d), out_scale),
    }


def _init_experts(key, cfg: NemotronHConfig, out_scale):
    d, lat = cfg.hidden_size, cfg.moe_latent_size
    inner, shared = (cfg.moe_intermediate_size,
                     cfg.moe_shared_expert_intermediate_size)
    held = cfg.experts_held[1]
    k = jax.random.split(key, 7)
    return {
        "norm": jnp.ones((d,)),
        "gate": _normal(k[0], (d, cfg.n_routed_experts)),
        # e_score_correction_bias: steers the choice, takes no gradient
        "bias": jnp.zeros((cfg.n_routed_experts,)),
        "w_down": _normal(k[1], (d, lat)),
        "w_up": _normal(k[2], (lat, d), out_scale),
        "w1": _normal(k[3], (held, lat, inner)),
        "w2": _normal(k[4], (held, inner, lat), out_scale),
        "ws1": _normal(k[5], (d, shared)),
        "ws2": _normal(k[6], (shared, d), out_scale),
    }


_INIT = {"M": _init_mamba, "*": _init_attention, "E": _init_experts}


def init_nemotron_h(rng: jax.Array, cfg: NemotronHConfig
                    ) -> Tuple[Dict, Dict]:
    """Returns (params, partition_specs); ``params["layers"]`` is a list
    with one dict per letter of ``cfg.pattern``. normal(0, 0.02) weights,
    out-projections scaled by 1 / sqrt(num_hidden_layers)."""
    unknown = set(cfg.pattern) - set(_INIT)
    if unknown:
        raise ValueError(f"pattern {cfg.pattern!r} has letters "
                         f"{sorted(unknown)}; known: M, *, E")
    with trace.span("nemotron_h/init", layers=len(cfg.pattern)):
        keys = jax.random.split(rng, len(cfg.pattern) + 2)
        out_scale = 0.02 / math.sqrt(cfg.num_hidden_layers)
        params = {
            "embed": _normal(keys[-2], (cfg.vocab_size, cfg.hidden_size)),
            "layers": [_INIT[letter](keys[i], cfg, out_scale)
                       for i, letter in enumerate(cfg.pattern)],
            "norm_f": jnp.ones((cfg.hidden_size,)),
            "head": _normal(keys[-1], (cfg.hidden_size, cfg.vocab_size)),
        }
        specs = jax.tree.map(lambda _: P(), params)
        specs["embed"] = P("mp", None)      # vocabulary-parallel
        specs["head"] = P(None, "mp")
    return params, specs


# -- layers ------------------------------------------------------------------

def _rms(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * gain


def _dot(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _mamba(p, h, cfg: NemotronHConfig):
    b, s, _ = h.shape
    di, gn = cfg.mamba_inner, cfg.n_groups * cfg.ssm_state_size
    heads, k = cfg.mamba_num_heads, cfg.conv_kernel
    z, xbc, dt = jnp.split(
        checkpoint_name(_dot(h, p["w_in"]), "mamba_in_proj"),
        [di, di + cfg.conv_dim], axis=-1)
    # causal depthwise convolution: position t sees t-k+1 .. t
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, i:i + s] * p["conv_w"][i]
                          for i in range(k)) + p["conv_b"])
    xs, bm, cm = jnp.split(xbc, [di, di + gn], axis=-1)
    mode = _kernel_mode(cfg)
    flags.note_kernel("nemotron_ssd", mode["name"])
    y = ssd_scan(
        xs.reshape(b, s, heads, cfg.mamba_head_dim),
        jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["a_log"]),
        bm.reshape(b, s, cfg.n_groups, cfg.ssm_state_size),
        cm.reshape(b, s, cfg.n_groups, cfg.ssm_state_size), p["d"],
        chunk=cfg.chunk_size, use_pallas=mode["use_pallas"],
        interpret=mode["interpret"])
    y = y.reshape(b, s, di) * jax.nn.silu(z)
    y = _rms(y.reshape(b, s, cfg.n_groups, di // cfg.n_groups),
             p["gnorm"].reshape(cfg.n_groups, -1), cfg.norm_eps)
    return _dot(y.reshape(b, s, di), p["w_out"]), None


def _attention(p, h, cfg: NemotronHConfig):
    b, s, _ = h.shape
    hd = cfg.head_dim
    q = _dot(h, p["wq"]).reshape(b, s, cfg.num_attention_heads, hd)
    kk = _dot(h, p["wk"]).reshape(b, s, cfg.num_key_value_heads, hd)
    v = _dot(h, p["wv"]).reshape(b, s, cfg.num_key_value_heads, hd)
    mode = _kernel_mode(cfg)
    flags.note_kernel("nemotron_attention", mode["name"])
    attn = flash_attention(q, kk, v, causal=True,
                           use_pallas=mode["use_pallas"],
                           interpret=mode["interpret"])
    return _dot(attn.reshape(b, s, -1), p["wo"]), None


# The looped dispatch's block, in even router's shares of a layer's
# assignments (a layer's held load is 0.7 to 1.2 of one): one and two
# shares read alike on the chip, one holds less (PERF.md section 6).
DISPATCH_SHARES = 1


def dispatch_block_rows(cfg: NemotronHConfig, rows: int) -> int:
    """Assignments a trip of the expert dispatch's loop holds, for
    ``rows`` rows through a layer: ``DISPATCH_SHARES`` times what the
    held experts get of an even router, in whole row tiles of the grouped
    products."""
    share = -(-rows * cfg.num_experts_per_tok * cfg.experts_held[1]
              // cfg.n_routed_experts)
    return -(-DISPATCH_SHARES * share // ROW_TILE) * ROW_TILE


def _held_experts(mode: Dict, mxu) -> moelib.LoopedExperts:
    """Squared-ReLU experts (``w1``, ``w2``) over contiguous row segments,
    ``sizes[e]`` rows for held expert e, each row's result times its
    ``scale``: a grouped product in and one out, forward; backward the
    one in again and four more (the rows' cotangent through ``w2`` and
    through ``w1`` transposed; the two weights' gradients, each added to
    the sum it is handed). Every product's operands are ``mxu``, every
    sum float32; what lies between the products is float32. ``mode``:
    ``flags.kernel_mode``'s."""
    def products(sizes, rows):
        kernels = dict(use_pallas=mode["use_pallas"],
                       interpret=mode["interpret"], sizes=sizes)
        if mode["use_pallas"]:      # one table of visits for all of them
            kernels["schedule"] = row_tile_schedule(sizes, rows, ROW_TILE)
        return (functools.partial(grouped_matmul, **kernels),
                functools.partial(grouped_weight_grad, **kernels))

    def forward(p, rows, scale, sizes):
        rows_by, _ = products(sizes, rows.shape[0])
        hidden = _relu2(rows_by(rows, p["w1"].astype(mxu))) * scale[:, None]
        return rows_by(hidden.astype(mxu), p["w2"].astype(mxu))

    def backward(p, rows, scale, sizes, dy, sums):
        rows_by, weights_by = products(sizes, rows.shape[0])
        w1, w2 = p["w1"].astype(mxu), p["w2"].astype(mxu)
        act = jax.nn.relu(rows_by(rows, w1))
        hidden = act * act
        # y = scale * (hidden @ w2): the cotangent of hidden is scale *
        # (dy @ w2.T) and that of scale is <dy @ w2.T, hidden>, so the
        # out-product is not computed again
        back = rows_by(dy, w2, transpose_w=True)
        din = (2.0 * back * scale[:, None] * act).astype(mxu)
        sums = {"w1": weights_by(rows, din, into=sums["w1"]),
                "w2": weights_by((hidden * scale[:, None]).astype(mxu), dy,
                                 into=sums["w2"])}
        return (rows_by(din, w1, transpose_w=True),
                jnp.sum(back * hidden, axis=-1), sums)
    return moelib.LoopedExperts(
        forward, backward, mxu, functools.partial(
            scatter_add_rows, use_pallas=mode["use_pallas"],
            interpret=mode["interpret"]))


def _experts(p, h, cfg: NemotronHConfig):
    b, s, d = h.shape
    x = h.reshape(b * s, d)
    idx, weights = moelib.topk_sigmoid_router(
        x, p["gate"], p["bias"], k=cfg.num_experts_per_tok,
        scaling=cfg.routed_scaling_factor)
    mode = _kernel_mode(cfg)
    flags.note_kernel(
        "nemotron_moe_dispatch",
        "sort_pallas_grouped" if mode["name"] == "pallas" else mode["name"])
    # The grouped products take their operands as they come: cast to what
    # XLA makes of a float32 product under the ambient precision, as the
    # stack's other products are (bfloat16; float32 under "highest").
    # Elsewhere XLA's own product, and the interpreter's, at float32.
    mxu = ambient_mxu_dtype() if mode["name"] == "pallas" else jnp.float32
    routed, counts = moelib.dropless_dispatch(
        checkpoint_name(_dot(x, p["w_down"]), "moe_latent"), idx, weights,
        cfg.experts_held, _held_experts(mode, mxu),
        {"w1": p["w1"], "w2": p["w2"]},
        block_rows=dispatch_block_rows(cfg, b * s))
    shared = checkpoint_name(_dot(x, p["ws1"]), "moe_shared_hidden")
    y = _dot(routed, p["w_up"]) + _dot(_relu2(shared), p["ws2"])
    return y.reshape(b, s, d), counts


_MIXER = {"M": _mamba, "*": _attention, "E": _experts}
# the named scope of each mixer's layer, norm and residual add included
_PART = {"M": "mamba", "*": "attention", "E": "moe"}


# -- what a layer keeps for its backward pass --------------------------------

def _keepable(cfg: NemotronHConfig, seq: int):
    """The candidates of ``residual_plan``, dearest to recompute per byte
    first. A product of the layer's input with a ``[hidden, width]``
    matrix gives hidden / 2 operations a byte whatever the width, so those
    tie and stay in the order written: attention, experts, Mamba."""
    d, hd = cfg.hidden_size, cfg.head_dim
    hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    routed, k = cfg.n_routed_experts, cfg.num_experts_per_tok
    return ranked([
        product("*", FLASH_RESIDUAL_NAMES[:3], d, (hq + 2 * hkv) * hd),
        # causal: half of the two products over every earlier position
        Keepable("*", FLASH_RESIDUAL_NAMES[3:], 4 * hq * (hd + 1),
                 2.0 * seq * hq * hd),
        # the router's product runs at Precision.HIGHEST, six passes; the
        # top-k and the sort come on top and are not counted
        Keepable("E", moelib.ROUTING_RESIDUAL_NAMES, 4 * (routed + 3 * k),
                 6 * 2.0 * d * routed),
        product("E", ("moe_latent",), d, cfg.moe_latent_size),
        product("E", ("moe_shared_hidden",), d,
                cfg.moe_shared_expert_intermediate_size),
        product("M", ("mamba_in_proj",), d,
                cfg.mamba_inner + cfg.conv_dim + cfg.mamba_num_heads),
    ])


def _plan_for(cfg: NemotronHConfig, mesh: Mesh, params, tokens):
    """What each layer keeps for one call's shapes (``residual_plan``):
    one application a letter of the pattern."""
    return residual_plan._plan_for(
        mesh, params, tokens, cfg.pattern,
        _keepable(cfg, tokens.shape[1]), cfg.hidden_size)


def nemotron_h_loss_fn(cfg: NemotronHConfig, mesh: Mesh, specs: Dict):
    """Builds ``loss(params, tokens, targets) -> (mean cross entropy,
    aux)``, shard_mapped over the hybrid mesh. tokens, targets ``[B, S]``
    int32, B sharded over the data axes. ``aux`` counts what the expert
    layers served, one row per ``E`` layer, summed over the data axes:
    ``load`` ``[layers, held]`` assignments per held expert, ``dropped``
    ``[layers]`` (0: the dispatch has no capacity)."""
    for axis in ("pp", "sp", "ep"):
        if int(mesh.shape[axis]) > 1:
            raise ValueError(
                f"nemotron_h on a mesh with {axis}={mesh.shape[axis]}: the "
                "stack is one pipeline stage, the scan needs its sequence "
                "whole and the expert layer has no exchange yet")
    daxes = _data_axes(mesh)

    def layer(letter, keep):
        def apply(lp, x):
            with jax.named_scope(_PART[letter]):
                y, counts = _MIXER[letter](lp, _rms(x, lp["norm"],
                                                    cfg.norm_eps), cfg)
                return x + y, counts
        return jax.checkpoint(
            apply, policy=jax.checkpoint_policies.save_only_these_names(
                *keep) if keep else None)

    def body(plan, params, tokens, targets):
        with jax.named_scope("embed"):
            x = tplib.vocab_parallel_embedding(
                {"table": params["embed"]}, tokens, axis="mp")
        served = []
        with jax.named_scope("stack"):
            for letter, keep, lp in zip(cfg.pattern, plan.names,
                                        params["layers"]):
                x, counts = layer(letter, keep)(lp, x)
                if counts is not None:
                    served.append(counts)
        with jax.named_scope("head"):
            logits = _dot(_rms(x, params["norm_f"], cfg.norm_eps),
                          params["head"])
            losses = tplib.parallel_cross_entropy(logits, targets,
                                                  axis="mp")
            total = lax.psum(jnp.sum(losses), daxes)
            count = lax.psum(jnp.asarray(losses.size, jnp.float32), daxes)
            held = cfg.experts_held[1]
            aux = {
                "load": lax.psum(jnp.stack(
                    [c.load for c in served]) if served
                    else jnp.zeros((0, held), jnp.int32), daxes),
                "dropped": lax.psum(jnp.stack(
                    [c.dropped for c in served]) if served
                    else jnp.zeros((0,), jnp.int32), daxes),
            }
            return total / count, aux

    def loss(params, tokens, targets):
        plan = _plan_for(cfg, mesh, params, tokens)
        return jax.shard_map(
            functools.partial(body, plan), mesh=mesh,
            in_specs=(specs, P(daxes, None), P(daxes, None)),
            out_specs=(P(), P()), check_vma=False)(params, tokens, targets)
    return loss


def make_nemotron_h_train_step(cfg: NemotronHConfig, mesh: Mesh,
                               specs: Dict, optimizer):
    """Jitted ``(params, opt_state, tokens, targets) -> (params,
    opt_state, loss, aux)`` with donation; ``aux`` as
    ``nemotron_h_loss_fn`` returns it. The ``nemotron_h/build_step`` span
    covers the tracing of the loss and its gradient, once a compilation,
    and says what the layers keep (``ResidualPlan.attributes``)."""
    vg = jax.value_and_grad(nemotron_h_loss_fn(cfg, mesh, specs),
                            has_aux=True)

    def traced(params, tokens, targets):
        plan = _plan_for(cfg, mesh, params, tokens)
        with trace.span("nemotron_h/build_step", layers=len(cfg.pattern),
                        **plan.attributes(cfg.pattern)):
            return vg(params, tokens, targets)
    return make_train_step(traced, optimizer, has_aux=True)
