"""Looped (weight-shared) decoder: one stack of equal layers applied
``total_ut_steps`` times to every token, with an exit gate and a loss over
every pass (the LoopLM family, ``model_type`` ``ouro``).

    h(0) = E[tokens]
    h(t) = RMSNorm_f( M_L o ... o M_1 ( h(t-1) ) )          t = 1 .. T
    M(h): a = h + N2(Attn(N1(h)));  M(h) = a + N4(SwiGLU(N3(a)))

the same layers and the same final norm in every pass (sandwich norms:
four RMSNorm gains a layer). ``Attn``: rotary embedding on q and k over
the whole head, rotate-half pairing, then causal softmax attention
(``ops/pallas_kernels/flash_attention.py``); no biases.
``SwiGLU(x) = (silu(x W_g) * (x W_u)) W_d``.

Every pass has its own head reading: ``l(t)`` the cross entropy of
``h(t) W_head``, and for ``t < T`` an exit gate ``lambda_t = sigmoid(w_g .
h(t) + b_g)`` a token. The exit distribution is ``p_t = lambda_t
prod_{j<t} (1 - lambda_j)``, ``p_T`` what is left, and the loss a token is
``sum_t p_t l(t) - beta H(p)``.

Built like ``models/gpt.py`` and ``models/nemotron_h.py``: one
``shard_map`` over the hybrid mesh, vocabulary-parallel embedding and
cross entropy over ``mp``, batch over the data axes; every other weight
is whole on every device. The layers are equal, so their parameters are
stacked, in ``pieces`` equal pieces (``LoopedConfig.pieces``), and a pass
is one ``lax.scan`` a piece; the passes are a Python loop around the scans
(``T`` is small). The program's size does not grow with the depth. Every
layer application is rematerialised (``jax.checkpoint``) and keeps its
input and what ``residual_plan`` finds room for. A scan has one save
policy, so the plan's unit is a piece in a pass; and a piece's gradient
exists once more while a pass's own is added to the sum over the passes,
which is why the stack is cut at all: ``1 / pieces`` of the stack's
gradient, not all of it.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
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
from paddlebox_tpu.parallel import tp as tplib


@dataclasses.dataclass(frozen=True)
class LoopedConfig:
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    num_hidden_layers: int = 48
    total_ut_steps: int = 4             # passes over the stack
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    exit_entropy_weight: float = 0.05   # beta
    # "auto": the Pallas kernel on a TPU, its XLA reference elsewhere;
    # "interpret": the kernel through the Pallas interpreter (tests);
    # "xla": the reference
    kernels: str = "auto"

    @property
    def pieces(self) -> int:
        """Into how many equal pieces the layers are stacked: the most,
        up to ``GRADIENT_PIECES``, that divide them."""
        return max(k for k in range(1, GRADIENT_PIECES + 1)
                   if self.num_hidden_layers % k == 0)


# The smallest share of the stacked layers' gradient that one scan's
# backward pass gives at a time is 1 / GRADIENT_PIECES: a piece's gradient
# exists twice while a pass's own is added to the sum over the passes.
GRADIENT_PIECES = 4


# -- parameters --------------------------------------------------------------

def init_looped(rng: jax.Array, cfg: LoopedConfig) -> Tuple[Dict, Dict]:
    """Returns (params, partition_specs); ``params["layers"]`` is a list
    of ``cfg.pieces`` dicts whose leaves are stacked ``[num_hidden_layers
    / pieces, ...]``: layer l is row ``l % (L / pieces)`` of piece ``l //
    (L / pieces)``.
    normal(0, 0.02) weights, gains 1, the exit gate zero (so that the
    exit distribution starts at 1/2, 1/4, ... and what is left)."""
    if cfg.tie_word_embeddings:
        raise ValueError("tie_word_embeddings: the looped stack keeps an "
                         "embedding and a head of their own")
    d, f, hd = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    n = cfg.num_hidden_layers // cfg.pieces
    hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    with trace.span("looped/init", layers=cfg.num_hidden_layers,
                    passes=cfg.total_ut_steps):
        k = jax.random.split(rng, 2 + cfg.pieces)

        def normal(key, *shape):
            return jax.random.normal(key, shape, jnp.float32) * 0.02

        def piece(key):
            k = jax.random.split(key, 7)
            return {
                "n1": jnp.ones((n, d)), "n2": jnp.ones((n, d)),
                "n3": jnp.ones((n, d)), "n4": jnp.ones((n, d)),
                "wq": normal(k[0], n, d, hq * hd),
                "wk": normal(k[1], n, d, hkv * hd),
                "wv": normal(k[2], n, d, hkv * hd),
                "wo": normal(k[3], n, hq * hd, d),
                "w_gate": normal(k[4], n, d, f),
                "w_up": normal(k[5], n, d, f),
                "w_down": normal(k[6], n, f, d),
            }
        params = {
            "embed": normal(k[0], cfg.vocab_size, d),
            "layers": [piece(key) for key in k[2:]],
            "norm_f": jnp.ones((d,)),
            "gate_w": jnp.zeros((d,)),
            "gate_b": jnp.zeros(()),
            "head": normal(k[1], d, cfg.vocab_size),
        }
        specs = jax.tree.map(lambda _: P(), params)
        specs["embed"] = P("mp", None)      # vocabulary-parallel
        specs["head"] = P(None, "mp")
    return params, specs


# -- the block ---------------------------------------------------------------

def _rms(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * gain


def _dot(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def rotary_embedding(x, positions, theta: float):
    """``x`` ``[..., S, H, D]`` float32 with its pairs ``(x_i, x_{i +
    D/2})`` each turned by ``positions[s] * theta^(-2i / D)`` (rotate-half
    pairing, the whole head). The D / 2 frequencies are made in float64
    where the program is traced; angle, cosine and sine are float32."""
    half = x.shape[-1] // 2
    freq = jnp.asarray(theta ** (-np.arange(half, dtype=np.float64) / half),
                       jnp.float32)
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                           axis=-1)


def _layer(lp, h, cfg: LoopedConfig):
    b, s, _ = h.shape
    hd, eps = cfg.head_dim, cfg.rms_norm_eps
    with jax.named_scope("attention"):
        x = _rms(h, lp["n1"], eps)
        positions = jnp.arange(s)
        q = rotary_embedding(
            _dot(x, lp["wq"]).reshape(b, s, cfg.num_attention_heads, hd),
            positions, cfg.rope_theta)
        k = rotary_embedding(
            _dot(x, lp["wk"]).reshape(b, s, cfg.num_key_value_heads, hd),
            positions, cfg.rope_theta)
        v = _dot(x, lp["wv"]).reshape(b, s, cfg.num_key_value_heads, hd)
        mode = flags.kernel_mode(cfg.kernels)
        flags.note_kernel("looped_attention", mode["name"])
        attn = flash_attention(q, k, v, causal=True,
                               use_pallas=mode["use_pallas"],
                               interpret=mode["interpret"])
        a = h + _rms(_dot(attn.reshape(b, s, -1), lp["wo"]), lp["n2"], eps)
    with jax.named_scope("mlp"):
        x = _rms(a, lp["n3"], eps)
        gate = checkpoint_name(_dot(x, lp["w_gate"]), "looped_gate")
        up = checkpoint_name(_dot(x, lp["w_up"]), "looped_up")
        return a + _rms(_dot(jax.nn.silu(gate) * up, lp["w_down"]),
                        lp["n4"], eps)


# -- what an application keeps for its backward pass -------------------------

def _keepable(cfg: LoopedConfig, seq: int):
    """The candidates of ``residual_plan`` for one application of the
    block (kind ``L``). The products tie at hidden / 2 operations a byte
    and stay in the order written."""
    d, hd = cfg.hidden_size, cfg.head_dim
    hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    return ranked([
        product("L", FLASH_RESIDUAL_NAMES[:3], d, (hq + 2 * hkv) * hd),
        # causal: half of the two products over every earlier position
        Keepable("L", FLASH_RESIDUAL_NAMES[3:], 4 * hq * (hd + 1),
                 2.0 * seq * hq * hd),
        product("L", ("looped_gate",), d, cfg.intermediate_size),
        product("L", ("looped_up",), d, cfg.intermediate_size),
    ])


# What a kept byte is charged against the plan's room. A value kept in an
# early pass outlives every later pass's scans, and the compiled step grows
# by 1.9 to 2.0 bytes for each such byte (the programs compiled for the
# v5e at the published widths, tools/aot_check_dense.py --looped): more
# than the unplanned share of the device covers at this stack's sizes.
KEPT_COST = 2.0


def _plan_for(cfg: LoopedConfig, mesh: Mesh, params, tokens):
    """What the stack keeps for one call's shapes (``residual_plan``):
    one entry a scan, pass by pass and piece by piece within a pass, each
    standing for the ``L / pieces`` applications the scan runs (so the
    plan counts ``T * L`` inputs). Reserved beside parameters, gradients
    and inputs: one piece's gradient a second time (a pass's own, before
    it joins the sum), and one pass's logits with their cotangent (the
    head is rematerialised pass by pass)."""
    t, per_scan = cfg.total_ut_steps, cfg.num_hidden_layers // cfg.pieces
    piece = sum(leaf.size * leaf.dtype.itemsize
                for leaf in jax.tree.leaves(params["layers"][0]))
    shards = int(np.prod([mesh.shape[a] for a in _data_axes(mesh)]))
    logits = (tokens.size // shards
              * (cfg.vocab_size // int(mesh.shape["mp"])) * 4)
    candidates = [c._replace(bytes=c.bytes * per_scan, ops=c.ops * per_scan)
                  for c in _keepable(cfg, tokens.shape[1])]
    return residual_plan._plan_for(
        mesh, params, tokens, "L" * (t * cfg.pieces), candidates,
        cfg.hidden_size * per_scan,
        reserved_bytes=(piece if t > 1 else 0) + 2 * logits,
        kept_cost=KEPT_COST)


def plan_attributes(cfg: LoopedConfig, plan) -> Dict:
    """The plan as the ``looped/build_step`` span reports it: the shared
    attributes (``layers_kept`` counts scans), and ``kept_by_pass``: for
    each group of names, how many of a pass's layers keep it, pass by
    pass."""
    t, k = cfg.total_ut_steps, cfg.pieces
    per_scan = cfg.num_hidden_layers // k
    by_pass = ";".join(
        names[0] + ":" + "/".join(
            str(per_scan * sum(set(names) <= set(kept)
                               for kept in plan.names[p * k:(p + 1) * k]))
            for p in range(t))
        for names in sorted(c.names for c in _keepable(cfg, 1)))
    return dict(plan.attributes("L" * (t * k)), kept_by_pass=by_pass)


# -- the loss ----------------------------------------------------------------

def _summed_where_made(run):
    """``run(shared, x, extra) -> (y, aux)`` as ``(shared, x, extra) ->
    (shared, y, aux)`` for weights every pass uses: they come back as they
    went in and the next pass takes them from here, so that the gradient
    the later passes gave them reaches this use's backward pass as a
    cotangent. This use's own is added to it there and then, behind a
    barrier that the backward passes still to come wait for. Left to
    itself XLA sums the passes' gradients where they are consumed, and
    holds every pass's until then. ``extra`` is not differentiated
    (integers, or None); ``aux`` is not either."""
    @jax.custom_vjp
    def used(shared, x, extra):
        return (shared,) + run(shared, x, extra)

    def forward(shared, x, extra):
        y, back, aux = jax.vjp(lambda s, x: run(s, x, extra), shared, x,
                               has_aux=True)
        return (shared, y, aux), back

    def backward(back, cotangents):
        later, dy, _ = cotangents
        own, dx = back(dy)
        summed, dx = lax.optimization_barrier(
            (jax.tree.map(jnp.add, later, own), dx))
        return summed, dx, None
    used.defvjp(forward, backward)
    return used


def exit_distribution(gate_logits):
    """``gate_logits`` ``[T - 1, ...]`` -> ``p`` ``[T, ...]``: the
    probability of leaving after pass t, the last pass taking what is
    left."""
    lam = jax.nn.sigmoid(gate_logits)
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([lam * before, stay[-1:]], axis=0)


def looped_loss_fn(cfg: LoopedConfig, mesh: Mesh, specs: Dict):
    """Builds ``loss(params, tokens, targets) -> (loss, aux)``,
    shard_mapped over the hybrid mesh. tokens, targets ``[B, S]`` int32, B
    sharded over the data axes. ``loss`` is the mean over tokens of
    ``sum_t p_t l(t) - beta H(p)``. ``aux``, over all tokens of the data
    axes: ``pass_losses`` ``[T]`` the mean cross entropy of each pass,
    ``exit_p`` ``[T]`` the mean exit distribution, ``exit_entropy`` its
    mean entropy; and ``applications``, the layer applications one
    replica's forward ran (``T * L``, counted where they run)."""
    for axis in ("pp", "sp"):
        if int(mesh.shape[axis]) > 1:
            raise ValueError(
                f"looped stack on a mesh with {axis}={mesh.shape[axis]}: "
                "the stack is one pipeline stage (its activations would go "
                "round the stages once a pass) and attention needs its "
                "sequence whole")
    daxes = _data_axes(mesh)
    passes = cfg.total_ut_steps

    def scan_layers(keep):
        """``(stacked, h, None) -> (h', applications run)``: one scan over
        a piece's layers under one save policy."""
        # inside a scan nothing can be shared with the first forward
        apply = jax.checkpoint(
            functools.partial(_layer, cfg=cfg), prevent_cse=False,
            policy=jax.checkpoint_policies.save_only_these_names(*keep)
            if keep else None)

        def run(stacked, h, _):
            def body(carry, lp):
                x, n = carry
                return (apply(lp, x), n + 1), None
            return lax.scan(body, (h, jnp.zeros((), jnp.int32)), stacked)[0]
        return _summed_where_made(run)

    @_summed_where_made
    @jax.checkpoint
    def read_pass(read, h, targets):
        """Per token: the pass's cross entropy and its exit-gate logit."""
        losses = tplib.parallel_cross_entropy(
            _dot(h, read["head"]), targets, axis="mp")
        return (losses, jnp.sum(h * read["gate_w"], axis=-1)
                + read["gate_b"]), None

    def body(plan, params, tokens, targets):
        with jax.named_scope("embed"):
            h = tplib.vocab_parallel_embedding(
                {"table": params["embed"]}, tokens, axis="mp")
        count = jnp.zeros((), jnp.int32)
        read = {n: params[n] for n in ("head", "gate_w", "gate_b")}
        pieces = list(params["layers"])
        losses, gates = [], []
        for p in range(passes):
            with jax.named_scope("stack"):
                for i in range(cfg.pieces):
                    pieces[i], h, ran = scan_layers(
                        plan.names[p * cfg.pieces + i])(pieces[i], h, None)
                    count = count + ran
            with jax.named_scope("head"):
                h = _rms(h, params["norm_f"], cfg.rms_norm_eps)
                read, (ce, gate), _ = read_pass(read, h, targets)
            losses.append(ce)
            gates.append(gate)
        with jax.named_scope("head"):
            losses = jnp.stack(losses)                      # [T, B, S]
            p_exit = exit_distribution(jnp.stack(gates[:-1]))
            entropy = jnp.sum(jax.scipy.special.entr(p_exit), axis=0)
            per_token = (jnp.sum(p_exit * losses, axis=0)
                         - cfg.exit_entropy_weight * entropy)
            tokens_all = lax.psum(
                jnp.asarray(per_token.size, jnp.float32), daxes)

            def mean(x):
                return (lax.psum(jnp.sum(x, axis=(-2, -1)), daxes)
                        / tokens_all)
            aux = {"pass_losses": mean(losses), "exit_p": mean(p_exit),
                   "exit_entropy": mean(entropy), "applications": count}
            return mean(per_token), aux

    def loss(params, tokens, targets):
        plan = _plan_for(cfg, mesh, params, tokens)
        return jax.shard_map(
            functools.partial(body, plan), mesh=mesh,
            in_specs=(specs, P(daxes, None), P(daxes, None)),
            out_specs=(P(), P()), check_vma=False)(params, tokens, targets)
    return loss


def make_looped_train_step(cfg: LoopedConfig, mesh: Mesh, specs: Dict,
                           optimizer):
    """Jitted ``(params, opt_state, tokens, targets) -> (params,
    opt_state, loss, aux)`` with donation; ``aux`` as ``looped_loss_fn``
    returns it. The ``looped/build_step`` span covers the tracing of the
    loss and its gradient, once a compilation, and says what the
    applications keep (``plan_attributes``)."""
    vg = jax.value_and_grad(looped_loss_fn(cfg, mesh, specs), has_aux=True)

    def traced(params, tokens, targets):
        plan = _plan_for(cfg, mesh, params, tokens)
        with trace.span("looped/build_step", passes=cfg.total_ut_steps,
                        layers=cfg.num_hidden_layers,
                        **plan_attributes(cfg, plan)):
            return vg(params, tokens, targets)
    return make_train_step(traced, optimizer, has_aux=True)
