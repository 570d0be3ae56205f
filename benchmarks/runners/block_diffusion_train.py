"""Runner ``block_diffusion_train``: ``init_block_diffusion`` +
``make_block_diffusion_train_step`` on
``build_mesh(HybridTopology(dp=chips))``, one sequence batch a step.

- set-up: parameters (from the configuration's ``weights_seed``: what a
  chip's 16 of 128 experts serve is the router's weights' own, and a
  step's time follows it, PERF.md section 4) and optimizer state are made
  on the device; the program's value-and-grad function (the one the timed
  step jits) runs on the first batch once at the reference's matmul
  precision; the plain reference runs on that batch twice, as the
  configuration states it and with its products' operands rounded as the
  timed program's are; two warm-up steps compile (or load) the step, and
  the first of them gives the timed step's own loss, counts and parameter
  change;
- window: as ``looped_train``: steps are dispatched one ahead of the one
  being waited for; each step's completion (``float(loss)``) is clocked,
  and the ``aux`` the step returned beside the loss is read then; the rate
  is **trained tokens** (positions of the sequence: L a step, not the 2 L
  rows the stack sees) over the median time between completions;
- every step draws its own batch on the device (``traffic/<mix>.json``:
  sequence length, Zipf exponent; the configuration's block length and
  ``t_min``): token ids over the held vocabulary less the mask id, one
  noise level a block, one Bernoulli draw a position. The draws are inputs
  of the step, so program and reference see the same ones.

``correct`` needs all of these (limits and their reasons below):
(a) of the timed step, first batch: its loss, its masked positions and
    the sum of their weights against the reference's;
(b) of the function the timed step jits, run once at the reference's
    matmul precision: gradients of named leaves against ``jax.grad`` of
    the reference, by relative L2, and the assignments served per held
    expert against the reference's routing;
(c) of the timed step: its change of the same leaves against the
    optimizer's first step on the gradients of the reference with rounded
    operands;
(d) of the timed step: the assignments it served per held expert against
    that reference's routing, and none dropped in any step;
(e) a finite loss in every step.
``controls/<config>.py`` reads every comparison with the reference a term
wrong or a precision below.
"""

from __future__ import annotations

import dataclasses
import importlib
import time
from typing import Dict

import numpy as np

from benchmarks.runners.hybrid_train import (first_updates, leaf_at,
                                             routing_shares)
from benchmarks.runners.looped_train import host_aux

# (a) The timed step's first loss. Its matmuls take bfloat16 operands (8
# bits of mantissa), the reference's run at ``highest``: 2^-8 of the
# value, as the other dense cells. The masked positions are counted, not
# computed: equal. The sum of their weights is one float32 sum of 2,048
# terms up to 1,000 in another order.
LOSS_RTOL = 2.0 ** -8
WEIGHT_RTOL = 1e-5

# (b) Relative L2 error of a leaf's gradient, from the value-and-grad
# function the timed step jits, run once at ``highest``: every leaf reads
# 8e-8 (head) .. 1.4e-5 (a router, the attention's q / k) over twelve
# seeds on the chip, seven of them run after the limits were set (PERF.md
# section 4); the reference with a bfloat16 router reads 6.2e-4 .. 2.9e-2,
# every fault a term wrong 1.1e-3 .. 14. The limit is seven times the
# largest reading and a sixth of the nearest control's smallest. A router's and an expert's gain room for the assignments of
# their own layers that flipped (``_flip_room``).
GRAD_RTOL = 1e-4
# Held assignments that land on another expert than in the reference's
# routing, pooled over the layers, as a share of the reference's: a
# chosen-8 boundary flips where two probabilities are closer than float32
# rounding leaves them. The program reads 0 over twelve seeds (~100,000
# held assignments a batch), the reference with a bfloat16 router 1.2e-2.
ROUTING_SHARE_TOL = 7e-4

# (c) Relative L2 error of what the timed, compiled step added to a leaf in
# its first step, against ``reference.first_update`` of the gradient the
# reference gives with its products' operands rounded to bfloat16. What is
# left is the rounding only the program has (inside the flash kernel, the
# grouped products' cotangents), the assignments that flip on it, and the
# compiled step's own backward pass and optimizer. It cannot see a
# bfloat16 router; (b) and (d) hold that.
UPDATE_RTOL = {
    # factored second moments: the update keeps the gradient's shape.
    # program 1.2e-3 (head) .. 2.5e-2 (the last piece's wq) over twelve
    # seeds; a term wrong 0.11 .. 1.4 on most leaves (a wrong mask inside
    # a block reads 1.6e-2 .. 0.36: (b) holds that one)
    "matrix": 6e-2,
    # the first and a middle piece's routers: program 4e-3 .. 3.1e-2; a
    # term wrong 0.31 .. 1.4. The last piece's is read and not limited
    # (``update_is_limited``: program 4.5e-2 .. 1.6)
    "router": 0.15,
    # element by element the first step leaves the gradient's sign, so
    # this counts signs: 2 sqrt(share that differ). program 0 or one sign
    # of a leaf (0.102 of 384, 0.044 of 2,048)
    "gain": 0.3,
    # one expert's slice of a stacked leaf, compared by direction (the
    # optimizer clips by the whole leaf's root mean square): program
    # 3e-3 .. 6.8e-2, and 0.16 .. 0.28 once in twelve seeds for the last
    # layer's, whose gradient the ~2,000 masked rows alone make; no
    # weight, ungated experts, a wrong position read 0.63 .. 1.45
    "expert": 0.5,
}
# (d) As the routing of (b), of the timed step's own counts against the
# reference with rounded operands: the operands' rounding reaches the
# router's input, so boundaries flip more often: program 2.6e-4 .. 4.0e-4
# pooled over twelve seeds; a bfloat16 router 1.1e-2.
STEP_ROUTING_SHARE_TOL = 4e-3

IN_A_PIECE = ("wq", "wk", "wv", "wo", "gq", "gk", "router")
OF_AN_EXPERT = ("w1", "w3", "w2")
GAIN_LEAVES = ("gq", "gk", "norm_f")


def checked_leaves(pieces: int, per_piece: int, experts=None):
    """Paths of the leaves whose gradients and updates are compared. Of
    the first, a middle and the last piece of the stacked layers: the
    attention's four matrices, the two head-norm gains and the router,
    each leaf stacking the piece's layers; of one layer in each (the
    first, a middle, the last of the stack) one held expert's three
    matrices ``(layers, piece, leaf, row, expert)``; the embedding, the
    head and the final norm. ``experts``: the held expert of each of the
    three layers, in order; None leaves the choice to ``program_reading``
    (the one whose ``w1`` has the largest gradient: in the last layer the
    loss reads the masked rows alone, which at initial weights look alike
    and go to the same few experts)."""
    picks = sorted({0, pieces // 2, pieces - 1})
    rows = {picks[0]: 0, picks[-1]: per_piece - 1}
    experts = iter(experts or [None] * len(picks))
    paths = []
    for i in picks:
        paths += [("layers", i, n) for n in IN_A_PIECE]
        expert = next(experts)
        paths += [("layers", i, n, rows.get(i, per_piece // 2), expert)
                  for n in OF_AN_EXPERT]
    return paths + [("embed",), ("head",), ("norm_f",)]


def update_is_limited(name: str, pieces: int) -> bool:
    """Is the leaf's update held to a limit in (c)? Not the last piece's
    router: no masked row chose most experts of the last layer, those
    columns' gradient is rounding alone, and the optimizer's first step
    scales every column to the same size, rounding included (read 0.7 to
    1.4 against a reference of the same mathematics)."""
    return not name == f"layers.{pieces - 1}.router"


def _kind(name: str) -> str:
    leaf = name.split(".")[2] if name.startswith("layers") else name
    return ("expert" if leaf in OF_AN_EXPERT else "router"
            if leaf == "router" else "gain" if leaf in GAIN_LEAVES
            else "matrix")


def _layers_of(name: str, per_piece: int):
    """The layers a compared leaf belongs to (none for the embedding, the
    head and the final norm)."""
    part = name.split(".")
    if part[0] != "layers":
        return []
    first = int(part[1]) * per_piece
    return ([first + int(part[3])] if len(part) > 3
            else list(range(first, first + per_piece)))


def _flip_room(name: str, per_piece: int, layer_share) -> float:
    """What a router's or an expert's limit gains for the share of its own
    layers' held assignments that flipped: with n assignments of
    near-orthogonal gradients, a share f of them landing elsewhere moves
    the sum by sqrt(2 f)."""
    if _kind(name) not in ("router", "expert"):
        return 0.0
    share = max(layer_share[l] for l in _layers_of(name, per_piece))
    return 2.0 * (2.0 * share) ** 0.5


def grad_errors(paths, got, want) -> Dict[str, float]:
    """Relative L2 error of each compared leaf's gradient (or update). An
    expert's slice is compared by direction, both sides scaled to a root
    mean square of 1: what the optimizer adds to a slice is scaled by the
    whole stacked leaf's clip."""
    out = {}
    for path, g, w in zip(paths, got, want):
        name = ".".join(map(str, path))
        g, w = (np.asarray(x, np.float64).ravel() for x in (g, w))
        if _kind(name) == "expert":
            g, w = (x / max(np.sqrt(np.mean(x * x)), 1e-30) for x in (g, w))
        out[name] = float(np.linalg.norm(g - w)
                          / max(np.linalg.norm(w), 1e-30))
    return out


def outside(loss, want_loss, aux, want_aux, grad_err, routing, per_piece):
    """Names of the comparisons (a), (b) that fall outside their limits;
    ``aux``, ``want_aux`` with ``masked`` and ``weight``; ``routing`` as
    ``routing_shares`` returns it."""
    layer_share, pooled = routing
    bad = []
    if not abs(loss - want_loss) <= LOSS_RTOL * abs(want_loss):
        bad.append("loss")
    if int(aux["masked"]) != int(want_aux["masked"]):
        bad.append("masked")
    if not abs(aux["weight"] - want_aux["weight"]) <= WEIGHT_RTOL * abs(
            want_aux["weight"]):
        bad.append("weight")
    bad += ["grad:" + name for name, err in grad_err.items()
            if not err <= GRAD_RTOL
            + _flip_room(name, per_piece, layer_share)]
    if not pooled <= ROUTING_SHARE_TOL:
        bad.append("routing")
    return bad


def outside_timed(update_err, step_routing, per_piece):
    """Names of the comparisons (c), (d) that fall outside their limits."""
    layer_share, pooled = step_routing
    pieces = len(layer_share) // per_piece
    bad = ["update:" + name for name, err in update_err.items()
           if update_is_limited(name, pieces)
           and not err <= UPDATE_RTOL[_kind(name)]
           + _flip_room(name, per_piece, layer_share)]
    if not pooled <= STEP_ROUTING_SHARE_TOL:
        bad.append("step_routing")
    return bad


def program_config(config: Dict):
    """The program's configuration from the file's keys: the published
    ones, but for what the cut renamed (the file counts the experts held
    under the published key and the router's width beside it)."""
    from paddlebox_tpu.models.block_diffusion import BlockDiffusionConfig
    held = tuple(config["experts_held"])
    if held[1] != config["num_experts"]:
        raise ValueError("num_experts counts the experts held: it and "
                         "experts_held disagree")
    cfg = BlockDiffusionConfig(**dict(
        {f.name: config[f.name]
         for f in dataclasses.fields(BlockDiffusionConfig)
         if f.name in config},
        model_layers=config["published"]["num_hidden_layers"],
        experts_held=held, rope_theta=float(config["rope_theta"])))
    if cfg.mask_token_id != config["mask_token_id"]:
        raise ValueError("mask_token_id: the program masks with the last "
                         "id of the vocabulary it holds")
    return cfg


def init_params(cfg, key, sharding):
    """``(params, specs)`` of ``init_block_diffusion``, made on the device
    from ``key`` and laid out as ``sharding``."""
    import jax
    from paddlebox_tpu.models.block_diffusion import init_block_diffusion
    specs = {}

    def make(k):
        params, s = init_block_diffusion(k, cfg)
        specs.update(s)
        return params
    return jax.jit(make, out_shardings=sharding)(key), specs


def batch_draw(key, config: Dict, zipf_a: float, batch: int, seq: int,
               data):
    """``draw(step) -> (tokens, levels, masked)`` laid out as ``data``,
    drawn on the device from ``key`` and the step's number: tokens
    ``[batch, seq]`` int32, id i with probability ~ (i + 1)^-a by inverse
    CDF over the held vocabulary less the mask id; levels ``[batch, seq /
    block]`` uniform in [t_min, 1]; masked ``[batch, seq]``, a position
    with the probability of its block's level."""
    import jax
    import jax.numpy as jnp
    ids, block = config["mask_token_id"], config["block_length"]
    cdf = jnp.cumsum(jnp.arange(1, ids + 1, dtype=jnp.float32) ** -zipf_a)
    cdf = cdf / cdf[-1]

    @jax.jit
    def draw(step):
        k_tok, k_level, k_mask = jax.random.split(
            jax.random.fold_in(jax.random.fold_in(key, 1), step), 3)
        toks = jnp.minimum(
            jnp.searchsorted(cdf, jax.random.uniform(k_tok, (batch, seq))),
            ids - 1).astype(jnp.int32)
        levels = jax.random.uniform(k_level, (batch, seq // block),
                                    jnp.float32, config["t_min"], 1.0)
        masked = jax.random.uniform(k_mask, (batch, seq)) < jnp.repeat(
            levels, block, axis=1)
        return tuple(jax.lax.with_sharding_constraint(x, data)
                     for x in (toks, levels, masked))
    return draw


def reference_params(reference, params, paths, picked):
    """``params`` as the reference reads them (one entry a layer), with the
    leaves at ``paths`` replaced by ``picked``: a stacked leaf of a piece
    in the piece, an expert's matrix in its layer's stack of experts once
    the layer is cut out of its piece."""
    per_piece = params["layers"][0]["wq"].shape[0]
    tree = dict(params, layers=[dict(piece) for piece in params["layers"]])
    experts = {}
    for path, leaf in zip(paths, picked):
        if path[0] != "layers":
            tree[path[0]] = leaf
        elif len(path) == 3:
            tree["layers"][path[1]][path[2]] = leaf
        else:
            experts.setdefault(path[1] * per_piece + path[3], []).append(
                (path[2], path[4], leaf))
    tree = reference.unstack(tree)

    def with_experts(cut, swaps):
        def layer():
            out = cut()
            for name, e, leaf in swaps:
                out[name] = out[name].at[e].set(leaf)
            return out
        return layer
    for index, swaps in experts.items():
        tree["layers"][index] = with_experts(tree["layers"][index], swaps)
    return tree


def reference_reading(reference, config: Dict, paths):
    """Jitted ``(picked, params, tokens, levels, masked, lower) -> ((loss,
    aux), gradients of picked)`` of the plain reference, ``picked`` being
    the leaves of ``params`` at ``paths`` and ``lower`` what the
    reference's text says: one program for every reading."""
    import jax

    def ref_loss(picked, params, tokens, levels, masked, lower):
        return reference.loss_and_aux(
            reference_params(reference, params, paths, picked), tokens,
            levels, masked, config, lower)
    return jax.jit(jax.value_and_grad(ref_loss, has_aux=True))


def program_reading(cfg, mesh, specs, paths):
    """Jitted ``(params, tokens, levels, masked) -> ((loss, aux),
    gradients of the leaves at ``paths``, the experts chosen)`` of the
    function the timed step jits. Where a path names no expert (None),
    its layer's is chosen here, one for the layer's three matrices: the
    held expert whose ``w1`` has the largest gradient."""
    import jax
    import jax.numpy as jnp
    from paddlebox_tpu.models.block_diffusion import block_diffusion_loss_fn
    vg = jax.value_and_grad(block_diffusion_loss_fn(cfg, mesh, specs),
                            has_aux=True)

    def read(params, tokens, levels, masked):
        out, grads = vg(params, tokens, levels, masked)
        chosen, picked = {}, []
        for path in paths:
            if len(path) > 3 and path[4] is None:
                layer = path[:2] + ("w1", path[3])
                if layer not in chosen:
                    chosen[layer] = jnp.argmax(jnp.sum(jnp.square(
                        leaf_at(grads, layer)), axis=(1, 2)))
                picked.append(leaf_at(grads, path[:4])[chosen[layer]])
            else:
                picked.append(leaf_at(grads, path))
        return out, picked, list(chosen.values())
    return jax.jit(read)


def run(job) -> Dict:
    # the program's entry points first: a checkout without them fails here,
    # before anything is built
    from paddlebox_tpu.models.block_diffusion import (
        make_block_diffusion_train_step)
    import jax
    import optax

    from paddlebox_tpu.core import flags, trace
    from paddlebox_tpu.parallel import HybridTopology, build_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    config, traffic, chips = job.config, job.traffic, job.chips
    flags.set_flags(job.workload.get("flags", {}))
    trace.GLOBAL.enable(ring_events=1 << 12)
    reference = importlib.import_module(
        f"benchmarks.reference.{job.config_name}")
    seq = int(traffic["sequence_length"])
    batch = int(config["sequences_per_chip"]) * chips
    cfg = program_config(config)
    per_piece = cfg.num_hidden_layers // cfg.pieces
    mesh = build_mesh(HybridTopology(dp=chips), devices=jax.devices()[:chips])
    rep = NamedSharding(mesh, P())
    key = jax.random.PRNGKey(job.seed)

    with job.span("setup/init"):
        # the weights' own key (the configuration's ``weights_seed``); the
        # traffic and the noise follow ``--seed``
        params, specs = init_params(
            cfg, jax.random.PRNGKey(config["weights_seed"]), rep)
        opt = optax.adafactor(config["learning_rate"])
        opt_state = jax.jit(opt.init, out_shardings=rep)(params)

    draw = batch_draw(key, config, float(traffic["zipf_a"]), batch, seq,
                      NamedSharding(mesh, P("dp")))
    batch0 = draw(0)
    with job.span("setup/program_grads"):
        # the function the timed step jits, at the reference's matmul
        # precision: see GRAD_RTOL
        with jax.default_matmul_precision("highest"):
            (_, aux0), grads, experts = program_reading(
                cfg, mesh, specs, checked_leaves(cfg.pieces, per_piece))(
                params, *batch0)
        got_grads, aux0 = jax.device_get(grads), host_aux(aux0)
        paths = checked_leaves(cfg.pieces, per_piece,
                               [int(e) for e in experts])
        del grads

    picked = [leaf_at(params, path) for path in paths]
    with job.span("setup/reference_compile"):
        read = reference_reading(reference, config, paths).lower(
            picked, params, *batch0, reference.STATED).compile()
    with job.span("setup/reference"):
        (want, want_aux), want_grads = read(
            picked, params, *batch0, reference.STATED)
        want, want_aux = float(want), host_aux(want_aux)
        grad_err = grad_errors(paths, got_grads, jax.device_get(want_grads))
        del got_grads, want_grads
        # the same, its products' operands rounded as the timed program's
        # (the CPU's default product, in a rehearsal, rounds nothing)
        (_, rounded_aux), rounded_grads = read(
            picked, params, *batch0,
            dict(reference.STATED, operands=not job.rehearse))
        rounded_load = np.asarray(rounded_aux["load"])
        want_update = jax.device_get(first_updates(
            reference, config["learning_rate"])(rounded_grads, picked))
        del rounded_grads
        # the step donates its parameters: what it changes is read
        # against the host's copy
        old = jax.device_get(picked)
        del picked
    routing = routing_shares(aux0["load"], want_aux["load"])

    with job.span("setup/compile"):
        step = make_block_diffusion_train_step(cfg, mesh, specs, opt).lower(
            params, opt_state, *batch0).compile()
        analysis = step.memory_analysis()
        temp_bytes = getattr(analysis, "temp_size_in_bytes", None)
    with job.span("setup/warmup"):
        params, opt_state, loss0, aux = step(params, opt_state, *batch0)
        got, step_aux = float(loss0), host_aux(aux)
        new = jax.device_get([leaf_at(params, path) for path in paths])
        update_err = grad_errors(
            paths, [n - o for n, o in zip(new, old)], want_update)
        del old, new, want_update
        params, opt_state, loss1, aux = step(params, opt_state, *draw(1))
        float(loss1)
        second_aux = host_aux(aux)
        jax.block_until_ready(draw(2))
    step_routing = routing_shares(step_aux["load"], rounded_load)
    bad = (outside(got, want, step_aux, want_aux, grad_err, routing,
                   per_piece)
           + outside_timed(update_err, step_routing, per_piece))

    compiles_at_open = job.compiles()
    if job.trace:
        job.start_device_trace()
    t_open = time.perf_counter()
    done_at, losses, seen = [], [], []
    trace_steps = int(traffic["traced_steps"])
    i, pending = 2, None
    while True:
        params, opt_state, loss, aux = step(params, opt_state, *draw(i))
        i += 1
        if pending is not None:
            losses.append(float(pending[0]))     # waits for that step
            done_at.append(time.perf_counter())
            seen.append(host_aux(pending[1]))
            if job.tracing_now() and len(done_at) >= trace_steps:
                job.stop_device_trace()
            if done_at[-1] - t_open >= job.seconds:
                break
        pending = (loss, aux)
    float(loss)                                  # drain the step in flight
    compiles_in_window = job.compiles() - compiles_at_open

    steps = len(done_at)
    wall = done_at[-1] - t_open
    finite = int(np.isfinite(losses).sum())
    step_s = float(np.median(np.diff([t_open] + done_at)))
    rate = batch * seq / step_s / chips          # trained tokens
    loads = np.asarray([a["load"] for a in seen])    # [steps, layers, held]
    served = int(loads.sum())
    dropped = int(sum(np.sum(a["dropped"])
                      for a in seen + [aux0, step_aux, second_aux]))
    # the fullest held expert of a layer over the layer's mean, worst
    # layer of each step, averaged over the window
    max_over_mean = float(np.mean(np.max(
        loads.max(axis=2) / np.maximum(loads.mean(axis=2), 1e-9), axis=1)))
    masked_positions = int(sum(a["masked"] for a in seen))
    resolved = flags.resolved_kernels()
    on_kernels = "pallas" if not job.rehearse else "xla"
    fallback = int(resolved.get("block_diffusion_attention") != [on_kernels])
    checked_load = {f"{p[1] * per_piece + p[3]}.{p[4]}": int(np.asarray(
        want_aux["load"])[p[1] * per_piece + p[3], p[4]])
        for p in paths if len(p) > 3}
    return {
        "attempted": steps, "failed": steps - finite,
        "correct": bool(not bad and dropped == 0 and finite == steps),
        "window_open": t_open,
        "program_temp_bytes": temp_bytes,
        "end_to_end": {"dense_tokens_per_s_per_chip": rate},
        "detail": {
            "first_step_loss": got, "reference_loss": want,
            "loss_tol": LOSS_RTOL * abs(want), "outside_limits": bad,
            "first_step_aux": step_aux, "reference_aux": want_aux,
            "grad_rel_err": grad_err, "grad_tol": GRAD_RTOL,
            "routing_share_differing": routing[0],
            "routing_share_pooled": routing[1],
            "routing_share_tol": ROUTING_SHARE_TOL,
            "first_batch_load": aux0["load"],
            "step_update_rel_err": update_err, "update_tol": UPDATE_RTOL,
            "step_routing_share_differing": step_routing[0],
            "step_routing_share_pooled": step_routing[1],
            "step_routing_share_tol": STEP_ROUTING_SHARE_TOL,
            "rounded_reference_load": rounded_load.tolist(),
            "checked_expert_load": checked_load,
            "dropped_assignments": dropped,
            "wall_s": wall, "step_ms_median": step_s * 1e3,
            "trained_tokens_per_s_per_chip_over_wall":
                steps * batch * seq / wall / chips,
            "step_program_temp_bytes": temp_bytes,
            "step_program_argument_bytes": getattr(
                analysis, "argument_size_in_bytes", None),
            "last_loss": losses[-1], "last_aux": seen[-1],
            "resolved_kernels": resolved,
        },
        "observed": {
            "program_spans": job.program_spans(),
            "window_unix_ns": (job.unix_ns(t_open), job.unix_ns(done_at[-1])),
            "steps": steps, "chips": chips,
            "traced_steps": min(trace_steps, steps),
            "tokens_per_s_per_chip": rate,
            "counters": {
                "compiles_in_window": compiles_in_window,
                "kernel_fallback": fallback,
                "moe_assignments_served": served,
                "moe_load_max_over_mean": max_over_mean,
                "moe_dropped_assignments": dropped,
                "blockdiff_masked_positions": masked_positions,
                "blockdiff_rows_per_token": 2,
                "resolved_kernels": resolved},
            "shapes": {
                "batch_per_chip": config["sequences_per_chip"], "seq": seq,
                "block_length": config["block_length"],
                "hidden_size": config["hidden_size"],
                "layers": config["num_hidden_layers"],
                "vocab_size": config["vocab_size"], "dtype_bytes": 4,
                "n_head": config["num_attention_heads"],
                "n_kv_head": config["num_key_value_heads"],
                "head_dim": config["head_dim"],
                "router_experts": config["router_experts"],
                "experts_held": config["experts_held"][1],
                "num_experts_per_tok": config["num_experts_per_tok"],
                "moe_intermediate_size": config["moe_intermediate_size"],
            },
        },
    }
