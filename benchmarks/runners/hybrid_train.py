"""Runner ``hybrid_train``: ``init_nemotron_h`` +
``make_nemotron_h_train_step`` on ``build_mesh(HybridTopology(dp=chips))``,
one sequence batch a step.

- set-up: parameters and optimizer state are made on the device from
  ``--seed``; the plain reference runs on the first batch twice, as the
  configuration states it and with its products' operands rounded as the
  timed program's are; the program's value-and-grad function (the one the
  timed step jits) runs on it once at the reference's matmul precision;
  two warm-up steps compile (or load) the step, and the first of them
  gives the timed step's own loss, router counts and parameter change;
- window: as ``dense_train``: steps are dispatched one ahead of the one
  being waited for; each step's completion (``float(loss)``) is clocked,
  and the router counts the step returned beside the loss are read then;
  the rate is tokens per step over the median time between completions;
- every step draws its own token batch on the device
  (``traffic/<mix>.json``: sequence length, Zipf exponent).

``correct`` needs all of these (limits and their reasons below). Of the
timed step itself, on the first batch:
(a) its loss against the reference's;
(e) its change of named leaves against the optimizer's first step on the
    gradients of the reference with rounded operands, by relative L2;
(f) the assignments it served per held expert against that reference's
    routing, as a share that may differ;
(d) no dropped assignment and a finite loss in every step.
And of the function the timed step jits, run once at the reference's
matmul precision, where the stated precisions can be told from the ones
below them:
(b) gradients of the same leaves against ``jax.grad`` of the reference;
(c) assignments served per held expert against the reference's routing,
    pooled over the E layers.
``controls/<config>.py`` reads every comparison with the reference a
precision below, or a term short.
"""

from __future__ import annotations

import dataclasses
import importlib
import time
from typing import Dict

import numpy as np

# (a) The timed step's own first loss. Its matmuls take bfloat16 operands
# (8 bits of mantissa), the reference's run at ``highest``: 2^-8 of the
# loss, as the GPT cell (measured 2e-6 .. 5e-5). At initial weights the
# loss is ln(V) plus what the logits' spread adds and barely feels the
# layers: it holds the head, the final norm and the targets' shift. The
# layers are held by (b), (c), (e) and (f).
LOSS_RTOL = 2.0 ** -8

# (b) Relative L2 error of a leaf's gradient, from the value-and-grad
# function the timed step jits, run once at the reference's matmul
# precision (``highest``; the scan kernel follows the ambient precision
# as XLA's matmuls do). This is the comparison that tells the stated
# precision from the one below: at the timed precision the rounding that
# only the program has (UPDATE_RTOL) moves these gradients as far as a
# bfloat16 scan state does. At ``highest`` each limit sits between what
# the program reads over its seeds and what ``controls/`` reads for
# the reference with a bfloat16 scan state (all on the chip, PERF.md
# section 4):
GRAD_RTOL = {
    # program 1.2e-5 .. 1.4e-4; bfloat16 scan state 3.5e-3 .. 1.1e-2,
    # bfloat16 router 7e-4 .. 8.9e-4 on layers 0 and 7
    "matrix": 4e-4,
    # a_log, dt_bias, d: sums of terms of both signs over every position.
    # program 3.3e-5 .. 5.2e-4 (median 1.3e-4); bfloat16 scan state
    # 1.0e-2 .. 1.8e-2
    "scan_head": 1.6e-3,
    # the router's matrix feels a flipped choice among all 512 experts
    # through the weights' normalisation: program 4.5e-5 .. 4.4e-3 where
    # no held assignment of its layer flipped; a missing term 0.97
    "router": 2e-2,
}
SCAN_HEAD_LEAVES = ("a_log", "dt_bias", "d")
ROUTER_LEAVES = ("gate",)
# The routed experts' leaves, and the router's, see a flipped held
# assignment whole: with n held assignments of near-orthogonal gradients,
# a share f of them landing elsewhere moves the sum by sqrt(2 f) (read:
# 1.6-2.0% for one flip in 2,548). They get their limit plus twice that
# for the share of their own layer's assignments that flipped.
ROUTED_LEAVES = ("w_down", "w1", "w2")

# (c) Held assignments that land on another expert than in the
# reference's routing, pooled over the E layers, as a share of the
# reference's: a chosen-22 boundary flips where two scores are closer
# than float32 rounding leaves them, 0 to 3 times in the ~14,000 held
# assignments of a batch (program 0 .. 2.3e-4 over its seeds). The
# reference with a bfloat16 router reads 1.2e-2 (8.8e-3 .. 1.8e-2 by
# layer), with a bfloat16 scan state 5.0e-3: this is the limit that holds
# the router's precision. Pooled, because flips are rare events: a single
# layer's share swings between 0 and 1e-3 on three flips.
ROUTING_SHARE_TOL = 7e-4

# (e) Relative L2 error of what the timed, compiled step added to a leaf
# in its first step (new - old, read back from the step's own outputs),
# against ``reference.first_update`` of the gradient the reference gives
# with its products' operands rounded to bfloat16 as XLA rounds the
# program's. What is left is the rounding only the program has (inside
# the scan kernel's dual form, the grouped product, the flash kernel) and
# the assignments that flip on it. It holds what (b) cannot see: the
# bfloat16-operand build of the kernels, the compiled step's own backward
# pass and the optimizer; it cannot see a bfloat16 scan state (1.6e-2 on
# layer 0's w_in, beside the program's 1.2e-2). Each limit between the
# program over its seeds and the reference a term short (no D x skip in
# layer 2, no shared expert in layer 1):
UPDATE_RTOL = {
    # leaves whose second moments are factored, so that the update keeps
    # the gradient's shape: program 6.2e-3 (head) .. 1.9e-2 (wq), routed
    # leaves 3.5e-2 .. 6.5e-2 with 0.2-0.4% of their layer's assignments
    # flipped; a term short 0.48 (head) .. 1.4
    "matrix": 6e-2,
    # the router's matrix feels the flips among all 512 experts: program
    # 9.7e-2 .. 0.11; a term short 0.91 .. 1.3
    "router": 0.15,
    # a_log, dt_bias, d, conv_w: element by element the first step leaves
    # the gradient's sign, so this counts signs: 2 sqrt(share that differ).
    # program 0 .. 0.25 (two of 128 heads), conv_w 0.11 .. 0.12; a term
    # short 0.92 .. 1.5
    "sign": 0.5,
}
SIGN_LEAVES = SCAN_HEAD_LEAVES + ("conv_w",)

# (f) As (c), of the timed step's own counts against the reference with
# rounded operands, pooled: the kernels' operand rounding reaches the
# router's input, so boundaries flip more often than in (c): program
# 2.8e-3 .. 4.5e-3 (a layer's up to 1.0e-2); a term short 6.6e-2 and 0.38.
# A bfloat16 router (1.1e-2) cannot be told here. It holds the timed
# program's routing and counting against a layer that serves other rows
# than it chose.
STEP_ROUTING_SHARE_TOL = 1.5e-2


def leaf_at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def checked_leaves(pattern: str):
    """Paths of the leaves whose gradients are compared: of the first
    layer of each kind its matrices, of the first Mamba layer everything
    the scan's backward pass feeds, and the head."""
    first = {letter: pattern.index(letter) for letter in "M*E"
             if letter in pattern}
    picks = [(first["M"], n) for n in ("w_in", "a_log", "dt_bias", "d",
                                       "conv_w", "w_out")]
    picks += [(first["*"], n) for n in ("wq", "wk", "wv")]
    picks += [(first["E"], n) for n in ("gate", "w_down", "w1", "w2",
                                        "ws1")]
    return [("layers", i, n) for i, n in picks] + [("head",)]


def with_leaves(params, paths, leaves):
    """``params`` with the leaves at ``paths`` replaced."""
    params = dict(params, layers=list(params["layers"]))
    for path, leaf in zip(paths, leaves):
        if path[0] == "layers":
            params["layers"][path[1]] = dict(params["layers"][path[1]],
                                             **{path[2]: leaf})
        else:
            params[path[0]] = leaf
    return params


def grad_errors(paths, got, want) -> Dict[str, float]:
    """Relative L2 error of each compared leaf's gradient (or update);
    leaves on the device or on the host."""
    import jax.numpy as jnp
    lib = np if isinstance(got[0], np.ndarray) else jnp
    return {".".join(map(str, path)): float(
        lib.linalg.norm((g - w).ravel())
        / lib.maximum(lib.linalg.norm(w.ravel()), 1e-30))
        for path, g, w in zip(paths, got, want)}


def routing_shares(got_load, want_load):
    """Assignments on another held expert than in the reference, over the
    reference's held assignments: (per E layer, pooled over the layers)."""
    got_load, want_load = np.asarray(got_load), np.asarray(want_load)
    moved = np.abs(got_load - want_load).sum(axis=1)
    return ((moved / np.maximum(want_load.sum(axis=1), 1)).tolist(),
            float(moved.sum() / max(want_load.sum(), 1)))


def _flip_room(name: str, pattern: str, layer_share) -> float:
    """What a routed or router leaf's limit gains for the share of its
    own layer's assignments that flipped; 0 for any other leaf."""
    leaf = name.rsplit(".", 1)[-1]
    if leaf not in ROUTER_LEAVES + ROUTED_LEAVES:
        return 0.0
    layer = int(name.split(".")[1])
    share = layer_share[pattern[:layer].count("E")]
    return 2.0 * (2.0 * share) ** 0.5


def grad_limit(name: str, pattern: str, layer_share) -> float:
    """The limit of (b) for the leaf ``layers.<i>.<leaf>`` or ``head``,
    given each E layer's share of flipped assignments."""
    leaf = name.rsplit(".", 1)[-1]
    kind = ("scan_head" if leaf in SCAN_HEAD_LEAVES
            else "router" if leaf in ROUTER_LEAVES else "matrix")
    return GRAD_RTOL[kind] + _flip_room(name, pattern, layer_share)


def update_limit(name: str, pattern: str, layer_share) -> float:
    """The limit of (e), likewise."""
    leaf = name.rsplit(".", 1)[-1]
    kind = ("sign" if leaf in SIGN_LEAVES
            else "router" if leaf in ROUTER_LEAVES else "matrix")
    return UPDATE_RTOL[kind] + _flip_room(name, pattern, layer_share)


def outside(loss, want_loss, grad_err, routing, pattern):
    """Names of the comparisons (a), (b), (c) that fall outside their
    limits; ``routing`` as ``routing_shares`` returns it."""
    layer_share, pooled = routing
    bad = []
    if not abs(loss - want_loss) <= LOSS_RTOL * abs(want_loss):
        bad.append("loss")
    bad += ["grad:" + name for name, err in grad_err.items()
            if not err <= grad_limit(name, pattern, layer_share)]
    if not pooled <= ROUTING_SHARE_TOL:
        bad.append("routing")
    return bad


def outside_timed(update_err, step_routing, pattern):
    """Names of the comparisons (e), (f) that fall outside their limits."""
    layer_share, pooled = step_routing
    bad = ["update:" + name for name, err in update_err.items()
           if not err <= update_limit(name, pattern, layer_share)]
    if not pooled <= STEP_ROUTING_SHARE_TOL:
        bad.append("step_routing")
    return bad


def program_config(config: Dict):
    """The program's configuration from the file's keys: the published
    ones, but for what the cut renamed (the file counts the experts held
    under the published key and the router's width beside it)."""
    from paddlebox_tpu.models.nemotron_h import NemotronHConfig
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != config["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern and num_hidden_layers "
                         "disagree")
    return NemotronHConfig(**dict(
        {f.name: config[f.name] for f in dataclasses.fields(NemotronHConfig)
         if f.name in config},
        pattern=pattern,
        num_hidden_layers=config["published"]["num_hidden_layers"],
        n_routed_experts=config["router_experts"],
        experts_held=tuple(config["experts_held"]),
        routed_scaling_factor=float(config["routed_scaling_factor"])))


def token_draw(key, vocab: int, zipf_a: float, batch: int, seq: int, data):
    """``draw(step) -> (tokens, targets)``, each ``[batch, seq]`` int32
    laid out as ``data``: id i with probability ~ (i + 1)^-a by inverse
    CDF, drawn on the device from ``key`` and the step's number; position
    t is trained to predict t + 1."""
    import jax
    import jax.numpy as jnp
    cdf = jnp.cumsum(jnp.arange(1, vocab + 1, dtype=jnp.float32) ** -zipf_a)
    cdf = cdf / cdf[-1]

    @jax.jit
    def draw(step):
        u = jax.random.uniform(
            jax.random.fold_in(jax.random.fold_in(key, 1), step),
            (batch, seq + 1))
        toks = jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1).astype(
            jnp.int32)
        return (jax.lax.with_sharding_constraint(toks[:, :-1], data),
                jax.lax.with_sharding_constraint(toks[:, 1:], data))
    return draw


def reference_reading(reference, config: Dict, leaves):
    """Jitted ``(picked, params, tokens, targets, lower) -> ((loss, load),
    gradients of picked)`` of the plain reference, ``picked`` being the
    leaves of ``params`` at the paths ``leaves`` and ``lower`` what the
    reference's text says: one program for every reading."""
    import jax

    def ref_loss(picked, params, tokens, targets, lower):
        return reference.loss_and_load(
            with_leaves(params, leaves, picked), tokens, targets, config,
            lower)
    return jax.jit(jax.value_and_grad(ref_loss, has_aux=True))


def first_updates(reference, learning_rate: float):
    """Jitted ``(grads, picked) -> reference.first_update`` leaf by leaf."""
    import jax
    return jax.jit(lambda grads, picked: [
        reference.first_update(g, p, learning_rate)
        for g, p in zip(grads, picked)])


def init_params(cfg, key, sharding):
    """``(params, specs)`` of ``init_nemotron_h``, made on the device from
    ``key`` and laid out as ``sharding``."""
    import jax
    from paddlebox_tpu.models.nemotron_h import init_nemotron_h
    specs = {}

    def make(k):
        params, s = init_nemotron_h(k, cfg)
        specs.update(s)
        return params
    return jax.jit(make, out_shardings=sharding)(key), specs


def run(job) -> Dict:
    import jax
    import optax

    from paddlebox_tpu.core import flags, trace
    from paddlebox_tpu.models.nemotron_h import (
        make_nemotron_h_train_step, nemotron_h_loss_fn)
    from paddlebox_tpu.parallel import HybridTopology, build_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    config, traffic, chips = job.config, job.traffic, job.chips
    flags.set_flags(job.workload.get("flags", {}))
    trace.GLOBAL.enable(ring_events=1 << 12)
    reference = importlib.import_module(
        f"benchmarks.reference.{job.config_name}")
    seq = int(traffic["sequence_length"])
    batch = int(config["sequences_per_chip"]) * chips
    pattern = config["hybrid_override_pattern"]
    cfg = program_config(config)
    mesh = build_mesh(HybridTopology(dp=chips), devices=jax.devices()[:chips])
    rep = NamedSharding(mesh, P())
    key = jax.random.PRNGKey(job.seed)

    with job.span("setup/init"):
        params, specs = init_params(cfg, key, rep)
        opt = optax.adafactor(config["learning_rate"])
        opt_state = jax.jit(opt.init, out_shardings=rep)(params)

    vocab = config["vocab_size"]
    draw = token_draw(key, vocab, float(traffic["zipf_a"]), batch, seq,
                      NamedSharding(mesh, P("dp")))
    leaves = checked_leaves(pattern)
    tokens0, targets0 = draw(0)
    with job.span("setup/program_grads"):
        # the function the timed step jits, at the reference's matmul
        # precision: see GRAD_RTOL
        with jax.default_matmul_precision("highest"):
            (_, aux0), grads = jax.jit(jax.value_and_grad(
                nemotron_h_loss_fn(cfg, mesh, specs), has_aux=True))(
                params, tokens0, targets0)
        # to the host: the reference's own gradient needs the room
        got_grads = jax.device_get([leaf_at(grads, path) for path in leaves])
        got_load = np.asarray(aux0["load"])
        del grads

    with job.span("setup/reference"):
        read = reference_reading(reference, config, leaves)
        picked = [leaf_at(params, path) for path in leaves]
        (want, want_load), want_grads = read(
            picked, params, tokens0, targets0, reference.STATED)
        want, want_load = float(want), np.asarray(want_load)
        grad_err = grad_errors(leaves, got_grads, jax.device_get(want_grads))
        del got_grads, want_grads
        # the same, its products' operands rounded as the timed program's
        # (the CPU's default product, in a rehearsal, rounds nothing)
        (_, rounded_load), rounded_grads = read(
            picked, params, tokens0, targets0,
            dict(reference.STATED, operands=not job.rehearse))
        rounded_load = np.asarray(rounded_load)
        want_update = jax.device_get(first_updates(
            reference, config["learning_rate"])(rounded_grads, picked))
        del rounded_grads
        # the step donates its parameters: what it changes is read
        # against the host's copy
        old = jax.device_get(picked)
        del picked
    routing = routing_shares(got_load, want_load)

    with job.span("setup/compile"):
        step = make_nemotron_h_train_step(cfg, mesh, specs, opt).lower(
            params, opt_state, tokens0, targets0).compile()
        analysis = step.memory_analysis()
        temp_bytes = getattr(analysis, "temp_size_in_bytes", None)
    with job.span("setup/warmup"):
        params, opt_state, loss0, aux = step(params, opt_state, tokens0,
                                             targets0)
        got = float(loss0)
        step_load = np.asarray(aux["load"])
        dropped = int(np.asarray(aux["dropped"]).sum())
        new = jax.device_get([leaf_at(params, path) for path in leaves])
        update_err = grad_errors(
            leaves, [n - o for n, o in zip(new, old)], want_update)
        del old, new, want_update
        params, opt_state, loss1, aux = step(params, opt_state, *draw(1))
        float(loss1)
        jax.block_until_ready(draw(2))
    step_routing = routing_shares(step_load, rounded_load)
    bad = (outside(got, want, grad_err, routing, pattern)
           + outside_timed(update_err, step_routing, pattern))

    compiles_at_open = job.compiles()
    if job.trace:
        job.start_device_trace()
    t_open = time.perf_counter()
    done_at, losses, loads = [], [], []
    trace_steps = int(traffic["traced_steps"])
    i, pending = 2, None
    while True:
        tok, tgt = draw(i)
        params, opt_state, loss, aux = step(params, opt_state, tok, tgt)
        i += 1
        if pending is not None:
            losses.append(float(pending[0]))     # waits for that step
            done_at.append(time.perf_counter())
            loads.append(np.asarray(pending[1]["load"]))
            dropped += int(np.asarray(pending[1]["dropped"]).sum())
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
    rate = batch * seq / step_s / chips
    loads = np.stack(loads)                      # [steps, E layers, held]
    served = int(loads.sum())
    # the fullest held expert of a layer over the layer's mean, worst
    # layer of each step, averaged over the window
    max_over_mean = float(np.mean(np.max(
        loads.max(axis=2) / np.maximum(loads.mean(axis=2), 1e-9), axis=1)))
    resolved = flags.resolved_kernels()
    on_kernels = "pallas" if not job.rehearse else "xla"
    fallback = sum(resolved.get(site) != [on_kernels]
                   for site in ("nemotron_ssd", "nemotron_attention"))
    n_e = pattern.count("E")
    return {
        "attempted": steps, "failed": steps - finite,
        "correct": bool(not bad and dropped == 0 and finite == steps),
        "window_open": t_open,
        "program_temp_bytes": temp_bytes,
        "end_to_end": {"dense_tokens_per_s_per_chip": rate},
        "detail": {
            "first_step_loss": got, "reference_loss": want,
            "loss_tol": LOSS_RTOL * abs(want), "outside_limits": bad,
            "grad_rel_err": grad_err, "grad_tol": GRAD_RTOL,
            "routing_share_differing": routing[0],
            "routing_share_pooled": routing[1],
            "routing_share_tol": ROUTING_SHARE_TOL,
            "first_batch_load": got_load.tolist(),
            "reference_load": want_load.tolist(),
            "step_update_rel_err": update_err, "update_tol": UPDATE_RTOL,
            "step_routing_share_differing": step_routing[0],
            "step_routing_share_pooled": step_routing[1],
            "step_routing_share_tol": STEP_ROUTING_SHARE_TOL,
            "first_step_load": step_load.tolist(),
            "rounded_reference_load": rounded_load.tolist(),
            "dropped_assignments": dropped,
            "wall_s": wall, "step_ms_median": step_s * 1e3,
            "tokens_per_s_per_chip_over_wall":
                steps * batch * seq / wall / chips,
            "step_program_temp_bytes": temp_bytes,
            "step_program_argument_bytes": getattr(
                analysis, "argument_size_in_bytes", None),
            "last_loss": losses[-1],
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
                "kernel_fallback": int(fallback),
                "moe_assignments_served": served,
                "moe_load_max_over_mean": max_over_mean,
                "moe_dropped_assignments": dropped,
                "resolved_kernels": resolved},
            "shapes": {
                "batch_per_chip": config["sequences_per_chip"], "seq": seq,
                "hidden_size": config["hidden_size"], "pattern": pattern,
                "vocab_size": vocab, "dtype_bytes": 4,
                "n_head": config["num_attention_heads"],
                "n_kv_head": config["num_key_value_heads"],
                "head_dim": config["head_dim"],
                "mamba_num_heads": config["mamba_num_heads"],
                "mamba_head_dim": config["mamba_head_dim"],
                "ssm_state_size": config["ssm_state_size"],
                "n_groups": config["n_groups"],
                "conv_kernel": config["conv_kernel"],
                "chunk_size": config["chunk_size"],
                "router_experts": config["router_experts"],
                "moe_latent_size": config["moe_latent_size"],
                "moe_intermediate_size": config["moe_intermediate_size"],
                "moe_shared_expert_intermediate_size": config[
                    "moe_shared_expert_intermediate_size"],
                "assignments_served_per_token":
                    served / max(steps * batch * seq * n_e, 1),
            },
        },
    }
