"""Runner ``looped_train``: ``init_looped`` + ``make_looped_train_step`` on
``build_mesh(HybridTopology(dp=chips))``, one sequence batch a step.

- set-up: parameters and optimizer state are made on the device from
  ``--seed``; the program's value-and-grad function (the one the timed
  step jits) runs on the first batch once at the reference's matmul
  precision; the plain reference runs on that batch twice, as the
  configuration states it and with its products' operands rounded as the
  timed program's are; two warm-up steps compile (or load) the step, and
  the first of them gives the timed step's own loss, per-pass losses, exit
  distribution and parameter change;
- window: as ``dense_train`` and ``hybrid_train``: steps are dispatched
  one ahead of the one being waited for; each step's completion
  (``float(loss)``) is clocked, and the ``aux`` the step returned beside
  the loss is read then; the rate is tokens per step over the median time
  between completions;
- every step draws its own token batch on the device
  (``traffic/<mix>.json``: sequence length, Zipf exponent).

``correct`` needs all of these (limits and their reasons below):
(a) of the timed step, first batch: its loss, its per-pass losses and its
    mean exit distribution against the reference's;
(b) of the function the timed step jits, run once at the reference's
    matmul precision: gradients of named leaves against ``jax.grad`` of
    the reference, by relative L2. A shared weight's gradient is the sum
    of the passes' terms, so this and (c) are what hold the loop;
(c) of the timed step: its change of the same leaves against the
    optimizer's first step on the gradients of the reference with rounded
    operands;
(d) a finite loss and ``aux.applications`` = passes x layers in every
    step.
``controls/<config>.py`` reads every comparison with the reference a term
short or a precision below.
"""

from __future__ import annotations

import dataclasses
import importlib
import time
from typing import Dict

import numpy as np

from benchmarks.runners.hybrid_train import (first_updates, grad_errors,
                                             leaf_at, token_draw,
                                             with_leaves)

# (a) The timed step's first loss and each pass's mean cross entropy. Its
# matmuls take bfloat16 operands (8 bits of mantissa), the reference's run
# at ``highest``: 2^-8 of the value, as the other dense cells (read on the
# chip over three seeds: the loss 2.2e-5 .. 8.6e-5, a pass's 7e-7 ..
# 3.7e-4; no entropy term reads 5.4e-3, and a missing norm moves three
# passes' losses by 5e-3 .. 9.6e-3).
LOSS_RTOL = 2.0 ** -8
# The mean exit distribution, by absolute difference of each p_t. The
# gate's weights start at zero, so program and reference both read 1/2,
# 1/4, 1/8, 1/8 exactly; a stack run three times reads 1/2, 1/4, 1/4, 0
# and no gate 1/4 each.
EXIT_ATOL = 2.0 ** -10

# (b) Relative L2 error of a leaf's gradient, from the value-and-grad
# function the timed step jits, run once at ``highest``: wq, wo, w_gate,
# w_down and n2 of the first, a middle and the last piece, head, embed,
# norm_f and the gate. The program reads 6.3e-7 (head) .. 3.0e-5 (the
# first piece's wq, where the scores' rounding at far positions enters)
# over four seeds on the chip; the reference with a bfloat16 state
# between passes reads 2.2e-4 (head) .. 2.0e-3 over two seeds, every fault
# a term short 1.4e-2 .. 1.4 (PERF.md section 4). The limit is 3.4 times
# the largest reading, for seeds not yet seen, and under half of the
# nearest control's smallest.
GRAD_RTOL = 1e-4
# (c) Relative L2 error of what the timed, compiled step added to a leaf in
# its first step, against ``reference.first_update`` of the gradient the
# reference gives with its products' operands rounded to bfloat16. What is
# left is the rounding only the program has (inside the flash kernel) and
# the compiled step's own backward pass and optimizer. It cannot see a
# bfloat16 state between passes (4e-3 .. 8e-3 on matrices, beside the
# program's own 1.5e-2 .. 2.9e-2); (b) holds that.
UPDATE_RTOL = {
    # factored second moments: the update keeps the gradient's shape.
    # program 1.5e-2 (embed) .. 2.9e-2 (wq); a term short 0.30 .. 1.4
    "matrix": 5e-2,
    # element by element the first step leaves the gradient's sign, so
    # this counts signs: 2 sqrt(share that differ). program 0.108 ..
    # 0.159 (0.3% .. 0.6% of signs); a term short 0.49 .. 1.4
    "gain": 0.3,
}
MATRIX_LEAVES = ("wq", "wo", "w_gate", "w_down", "head", "embed")
CHECKED_IN_A_PIECE = ("wq", "wo", "w_gate", "w_down", "n2")


def checked_leaves(pieces: int):
    """Paths of the leaves whose gradients and updates are compared: of
    the first, a middle and the last piece of the stacked layers (each
    leaf stacks the piece's layers) four matrices and one norm gain; the
    head, the embedding, the final norm and the exit gate."""
    picks = sorted({0, pieces // 2, pieces - 1})
    return ([("layers", i, n) for i in picks for n in CHECKED_IN_A_PIECE]
            + [("head",), ("embed",), ("norm_f",), ("gate_w",),
               ("gate_b",)])


def _kind(name: str) -> str:
    return "matrix" if name.rsplit(".", 1)[-1] in MATRIX_LEAVES else "gain"


def outside(loss, want_loss, aux, want_aux, grad_err):
    """Names of the comparisons (a), (b) that fall outside their limits;
    ``aux``, ``want_aux`` with ``pass_losses`` and ``exit_p``."""
    bad = []
    if not abs(loss - want_loss) <= LOSS_RTOL * abs(want_loss):
        bad.append("loss")
    got, want = (np.asarray(a["pass_losses"], np.float64)
                 for a in (aux, want_aux))
    bad += [f"pass_loss:{t + 1}" for t in range(len(want))
            if not abs(got[t] - want[t]) <= LOSS_RTOL * abs(want[t])]
    got, want = (np.asarray(a["exit_p"], np.float64)
                 for a in (aux, want_aux))
    bad += [f"exit_p:{t + 1}" for t in range(len(want))
            if not abs(got[t] - want[t]) <= EXIT_ATOL]
    bad += ["grad:" + name for name, err in grad_err.items()
            if not err <= GRAD_RTOL]
    return bad


def outside_timed(update_err):
    """Names of the comparisons (c) that fall outside their limits."""
    return ["update:" + name for name, err in update_err.items()
            if not err <= UPDATE_RTOL[_kind(name)]]


def program_config(config: Dict):
    """The program's configuration from the file's published keys."""
    from paddlebox_tpu.models.looped import LoopedConfig
    if len(config["layer_types"]) != config["num_hidden_layers"] or set(
            config["layer_types"]) != {"full_attention"}:
        raise ValueError("layer_types: num_hidden_layers x full_attention "
                         "is what the looped stack runs")
    return LoopedConfig(**dict(
        {f.name: config[f.name] for f in dataclasses.fields(LoopedConfig)
         if f.name in config}, rope_theta=float(config["rope_theta"])))


def init_params(cfg, key, sharding):
    """``(params, specs)`` of ``init_looped``, made on the device from
    ``key`` and laid out as ``sharding``."""
    import jax
    from paddlebox_tpu.models.looped import init_looped
    specs = {}

    def make(k):
        params, s = init_looped(k, cfg)
        specs.update(s)
        return params
    return jax.jit(make, out_shardings=sharding)(key), specs


def reference_reading(reference, config: Dict, leaves):
    """Jitted ``(picked, params, tokens, targets, lower) -> ((loss, aux),
    gradients of picked)`` of the plain reference, ``picked`` being the
    leaves of ``params`` at the paths ``leaves`` and ``lower`` what the
    reference's text says: one program for every reading."""
    import jax

    def ref_loss(picked, params, tokens, targets, lower):
        return reference.loss_and_aux(
            with_leaves(params, leaves, picked), tokens, targets, config,
            lower)
    return jax.jit(jax.value_and_grad(ref_loss, has_aux=True))


def program_reading(cfg, mesh, specs, leaves):
    """Jitted ``(params, tokens, targets) -> ((loss, aux), gradients of
    the leaves at ``leaves``)`` of the function the timed step jits."""
    import jax
    from paddlebox_tpu.models.looped import looped_loss_fn
    vg = jax.value_and_grad(looped_loss_fn(cfg, mesh, specs), has_aux=True)

    def read(params, tokens, targets):
        out, grads = vg(params, tokens, targets)
        return out, [leaf_at(grads, path) for path in leaves]
    return jax.jit(read)


def host_aux(aux) -> Dict:
    return {k: np.asarray(v).tolist() for k, v in aux.items()}


def run(job) -> Dict:
    import jax
    import optax

    from paddlebox_tpu.core import flags, trace
    from paddlebox_tpu.models.looped import make_looped_train_step
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
    expected = cfg.total_ut_steps * cfg.num_hidden_layers
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
    leaves = checked_leaves(cfg.pieces)
    tokens0, targets0 = draw(0)
    with job.span("setup/program_grads"):
        # the function the timed step jits, at the reference's matmul
        # precision: see GRAD_RTOL
        with jax.default_matmul_precision("highest"):
            (_, aux0), grads = program_reading(cfg, mesh, specs, leaves)(
                params, tokens0, targets0)
        got_grads, aux0 = jax.device_get(grads), host_aux(aux0)
        del grads

    with job.span("setup/reference"):
        read = reference_reading(reference, config, leaves)
        picked = [leaf_at(params, path) for path in leaves]
        (want, want_aux), want_grads = read(
            picked, params, tokens0, targets0, reference.STATED)
        want, want_aux = float(want), host_aux(want_aux)
        grad_err = grad_errors(leaves, got_grads, jax.device_get(want_grads))
        del got_grads, want_grads
        # the same, its products' operands rounded as the timed program's
        # (the CPU's default product, in a rehearsal, rounds nothing)
        _, rounded_grads = read(
            picked, params, tokens0, targets0,
            dict(reference.STATED, operands=not job.rehearse))
        want_update = jax.device_get(first_updates(
            reference, config["learning_rate"])(rounded_grads, picked))
        del rounded_grads
        # the step donates its parameters: what it changes is read
        # against the host's copy
        old = jax.device_get(picked)
        del picked

    with job.span("setup/compile"):
        step = make_looped_train_step(cfg, mesh, specs, opt).lower(
            params, opt_state, tokens0, targets0).compile()
        analysis = step.memory_analysis()
        temp_bytes = getattr(analysis, "temp_size_in_bytes", None)
    with job.span("setup/warmup"):
        params, opt_state, loss0, aux = step(params, opt_state, tokens0,
                                             targets0)
        got, step_aux = float(loss0), host_aux(aux)
        new = jax.device_get([leaf_at(params, path) for path in leaves])
        update_err = grad_errors(
            leaves, [n - o for n, o in zip(new, old)], want_update)
        del old, new, want_update
        params, opt_state, loss1, aux = step(params, opt_state, *draw(1))
        float(loss1)
        jax.block_until_ready(draw(2))
    bad = (outside(got, want, step_aux, want_aux, grad_err)
           + outside_timed(update_err))

    compiles_at_open = job.compiles()
    if job.trace:
        job.start_device_trace()
    t_open = time.perf_counter()
    done_at, losses, seen = [], [], []
    trace_steps = int(traffic["traced_steps"])
    i, pending = 2, None
    while True:
        tok, tgt = draw(i)
        params, opt_state, loss, aux = step(params, opt_state, tok, tgt)
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
    rate = batch * seq / step_s / chips
    applications = [a["applications"] for a in seen]
    ran_all = all(n == expected for n in applications
                  + [aux0["applications"], step_aux["applications"]])
    exit_p = np.mean([a["exit_p"] for a in seen], axis=0)
    resolved = flags.resolved_kernels()
    on_kernels = "pallas" if not job.rehearse else "xla"
    fallback = int(resolved.get("looped_attention") != [on_kernels])
    return {
        "attempted": steps, "failed": steps - finite,
        "correct": bool(not bad and ran_all and finite == steps),
        "window_open": t_open,
        "program_temp_bytes": temp_bytes,
        "end_to_end": {"dense_tokens_per_s_per_chip": rate},
        "detail": {
            "first_step_loss": got, "reference_loss": want,
            "loss_tol": LOSS_RTOL * abs(want), "outside_limits": bad,
            "first_step_aux": step_aux, "reference_aux": want_aux,
            "exit_tol": EXIT_ATOL,
            "grad_rel_err": grad_err, "grad_tol": GRAD_RTOL,
            "step_update_rel_err": update_err, "update_tol": UPDATE_RTOL,
            "applications_expected": expected,
            "applications_seen": sorted(set(applications)),
            "wall_s": wall, "step_ms_median": step_s * 1e3,
            "tokens_per_s_per_chip_over_wall":
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
                "looped_applications": int(np.sum(applications)),
                "looped_exit_expected_pass": float(np.sum(
                    np.arange(1, len(exit_p) + 1) * exit_p)),
                "resolved_kernels": resolved},
            "shapes": {
                "batch_per_chip": config["sequences_per_chip"], "seq": seq,
                "hidden_size": config["hidden_size"],
                "intermediate_size": config["intermediate_size"],
                "layers": config["num_hidden_layers"],
                "passes": config["total_ut_steps"],
                "vocab_size": vocab, "dtype_bytes": 4,
                "n_head": config["num_attention_heads"],
                "n_kv_head": config["num_key_value_heads"],
                "head_dim": config["head_dim"],
            },
        },
    }
