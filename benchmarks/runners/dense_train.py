"""Runner ``dense_train``: ``init_gpt`` + ``make_gpt_train_step`` on
``build_mesh(HybridTopology(dp=chips))``, one sequence batch a step.

- set-up: parameters and optimizer state are made on the device in one
  jitted call each from ``--seed``; the plain reference computes the
  first batch's loss on those parameters; two warm-up steps compile (or
  load) the step and give the program's first-step loss;
- window: steps are dispatched one ahead of the one being waited for, so
  the device is never left without work and never more than one step
  ahead; each step's completion (``float(loss)``) is clocked, until
  ``--seconds`` have passed. The rate is tokens per step over the median
  time between completions: one stalled step among 480 (seen once in seven
  runs, 1.9 s lost, on a machine that shares its host's cores) otherwise
  moves a 40 s mean by 5%, the median by nothing;
- every step draws its own token batch on the device
  (``traffic/<mix>.json``: sequence length, token distribution).
"""

from __future__ import annotations

import importlib
import time
from typing import Dict

import numpy as np

# The program's f32 matmuls run as single bf16 passes on the MXU (XLA's
# default precision); the reference runs them at ``highest``. bf16 keeps 8
# bits of mantissa, and PR 21 measured the same gap between the program's
# two attention paths: 2^-8 of the loss. A step computed in a lower
# precision than that, or a wrong block, lands outside.
LOSS_RTOL = 2.0 ** -8


def run(job) -> Dict:
    import jax
    import jax.numpy as jnp
    import optax

    from paddlebox_tpu.core import flags
    from paddlebox_tpu.models.gpt import (GPTConfig, init_gpt,
                                          make_gpt_train_step)
    from paddlebox_tpu.parallel import HybridTopology, build_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    config, traffic, chips = job.config, job.traffic, job.chips
    flags.set_flags(job.workload.get("flags", {}))
    reference = importlib.import_module(
        f"benchmarks.reference.{job.config_name}")
    seq = int(traffic["sequence_length"])
    if seq > config["n_positions"]:
        raise ValueError("traffic asks for more positions than the "
                         "configuration has")
    batch = int(config["sequences_per_chip"]) * chips
    cfg = GPTConfig(vocab_size=config["vocab_size"],
                    d_model=config["n_embd"], n_heads=config["n_head"],
                    n_layers=config["n_layer"], d_ff=config["n_inner"],
                    max_seq_len=config["n_positions"])
    mesh = build_mesh(HybridTopology(dp=chips), devices=jax.devices()[:chips])
    rep = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P("dp"))
    key = jax.random.PRNGKey(job.seed)

    with job.span("setup/init"):
        specs = {}

        def make(k):
            params, s = init_gpt(k, cfg, pp_stages=1)
            specs.update(s)
            return params
        params = jax.jit(make, out_shardings=rep)(key)
        opt = optax.adafactor(config["learning_rate"])
        opt_state = jax.jit(opt.init, out_shardings=rep)(params)

    @jax.jit
    def draw(step):
        """[batch, seq + 1] tokens, uniform over the vocabulary; the step
        trains position t to predict position t + 1."""
        toks = jax.random.randint(
            jax.random.fold_in(jax.random.fold_in(key, 1), step),
            (batch, seq + 1), 0, config["vocab_size"], jnp.int32)
        return (jax.lax.with_sharding_constraint(toks[:, :-1], data),
                jax.lax.with_sharding_constraint(toks[:, 1:], data))

    with job.span("setup/reference"):
        tokens0, targets0 = draw(0)
        want = float(jax.jit(
            lambda p, t, y: reference.loss(
                p, t, y, n_head=config["n_head"],
                layer_norm_epsilon=config["layer_norm_epsilon"]))(
            params, tokens0, targets0))

    with job.span("setup/compile"):
        step = make_gpt_train_step(
            cfg, mesh, specs, opt, num_microbatches=1).lower(
            params, opt_state, tokens0, targets0).compile()
        analysis = step.memory_analysis()
        temp_bytes = getattr(analysis, "temp_size_in_bytes", None)
    with job.span("setup/warmup"):
        params, opt_state, loss0 = step(params, opt_state, tokens0, targets0)
        got = float(loss0)
        params, opt_state, loss1 = step(params, opt_state, *draw(1))
        float(loss1)
        jax.block_until_ready(draw(2))
    first_ok = abs(got - want) <= LOSS_RTOL * abs(want)

    compiles_at_open = job.compiles()
    if job.trace:
        job.start_device_trace()
    t_open = time.perf_counter()
    done_at = []                 # perf_counter at each completed step
    losses = []
    trace_steps = int(traffic["traced_steps"])
    i, pending = 2, None
    while True:
        tok, tgt = draw(i)
        params, opt_state, loss = step(params, opt_state, tok, tgt)
        i += 1
        if pending is not None:
            losses.append(float(pending))        # waits for that step
            done_at.append(time.perf_counter())
            if job.tracing_now() and len(done_at) >= trace_steps:
                job.stop_device_trace()
            if done_at[-1] - t_open >= job.seconds:
                break
        pending = loss
    float(loss)                                  # drain the step in flight
    compiles_in_window = job.compiles() - compiles_at_open

    steps = len(done_at)
    wall = done_at[-1] - t_open
    finite = int(np.isfinite(losses).sum())
    step_s = float(np.median(np.diff([t_open] + done_at)))
    rate = batch * seq / step_s / chips
    return {
        "attempted": steps, "failed": steps - finite,
        "correct": bool(first_ok and finite == steps),
        "window_open": t_open,
        "program_temp_bytes": temp_bytes,
        "end_to_end": {"dense_tokens_per_s_per_chip": rate},
        "detail": {
            "first_step_loss": got, "reference_loss": want,
            "loss_tol": LOSS_RTOL * abs(want), "wall_s": wall,
            "step_ms_median": step_s * 1e3,
            "tokens_per_s_per_chip_over_wall":
                steps * batch * seq / wall / chips,
            "step_program_temp_bytes": temp_bytes,
            "last_loss": losses[-1],
            "resolved_kernels": flags.resolved_kernels(),
        },
        "observed": {
            "program_spans": [],
            "window_unix_ns": (job.unix_ns(t_open), job.unix_ns(done_at[-1])),
            "steps": steps, "chips": chips,
            "traced_steps": min(trace_steps, steps),
            "tokens_per_s_per_chip": rate,
            "counters": {"compiles_in_window": compiles_in_window,
                         "resolved_kernels": flags.resolved_kernels()},
            "shapes": {"batch_per_chip": config["sequences_per_chip"],
                       "seq": seq, "n_head": config["n_head"],
                       "head_dim": config["n_embd"] // config["n_head"],
                       "n_layer": config["n_layer"],
                       "n_embd": config["n_embd"],
                       "n_inner": config["n_inner"],
                       "vocab_size": config["vocab_size"],
                       "dtype_bytes": 4},
        },
    }
