"""The optimizer / donation / jit wrapper every dense model's train step
goes through."""

from __future__ import annotations

import jax


def make_train_step(value_and_grad, optimizer, *, has_aux: bool = False,
                    out_shardings=None):
    """Jitted ``(params, opt_state, *batch) -> (params, opt_state, loss)``
    with ``params`` and ``opt_state`` donated; ``(..., loss, aux)`` where
    ``value_and_grad`` returns ``((loss, aux), grads)``.

    ``out_shardings`` (a pytree matching the outputs) lets a caller pin
    them: the ZeRO bench path shards opt_state over dp and must pin params
    replicated, or the sharded state inputs would leak their sharding
    into p+u (accidental ZeRO-3).
    """
    def step(params, opt_state, *batch):
        out, grads = value_and_grad(params, *batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
        if has_aux:
            return (params, opt_state) + tuple(out)
        return params, opt_state, out

    jit_kw = {} if out_shardings is None else {
        "out_shardings": out_shardings}
    return jax.jit(step, donate_argnums=(0, 1), **jit_kw)
