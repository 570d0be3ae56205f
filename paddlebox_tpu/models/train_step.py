"""The optimizer / donation / jit wrapper every dense model's train step
goes through."""

from __future__ import annotations

import jax

from paddlebox_tpu.core import trace


class _Lowered:
    """A lowered step whose ``compile()`` also records the program
    (``trace.record_program``); everything else is the ``jax.stages.Lowered``
    it holds."""

    def __init__(self, lowered):
        self._lowered = lowered

    def compile(self, *args, **kwargs):
        compiled = self._lowered.compile(*args, **kwargs)
        trace.record_program(compiled)
        return compiled

    def __getattr__(self, name):
        return getattr(self._lowered, name)


class _Step:
    """The jitted step, called as it is; what ``lower(...).compile()``
    makes of it is recorded for ``trace.device_scope_table``."""

    def __init__(self, jitted):
        self._jitted = jitted

    def __call__(self, *args, **kwargs):
        return self._jitted(*args, **kwargs)

    def lower(self, *args, **kwargs) -> _Lowered:
        return _Lowered(self._jitted.lower(*args, **kwargs))

    def __getattr__(self, name):
        return getattr(self._jitted, name)


def make_train_step(value_and_grad, optimizer, *, has_aux: bool = False,
                    out_shardings=None):
    """Jitted ``(params, opt_state, *batch) -> (params, opt_state, loss)``
    with ``params`` and ``opt_state`` donated; ``(..., loss, aux)`` where
    ``value_and_grad`` returns ``((loss, aux), grads)``. The update and its
    apply run under the named scope ``optimizer`` (the loss functions open
    ``embed``, ``stack`` and ``head``: ``core/trace.py``), and a program
    compiled through ``lower(...).compile()`` is recorded.

    ``out_shardings`` (a pytree matching the outputs) lets a caller pin
    them: the ZeRO bench path shards opt_state over dp and must pin params
    replicated, or the sharded state inputs would leak their sharding
    into p+u (accidental ZeRO-3).
    """
    def step(params, opt_state, *batch):
        out, grads = value_and_grad(params, *batch)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = jax.tree.map(lambda p, u: p + u, params, updates)
        if has_aux:
            return (params, opt_state) + tuple(out)
        return params, opt_state, out

    jit_kw = {} if out_shardings is None else {
        "out_shardings": out_shardings}
    return _Step(jax.jit(step, donate_argnums=(0, 1), **jit_kw))
