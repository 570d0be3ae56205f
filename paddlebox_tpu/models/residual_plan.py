"""What a rematerialised stack keeps for its backward pass, planned from
the device's memory: one rule for every stack, with parameters per block
kind.

A stack whose layers sit in ``jax.checkpoint`` keeps each layer
application's input and, of what the application's forward computes, the
``checkpoint_name``d values this plan finds room for. The stack that asks
says what its applications are (one kind letter each, in forward order: a
layer run ``T`` times counts ``T`` times), which named values a kind can
keep (``Keepable``) and what it holds besides; the rule is the same:
parameters, their gradients, every application's input and the stack's
own reserve are planned first, and the candidates then take what is left
of ``PLANNED_MEMORY_SHARE`` of the device, dearest to recompute per byte
first and, within one, last application first (its backward pass comes
first, so it holds what it keeps the shortest).
"""

from __future__ import annotations

import math
from typing import Dict, Iterable, NamedTuple, Sequence, Tuple

import jax
from jax.sharding import Mesh

from paddlebox_tpu.models.gpt import _data_axes

# The share of the device's memory that parameters, their gradients, the
# applications' inputs and the kept values may fill together; the rest is
# room for the layer being differentiated, the head and the compiler's own
# temporaries (a kept value costs the compiled programs up to twice its
# size: XLA's schedule, read with tools/aot_check_dense.py, which holds
# the programs a benchmark cell builds from a plan under the device's
# memory).
PLANNED_MEMORY_SHARE = 0.83
# Where the backend reports no memory (the CPU; a device that is described
# and not attached): the smallest HBM of a TPU these stacks are run on.
DEFAULT_DEVICE_BYTES = int(15.75 * 2 ** 30)


class Keepable(NamedTuple):
    """Named values of one layer kind, a token of the layer's input."""
    kind: str
    names: Tuple[str, ...]
    bytes: int          # to hold them, float32
    ops: float          # matmul operations the second forward spends on them


def product(kind: str, names: Tuple[str, ...], hidden: int,
            width: int) -> Keepable:
    """A product of the layer's ``hidden``-wide input with a ``[hidden,
    width]`` matrix: hidden / 2 operations a byte whatever the width."""
    return Keepable(kind, names, 4 * width, 2.0 * hidden * width)


def ranked(found: Iterable[Keepable]):
    """Dearest to recompute per byte first; ties stay as written."""
    return sorted(found, key=lambda c: -c.ops / c.bytes)


class ResidualPlan(NamedTuple):
    """What each layer application keeps beside its input: one tuple of
    names an application, in forward order (empty: the application is
    rematerialised whole)."""
    names: Tuple[Tuple[str, ...], ...]
    bytes: int

    def attributes(self, kinds: Sequence[str]) -> Dict:
        """The plan as a stack's ``build_step`` span reports it."""
        kept = {kind: sum(bool(n) for n, letter in zip(self.names, kinds)
                          if letter == kind)
                for kind in dict.fromkeys(kinds)}
        return {
            "layers_kept": ",".join(f"{k}:{n}" for k, n in kept.items()),
            "names_kept": ",".join(sorted({n for names in self.names
                                           for n in names})),
            "planned_residual_bytes": self.bytes,
        }


def plan_residuals(kinds: Sequence[str], candidates: Sequence[Keepable],
                   tokens: int, hidden_size: int, param_bytes: int,
                   device_bytes: int, reserved_bytes: int = 0,
                   kept_cost: float = 1.0) -> ResidualPlan:
    """Chooses what each of ``len(kinds)`` layer applications keeps, for
    ``tokens`` tokens a device: ``candidates`` in the order they are tried
    (``ranked``), ``reserved_bytes`` what the stack holds beside
    parameters, gradients and inputs, ``kept_cost`` the bytes of room a
    kept byte is charged (1: the unplanned share of the device covers what
    the compiled program spends on top)."""
    room = (int(PLANNED_MEMORY_SHARE * device_bytes) - 2 * param_bytes
            - len(kinds) * tokens * hidden_size * 4 - reserved_bytes)
    names = [()] * len(kinds)
    planned = 0
    for cand in candidates:
        for i in reversed(range(len(kinds))):
            if (kinds[i] == cand.kind and kept_cost
                    * (planned + tokens * cand.bytes) <= room):
                names[i] += cand.names
                planned += tokens * cand.bytes
    return ResidualPlan(tuple(names), planned)


def _device_bytes(mesh: Mesh) -> int:
    try:
        stats = mesh.devices.flat[0].memory_stats()
    except jax.errors.JaxRuntimeError:      # described, not attached
        stats = None
    return int((stats or {}).get("bytes_limit", DEFAULT_DEVICE_BYTES))


def _plan_for(mesh: Mesh, params, tokens, kinds: Sequence[str],
              candidates: Sequence[Keepable], hidden_size: int,
              reserved_bytes: int = 0, kept_cost: float = 1.0
              ) -> ResidualPlan:
    """The plan for one call's shapes: ``tokens`` ``[B, S]`` over the data
    axes, ``params`` whole on every device but for the vocabulary's
    split (``embed``, ``head``) over ``mp``."""
    shards = math.prod(int(mesh.shape[a]) for a in _data_axes(mesh))
    whole = sum(leaf.size * leaf.dtype.itemsize
                for leaf in jax.tree.leaves(params))
    split = sum(params[n].size * params[n].dtype.itemsize
                for n in ("embed", "head"))
    return plan_residuals(
        kinds, candidates, tokens.size // shards, hidden_size,
        whole - split + split // int(mesh.shape["mp"]),
        _device_bytes(mesh), reserved_bytes, kept_cost)
