"""Shared helpers for the aot_check_* tools.

Import AFTER the tool has pinned its platform env (each tool sets
JAX_PLATFORMS/XLA_FLAGS before importing jax; this module only assumes
jax is importable by then).
"""

from __future__ import annotations

import importlib.util

import jax
import jax.numpy as jnp
import numpy as np

# Printed (with a clean exit) when libtpu is not installed: the one case
# tests/test_aot_step.py may skip on.
NO_LIBTPU = "TPU-AOT-NO-LIBTPU"


def tpu_topology(name: str):
    """The compile-only TPU topology ``name`` — or None, after printing
    the NO_LIBTPU sentinel, where libtpu is not installed. Any other
    failure raises: a libtpu that is present but cannot initialize (its
    multi-process lockfile held by another process, a bad topology
    name) is an error to report, not a missing device to skip."""
    if importlib.util.find_spec("libtpu") is None:
        print(f"{NO_LIBTPU}: libtpu is not installed", flush=True)
        return None
    from jax.experimental import topologies
    return topologies.get_topology_desc(name, "tpu")


def sds(tree):
    """Pytree of arrays -> pytree of ShapeDtypeStructs (compile-only
    stand-ins; nothing touches a device)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype),
        tree)
