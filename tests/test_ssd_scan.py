"""The Mamba-2 scan kernels (Pallas interpreter, tiny shapes) against the
sequential recurrence, forward and backward, and their names in the
compiled program."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ss = importlib.import_module("paddlebox_tpu.ops.pallas_kernels.ssd_scan")


def _case(seed, bt=2, s=40, h=4, p=64, g=2, n=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (bt, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (bt, s, h)) - 2.0)
    a = -jnp.exp(jax.random.uniform(k[2], (h,), minval=0.0, maxval=2.7))
    b = jax.random.normal(k[3], (bt, s, g, n))
    c = jax.random.normal(k[4], (bt, s, g, n))
    d = jax.random.normal(k[5], (h,))
    return x, dt, a, b, c, d


def _rel(got, want):
    return float(jnp.linalg.norm((got - want).ravel())
                 / jnp.linalg.norm(want.ravel()))


# (sequence, chunk, heads, head dim, groups): a sequence that is not a
# multiple of the chunk; two heads to a lane tile (P = 64) and one
# (P = 128); one chunk only
SHAPES = [(40, 16, 4, 64, 2), (33, 16, 2, 128, 1), (16, 16, 4, 32, 1),
          (70, 32, 8, 64, 2)]


@pytest.mark.parametrize("s,chunk,h,p,g", SHAPES)
def test_forward_is_the_recurrence(s, chunk, h, p, g):
    args = _case(1, s=s, h=h, p=p, g=g)
    with jax.default_matmul_precision("highest"):
        want = ss.ssd_scan_reference(*args)
        got = ss.ssd_scan(*args, chunk=chunk, interpret=True,
                          mxu_dtype=jnp.float32)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert _rel(got, want) < 1e-5


@pytest.mark.parametrize("s,chunk,h,p,g", SHAPES[:2])
def test_backward_is_the_recurrences_gradient(s, chunk, h, p, g):
    args = _case(2, s=s, h=h, p=p, g=g)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda *a: jnp.sum(ss.ssd_scan_reference(*a)
                                           * weight),
                        argnums=tuple(range(6)))(*args)
        got = jax.grad(lambda *a: jnp.sum(ss.ssd_scan(
            *a, chunk=chunk, interpret=True, mxu_dtype=jnp.float32)
            * weight), argnums=tuple(range(6)))(*args)
    for name, gg, ww in zip("x dt a b c d".split(), got, want):
        assert gg.shape == ww.shape, name
        assert _rel(gg, ww) < 2e-5, name


def test_bfloat16_operands_keep_state_and_decay_in_float32():
    """The production setting: operands rounded to 8 bits of mantissa for
    the MXU, the carried state not. A long, slowly decaying sequence is
    where a bfloat16 state would show: 2^-9 a chunk, never forgotten."""
    x, dt, a, b, c, d = _case(3, bt=1, s=256, h=2, p=64, g=1, n=16)
    dt, a = dt * 0.02, a * 0.1          # decay of ~0.998 a position
    want = ss.ssd_scan_reference(x, dt, a, b, c, d)
    got = ss.ssd_scan(x, dt, a, b, c, d, chunk=16, interpret=True)
    per_chunk = [_rel(got[:, i:i + 16], want[:, i:i + 16])
                 for i in range(0, 256, 16)]
    assert max(per_chunk) < 6e-3            # operand rounding, each chunk
    assert per_chunk[-1] < 2 * np.median(per_chunk)     # and no drift


def test_operand_dtype_follows_the_ambient_matmul_precision():
    assert ss.ambient_mxu_dtype() == jnp.bfloat16
    with jax.default_matmul_precision("highest"):
        assert ss.ambient_mxu_dtype() == jnp.float32
        args = _case(7, s=32, h=2, p=64, g=1)
        got = ss.ssd_scan(*args, chunk=16, interpret=True)
        assert _rel(got, ss.ssd_scan_reference(*args)) < 1e-5
    with jax.default_matmul_precision("bfloat16"):
        assert ss.ambient_mxu_dtype() == jnp.bfloat16


def test_padding_positions_leave_the_state_alone():
    """33 positions in chunks of 16: the 15 padded ones have dt = 0."""
    args = _case(4, s=33, h=2, p=64, g=1)
    long = ss.ssd_scan(*args, chunk=16, interpret=True,
                       mxu_dtype=jnp.float32)
    short = ss.ssd_scan(*(v[:, :32] if v.ndim > 1 else v for v in args),
                        chunk=16, interpret=True, mxu_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(long[:, :32]), np.asarray(short),
                               rtol=1e-5, atol=1e-5)


def test_xla_path_is_chosen_off_the_chip_and_noted():
    from paddlebox_tpu.core import flags
    flags.resolved_kernels(reset=True)
    args = _case(5, s=8, h=2, p=64, g=1)
    got = ss.ssd_scan(*args)
    want = ss.ssd_scan_reference(*args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert flags.resolved_kernels()["ssd_scan"] == ["xla"]


@pytest.mark.parametrize("helper", ["_ssd_fwd_call", "_ssd_bwd_call"])
def test_kernels_are_named_in_the_compiled_program(helper):
    """Each pallas_call is the whole result of a jitted helper, so the
    lowered module carries the helper's name where a device trace looks
    for the kernel (benchmarks/metrics/ssd_scan_roofline.json)."""
    args = _case(6, s=32, h=2, p=64, g=1)

    def loss(*a):
        return jnp.sum(ss.ssd_scan(*a, chunk=16, interpret=True))
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*args).as_text()
    assert helper in text
