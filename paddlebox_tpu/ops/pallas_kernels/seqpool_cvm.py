"""Fused sequence-pool + CVM as a Pallas TPU kernel.

Role of the reference's hand-written CUDA kernel
``operators/fused/fused_seqpool_cvm_op.cu`` (SURVEY.md §2.2): pool each
instance's variable-length slot embeddings and apply the CVM counter
transform in one pass over the data.

TPU-first design: scatter-free pooling as an MXU matmul — the CSR
segment-id vector becomes a one-hot block ``onehot[n, b] = (seg[n] == b)``
and ``pooled = onehot^T @ x`` rides the systolic array, blocked over
(batch rows, input rows) with the input-row axis innermost so the VMEM
accumulator persists across grid steps. The CVM log-transform happens in
VMEM right before the single output write — the same fusion the CUDA
kernel does by hand. Padding rows carry segment id >= num_rows and fall
out of the one-hot automatically (the reference's "discard row").

The XLA reference path (``ops/seqpool.py``, segment_sum-based) is the
correctness oracle and the non-TPU fallback.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pool_kernel(seg_ref, x_ref, out_ref, acc, *, block_b: int,
                 block_n: int, use_cvm: bool):
    bi = pl.program_id(0)
    ni = pl.program_id(1)
    nn = pl.num_programs(1)

    @pl.when(ni == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)

    seg = seg_ref[0]                      # [block_n]
    rows = (bi * block_b
            + lax.broadcasted_iota(jnp.int32, (block_n, block_b), 1))
    onehot = (seg[:, None] == rows).astype(jnp.float32)
    x = x_ref[:].astype(jnp.float32)      # [block_n, F]
    # HIGHEST: the MXU's default single bf16 pass would round every
    # pooled value to 8 mantissa bits (2e-3 relative, measured on a v5e
    # against ops/seqpool.py); the one-hot operand is exact either way.
    acc[:] += jnp.dot(onehot.T, x, preferred_element_type=jnp.float32,
                      precision=lax.Precision.HIGHEST)

    @pl.when(ni == nn - 1)
    def _():
        pooled = acc[:]
        if use_cvm:
            show = pooled[:, :1]
            click = pooled[:, 1:2]
            log_show = jnp.log(show + 1.0)
            ctr = jnp.log(click + 1.0) - log_show
            pooled = jnp.concatenate([log_show, ctr, pooled[:, 2:]],
                                     axis=1)
        out_ref[:] = pooled.astype(out_ref.dtype)


def _pool_pallas(x, segments, num_rows, *, use_cvm, block_b, block_n,
                 interpret):
    n, f = x.shape
    n_pad = _round_up(max(n, 1), block_n)
    b_pad = _round_up(max(num_rows, 1), block_b)
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
        segments = jnp.pad(segments, (0, n_pad - n),
                           constant_values=num_rows)
    seg2 = segments.astype(jnp.int32).reshape(1, n_pad)
    out = pl.pallas_call(
        functools.partial(_pool_kernel, block_b=block_b, block_n=block_n,
                          use_cvm=use_cvm),
        grid=(b_pad // block_b, n_pad // block_n),
        in_specs=[
            pl.BlockSpec((1, block_n), lambda b, i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_n, f), lambda b, i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_b, f), lambda b, i: (b, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b_pad, f), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_b, f), jnp.float32)],
        interpret=interpret,
    )(seg2, x)
    return out[:num_rows]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _seqpool_cvm(x, segments, num_rows, use_cvm, block_b, block_n,
                 interpret):
    out, _ = _seqpool_cvm_fwd(x, segments, num_rows, use_cvm, block_b,
                              block_n, interpret)
    return out


def _seqpool_cvm_fwd(x, segments, num_rows, use_cvm, block_b, block_n,
                     interpret):
    out = _pool_pallas(x, segments, num_rows, use_cvm=use_cvm,
                       block_b=block_b, block_n=block_n,
                       interpret=interpret)
    pooled_counters = None
    if use_cvm:
        # Raw pooled (show, click) recovered from the outputs: the CVM
        # transform is invertible — show = exp(out0)-1, click = exp(ctr
        # + log_show)-1 — so no extra residual pass is needed.
        pooled_counters = (jnp.exp(out[:, 0]) - 1.0,
                           jnp.exp(out[:, 1] + out[:, 0]) - 1.0)
    return out, (segments, pooled_counters)


def _seqpool_cvm_bwd(num_rows, use_cvm, block_b, block_n, interpret,
                     res, g):
    segments, pooled_counters = res
    g = g.astype(jnp.float32)
    if use_cvm:
        show, click = pooled_counters
        d_show = g[:, 0] / (show + 1.0) - g[:, 1] / (show + 1.0)
        d_click = g[:, 1] / (click + 1.0)
        g = jnp.concatenate([d_show[:, None], d_click[:, None], g[:, 2:]],
                            axis=1)
    # dx[i] = dpooled[seg[i]]; discard rows (seg >= num_rows) get zero.
    gpad = jnp.concatenate([g, jnp.zeros((1, g.shape[1]), g.dtype)], 0)
    seg = jnp.minimum(segments.astype(jnp.int32), num_rows)
    return gpad[seg], None


_seqpool_cvm.defvjp(_seqpool_cvm_fwd, _seqpool_cvm_bwd)


def seqpool_cvm_pallas(emb: jax.Array, show: jax.Array, click: jax.Array,
                       segments: jax.Array, num_rows: int, *,
                       use_cvm: bool = True,
                       clip_value: Optional[float] = None,
                       block_b: int = 256, block_n: int = 256,
                       use_pallas: Optional[bool] = None,
                       interpret: bool = False) -> jax.Array:
    """Drop-in Pallas twin of ``ops.fused_seqpool_cvm`` (sum mode).

    emb [n, D], show/click [n], segments [n] sorted CSR row ids with
    ``num_rows`` marking padding. Returns [num_rows, 2+D] (use_cvm) or
    [num_rows, D].
    """
    from paddlebox_tpu.core import flags as _flags
    if use_pallas is None:
        use_pallas = interpret or _flags.pallas_kernels_enabled()
    _flags.note_kernel("seqpool_cvm", "interpret" if interpret
                       else "pallas" if use_pallas else "xla")
    if not use_pallas:
        from paddlebox_tpu.ops.seqpool import fused_seqpool_cvm
        return fused_seqpool_cvm(emb, show, click, segments, num_rows,
                                 use_cvm=use_cvm, clip_value=clip_value)
    if clip_value is not None:
        emb = jnp.clip(emb, -clip_value, clip_value)
    x = jnp.concatenate([show[:, None], click[:, None], emb], axis=-1)
    out = _seqpool_cvm(x, segments, num_rows, use_cvm, block_b, block_n,
                       interpret)
    if not use_cvm:
        out = out[:, 2:]
    return out
