"""Blocked flash attention as a Pallas TPU kernel (fwd + bwd).

Role of the reference's fused attention CUDA ops
(``operators/fused/fused_attention_op.cu``,
``fused_multi_transformer_op.cu``, ``fused_softmax_mask.cu.h``): one
kernel computes softmax(QK^T)V without materializing the [S, S] score
matrix in HBM.

TPU-first design: the classic flash schedule mapped onto the Pallas grid —
grid (batch*heads, q_blocks, k_blocks) with the k-block axis innermost so
VMEM scratch (acc, running max m, running sum l) persists across the
sequential TPU grid steps; QK^T and PV ride the MXU via ``jnp.dot`` with
``preferred_element_type=float32``; the online-softmax rescale is VPU
work fused in VMEM. The backward pass is two more kernels (dq, and dk/dv)
recomputing P from the saved logsumexp — the standard recompute-not-store
flash backward.

``q_offset``/``k_offset`` shift the *global* positions used for causal
masking, so the same kernel serves ring attention's per-step blocks
(``parallel/sp.py``) where each device holds a rotated K/V shard.

Grouped heads (H query heads over H_kv < H key/value heads, query head h
reading key/value head ``h // (H / H_kv)``): K and V stay ``[B * H_kv, S,
D]`` in HBM and the block index maps send each query head to its group's
block, so nothing is repeated; the dK/dV kernel's inner grid axis runs
over the group's query heads as well as the query blocks and sums them in
its VMEM accumulators.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -1e30


def _pick_block(s: int, preferred: int) -> int:
    """Block size for a sequence of length s: the preferred tile when the
    sequence is at least that long, else s rounded up to a sublane
    multiple (the wrapper pads the sequence to a block multiple)."""
    if s >= preferred:
        return preferred
    return max(8, -(-s // 8) * 8)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _block_live(qoff_ref, koff_ref, kreal_ref, qi, ki, *, causal,
                block_q, block_k):
    """Scalar predicate: does block (qi, ki) contain any unmasked entry?
    False for k-padding blocks and the causal upper triangle — lets every
    kernel skip them (the flash 2x-causal saving)."""
    live = (ki * block_k) < kreal_ref[0, 0]
    if causal:
        first_k = koff_ref[0, 0] + ki * block_k
        last_q = qoff_ref[0, 0] + qi * block_q + (block_q - 1)
        live = jnp.logical_and(live, first_k <= last_q)
    return live


def _fwd_kernel(qoff_ref, koff_ref, kreal_ref, q_ref, k_ref, v_ref,
                out_ref, lse_ref, acc, m_scr, l_scr, *, scale: float,
                causal: bool, block_q: int, block_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[:] = jnp.zeros_like(l_scr)

    q_pos = (qoff_ref[0, 0] + qi * block_q
             + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0))
    k_local = (ki * block_k
               + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1))
    k_pos = koff_ref[0, 0] + k_local
    valid = k_local < kreal_ref[0, 0]
    if causal:
        valid = jnp.logical_and(valid, k_pos <= q_pos)

    # Skip fully-masked k blocks (the causal upper triangle).
    any_valid = _block_live(qoff_ref, koff_ref, kreal_ref, qi, ki,
                            causal=causal, block_q=block_q,
                            block_k=block_k)

    @pl.when(any_valid)
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, _NEG_BIG)

        m_prev = m_scr[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.where(valid, jnp.exp(s - m_cur[:, None]), 0.0)
        w_prev = jnp.exp(m_prev - m_cur)
        l_scr[:, 0] = l_scr[:, 0] * w_prev + jnp.sum(p, axis=1)
        v = v_ref[0].astype(jnp.float32)
        acc[:] = (acc[:] * w_prev[:, None]
                  + jnp.dot(p, v, preferred_element_type=jnp.float32))
        m_scr[:, 0] = m_cur

    @pl.when(ki == nk - 1)
    def _():
        l = l_scr[:, 0]
        m = m_scr[:, 0]
        out_ref[0] = (acc[:] / jnp.maximum(l, 1e-20)[:, None]
                      ).astype(out_ref.dtype)
        # lse block is (1, 1, block_q): TPU tiling requires the block's
        # second-minor dim to divide 8 or equal the array dim, which a
        # (1, block_q) view of [BH, Sq] cannot satisfy — row stats ride
        # as [BH, 1, Sq] instead.
        lse_ref[0, 0] = jnp.where(l > 0.0,
                                  m + jnp.log(jnp.maximum(l, 1e-20)),
                                  _NEG_BIG)


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "block_q", "block_k", "interpret"))
def _flash_fwd_call(qoff, koff, sk_real, q3, k3, v3, *, scale, causal,
                    block_q, block_k, interpret):
    """The forward kernel's call and nothing else: a jitted function whose
    result is the ``pallas_call``'s own gives the custom-call this
    function's name in the compiled program, which is where a device
    trace finds the kernel (the jit itself is inlined)."""
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    nq, nk = sq // block_q, sk // block_k
    grid = (bh, nq, nk)
    group = bh // k3.shape[0]           # query heads per key/value head
    smem = functools.partial(pl.BlockSpec, (1, 1),
                             memory_space=pltpu.SMEM)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            smem(lambda b, i, j: (0, 0)),
            smem(lambda b, i, j: (0, 0)),
            smem(lambda b, i, j: (0, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j: (b // group, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j: (b // group, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
    )(qoff, koff, sk_real, q3, k3, v3)


def _fwd_pallas(q3, k3, v3, qoff, koff, sk_real, *, scale, causal,
                block_q, block_k, interpret):
    """q3 [BH, Sq, D] (padded); returns (out [BH, Sq, D], lse [BH, Sq])."""
    out, lse3 = _flash_fwd_call(qoff, koff, sk_real, q3, k3, v3,
                                scale=scale, causal=causal,
                                block_q=block_q, block_k=block_k,
                                interpret=interpret)
    return out, lse3.reshape(q3.shape[:2])


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _recompute_p(q_ref, k_ref, lse_ref, qoff_ref, koff_ref, kreal_ref,
                 qi, ki, *, scale, causal, block_q, block_k):
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    q_pos = (qoff_ref[0, 0] + qi * block_q
             + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0))
    k_local = (ki * block_k
               + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1))
    k_pos = koff_ref[0, 0] + k_local
    valid = k_local < kreal_ref[0, 0]
    if causal:
        valid = jnp.logical_and(valid, k_pos <= q_pos)
    lse = lse_ref[0, 0]
    p = jnp.where(valid, jnp.exp(s - lse[:, None]), 0.0)
    return p, valid


def _dq_kernel(qoff_ref, koff_ref, kreal_ref, q_ref, k_ref, v_ref,
               do_ref, lse_ref, delta_ref, dq_ref, dq_acc, *, scale,
               causal, block_q, block_k):
    qi, ki, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(_block_live(qoff_ref, koff_ref, kreal_ref, qi, ki,
                         causal=causal, block_q=block_q, block_k=block_k))
    def _():
        p, _ = _recompute_p(q_ref, k_ref, lse_ref, qoff_ref, koff_ref,
                            kreal_ref, qi, ki, scale=scale, causal=causal,
                            block_q=block_q, block_k=block_k)
        do = do_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        k = k_ref[0].astype(jnp.float32)
        dq_acc[:] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(qoff_ref, koff_ref, kreal_ref, q_ref, k_ref, v_ref,
                do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_acc,
                dv_acc, *, scale, causal, block_q, block_k, nq):
    # the inner axis runs over (query head of the group, query block)
    ki, step, steps = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    qi = step % nq

    @pl.when(step == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(_block_live(qoff_ref, koff_ref, kreal_ref, qi, ki,
                         causal=causal, block_q=block_q, block_k=block_k))
    def _():
        p, _ = _recompute_p(q_ref, k_ref, lse_ref, qoff_ref, koff_ref,
                            kreal_ref, qi, ki, scale=scale, causal=causal,
                            block_q=block_q, block_k=block_k)
        do = do_ref[0].astype(jnp.float32)
        dv_acc[:] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        q = q_ref[0].astype(jnp.float32)
        dk_acc[:] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)

    @pl.when(step == steps - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_specs(d):
    smem = functools.partial(pl.BlockSpec, (1, 1),
                             memory_space=pltpu.SMEM)
    qspec = lambda bm, im: pl.BlockSpec((1, bm, d), im,
                                        memory_space=pltpu.VMEM)
    rspec = lambda bm, im: pl.BlockSpec((1, 1, bm), im,
                                        memory_space=pltpu.VMEM)
    return smem, qspec, rspec


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "block_q", "block_k", "interpret"))
def _flash_dq_call(qoff, koff, sk_real, q3, k3, v3, do3, lse3, delta3, *,
                   scale, causal, block_q, block_k, interpret):
    """The dq kernel's call and nothing else (see ``_flash_fwd_call``)."""
    bh, sq, d = q3.shape
    nq, nk = sq // block_q, k3.shape[1] // block_k
    group = bh // k3.shape[0]
    smem, qspec, rspec = _bwd_specs(d)
    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(bh, nq, nk),
        in_specs=[
            smem(lambda b, i, j: (0, 0)), smem(lambda b, i, j: (0, 0)),
            smem(lambda b, i, j: (0, 0)),
            qspec(block_q, lambda b, i, j: (b, i, 0)),
            qspec(block_k, lambda b, i, j: (b // group, j, 0)),
            qspec(block_k, lambda b, i, j: (b // group, j, 0)),
            qspec(block_q, lambda b, i, j: (b, i, 0)),
            rspec(block_q, lambda b, i, j: (b, 0, i)),
            rspec(block_q, lambda b, i, j: (b, 0, i)),
        ],
        out_specs=qspec(block_q, lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qoff, koff, sk_real, q3, k3, v3, do3, lse3, delta3)


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "block_q", "block_k", "interpret"))
def _flash_dkv_call(qoff, koff, sk_real, q3, k3, v3, do3, lse3, delta3, *,
                    scale, causal, block_q, block_k, interpret):
    """The dk/dv kernel's call and nothing else (see ``_flash_fwd_call``)."""
    bh, sq, d = q3.shape
    bkv, sk = k3.shape[:2]
    nq, nk = sq // block_q, sk // block_k
    group = bh // bkv
    smem, qspec, rspec = _bwd_specs(d)
    return pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nq=nq),
        grid=(bkv, nk, group * nq),
        in_specs=[
            smem(lambda b, j, i: (0, 0)), smem(lambda b, j, i: (0, 0)),
            smem(lambda b, j, i: (0, 0)),
            qspec(block_q, lambda b, j, i: (b * group + i // nq, i % nq, 0)),
            qspec(block_k, lambda b, j, i: (b, j, 0)),
            qspec(block_k, lambda b, j, i: (b, j, 0)),
            qspec(block_q, lambda b, j, i: (b * group + i // nq, i % nq, 0)),
            rspec(block_q, lambda b, j, i: (b * group + i // nq, 0, i % nq)),
            rspec(block_q, lambda b, j, i: (b * group + i // nq, 0, i % nq)),
        ],
        out_specs=[qspec(block_k, lambda b, j, i: (b, j, 0)),
                   qspec(block_k, lambda b, j, i: (b, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((bkv, sk, d), k3.dtype),
                   jax.ShapeDtypeStruct((bkv, sk, d), v3.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
    )(qoff, koff, sk_real, q3, k3, v3, do3, lse3, delta3)


def _bwd_pallas(q3, k3, v3, out3, lse, do3, qoff, koff, sk_real, *,
                scale, causal, block_q, block_k, interpret):
    bh, sq, _ = q3.shape
    delta = jnp.sum(do3.astype(jnp.float32) * out3.astype(jnp.float32),
                    axis=-1)
    # Row stats as [BH, 1, Sq] — (1, block) blocks of a 2-D array break
    # the TPU block-tiling rule (see the fwd lse spec).
    lse3 = lse.reshape(bh, 1, sq)
    delta3 = delta.reshape(bh, 1, sq)
    static = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, interpret=interpret)
    dq = _flash_dq_call(qoff, koff, sk_real, q3, k3, v3, do3, lse3,
                        delta3, **static)
    dk, dv = _flash_dkv_call(qoff, koff, sk_real, q3, k3, v3, do3, lse3,
                             delta3, **static)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public API: custom-VJP wrapper over [B, S, H, D] tensors
# ---------------------------------------------------------------------------

def _to3d(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _to4d(x3, b, h):
    bh, s, d = x3.shape
    return x3.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _pad_seq(x3, block):
    s = x3.shape[1]
    pad = (-s) % block
    if pad:
        x3 = jnp.pad(x3, ((0, 0), (0, pad), (0, 0)))
    return x3


# What the backward kernels read of the forward pass, under the names a
# ``jax.checkpoint`` save policy may keep them by (``_flash_fwd``). Without
# such a policy a name is the identity and the program is what it was.
# (Imported here and not at the top: the kernels' serialized bodies carry
# their source lines into the compiled program, so nothing above this line
# moves.)
from jax.ad_checkpoint import checkpoint_name as _named  # noqa: E402

RESIDUAL_NAMES = ("flash_q", "flash_k", "flash_v", "flash_out", "flash_lse")


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q3, k3, v3, qoff, koff, scale, causal, block_q, block_k,
           interpret):
    out, _ = _flash_fwd(q3, k3, v3, qoff, koff, scale, causal, block_q,
                        block_k, interpret)
    return out


def _flash_fwd(q3, k3, v3, qoff, koff, scale, causal, block_q, block_k,
               interpret):
    q3, k3, v3 = map(_named, (q3, k3, v3), RESIDUAL_NAMES[:3])
    sq, sk = q3.shape[1], k3.shape[1]
    sk_real = jnp.full((1, 1), sk, jnp.int32)
    qp = _pad_seq(q3, block_q)
    kp = _pad_seq(k3, block_k)
    vp = _pad_seq(v3, block_k)
    out, lse = _fwd_pallas(qp, kp, vp, qoff, koff, sk_real, scale=scale,
                           causal=causal, block_q=block_q,
                           block_k=block_k, interpret=interpret)
    out, lse = map(_named, (out[:, :sq], lse[:, :sq]), RESIDUAL_NAMES[3:])
    return out, (q3, k3, v3, out, lse, qoff, koff)


def _flash_bwd(scale, causal, block_q, block_k, interpret, res, g):
    q3, k3, v3, out, lse, qoff, koff = res
    sq, sk = q3.shape[1], k3.shape[1]
    sk_real = jnp.full((1, 1), sk, jnp.int32)
    qp, dop = _pad_seq(q3, block_q), _pad_seq(g, block_q)
    outp = _pad_seq(out, block_q)
    # Padded q rows recompute against lse=0 garbage; force them inert.
    lsep = jnp.pad(lse, ((0, 0), (0, qp.shape[1] - sq)),
                   constant_values=jnp.inf)
    kp, vp = _pad_seq(k3, block_k), _pad_seq(v3, block_k)
    dq, dk, dv = _bwd_pallas(qp, kp, vp, outp, lsep, dop, qoff, koff,
                             sk_real, scale=scale, causal=causal,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret)
    return dq[:, :sq], dk[:, :sk], dv[:, :sk], None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_reference(q, k, v, *, causal: bool = False,
                              scale: Optional[float] = None,
                              q_offset=0, k_offset=0) -> jax.Array:
    """XLA reference (materializes scores): oracle + non-TPU fallback."""
    d = q.shape[-1]
    if scale is None:
        scale = float(d) ** -0.5
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bqhk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])
        kpos = k_offset + jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, :, None, :], s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqhk,bkhd->bqhd", p, v.astype(p.dtype),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None, q_offset=0, k_offset=0,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    use_pallas: Optional[bool] = None,
                    interpret: bool = False) -> jax.Array:
    """Flash attention over [B, S, H, D] tensors (differentiable); k and
    v may carry fewer heads ``[B, S, H_kv, D]`` with H a multiple of H_kv
    (grouped-query attention).

    ``use_pallas=None`` auto-selects: the Pallas kernel on TPU backends,
    the XLA reference elsewhere (``interpret=True`` forces the kernel in
    interpreter mode — for tests). ``block_q``/``block_k`` default to
    the ``flash_block_{q,k}`` flags so every call site picks up the same
    tiles without plumbing.
    """
    from paddlebox_tpu.core import flags as _flags
    # Per-parameter None checks: an explicit (invalid) 0 must error in
    # the kernel's own validation, not silently fall back to the flag.
    if block_q is None:
        block_q = int(_flags.flag("flash_block_q"))
    if block_k is None:
        block_k = int(_flags.flag("flash_block_k"))
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    if use_pallas is None:
        use_pallas = interpret or _flags.pallas_kernels_enabled()
    _flags.note_kernel("flash_attention", "interpret" if interpret
                       else "pallas" if use_pallas else "xla")
    if not use_pallas:
        return flash_attention_reference(q, k, v, causal=causal,
                                         scale=scale, q_offset=q_offset,
                                         k_offset=k_offset)
    b, sq, h, d = q.shape
    if h % k.shape[2] or k.shape[2] != v.shape[2]:
        raise ValueError(f"{h} query heads do not group over "
                         f"{k.shape[2]} key / {v.shape[2]} value heads")
    bq = _pick_block(max(sq, 1), block_q)
    bk = _pick_block(max(k.shape[1], 1), block_k)
    qoff = jnp.full((1, 1), q_offset, jnp.int32)
    koff = jnp.full((1, 1), k_offset, jnp.int32)
    out3 = _flash(_to3d(q), _to3d(k), _to3d(v), qoff, koff, scale,
                  causal, bq, bk, interpret)
    return _to4d(out3, b, h).astype(q.dtype)
