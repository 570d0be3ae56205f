"""Mamba-2 selective state-space scan as Pallas TPU kernels (fwd + bwd).

The recurrence, per head with state ``h`` ``[N, P]`` (Dao & Gu 2024,
"Transformers are SSMs", the state-space dual form):

    h_t = exp(dt_t * A) h_{t-1} + dt_t * B_t (x) x_t
    y_t = C_t . h_t + D x_t

computed in chunks of ``chunk`` positions. With ``cum_t`` the running sum
of ``dt * A`` inside a chunk, a chunk's output is a masked quadratic form
plus what the state carried into the chunk adds,

    y_t = sum_{s<=t} (C_t . B_s) exp(cum_t - cum_s) dt_s x_s
          + exp(cum_t) C_t . h_in + D x_t
    h_out = exp(cum_L) h_in + sum_s exp(cum_L - cum_s) dt_s B_s (x) x_s

so a sequence of S positions is S / chunk sequential steps of MXU work
instead of S steps of vector work.

TPU mapping: grid (batch, B/C group, chunk) with the chunk axis innermost,
so the group's state ``[N, heads_per_group * P]`` lives in VMEM scratch
across the sequential grid steps. ``C B^T`` is computed once per group and
chunk and shared by the group's heads; the carried-state products run over
the whole group in one matmul each; only the decay-masked ``[L, L]`` form
is per head. Heads narrower than a lane tile (P = 64) are handled two to a
128-lane tile with a lane select, never with an unaligned slice. Decay and
state are float32 throughout; matmul operands are cast to ``mxu_dtype``:
bfloat16 (one MXU pass, float32 accumulation) unless the ambient matmul
precision is "highest", as XLA treats float32 operands.

The backward pass is one more kernel that walks the chunks in reverse with
the state's cotangent in scratch, reading the chunk-entry states the
forward saved. ``cumsum`` and its transpose stay in XLA (exact float32, a
few MB).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM_LIMIT = 64 * 1024 * 1024


def _tile_heads(p: int, heads_per_group: int) -> int:
    """Heads of one group that share a 128-lane tile."""
    return math.gcd(max(1, 128 // p), heads_per_group)


def _dot(a, b, mxu):
    """MXU product with float32 accumulation: operands rounded to
    bfloat16 (one pass), or float32 operands at full precision."""
    precision = lax.Precision.HIGHEST if mxu == jnp.float32 else None
    return jnp.dot(a.astype(mxu), b.astype(mxu), precision=precision,
                   preferred_element_type=jnp.float32)


def ambient_mxu_dtype():
    """What XLA does to a float32 matmul under the ambient
    ``jax.default_matmul_precision``: bfloat16 operands by default,
    float32 under "highest" / "float32". The kernels follow it, so a
    caller that asks for full precision gets it from them as well."""
    ambient = jax.config.jax_default_matmul_precision
    return jnp.float32 if ambient in ("highest", "float32") else jnp.bfloat16


def _sum_all(v):
    return jnp.sum(jnp.sum(v, axis=1, keepdims=True), axis=0, keepdims=True)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, b_ref, c_ref, cumc_ref, cumr_ref, dtc_ref, dtr_ref,
                d_ref, y_ref, st_ref, state, wx_scr, dec_scr, *, chunk, p,
                hpg, hpt, mxu):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[:] = jnp.zeros_like(state)

    ln, tw = chunk, hpt * p
    bm, cm = b_ref[0], c_ref[0]
    h = state[:]
    st_ref[0, 0, 0] = h
    g = _dot(cm, bm.T, mxu)                                   # C B^T [L, L]
    inter = _dot(cm, h, mxu)                                  # C h_in [L, GW]
    tril = (lax.broadcasted_iota(jnp.int32, (ln, ln), 1)
            <= lax.broadcasted_iota(jnp.int32, (ln, ln), 0))
    lane_head = lax.broadcasted_iota(jnp.int32, (ln, tw), 1) // p
    cumc, cumr = cumc_ref[0, 0], cumr_ref[0, 0]
    dtc, dtr = dtc_ref[0, 0], dtr_ref[0, 0]
    for tile in range(hpg // hpt):
        sl = slice(tile * tw, (tile + 1) * tw)
        xt = x_ref[0, :, sl]
        y_t = jnp.zeros((ln, tw), jnp.float32)
        e_t, w_t = y_t, y_t
        for i in range(hpt):
            j = tile * hpt + i
            col, row = cumc[:, j:j + 1], cumr[j:j + 1, :]
            lam = jnp.exp(jnp.where(tril, col - row, -jnp.inf))
            m = g * lam * dtr[j:j + 1, :]
            pick = lane_head == i
            y_t = jnp.where(pick, _dot(m, xt, mxu), y_t)
            e_t = jnp.where(pick, jnp.exp(col), e_t)
            w_t = jnp.where(
                pick, jnp.exp(cumr[j:j + 1, ln - 1:ln] - col)
                * dtc[:, j:j + 1], w_t)
        y_ref[0, :, sl] = y_t + e_t * inter[:, sl] + d_ref[:, sl] * xt
        wx_scr[:, sl] = w_t * xt
        # exp(cum) at the chunk's last position: the decay over the chunk
        dec_scr[:, sl] = e_t[ln - 1:ln, :]
    state[:] = dec_scr[:] * h + _dot(bm.T, wx_scr[:], mxu)


def _specs(chunk, n, gw, hpg, order):
    """Block specs shared by both kernels; ``order(c)`` maps the grid's
    chunk index to the chunk it works on."""
    vm = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    wide = vm((1, chunk, gw), lambda b, g, c: (b, order(c), g))
    bc = vm((1, chunk, n), lambda b, g, c: (b, order(c), g))
    col = vm((1, 1, chunk, hpg), lambda b, g, c: (b, g, order(c), 0))
    row = vm((1, 1, hpg, chunk), lambda b, g, c: (b, g, 0, order(c)))
    dvec = vm((1, gw), lambda b, g, c: (0, g))
    st = vm((1, 1, 1, n, gw), lambda b, g, c: (b, g, order(c), 0, 0))
    return wide, bc, col, row, dvec, st


@functools.partial(jax.jit, static_argnames=(
    "chunk", "p", "mxu", "interpret"))
def _ssd_fwd_call(x, b, c, cumc, cumr, dtc, dtr, dvec, *, chunk, p, mxu,
                  interpret):
    """The forward kernel's call and nothing else, so that the custom-call
    carries this function's name in the device trace."""
    bt, s, hp = x.shape
    groups, hpg = cumc.shape[1], cumc.shape[3]
    n, gw, nc = b.shape[2] // groups, hpg * p, s // chunk
    wide, bc, col, row, dv, st = _specs(chunk, n, gw, hpg, lambda c: c)
    kernel = functools.partial(_fwd_kernel, chunk=chunk, p=p, hpg=hpg,
                               hpt=_tile_heads(p, hpg), mxu=mxu)
    return pl.pallas_call(
        kernel, grid=(bt, groups, nc),
        in_specs=[wide, bc, bc, col, row, col, row, dv],
        out_specs=[wide, st],
        out_shape=[jax.ShapeDtypeStruct((bt, s, hp), jnp.float32),
                   jax.ShapeDtypeStruct((bt, groups, nc, n, gw),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, gw), jnp.float32),
                        pltpu.VMEM((chunk, gw), jnp.float32),
                        pltpu.VMEM((1, gw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(x, b, c, cumc, cumr, dtc, dtr, dvec)


# ---------------------------------------------------------------------------
# Backward kernel
# ---------------------------------------------------------------------------

def _bwd_kernel(x_ref, dy_ref, b_ref, c_ref, cumc_ref, cumr_ref, dtc_ref,
                dtr_ref, d_ref, st_ref, dx_ref, db_ref, dc_ref, dcumc_ref,
                dcumr_ref, ddtc_ref, ddtr_ref, dd_ref, dh, edy_scr, wx_scr,
                dec_scr, *, chunk, p, hpg, hpt, mxu):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dh[:] = jnp.zeros_like(dh)

    ln, tw = chunk, hpt * p
    n = b_ref.shape[2]
    bm, cm = b_ref[0], c_ref[0]
    g = _dot(cm, bm.T, mxu)
    tril = (lax.broadcasted_iota(jnp.int32, (ln, ln), 1)
            <= lax.broadcasted_iota(jnp.int32, (ln, ln), 0))
    lane_head = lax.broadcasted_iota(jnp.int32, (ln, tw), 1) // p
    lane_head_n = lax.broadcasted_iota(jnp.int32, (n, tw), 1) // p
    head_lane = lax.broadcasted_iota(jnp.int32, (ln, hpg), 1)
    last_row = lax.broadcasted_iota(jnp.int32, (ln, 1), 0) == ln - 1
    cumc, cumr = cumc_ref[0, 0], cumr_ref[0, 0]
    dtc, dtr = dtc_ref[0, 0], dtr_ref[0, 0]
    h0, dhv = st_ref[0, 0, 0], dh[:]
    z = _dot(bm, dhv, mxu)                  # B dh_out            [L, GW]
    inter = _dot(cm, h0, mxu)               # C h_in              [L, GW]
    dg = jnp.zeros((ln, ln), jnp.float32)
    dcum_col = jnp.zeros((ln, hpg), jnp.float32)
    ddt_col = dcum_col
    for tile in range(hpg // hpt):
        sl = slice(tile * tw, (tile + 1) * tw)
        xt, dyt = x_ref[0, :, sl], dy_ref[0, :, sl]
        zt, it = z[:, sl], inter[:, sl]
        hh = dhv[:, sl] * h0[:, sl]
        dx_t = jnp.zeros((ln, tw), jnp.float32)
        e_t, w_t = dx_t, dx_t
        for i in range(hpt):
            j = tile * hpt + i
            col, row = cumc[:, j:j + 1], cumr[j:j + 1, :]
            dt_row, dt_col = dtr[j:j + 1, :], dtc[:, j:j + 1]
            tot = cumr[j:j + 1, ln - 1:ln]
            pick = lane_head == i
            lam = jnp.exp(jnp.where(tril, col - row, -jnp.inf))
            gl = g * lam
            m = gl * dt_row
            dm = _dot(jnp.where(pick, dyt, 0.0), xt.T, mxu)   # dy_j x_j^T
            dx_t = jnp.where(pick, _dot(m.T, dyt, mxu), dx_t)
            q = dm * m
            dg = dg + dm * lam * dt_row
            e_i = jnp.exp(col)
            decay = jnp.exp(tot - col)
            w_i = decay * dt_col
            # cotangent of w_s = exp(cum_L - cum_s) dt_s through
            # h_out += B^T (w x)
            dw = jnp.sum(jnp.where(pick, zt * xt, 0.0), axis=1,
                         keepdims=True)
            dww = dw * w_i
            dtot = (_sum_all(dww) + jnp.exp(tot) * _sum_all(
                jnp.where(lane_head_n == i, hh, 0.0)))
            dcum_j = (jnp.sum(q, axis=1, keepdims=True)
                      + e_i * jnp.sum(jnp.where(pick, dyt * it, 0.0),
                                      axis=1, keepdims=True)
                      - dww + jnp.where(last_row, dtot, 0.0))
            dcum_col = jnp.where(head_lane == j, dcum_j, dcum_col)
            ddt_col = jnp.where(head_lane == j, dw * decay, ddt_col)
            dcumr_ref[0, 0, j:j + 1, :] = -jnp.sum(q, axis=0, keepdims=True)
            ddtr_ref[0, 0, j:j + 1, :] = jnp.sum(dm * gl, axis=0,
                                                 keepdims=True)
            e_t = jnp.where(pick, e_i, e_t)
            w_t = jnp.where(pick, w_i, w_t)
        dx_ref[0, :, sl] = dx_t + d_ref[:, sl] * dyt + w_t * zt
        dd_ref[0, 0, :, sl] = jnp.sum(dyt * xt, axis=0, keepdims=True)
        edy_scr[:, sl] = e_t * dyt
        wx_scr[:, sl] = w_t * xt
        dec_scr[:, sl] = e_t[ln - 1:ln, :]
    dcumc_ref[0, 0] = dcum_col
    ddtc_ref[0, 0] = ddt_col
    edy, wx = edy_scr[:], wx_scr[:]
    dc_ref[0] = _dot(dg, bm, mxu) + _dot(edy, h0.T, mxu)
    db_ref[0] = _dot(dg.T, cm, mxu) + _dot(wx, dhv.T, mxu)
    dh[:] = dec_scr[:] * dhv + _dot(cm.T, edy, mxu)


@functools.partial(jax.jit, static_argnames=(
    "chunk", "p", "mxu", "interpret"))
def _ssd_bwd_call(x, dy, b, c, cumc, cumr, dtc, dtr, dvec, states, *,
                  chunk, p, mxu, interpret):
    """The backward kernel's call and nothing else (see
    ``_ssd_fwd_call``)."""
    bt, s, hp = x.shape
    groups, hpg = cumc.shape[1], cumc.shape[3]
    n, gw, nc = b.shape[2] // groups, hpg * p, s // chunk
    wide, bc, col, row, dv, st = _specs(chunk, n, gw, hpg,
                                        lambda c: nc - 1 - c)
    dd = pl.BlockSpec((1, 1, 1, gw), lambda b, g, c: (b, nc - 1 - c, 0, g),
                      memory_space=pltpu.VMEM)
    kernel = functools.partial(_bwd_kernel, chunk=chunk, p=p, hpg=hpg,
                               hpt=_tile_heads(p, hpg), mxu=mxu)
    f32 = jnp.float32
    return pl.pallas_call(
        kernel, grid=(bt, groups, nc),
        in_specs=[wide, wide, bc, bc, col, row, col, row, dv, st],
        out_specs=[wide, bc, bc, col, row, col, row, dd],
        out_shape=[jax.ShapeDtypeStruct(x.shape, f32),
                   jax.ShapeDtypeStruct(b.shape, f32),
                   jax.ShapeDtypeStruct(c.shape, f32),
                   jax.ShapeDtypeStruct(cumc.shape, f32),
                   jax.ShapeDtypeStruct(cumr.shape, f32),
                   jax.ShapeDtypeStruct(cumc.shape, f32),
                   jax.ShapeDtypeStruct(cumr.shape, f32),
                   jax.ShapeDtypeStruct((bt, nc, 1, hp), f32)],
        scratch_shapes=[pltpu.VMEM((n, gw), f32),
                        pltpu.VMEM((chunk, gw), f32),
                        pltpu.VMEM((chunk, gw), f32),
                        pltpu.VMEM((1, gw), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(x, dy, b, c, cumc, cumr, dtc, dtr, dvec, states)


# ---------------------------------------------------------------------------
# custom-VJP core over chunk-aligned [Bt, S, H * P] tensors
# ---------------------------------------------------------------------------

def _col_row(v, groups):
    """[Bt, S, H] per-head scalars -> time-on-sublanes ``[Bt, G, S, hpg]``
    and time-on-lanes ``[Bt, G, hpg, S]`` views for the kernels."""
    bt, s, h = v.shape
    v = v.reshape(bt, s, groups, h // groups)
    return v.transpose(0, 2, 1, 3), v.transpose(0, 2, 3, 1)


def _chunk_cumsum(a, chunk):
    bt, s, h = a.shape
    return jnp.cumsum(a.reshape(bt, s // chunk, chunk, h),
                      axis=2).reshape(bt, s, h)


def _prepared(dt, a, groups, chunk):
    """The kernels' four per-head inputs: the running sum of ``a`` inside
    each chunk and ``dt``, each in both views."""
    return _col_row(_chunk_cumsum(a, chunk), groups) + _col_row(dt, groups)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _ssd(x, dt, a, b, c, dvec, chunk, p, groups, mxu, interpret):
    return _ssd_fwd(x, dt, a, b, c, dvec, chunk, p, groups, mxu,
                    interpret)[0]


def _ssd_fwd(x, dt, a, b, c, dvec, chunk, p, groups, mxu, interpret):
    y, states = _ssd_fwd_call(x, b, c, *_prepared(dt, a, groups, chunk),
                              dvec, chunk=chunk, p=p, mxu=mxu,
                              interpret=interpret)
    return y, (x, dt, a, b, c, dvec, states)


def _ssd_bwd(chunk, p, groups, mxu, interpret, res, dy):
    x, dt, a, b, c, dvec, states = res
    bt, s, h = dt.shape
    dx, db, dc, dcumc, dcumr, ddtc, ddtr, dd = _ssd_bwd_call(
        x, dy, b, c, *_prepared(dt, a, groups, chunk), dvec, states,
        chunk=chunk, p=p, mxu=mxu, interpret=interpret)

    def merged(col, row):
        return (col.transpose(0, 2, 1, 3)
                + row.transpose(0, 3, 1, 2)).reshape(bt, s, h)
    # a -> cum is a cumsum inside each chunk; its transpose is the
    # reversed cumsum of the cotangent
    dcum = merged(dcumc, dcumr).reshape(bt, s // chunk, chunk, h)
    da = jnp.flip(jnp.cumsum(jnp.flip(dcum, 2), axis=2), 2)
    return (dx, merged(ddtc, ddtr), da.reshape(bt, s, h), db, dc,
            jnp.sum(dd, axis=(0, 1)))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def ssd_scan_reference(x, dt, a_head, b, c, d_head, *,
                       block: int = 128) -> jax.Array:
    """The recurrence itself, one position at a time in float32 with
    ``lax.scan``: oracle and non-TPU path. Shapes as ``ssd_scan``. The
    positions run in rematerialised blocks, so a gradient keeps one state
    per block and not one per position (4 MB each at published widths)."""
    bt, s, h, p = x.shape
    rep = h // b.shape[2]
    f32 = jnp.float32
    pad = (-s) % block

    def step(state, inp):
        xt, dtt, bt_, ct = inp              # [Bt,H,P] [Bt,H] [Bt,G,N] x2
        bh, ch = jnp.repeat(bt_, rep, axis=1), jnp.repeat(ct, rep, axis=1)
        state = (jnp.exp(dtt * a_head)[..., None, None] * state
                 + (dtt[..., None] * bh)[..., :, None] * xt[..., None, :])
        yt = jnp.sum(ch[..., :, None] * state, axis=2)
        return state, yt + d_head[:, None] * xt

    def blocks(v):
        """[Bt, S, ...] -> [S / block, block, Bt, ...]; the padding's
        dt = 0 leaves the state as it is."""
        v = jnp.moveaxis(v.astype(f32), 1, 0)
        v = jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
        return v.reshape((-1, block) + v.shape[1:])

    init = jnp.zeros((bt, h, b.shape[3], p), f32)
    _, y = lax.scan(jax.checkpoint(lambda st, inp: lax.scan(step, st, inp)),
                    init, tuple(blocks(v) for v in (x, dt, b, c)))
    return jnp.moveaxis(y.reshape((-1,) + y.shape[2:])[:s], 0, 1)


def ssd_scan(x, dt, a_head, b, c, d_head, *, chunk: int = 128,
             use_pallas: Optional[bool] = None, interpret: bool = False,
             mxu_dtype=None) -> jax.Array:
    """Mamba-2 scan (differentiable in every array argument).

    x ``[Bt, S, H, P]``; dt ``[Bt, S, H]`` (the step size after its
    softplus); a_head ``[H]`` (negative); b, c ``[Bt, S, G, N]`` with head
    h reading group ``h // (H / G)``; d_head ``[H]``. Returns y
    ``[Bt, S, H, P]`` float32. ``use_pallas=None``: the kernels on a TPU,
    the sequential reference elsewhere; ``interpret=True`` forces the
    kernels through the Pallas interpreter (tests). ``mxu_dtype=None``:
    ``ambient_mxu_dtype()``. A sequence that is not a multiple of ``chunk``
    is padded with ``dt = 0`` positions, which leave the state as it is.
    """
    from paddlebox_tpu.core import flags as _flags
    if use_pallas is None:
        use_pallas = interpret or _flags.pallas_kernels_enabled()
    _flags.note_kernel("ssd_scan", "interpret" if interpret
                       else "pallas" if use_pallas else "xla")
    if not use_pallas:
        return ssd_scan_reference(x, dt, a_head, b, c, d_head)
    bt, s, h, p = x.shape
    groups = b.shape[2]
    f32 = jnp.float32
    pad = (-s) % chunk
    if mxu_dtype is None:
        mxu_dtype = ambient_mxu_dtype()

    def padded(v):
        v = v.astype(f32).reshape(bt, s, -1)
        return jnp.pad(v, ((0, 0), (0, pad), (0, 0))) if pad else v
    dt = padded(dt)
    y = _ssd(padded(x), dt, dt * a_head.astype(f32), padded(b), padded(c),
             jnp.repeat(d_head.astype(f32), p)[None, :], chunk, p, groups,
             mxu_dtype, interpret)
    return y[:, :s].reshape(bt, s, h, p)
