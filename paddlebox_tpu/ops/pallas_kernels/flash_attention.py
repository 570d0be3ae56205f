"""Blocked flash attention as a Pallas TPU kernel (fwd + bwd).

Role of the reference's fused attention CUDA ops
(``operators/fused/fused_attention_op.cu``,
``fused_multi_transformer_op.cu``, ``fused_softmax_mask.cu.h``): one
kernel computes softmax(QK^T)V without materializing the [S, S] score
matrix in HBM.

TPU-first design: the classic flash schedule mapped onto the Pallas grid.
The scores of one head are cut into ``block_q`` x ``block_k`` tiles; the
grid is (head, tile) with the tile axis innermost, so VMEM scratch (acc,
running max m, running sum l) persists across the sequential grid steps;
QK^T and PV ride the MXU; the online-softmax rescale is VPU work fused in
VMEM. The backward pass is two more kernels (dq, and dk/dv) recomputing P
from the saved logsumexp — the standard recompute-not-store flash
backward.

**A tile does only the work its position needs.** A tile is *dead* when
every entry is masked (the causal upper triangle, k padding), *interior*
when none is (its last key position <= its first query position, and it
lies wholly under the real key count), an *edge* tile otherwise
(``_tile_kind``). A dead tile computes nothing; an interior tile takes a
body with no iota, compare or select; only an edge tile builds the mask.
The choice is made at run time from the offsets and the key count in
SMEM, so it is exact whatever the offsets are. Which tiles the grid
*visits* is a table (``_schedule``, scalar prefetch): with offsets known
when the program is traced it lists the live tiles only, so the grid has
no dead step (a step costs ~0.35 us and its block fetches whether or not
it computes); with traced offsets it lists them all.

**Layouts.** The forward's running maximum and sum are lane-replicated
``[block_q, 128]`` values, read and written whole: no ``(block_q,)``
vector crosses between lane and sublane layout inside the key loop. The
dk/dv kernel computes its tile *transposed*, keys down the rows
(``sT = K Q^T``, ``pT = exp(sT - lse[None, :])``, ``dV += pT dO``,
``dpT = V dO^T``, ``dK += dsT Q``): both accumulators take the tile as it
is, where the untransposed form needs ``p.T`` and ``ds.T`` (two 512 x 512
transposes a tile), and the row statistics, stored lane-major as
``[BH, 1, Sq]``, are read along the lanes as they lie.

``q_offset``/``k_offset`` shift the *global* positions used for causal
masking, so the same kernel serves ring attention's per-step blocks where
each device holds a rotated K/V shard.

**A mask rule beside causal.** ``flash_attention(mask=...)`` takes one
static rule, ``BlockDiffusionMask(seq, block)``: the scores of ``2 *
seq`` rows, a clean copy of a sequence followed by a noisy copy, where a
clean row reads the clean rows of its own and earlier blocks, a noisy row
the clean rows of strictly earlier blocks and the noisy rows of its own
block, and no clean row reads a noisy one. The rule is resolved when the
program is traced: tiles divide ``seq``, so a tile lies in one of the
four quadrants, ``_tile_kind`` answers live / interior from the block
numbers of the tile's corners, the schedule lists the live tiles (80 of
256 a head at 4,096 x 4, 512 x 512 tiles) and an edge tile builds the
rule from two shifts, a subtraction and two compares. Without a rule the
three kernels are the programs they were.

Grouped heads (H query heads over H_kv < H key/value heads, query head h
reading key/value head ``h // (H / H_kv)``): K and V stay ``[B * H_kv, S,
D]`` in HBM and the block index maps send each query head to its group's
block, so nothing is repeated; the dK/dV kernel's inner grid axis runs
over the group's query heads as well as the query blocks and sums them in
its VMEM accumulators.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name as _named
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -1e30
_LANES = 128


def _pick_block(s: int, preferred: int) -> int:
    """Block size for a sequence of length s: the preferred tile when the
    sequence is at least that long, else s rounded up to a sublane
    multiple (the wrapper pads the sequence to a block multiple)."""
    if s >= preferred:
        return preferred
    return max(8, -(-s // 8) * 8)


@dataclasses.dataclass(frozen=True)
class BlockDiffusionMask:
    """The mask of block-diffusion training over ``2 * seq`` rows: rows
    ``0 .. seq - 1`` a clean sequence, rows ``seq .. 2 seq - 1`` its noisy
    copy, both cut into blocks of ``block`` positions. With ``noisy(r) =
    r >= seq`` and ``b(r) = (r mod seq) // block``, row q reads row k iff

        (not noisy(k) and b(k) < b(q))
        or (noisy(k) == noisy(q) and b(k) == b(q))

    The kernels' tiles must divide ``seq`` (``flash_attention`` picks them
    so, or refuses): a tile then lies in one quadrant."""
    seq: int
    block: int

    def __post_init__(self):
        if self.seq < 1 or self.block < 1 or self.seq % self.block:
            raise ValueError(f"BlockDiffusionMask(seq={self.seq}, block="
                             f"{self.block}): blocks must divide the "
                             "sequence")

    def everywhere(self):
        """The rule as written, over all ``[2 seq, 2 seq]`` entries (the
        XLA path and the tests; the kernels never build it)."""
        row = jnp.arange(2 * self.seq)
        noisy, b = row >= self.seq, (row % self.seq) // self.block
        return ((~noisy[None, :] & (b[None, :] < b[:, None]))
                | ((noisy[None, :] == noisy[:, None])
                   & (b[None, :] == b[:, None])))

    def block_of(self, local):
        """The block of a position ``local`` in [0, seq): a shift where
        the block length is a power of two (a vector has no cheap
        integer division)."""
        if self.block & (self.block - 1) == 0:
            return local >> (self.block.bit_length() - 1)
        return local // self.block

    def quadrant(self, first_q, first_k):
        """For a tile that starts at row ``first_q``, key row ``first_k``:
        (its first position within the query rows' copy, within the key
        rows' copy, ``below``, ``above``) such that an entry is allowed
        iff ``below < b(k) - b(q) < above`` (no clean row reads a noisy
        one: ``above`` lies under every difference there). Python ints or
        traced scalars."""
        q_noisy, k_noisy = first_q >= self.seq, first_k >= self.seq
        far = -2 * self.seq
        if isinstance(q_noisy, bool) and isinstance(k_noisy, bool):
            if k_noisy:             # its own block only
                below, above = (-1, 1) if q_noisy else (far, far)
            else:                   # earlier blocks, a clean row its own too
                below, above = far, (0 if q_noisy else 1)
        else:
            below = jnp.where(jnp.logical_and(k_noisy, q_noisy), -1, far)
            above = jnp.where(k_noisy, jnp.where(q_noisy, 1, far),
                              jnp.where(q_noisy, 0, 1))
        return (first_q % self.seq, first_k % self.seq, below, above)

    def tile_kind(self, qi, ki, block_q, block_k):
        """(live, interior) of tile (qi, ki), from the block numbers of
        its corners."""
        q0, k0, below, above = self.quadrant(qi * block_q, ki * block_k)
        q_lo, q_hi = self.block_of(q0), self.block_of(q0 + (block_q - 1))
        k_lo, k_hi = self.block_of(k0), self.block_of(k0 + (block_k - 1))
        # b(k) - b(q) runs over [k_lo - q_hi, k_hi - q_lo]
        live = (k_lo - q_hi < above) & (k_hi - q_lo > below)
        interior = (k_hi - q_lo < above) & (k_lo - q_hi > below)
        return live, interior

    def allowed(self, first_q, first_k, shape, q_axis):
        """The rule over one tile as a boolean array of ``shape``, queries
        along ``q_axis``."""
        q0, k0, below, above = self.quadrant(first_q, first_k)
        diff = (self.block_of(k0 + lax.broadcasted_iota(
            jnp.int32, shape, 1 - q_axis)) - self.block_of(
            q0 + lax.broadcasted_iota(jnp.int32, shape, q_axis)))
        return jnp.logical_and(diff > below, diff < above)


def _tile_kind(qoff, koff, kreal, qi, ki, *, causal, block_q, block_k,
               mask=None):
    """(live, interior) for tile (qi, ki): live, some entry is unmasked;
    interior, none is masked. Works on Python ints and on traced scalars
    alike: the kernels, ``_schedule`` and ``tile_counts`` share it."""
    if mask is not None:            # no offsets, no key padding
        return mask.tile_kind(qi, ki, block_q, block_k)
    first_k = ki * block_k
    live = first_k < kreal
    interior = first_k + block_k <= kreal
    if causal:
        first_kpos = koff + first_k
        first_qpos = qoff + qi * block_q
        live = live & (first_kpos <= first_qpos + (block_q - 1))
        interior = interior & (first_kpos + (block_k - 1) <= first_qpos)
    return live, interior


def _mask_blocks(mask: BlockDiffusionMask, block_q: int, block_k: int):
    """The tiles ``flash_attention`` takes under ``mask`` from the
    preferred ``block_q`` / ``block_k``: picked for one copy's length, so
    that no tile straddles the two copies."""
    blocks = (_pick_block(mask.seq, block_q), _pick_block(mask.seq, block_k))
    if any(mask.seq % b for b in blocks):
        raise ValueError(f"tiles {blocks} do not divide the {mask.seq} "
                         "positions of a copy: choose block_q / block_k "
                         "that do")
    return blocks


def tile_counts(sq: int, sk: int, block_q: int, block_k: int, causal: bool,
                q_offset: int = 0, k_offset: int = 0,
                mask: Optional[BlockDiffusionMask] = None
                ) -> Tuple[int, int, int]:
    """(grid, live, edge) tiles of one head's [sq, sk] scores, for the
    blocks ``flash_attention`` picks from ``block_q`` / ``block_k``: the
    tiles of the whole rectangle, those that compute anything (the steps
    a kernel makes when the offsets are known at trace time), and those
    of them that take the masked body."""
    if mask is not None:
        bq, bk = _mask_blocks(mask, block_q, block_k)
    else:
        bq, bk = (_pick_block(max(sq, 1), block_q),
                  _pick_block(max(sk, 1), block_k))
    nq, nk = -(-sq // bq), -(-sk // bk)
    kinds = [_tile_kind(q_offset, k_offset, sk, qi, ki, causal=causal,
                        block_q=bq, block_k=bk, mask=mask)
             for qi in range(nq) for ki in range(nk)]
    return (nq * nk, sum(bool(live) for live, _ in kinds),
            sum(bool(live and not inner) for live, inner in kinds))


def _schedule(nq, nk, group, offsets, *, keys_outer, sk, causal, block_q,
              block_k, mask=None):
    """The tiles a head's grid axis visits, in order: int32 [3, steps],
    rows (query head within the group, query block, key block). The
    forward and dq kernels walk a query block's key blocks
    (``keys_outer`` false), dk/dv a key block's query blocks over the
    group's heads. ``offsets`` = (q_offset, k_offset) when both are known
    at trace time: then only live tiles are listed (a row without any
    keeps its first tile, so its output block is still written).
    ``None`` (traced offsets) lists every tile; the kernels skip the dead
    ones at run time."""
    def live(qi, ki):
        return offsets is None or bool(_tile_kind(
            *offsets, sk, qi, ki, causal=causal, block_q=block_q,
            block_k=block_k, mask=mask)[0])

    if keys_outer:
        rows = [[(g, qi, ki) for g in range(group) for qi in range(nq)]
                for ki in range(nk)]
    else:
        rows = [[(0, qi, ki) for ki in range(nk)] for qi in range(nq)]
    steps = []
    for row in rows:
        steps += [t for t in row if live(t[1], t[2])] or row[:1]
    return np.asarray(steps, np.int32).T


def _row_ends(tab_ref, t, row):
    """Is step ``t`` the first / the last of its row of the schedule
    (``row`` 1: a query block's steps; 2: a key block's)?"""
    last_t = tab_ref.shape[1] - 1
    cur = tab_ref[row, t]
    first = jnp.logical_or(
        t == 0, tab_ref[row, jnp.maximum(t - 1, 0)] != cur)
    last = jnp.logical_or(
        t == last_t, tab_ref[row, jnp.minimum(t + 1, last_t)] != cur)
    return first, last


def _dot_nt(a, b):
    """a [m, c] x b [n, c] -> [m, n]: the contraction the MXU takes with
    the second operand as it lies in memory (no transpose is made)."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _lanes(x, n):
    """A lane-replicated [rows, 128] value at width n, by whole-register
    copies where n allows it (no lane broadcast)."""
    if n <= _LANES:
        return x[:, :n]
    if n % _LANES == 0:
        return jnp.concatenate([x] * (n // _LANES), axis=1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _on_live_tile(body, qoff_ref, koff_ref, kreal_ref, qi, ki, *, causal,
                  block_q, block_k, q_axis, mask=None):
    """Run ``body(valid)`` if tile (qi, ki) is live: ``valid`` is None on
    an interior tile and the tile's mask, queries along ``q_axis``, on an
    edge tile."""
    live, interior = _tile_kind(qoff_ref[0, 0], koff_ref[0, 0],
                                kreal_ref[0, 0], qi, ki, causal=causal,
                                block_q=block_q, block_k=block_k, mask=mask)

    @pl.when(interior)
    def _():
        body(None)

    @pl.when(jnp.logical_and(live, jnp.logical_not(interior)))
    def _():
        shape = (block_q, block_k) if q_axis == 0 else (block_k, block_q)
        if mask is not None:
            body(mask.allowed(qi * block_q, ki * block_k, shape, q_axis))
            return
        k_local = ki * block_k + lax.broadcasted_iota(jnp.int32, shape,
                                                      1 - q_axis)
        valid = k_local < kreal_ref[0, 0]
        if causal:
            q_pos = (qoff_ref[0, 0] + qi * block_q
                     + lax.broadcasted_iota(jnp.int32, shape, q_axis))
            valid = jnp.logical_and(valid,
                                    koff_ref[0, 0] + k_local <= q_pos)
        body(valid)


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(qoff_ref, koff_ref, kreal_ref, tab_ref, q_ref, k_ref, v_ref,
                out_ref, lse_ref, acc, m_scr, l_scr, *, scale: float,
                causal: bool, block_q: int, block_k: int, mask=None):
    t = pl.program_id(1)
    qi, ki = tab_ref[1, t], tab_ref[2, t]
    first, last = _row_ends(tab_ref, t, 1)
    d = acc.shape[1]

    @pl.when(first)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[:] = jnp.zeros_like(l_scr)

    def tile(valid):
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = _dot_nt(q, k) * scale
        if valid is not None:
            s = jnp.where(valid, s, _NEG_BIG)
        m_prev = m_scr[:]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_cur, block_k))
        if valid is not None:
            # A row with no unmasked key in this tile has m_cur =
            # _NEG_BIG and would read exp(0) = 1.
            p = jnp.where(valid, p, 0.0)
        w_prev = jnp.exp(m_prev - m_cur)
        l_scr[:] = l_scr[:] * w_prev + jnp.sum(p, axis=1, keepdims=True)
        m_scr[:] = m_cur
        v = v_ref[0].astype(jnp.float32)
        acc[:] = (acc[:] * _lanes(w_prev, d)
                  + jnp.dot(p, v, preferred_element_type=jnp.float32))

    _on_live_tile(tile, qoff_ref, koff_ref, kreal_ref, qi, ki,
                  causal=causal, block_q=block_q, block_k=block_k, q_axis=0,
                  mask=mask)

    @pl.when(last)
    def _():
        l = l_scr[:]
        out_ref[0] = (acc[:] / _lanes(jnp.maximum(l, 1e-20), d)
                      ).astype(out_ref.dtype)
        # lse block is (1, 1, block_q): TPU tiling requires the block's
        # second-minor dim to divide 8 or equal the array dim, which a
        # (1, block_q) view of [BH, Sq] cannot satisfy — row stats ride
        # as [BH, 1, Sq] instead. The lane-replicated statistic transposed
        # is that lane-major row, eight times over.
        lse = jnp.where(l > 0.0, m_scr[:] + jnp.log(jnp.maximum(l, 1e-20)),
                        _NEG_BIG)
        lse_ref[0] = lse.T[:1]


def _dq_kernel(qoff_ref, koff_ref, kreal_ref, tab_ref, q_ref, k_ref, v_ref,
               do_ref, lse_ref, delta_ref, dq_ref, dq_acc, *, scale,
               causal, block_q, block_k, mask=None):
    t = pl.program_id(1)
    qi, ki = tab_ref[1, t], tab_ref[2, t]
    first, last = _row_ends(tab_ref, t, 1)

    @pl.when(first)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def tile(valid):
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = _dot_nt(q, k) * scale
        p = jnp.exp(s - lse_ref[0, 0][:, None])
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        do = do_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        dp = _dot_nt(do, v)
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        dq_acc[:] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    _on_live_tile(tile, qoff_ref, koff_ref, kreal_ref, qi, ki,
                  causal=causal, block_q=block_q, block_k=block_k, q_axis=0,
                  mask=mask)

    @pl.when(last)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(qoff_ref, koff_ref, kreal_ref, tab_ref, q_ref, k_ref, v_ref,
                do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_acc,
                dv_acc, *, scale, causal, block_q, block_k, mask=None):
    t = pl.program_id(1)
    qi, ki = tab_ref[1, t], tab_ref[2, t]
    first, last = _row_ends(tab_ref, t, 2)

    @pl.when(first)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def tile(valid):
        # The tile transposed, keys down the rows (module docstring).
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        st = _dot_nt(k, q) * scale
        pt = jnp.exp(st - lse_ref[0])
        if valid is not None:
            pt = jnp.where(valid, pt, 0.0)
        do = do_ref[0].astype(jnp.float32)
        dv_acc[:] += jnp.dot(pt, do, preferred_element_type=jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        dpt = _dot_nt(v, do)
        dst = pt * (dpt - delta_ref[0]) * scale
        dk_acc[:] += jnp.dot(dst, q, preferred_element_type=jnp.float32)

    _on_live_tile(tile, qoff_ref, koff_ref, kreal_ref, qi, ki,
                  causal=causal, block_q=block_q, block_k=block_k, q_axis=1,
                  mask=mask)

    @pl.when(last)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# The three calls
# ---------------------------------------------------------------------------

def _vmem(block, index_map):
    return pl.BlockSpec(block, index_map, memory_space=pltpu.VMEM)


def _plan(q3, k3, offsets, *, keys_outer, causal, block_q, block_k,
          mask=None):
    """A call's schedule and its (q, k/v, row statistics) index maps over
    (head, step, *scalar prefetch), the schedule being the fourth scalar.
    The forward and dq grids run over query heads; dk/dv's runs over
    key/value heads, the query head within the group in the schedule's
    first row."""
    group = q3.shape[0] // k3.shape[0]      # query heads per key/value head
    tab = _schedule(q3.shape[1] // block_q, k3.shape[1] // block_k, group,
                    offsets, keys_outer=keys_outer, sk=k3.shape[1],
                    causal=causal, block_q=block_q, block_k=block_k,
                    mask=mask)

    def heads(b, t, tab_ref):               # (query head, key/value head)
        if keys_outer:
            return b * group + tab_ref[0, t], b
        return b, b // group

    q_map = lambda b, t, *s: (heads(b, t, s[3])[0], s[3][1, t], 0)
    kv_map = lambda b, t, *s: (heads(b, t, s[3])[1], s[3][2, t], 0)
    row_map = lambda b, t, *s: (heads(b, t, s[3])[0], 0, s[3][1, t])
    return tab, q_map, kv_map, row_map


def _call(kernel, tab, heads, tile, in_specs, out_specs, out_shape, scratch,
          interpret, scalars, operands):
    """One ``pallas_call`` over (head, step of the schedule). The offsets,
    the real key count and the schedule ride as scalar prefetch: the
    kernels read them from SMEM and the index maps see the schedule.
    A tile's float32 intermediates live on the kernel's VMEM stack, 16 MiB
    by default: tiles beyond 512 x 512 entries ask for 32 bytes an entry."""
    stack = 32 * tile
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(heads, tab.shape[1]),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape, interpret=interpret,
        compiler_params=(pltpu.CompilerParams(vmem_limit_bytes=stack)
                         if stack > 16 * 2 ** 20 else None),
    )(*scalars, jnp.asarray(tab), *operands)


def _bwd_in_specs(d, block_q, block_k, q_map, kv_map, row_map):
    """q, k, v, dO, lse, delta: what both backward kernels read."""
    return [_vmem((1, block_q, d), q_map), _vmem((1, block_k, d), kv_map),
            _vmem((1, block_k, d), kv_map), _vmem((1, block_q, d), q_map),
            _vmem((1, 1, block_q), row_map), _vmem((1, 1, block_q), row_map)]


_STATIC = ("scale", "causal", "block_q", "block_k", "offsets", "interpret",
           "mask")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _flash_fwd_call(qoff, koff, sk_real, q3, k3, v3, *, scale, causal,
                    block_q, block_k, interpret, offsets=None, mask=None):
    """The forward kernel's call and nothing else: a jitted function whose
    result is the ``pallas_call``'s own gives the custom-call this
    function's name in the compiled program, which is where a device
    trace finds the kernel (the jit itself is inlined)."""
    bh, sq, d = q3.shape
    tiles = dict(causal=causal, block_q=block_q, block_k=block_k, mask=mask)
    tab, q_map, kv_map, row_map = _plan(q3, k3, offsets, keys_outer=False,
                                        **tiles)
    return _call(
        functools.partial(_fwd_kernel, scale=scale, **tiles), tab, bh,
        block_q * block_k,
        [_vmem((1, block_q, d), q_map), _vmem((1, block_k, d), kv_map),
         _vmem((1, block_k, d), kv_map)],
        [_vmem((1, block_q, d), q_map), _vmem((1, 1, block_q), row_map)],
        [jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
         jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32)],
        [pltpu.VMEM((block_q, d), jnp.float32),
         pltpu.VMEM((block_q, _LANES), jnp.float32),
         pltpu.VMEM((block_q, _LANES), jnp.float32)],
        interpret, (qoff, koff, sk_real), (q3, k3, v3))


@functools.partial(jax.jit, static_argnames=_STATIC)
def _flash_dq_call(qoff, koff, sk_real, q3, k3, v3, do3, lse3, delta3, *,
                   scale, causal, block_q, block_k, interpret, offsets=None,
                   mask=None):
    """The dq kernel's call and nothing else (see ``_flash_fwd_call``)."""
    bh, sq, d = q3.shape
    tiles = dict(causal=causal, block_q=block_q, block_k=block_k, mask=mask)
    tab, q_map, kv_map, row_map = _plan(q3, k3, offsets, keys_outer=False,
                                        **tiles)
    return _call(
        functools.partial(_dq_kernel, scale=scale, **tiles), tab, bh,
        block_q * block_k,
        _bwd_in_specs(d, block_q, block_k, q_map, kv_map, row_map),
        _vmem((1, block_q, d), q_map),
        jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
        [pltpu.VMEM((block_q, d), jnp.float32)],
        interpret, (qoff, koff, sk_real), (q3, k3, v3, do3, lse3, delta3))


@functools.partial(jax.jit, static_argnames=_STATIC)
def _flash_dkv_call(qoff, koff, sk_real, q3, k3, v3, do3, lse3, delta3, *,
                    scale, causal, block_q, block_k, interpret, offsets=None,
                    mask=None):
    """The dk/dv kernel's call and nothing else (see ``_flash_fwd_call``)."""
    bkv, sk, d = k3.shape
    tiles = dict(causal=causal, block_q=block_q, block_k=block_k, mask=mask)
    tab, q_map, kv_map, row_map = _plan(q3, k3, offsets, keys_outer=True,
                                        **tiles)
    return _call(
        functools.partial(_dkv_kernel, scale=scale, **tiles), tab, bkv,
        block_q * block_k,
        _bwd_in_specs(d, block_q, block_k, q_map, kv_map, row_map),
        [_vmem((1, block_k, d), kv_map), _vmem((1, block_k, d), kv_map)],
        [jax.ShapeDtypeStruct((bkv, sk, d), k3.dtype),
         jax.ShapeDtypeStruct((bkv, sk, d), v3.dtype)],
        [pltpu.VMEM((block_k, d), jnp.float32),
         pltpu.VMEM((block_k, d), jnp.float32)],
        interpret, (qoff, koff, sk_real), (q3, k3, v3, do3, lse3, delta3))


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
RESIDUAL_NAMES = ("flash_q", "flash_k", "flash_v", "flash_out", "flash_lse")


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _flash(q3, k3, v3, qoff, koff, static):
    return _flash_fwd(q3, k3, v3, qoff, koff, static)[0]


def _flash_fwd(q3, k3, v3, qoff, koff, static):
    """``static``: the calls' static arguments (``_STATIC``'s names) as a
    tuple of pairs, which a ``custom_vjp`` can hash."""
    opts = dict(static)
    q3, k3, v3 = map(_named, (q3, k3, v3), RESIDUAL_NAMES[:3])
    sq, sk = q3.shape[1], k3.shape[1]
    sk_real = jnp.full((1, 1), sk, jnp.int32)
    out, lse3 = _flash_fwd_call(
        qoff, koff, sk_real, _pad_seq(q3, opts["block_q"]),
        _pad_seq(k3, opts["block_k"]), _pad_seq(v3, opts["block_k"]), **opts)
    out, lse = map(_named, (out[:, :sq], lse3[:, 0, :sq]), RESIDUAL_NAMES[3:])
    return out, (q3, k3, v3, out, lse, qoff, koff)


def _flash_bwd(static, res, g):
    opts = dict(static)
    q3, k3, v3, out, lse, qoff, koff = res
    bh, sq, _ = q3.shape
    sk = k3.shape[1]
    sk_real = jnp.full((1, 1), sk, jnp.int32)
    qp, dop = (_pad_seq(x, opts["block_q"]) for x in (q3, g))
    kp, vp = (_pad_seq(x, opts["block_k"]) for x in (k3, v3))
    pad = qp.shape[1] - sq
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    # Row stats as [BH, 1, Sq] — (1, block) blocks of a 2-D array break
    # the TPU block-tiling rule (see the fwd lse spec). Padded q rows
    # would recompute against lse = 0 garbage: lse = inf makes them inert.
    lse3 = jnp.pad(lse, ((0, 0), (0, pad)),
                   constant_values=jnp.inf).reshape(bh, 1, sq + pad)
    delta3 = jnp.pad(delta, ((0, 0), (0, pad))).reshape(bh, 1, sq + pad)
    args = (qoff, koff, sk_real, qp, kp, vp, dop, lse3, delta3)
    dq = _flash_dq_call(*args, **opts)
    dk, dv = _flash_dkv_call(*args, **opts)
    return dq[:, :sq], dk[:, :sk], dv[:, :sk], None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def _check_mask(mask: BlockDiffusionMask, q, k, causal, q_offset, k_offset):
    if causal or not all(isinstance(o, (int, np.integer)) and o == 0
                         for o in (q_offset, k_offset)):
        raise ValueError("a mask rule stands alone: no causal flag, no "
                         "offsets")
    if q.shape[1] != 2 * mask.seq or k.shape[1] != 2 * mask.seq:
        raise ValueError(f"{mask} is over {2 * mask.seq} rows; q has "
                         f"{q.shape[1]}, k {k.shape[1]}")


def flash_attention_reference(q, k, v, *, causal: bool = False,
                              scale: Optional[float] = None,
                              q_offset=0, k_offset=0,
                              mask: Optional[BlockDiffusionMask] = None
                              ) -> jax.Array:
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
        seen = qpos[:, None] >= kpos[None, :]
        s = jnp.where(seen[None, :, None, :], s, _NEG_BIG)
    if mask is not None:
        _check_mask(mask, q, k, causal, q_offset, k_offset)
        seen = mask.everywhere()
        s = jnp.where(seen[None, :, None, :], s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqhk,bkhd->bqhd", p, v.astype(p.dtype),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None, q_offset=0, k_offset=0,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    use_pallas: Optional[bool] = None,
                    interpret: bool = False,
                    mask: Optional[BlockDiffusionMask] = None) -> jax.Array:
    """Flash attention over [B, S, H, D] tensors (differentiable); k and
    v may carry fewer heads ``[B, S, H_kv, D]`` with H a multiple of H_kv
    (grouped-query attention). ``mask``: a static rule in place of
    ``causal`` (``BlockDiffusionMask``), over ``2 * mask.seq`` rows.

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
                                         k_offset=k_offset, mask=mask)
    b, sq, h, d = q.shape
    if h % k.shape[2] or k.shape[2] != v.shape[2]:
        raise ValueError(f"{h} query heads do not group over "
                         f"{k.shape[2]} key / {v.shape[2]} value heads")
    known = all(isinstance(o, (int, np.integer))
                for o in (q_offset, k_offset))
    if mask is not None:
        _check_mask(mask, q, k, causal, q_offset, k_offset)
        block_q, block_k = _mask_blocks(mask, block_q, block_k)
    else:
        block_q = _pick_block(max(sq, 1), block_q)
        block_k = _pick_block(max(k.shape[1], 1), block_k)
    static = dict(
        scale=scale, causal=causal, interpret=interpret, block_q=block_q,
        block_k=block_k, mask=mask,
        offsets=(int(q_offset), int(k_offset)) if known else None)
    qoff = jnp.full((1, 1), q_offset, jnp.int32)
    koff = jnp.full((1, 1), k_offset, jnp.int32)
    out3 = _flash(_to3d(q), _to3d(k), _to3d(v), qoff, koff,
                  tuple(static.items()))
    return _to4d(out3, b, h).astype(q.dtype)
