"""Mixture-of-Experts with expert parallelism over the ``ep`` mesh axis.

Role of the reference MoE stack (``python/paddle/incubate/distributed/
models/moe/moe_layer.py`` MoELayer, ``gate/gshard_gate.py``, C++
``global_scatter/global_gather`` ops, ``operators/collective/
global_scatter_op.cc``): top-k gating, capacity-limited dispatch to
experts sharded across devices, weighted combine on return.

TPU-first: GShard-style static-shape dispatch — position-in-expert via
cumsum over one-hot assignments, fixed capacity buffers, one all_to_all
out and one back (replacing brpc/NCCL global_scatter/global_gather). The
einsum-heavy dispatch/combine maps onto the MXU.

Beside it, for layers with hundreds of experts and tens per token (where
a ``[T, E, C]`` one-hot cannot exist): ``topk_sigmoid_router``,
``topk_softmax_router`` and ``dropless_dispatch``, which sorts the
assignments by expert and runs the experts as one grouped product over
contiguous row segments. The layer is told which experts it holds
(``held = (first, count)``): it routes over
all of them, normalises over all chosen and computes the held part, which
is what a chip of an expert-parallel deployment does between the two
exchanges. No capacity, so no assignment is ever dropped.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name


def top2_gate(logits: jax.Array, *, capacity: int
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """GShard top-2 gating (role of gshard_gate.py).

    logits [T, E] → (combine [T, E, C], dispatch [T, E, C] bool, aux_loss).
    combine[t, e, c] is the gate weight with which token t lands in
    expert e's capacity slot c.
    """
    t, e = logits.shape
    gates = jax.nn.softmax(logits, axis=-1)

    # Top-1 and top-2 expert per token.
    idx1 = jnp.argmax(gates, axis=-1)                          # [T]
    mask1 = jax.nn.one_hot(idx1, e, dtype=gates.dtype)
    gates2 = gates * (1.0 - mask1)
    idx2 = jnp.argmax(gates2, axis=-1)
    mask2 = jax.nn.one_hot(idx2, e, dtype=gates.dtype)

    # Aux load-balancing loss (mean gate * mean assignment per expert).
    density = jnp.mean(mask1, axis=0)
    density_proxy = jnp.mean(gates, axis=0)
    aux_loss = jnp.sum(density * density_proxy) * (e * e) / e

    # Capacity positions: top-1 tokens first, then top-2.
    pos1 = jnp.cumsum(mask1, axis=0) * mask1 - mask1           # pos in expert
    pos2 = (jnp.cumsum(mask2, axis=0) - mask2 +
            jnp.sum(mask1, axis=0, keepdims=True)) * mask2
    keep1 = mask1 * (pos1 < capacity)
    keep2 = mask2 * (pos2 < capacity)

    g1 = jnp.sum(gates * keep1, axis=-1)
    g2 = jnp.sum(gates * keep2, axis=-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    loc1 = jnp.sum(pos1 * keep1, axis=-1).astype(jnp.int32)    # [T]
    loc2 = jnp.sum(pos2 * keep2, axis=-1).astype(jnp.int32)

    oh_c1 = jax.nn.one_hot(loc1, capacity, dtype=gates.dtype)  # [T, C]
    oh_c2 = jax.nn.one_hot(loc2, capacity, dtype=gates.dtype)
    combine = (g1[:, None, None] * keep1[:, :, None] * oh_c1[:, None, :] +
               g2[:, None, None] * keep2[:, :, None] * oh_c2[:, None, :])
    dispatch = combine > 0.0
    return combine, dispatch, aux_loss


def moe_layer(gate_w: jax.Array, expert_params: Dict[str, jax.Array],
              expert_fn: Callable[[Dict, jax.Array], jax.Array],
              x: jax.Array, *, axis: str = "ep",
              capacity_factor: float = 1.25
              ) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE layer (call INSIDE shard_map).

    gate_w [F, E_total] (replicated); expert_params: pytree whose leaves
    have leading dim E_local (this device's experts); expert_fn(params_e,
    tokens [N, F]) -> [N, F] is vmapped over local experts.
    x [T_local, F] local tokens. Returns (y [T_local, F], aux_loss).
    """
    n = lax.axis_size(axis)
    t_local, f = x.shape
    e_local = jax.tree.leaves(expert_params)[0].shape[0]
    e_total = e_local * n
    capacity = max(int(capacity_factor * (2 * t_local) / e_total), 1)

    logits = jnp.dot(x, gate_w, preferred_element_type=jnp.float32)
    combine, dispatch, aux = top2_gate(logits, capacity=capacity)

    # Dispatch: [T, E, C] x [T, F] -> [E, C, F] buffers.
    dispatched = jnp.einsum("tec,tf->ecf", dispatch.astype(x.dtype), x,
                            preferred_element_type=jnp.float32)
    # all_to_all: split experts across ep, gather source-device dim:
    # [E_total, C, F] -> [n * E_local, C, F] -> recv [n, E_local, C, F]
    recv = lax.all_to_all(
        dispatched.reshape(n, e_local, capacity, f), axis,
        split_axis=0, concat_axis=0, tiled=False)      # [n, n?..]
    # tiled=False adds a leading axis: [n, 1, e_local, C, F] — normalize.
    recv = recv.reshape(n, e_local, capacity, f)
    # Per-local-expert token batch: [E_local, n*C, F].
    expert_in = recv.transpose(1, 0, 2, 3).reshape(e_local, n * capacity, f)
    expert_out = jax.vmap(expert_fn)(expert_params, expert_in)
    # Return trip.
    back = expert_out.reshape(e_local, n, capacity, f).transpose(1, 0, 2, 3)
    returned = lax.all_to_all(back, axis, split_axis=0, concat_axis=0,
                              tiled=False).reshape(e_total, capacity, f)
    # Combine: [T, E, C] x [E, C, F] -> [T, F].
    y = jnp.einsum("tec,ecf->tf", combine.astype(returned.dtype), returned,
                   preferred_element_type=jnp.float32)
    return y.astype(x.dtype), aux


# -- top-k sigmoid routing, dropless sort/segment dispatch -------------------

# What the two functions below compute that a rematerialised caller would
# rather keep than compute again (a full-precision product, a top-k and a
# sort, for a few values a token), under the names a ``jax.checkpoint``
# save policy may keep them by; without one a name is the identity.
ROUTING_RESIDUAL_NAMES = ("moe_logits", "moe_idx", "moe_weights",
                          "moe_order", "moe_load")


def topk_sigmoid_router(x: jax.Array, gate_w: jax.Array, bias: jax.Array,
                        *, k: int, scaling: float = 1.0
                        ) -> Tuple[jax.Array, jax.Array]:
    """Sigmoid scores over all experts, the ``k`` largest of ``score +
    bias`` chosen, weighted by the scores themselves (the bias steers the
    choice only, DeepSeek-V3's bias-corrected routing).

    x [T, F]; gate_w [F, E]; bias [E]. Returns (idx [T, k] int32,
    weights [T, k] float32 = scaling * s / sum of the chosen s). Scores
    are float32 at full matmul precision: a choice between near-equal
    experts must not turn on the MXU's bfloat16 pass.
    """
    f32 = jnp.float32
    # the product is named, not the scores: the sigmoid's own derivative
    # reads what the sigmoid returned, and a name is a new value
    scores = jax.nn.sigmoid(checkpoint_name(jnp.dot(
        x.astype(f32), gate_w.astype(f32), precision=lax.Precision.HIGHEST,
        preferred_element_type=f32), "moe_logits"))
    _, idx = lax.top_k(scores + lax.stop_gradient(bias.astype(f32)), k)
    idx = checkpoint_name(idx, "moe_idx")
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return idx, checkpoint_name(chosen * scaling, "moe_weights")


def topk_softmax_router(x: jax.Array, gate_w: jax.Array, k: int
                        ) -> Tuple[jax.Array, jax.Array]:
    """Softmax over all experts, the ``k`` likeliest chosen (the lower
    index of equals first), weighted by their probabilities renormalised
    over the chosen: no bias, no scaling.

    x [T, F]; gate_w [F, E]. Returns (idx [T, k] int32, weights [T, k]
    float32, summing to 1 a token). Logits and softmax are float32 at
    full matmul precision, as ``topk_sigmoid_router``'s scores.
    """
    f32 = jnp.float32
    probs = jax.nn.softmax(checkpoint_name(jnp.dot(
        x.astype(f32), gate_w.astype(f32), precision=lax.Precision.HIGHEST,
        preferred_element_type=f32), "moe_logits"), axis=-1)
    idx = checkpoint_name(lax.top_k(probs, k)[1], "moe_idx")
    # gathered by the named indices: a caller that keeps them does not
    # run the top-k a second time
    chosen = jnp.take_along_axis(probs, idx, axis=-1)
    return idx, checkpoint_name(
        chosen / jnp.sum(chosen, axis=-1, keepdims=True), "moe_weights")


class DispatchCounts(NamedTuple):
    """What one call of ``dropless_dispatch`` served."""
    load: jax.Array       # [count] assignments per held expert
    # [] held assignments whose rows no served block added to the result,
    # counted where the rows are added: 0 unless a block was skipped
    dropped: jax.Array


class _Block(NamedTuple):
    """Block ``lo .. lo + rows`` of the sorted assignments."""
    sel: jax.Array      # [rows] their places in the flat [t * k] assignments
    tok: jax.Array      # [rows] their tokens
    sizes: jax.Array    # [count] rows per held expert
    held: jax.Array     # [] how many of the rows are held assignments
    # places and tokens once more, for adding to: those of a row past the
    # last held assignment lie past the end, where nothing is added. A
    # grouped product leaves such rows unwritten (whatever the buffer
    # held), forward and in its transposes: neither their values nor their
    # cotangents may go anywhere.
    to_sel: jax.Array
    to_tok: jax.Array


def _block_at(lo, k, rows, order, load, t) -> _Block:
    ends = jnp.cumsum(load)
    sel = lax.dynamic_slice(order, (lo,), (rows,))
    live = (lo + jnp.arange(rows)) < ends[-1]
    sizes = jnp.clip(ends - lo, 0, rows) - jnp.clip(ends - load - lo, 0, rows)
    return _Block(sel, sel // k, sizes, jnp.sum(live, dtype=jnp.int32),
                  jnp.where(live, sel, t * k), jnp.where(live, sel // k, t))


def _blocks_needed(load, rows):
    return (jnp.sum(load) + (rows - 1)) // rows


class LoopedExperts(NamedTuple):
    """The experts of ``dropless_dispatch``'s looped form, forward and
    backward written out: the loop's backward pass sums the experts'
    gradient where it is made, which ``jax.vjp`` of a ``rows_fn`` cannot
    say (its gradient is a new array a trip, as large as the weights).

    ``forward(params, rows [R, F], scale [R], sizes [count]) -> [R, F]``
    float32: ``scale[r]`` times expert ``i`` applied to each of the
    ``sizes[i]`` consecutive rows of its segment. ``backward(params, rows,
    scale, sizes, dy [R, F], sums) -> (drows [R, F], dscale [R], sums')``:
    the cotangents of ``rows`` and ``scale`` and ``sums`` (a pytree like
    ``params``, float32) with this call's gradient of the experts' weights
    added to it, in ``sums``' own buffers where it can be. Both may leave
    anything in rows past the last segment and must let nothing of such
    rows, of ``rows`` or ``dy``, reach a live row or ``sums'``. ``rows``
    and ``dy`` arrive as ``operand``: the loop gathers them already cast.
    ``add_rows(into [T, F], index [R], values [R, F]) -> into'`` adds each
    trip's results to their tokens' rows (an index past the end adds
    nothing): XLA's scatter-add unless the caller has a faster one.
    """
    forward: Callable[..., jax.Array]
    backward: Callable[..., Tuple[jax.Array, jax.Array, Dict]]
    operand: jnp.dtype = jnp.float32
    add_rows: Callable[..., jax.Array] = (
        lambda into, index, values: into.at[index].add(values, mode="drop"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _looped_blocks(experts, k, rows, u, flat_w, order, load, params):
    """``dropless_dispatch``'s blocks, ``rows`` assignments each, as a
    loop over the blocks the load needs: ``(out [T, F] float32 -> u's
    dtype, assignments served)``. ``order`` is padded to whole blocks.
    What a trip computes for rows past the last held assignment is added
    nowhere, forward and backward (``_block_at``)."""
    cast = u.astype(experts.operand)

    def block(i, acc):
        out, served = acc
        b = _block_at(i * rows, k, rows, order, load, u.shape[0])
        y = experts.forward(params, cast[b.tok], flat_w[b.sel], b.sizes)
        return experts.add_rows(out, b.to_tok, y), served + b.held
    out, served = lax.fori_loop(
        0, _blocks_needed(load, rows), block,
        (jnp.zeros(u.shape, jnp.float32), jnp.zeros((), jnp.int32)))
    return out.astype(u.dtype), served


def _looped_blocks_fwd(experts, k, rows, u, flat_w, order, load, params):
    return (_looped_blocks(experts, k, rows, u, flat_w, order, load, params),
            (u, flat_w, order, load, params))


def _looped_blocks_bwd(experts, k, rows, res, cotangents):
    u, flat_w, order, load, params = res
    cast, g = u.astype(experts.operand), cotangents[0].astype(experts.operand)

    def block(i, acc):
        du, dw, sums = acc
        b = _block_at(i * rows, k, rows, order, load, u.shape[0])
        drows, dscale, sums = experts.backward(
            params, cast[b.tok], flat_w[b.sel], b.sizes, g[b.tok], sums)
        return (experts.add_rows(du, b.to_tok, drows),
                dw.at[b.to_sel].add(dscale, mode="drop"), sums)
    du, dw, sums = lax.fori_loop(
        0, _blocks_needed(load, rows), block,
        (jnp.zeros(u.shape, jnp.float32), jnp.zeros(flat_w.shape,
                                                    jnp.float32),
         jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)))
    return (du.astype(u.dtype), dw.astype(flat_w.dtype), None, None,
            jax.tree.map(lambda s, p: s.astype(p.dtype), sums, params))


_looped_blocks.defvjp(_looped_blocks_fwd, _looped_blocks_bwd)


def dropless_dispatch(u: jax.Array, idx: jax.Array, weights: jax.Array,
                      held: Tuple[int, int],
                      rows_fn: Callable[..., jax.Array],
                      expert_params=None, block_rows: int = 0
                      ) -> Tuple[jax.Array, DispatchCounts]:
    """Weighted sum over each token's chosen experts, restricted to the
    experts ``first <= e < first + count`` this device holds.

    u [T, F]; idx, weights [T, k]; ``rows_fn(rows [R, F], sizes [count])
    -> [R, F]`` applies expert ``i`` to the ``sizes[i]`` consecutive rows
    of its segment (``lax.ragged_dot`` over stacked expert weights).
    Returns (r [T, F], counts).

    Assignments are sorted by held expert (the others sort last and are
    never touched) and served in blocks of T rows; a block past the last
    held assignment is skipped by ``lax.cond``, so the work follows the
    load and the static bound ``T * min(k, count)`` costs no time. It
    costs memory: every static block's backward pass has its own
    gradient of the experts' weights and its own intermediates, allocated
    whether it is served or not (0.7 GB a block for 16 experts of 2048 x
    768 x 3 and 8,192 rows).

    With ``expert_params`` (the experts' stacked weights, a pytree)
    ``rows_fn`` is a ``LoopedExperts`` and the blocks are a loop whose trip
    count is the blocks the load needs, forward and backward
    (``_looped_blocks``): one block's intermediates, and one gradient of
    the experts' weights that every trip's ``backward`` adds to where it
    is, whatever the static bound. The backward pass computes of a served
    block's forward what it needs again and keeps nothing of the first.
    ``block_rows`` (default T) is the loop's block. A trip gathers and
    scatters all its rows, held or not, and its products visit the row
    tiles that hold a held one (``ops/pallas_kernels/grouped_matmul.py``),
    so a caller that knows its share sizes the block near it.
    """
    t, k = idx.shape
    first, count = held
    flat_e, flat_w = idx.reshape(t * k), weights.reshape(t * k)
    local = jnp.where((flat_e >= first) & (flat_e < first + count),
                      flat_e - first, count)
    order = checkpoint_name(
        jnp.argsort(local, stable=True).astype(jnp.int32), "moe_order")
    load = checkpoint_name(
        jnp.sum(local[:, None] == jnp.arange(count)[None, :], axis=0,
                dtype=jnp.int32), "moe_load")
    ends = jnp.cumsum(load)
    starts, n_held = ends - load, ends[-1]
    if expert_params is not None:
        rows = block_rows or t
        out, served = _looped_blocks(
            rows_fn, k, rows, u, flat_w,
            jnp.pad(order, (0, -(t * k) % rows)), load, expert_params)
        return out, DispatchCounts(load, n_held - served)
    acc = (jnp.zeros(u.shape, jnp.float32), jnp.zeros((), jnp.int32))
    for lo in range(0, t * min(k, count), t):
        sizes = jnp.clip(ends - lo, 0, t) - jnp.clip(starts - lo, 0, t)

        def serve(acc, lo=lo, sizes=sizes):
            out, served = acc
            sel = order[lo:lo + t]
            tok = sel // k
            # Rows past the last held assignment belong to no segment: a
            # grouped product leaves them unwritten (whatever the buffer
            # held), forward and in its transposes. Select them away on
            # both sides, so that neither the values nor the cotangents
            # of such rows go anywhere.
            live = ((lo + jnp.arange(t)) < n_held)[:, None]
            y = rows_fn(jnp.where(live, u[tok], 0.0), sizes)
            y = jnp.where(live, y, 0.0) * flat_w[sel][:, None]
            return out.at[tok].add(y), served + jnp.sum(live, dtype=jnp.int32)
        acc = lax.cond(lo < n_held, serve, lambda a: a, acc)
    out, served = acc
    return out.astype(u.dtype), DispatchCounts(load, n_held - served)
