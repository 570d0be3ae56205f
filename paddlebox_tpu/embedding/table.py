"""Per-pass device table: ONE fused sharded value array + index math.

Role of the HeterPS HBM structures: the per-GPU hashtable + mem_pool value
slabs (``heter_ps/hashtable.h``, ``mem_pool.h``) and the
``CommonFeatureValue`` record layout (``heter_ps/feature_value.h:44-120``:
show, click, embed_w(lr), embed_g2sum, embedx_w[mf], embedx_g2sum).

TPU-first: because the pass key set is pre-registered (pass-based design),
the device table needs NO hashtable — rows are assigned by sorted-key rank,
dealt ROUND-ROBIN across shards (rank g -> shard g % S, slot g // S). The
round-robin deal is load-bearing: ``plan_shards`` rounds rows_per_shard up
to a power of two for compile stability, and a contiguous split would then
leave the tail shards empty (a 20K-key pass over 8 shards of 4096 rows
puts everything in shards 0-4), concentrating the pull/push all-to-all on
a subset of links and overflowing their fixed-capacity buckets — the
reference gets the same balance by hashing keys to shards
(``key % shard_num``, heter_comm_inl.h:267). Each shard carries one extra
trash row (index ``rows_per_shard``) that absorbs padding lookups and
padding grads, so every kernel is mask-free and static-shape.

All per-row fields live in ONE ``[rows, W]`` float32 array (the
CommonFeatureValue packing) so the hot path is a single gather per pull and
a single scatter per push — XLA scatter/gather on TPU pays a fixed cost
per *op*, and the r02 six-arrays layout paid it six times per step
(measured on the r02 chip run: ~50 ms per 426K-row scatter).

Column layout (D = emb dim, Ke/Kw = optimizer state widths):

    [ emb(D) | w | show | click | emb_state(Ke) | w_state(Kw) ]
      `--------- pull payload = [:, :D+3] (one contiguous slice) ---'

Index math (device-side, int32):
  global row g of key k  = rank of k in the sorted pass key set (host)
  shard(g)               = g %  num_shards
  row_in_shard(g)        = g // num_shards
  padding sentinel       = trash row of shard (i % S)
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class TableConfig:
    """Sparse table hyper-params (role of the accessor/optimizer config in
    the_one_ps.proto + optimizer_conf.h)."""

    name: str = "embedding"
    dim: int = 8                  # mf embedding width (embedx_dim)
    num_shards: int = 1           # table shards == size of the shard mesh axis
    # Initialization (role of CtrCommonAccessor init ranges).
    init_scale: float = 0.01
    # Sparse optimizer selection + hyper-params (role of optimizer_conf.h
    # bounds/decay and HeterPs optimizer_type dispatch).
    optimizer: str = "adagrad"    # adagrad | adam | adam_shared | ftrl
    learning_rate: float = 0.05
    initial_g2sum: float = 3.0
    beta1: float = 0.9
    beta2: float = 0.999
    # FTRL-proximal knobs (optimizer="ftrl"; role of ftrl_op.cc attrs).
    ftrl_l1: float = 0.1
    ftrl_l2: float = 1.0
    ftrl_beta: float = 1.0
    min_bound: float = -10.0
    max_bound: float = 10.0
    # Show/click decay applied at end-of-day shrink (role of ShrinkTable).
    show_click_decay: float = 0.98

    @property
    def w_width(self) -> int:
        """Scalar LR weight + its g2sum (wide/linear term)."""
        return 2


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PassTable:
    """Device-resident per-pass table (a one-leaf pytree).

    ``vals [S*(R+1), W]`` — fused per-row record (module docstring layout);
    shard s owns rows [s*(R+1), (s+1)*(R+1)), the last row of each shard
    block being its trash row. Under shard_map the leading dim is sharded
    over the table axis so each device holds exactly its [(R+1), W] block.
    """

    vals: jax.Array
    rows_per_shard: int            # real rows (excludes trash row)
    num_shards: int
    dim: int
    ke: int                        # emb_state width
    kw: int                        # w_state width

    def tree_flatten(self):
        return (self.vals,), (self.rows_per_shard, self.num_shards,
                              self.dim, self.ke, self.kw)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        rows_per_shard, num_shards, dim, ke, kw = aux
        return cls(leaves[0], rows_per_shard=rows_per_shard,
                   num_shards=num_shards, dim=dim, ke=ke, kw=kw)

    # -- column views (read-only slices of the fused record) ---------------

    @property
    def pull_width(self) -> int:
        return self.dim + 3

    @property
    def width(self) -> int:
        return self.dim + 3 + self.ke + self.kw

    @property
    def emb(self) -> jax.Array:
        return self.vals[:, :self.dim]

    @property
    def w(self) -> jax.Array:
        return self.vals[:, self.dim]

    @property
    def show(self) -> jax.Array:
        return self.vals[:, self.dim + 1]

    @property
    def click(self) -> jax.Array:
        return self.vals[:, self.dim + 2]

    @property
    def emb_state(self) -> jax.Array:
        return self.vals[:, self.dim + 3:self.dim + 3 + self.ke]

    @property
    def w_state(self) -> jax.Array:
        return self.vals[:, self.dim + 3 + self.ke:]

    @property
    def num_rows_padded(self) -> int:
        return self.num_shards * (self.rows_per_shard + 1)

    def with_emb(self, emb: jax.Array) -> "PassTable":
        """Copy with the emb columns replaced (test/tooling helper)."""
        return dataclasses.replace(
            self, vals=self.vals.at[:, :self.dim].set(emb))


def table_widths(config: TableConfig) -> Tuple[int, int, int]:
    """(dim, ke, kw) for a config's optimizer."""
    from paddlebox_tpu.embedding.optimizers import make_sparse_optimizer
    opt = make_sparse_optimizer(config)
    return config.dim, opt.emb_state_width(config.dim), opt.w_state_width()


def plan_shards(num_keys: int, num_shards: int) -> int:
    """Rows per shard covering num_keys, rounded up to a power of two.

    The jitted train step's shapes depend on the table's leading dim, so
    WITHOUT rounding every pass with a new key count would recompile
    (~tens of seconds); with it, steady-state online passes hit the same
    size bucket and reuse the compiled program (one recompile per size
    doubling; at most 2x table HBM). Row alignment beyond that is
    unnecessary — gathers index the row dim; only the trailing feature
    dim needs TPU tiling."""
    rps = -(-max(num_keys, 1) // num_shards)
    return 1 << (rps - 1).bit_length()


def fuse_values_host(values: Dict[str, np.ndarray]) -> np.ndarray:
    """Pack the store's per-field host arrays into the fused [n, W] record
    (column layout per module docstring)."""
    n = values["emb"].shape[0]
    cols = [values["emb"],
            values["w"].reshape(n, 1),
            values["show"].reshape(n, 1),
            values["click"].reshape(n, 1),
            values["emb_state"],
            values["w_state"]]
    return np.concatenate([np.asarray(c, np.float32) for c in cols], axis=1)


def split_values_host(fused: np.ndarray, dim: int, ke: int, kw: int
                      ) -> Dict[str, np.ndarray]:
    """Inverse of fuse_values_host."""
    return {
        "emb": fused[:, :dim].copy(),
        "w": fused[:, dim].copy(),
        "show": fused[:, dim + 1].copy(),
        "click": fused[:, dim + 2].copy(),
        "emb_state": fused[:, dim + 3:dim + 3 + ke].copy(),
        "w_state": fused[:, dim + 3 + ke:dim + 3 + ke + kw].copy(),
    }


def lay_fused_host(fused: np.ndarray, num_shards: int, rps: int
                   ) -> np.ndarray:
    """[n, W] sorted-rank rows → round-robin sharded [S*(rps+1), W] with a
    zeroed trash row per shard (role of BuildGPUTask filling HBM mem-pool
    records, ps_gpu_wrapper.cc:684): rank g lands in shard g % S at slot
    g // S, so every shard holds ~n/S rows for ANY n (module docstring)."""
    n, w = fused.shape
    out = np.zeros((num_shards, rps + 1, w), np.float32)
    for s in range(num_shards):
        part = fused[s::num_shards]
        out[s, :part.shape[0]] = part
    return out.reshape(num_shards * (rps + 1), w)


def unlay_fused_host(laid: np.ndarray, num_shards: int, rps: int,
                     num_keys: int) -> np.ndarray:
    """Inverse of lay_fused_host: strip trash rows, back to sorted-rank
    order."""
    a = laid.reshape(num_shards, rps + 1, laid.shape[-1])[:, :rps]
    out = np.empty((num_keys, laid.shape[-1]), laid.dtype)
    for s in range(num_shards):
        cnt = len(range(s, num_keys, num_shards))
        out[s::num_shards] = a[s, :cnt]
    return out


def build_pass_table_host(values: Dict[str, np.ndarray], num_shards: int,
                          config: TableConfig) -> PassTable:
    """Assemble a PassTable from host arrays produced by the FeatureStore.

    ``values`` carries per-key arrays in sorted-key order: emb [N, D],
    emb_state [N, Ke], w [N], w_state [N, Kw], show [N], click [N]. One
    fused host pack + ONE H2D transfer (vs six in the r02 layout).
    """
    dim, ke, kw = table_widths(config)
    n = values["emb"].shape[0]
    rps = plan_shards(n, num_shards)
    fused = fuse_values_host(values)
    return PassTable(
        vals=jnp.asarray(lay_fused_host(fused, num_shards, rps)),
        rows_per_shard=rps, num_shards=num_shards, dim=dim, ke=ke, kw=kw)


def extract_pass_values_host(table: PassTable, num_keys: int
                             ) -> Dict[str, np.ndarray]:
    """Inverse of build_pass_table_host: ONE D2H transfer, strip trash
    rows, return sorted-key order host arrays (role of EndPass dumping
    dirty HBM values back to the CPU table, ps_gpu_wrapper.cc:983).

    Under a multi-process cluster the table spans hosts; every process
    needs the full values (the host store is a per-rank replica), so the
    extraction is a process allgather there (role of the PS pull in the
    reference's write-back — values cross the host network exactly once
    per pass)."""
    if table.vals.is_fully_addressable:
        laid = np.asarray(table.vals)
    else:
        from jax.experimental import multihost_utils
        laid = np.asarray(
            multihost_utils.process_allgather(table.vals, tiled=True))
    fused = unlay_fused_host(laid, table.num_shards, table.rows_per_shard,
                             num_keys)
    return split_values_host(fused, table.dim, table.ke, table.kw)


def shared_key_mask(active_sorted: np.ndarray,
                    keys_sorted: np.ndarray) -> np.ndarray:
    """Boolean mask over ``keys_sorted``: True where the key is also in
    ``active_sorted`` (both sorted unique). The split pass build keys off
    this: the active pass's end_pass writes back ONLY its own keys, so
    the False positions can be pulled/gathered while it still trains."""
    if active_sorted.size == 0 or keys_sorted.size == 0:
        return np.zeros(keys_sorted.shape, bool)
    pos = np.minimum(np.searchsorted(active_sorted, keys_sorted),
                     active_sorted.size - 1)
    return active_sorted[pos] == keys_sorted


def map_keys_to_rows(pass_keys_sorted: np.ndarray, batch_keys: np.ndarray,
                     rows_per_shard: int, num_shards: int = 1,
                     index_offset: int = 0) -> np.ndarray:
    """Host-side: feasigns → device row ids in the ROUND-ROBIN sharded
    layout (rank g -> shard g % num_shards at slot g // num_shards —
    module docstring).

    Role of the key→slot flattening in CopyKeys + the per-pass perfect
    index (SURVEY.md §7 design note). Unknown keys and the 0 padding
    feasign map to trash rows, spread round-robin across ALL shards —
    padding concentrated on one shard would overflow its fixed-capacity
    all-to-all bucket and silently drop that shard's real lookups.

    ``index_offset``: global position of ``batch_keys[0]`` when the
    caller shards one big batch across lookup workers — the round-robin
    trash assignment depends on the GLOBAL position, so a chunked lookup
    must stay bit-identical to the unchunked one.
    """
    n = pass_keys_sorted.shape[0]
    m = batch_keys.shape[0]
    # Round-robin trash row per position: shard (i % S)'s trash row.
    pad_shard = (np.arange(m, dtype=np.int64)
                 + int(index_offset)) % num_shards
    sentinel = (pad_shard * (rows_per_shard + 1) + rows_per_shard
                ).astype(np.int32)
    if n == 0:
        return sentinel  # empty pass: everything hits a trash row
    g = np.searchsorted(pass_keys_sorted, batch_keys)
    g_c = np.minimum(g, n - 1)
    found = (pass_keys_sorted[g_c] == batch_keys) & (batch_keys != 0)
    shard = g_c % num_shards
    row = g_c // num_shards
    dev_row = shard * (rows_per_shard + 1) + row
    return np.where(found, dev_row, sentinel).astype(np.int32)
