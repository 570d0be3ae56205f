"""Pass-driven CTR trainer: the BoxPSTrainer/BoxPSWorker equivalent.

Role of the reference hot loop (``boxps_worker.cc:666-724`` TrainFiles):
per minibatch — pack batch (``BuildSlotBatchGPU``), pull sparse
(``PullSparse``), run fwd/bwd ops, push sparse grads (``PushSparseGrad``),
sync dense (``SyncParam``), collect AUC (``AddAucMonitor``) — plus the
``train_from_dataset`` pass loop around it.

TPU-first: the whole per-batch sequence is ONE jitted shard_map program —
pull (all slots fused into one all-to-all), model fwd/bwd, exact global
logloss, dense psum + optax update, sparse push with fused optimizer, and
AUC histogram accumulation — so XLA overlaps compute with the pull/push
collectives and there is no per-op dispatch. Device threads, streams, and
the NCCL ring of the reference collapse into the compiled program.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddlebox_tpu.core import (faults, flags, log, monitor, quality,
                                report, timers, trace, watchdog)
from paddlebox_tpu.data.dataset import Dataset
from paddlebox_tpu.data.slots import DataFeedConfig, SlotBatch
from paddlebox_tpu.embedding import TableConfig, make_sparse_optimizer
from paddlebox_tpu.embedding.grouped import GroupedEngine
from paddlebox_tpu.embedding.lookup import (compute_bucketing,
                                            kernel_fallback,
                                            kernel_hot_served, pull_local,
                                            push_local,
                                            record_exchange_stats)
from paddlebox_tpu.metrics import (AucState, auc_accumulate, auc_compute,
                                   auc_state_init)
from paddlebox_tpu.ops.data_norm import (data_norm_apply, data_norm_init,
                                         normalize_dense_and_strip)
from paddlebox_tpu.parallel.collective import (hierarchical_psum_tree,
                                               quantized_psum)
from paddlebox_tpu.parallel import zero as zero_lib


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    dense_learning_rate: float = 1e-3
    dense_optimizer: str = "adam"
    auc_num_buckets: int = 1 << 16
    check_nan_inf: bool = False
    # Dense gradient synchronization across the dp axis (role of the
    # BoxPSWorker dense-sync modes, boxps_worker.cc:584-645):
    #   "step"  — psum grads every step (default; c_allreduce_sum role)
    #   "kstep" — local-SGD: local update each step with the grad scaled
    #             by world size, params averaged (pmean) every
    #             dense_sync_interval steps (SyncParam's k-step
    #             ReduceScatter+SyncDense+AllGather role). Optimizer
    #             state stays worker-local between syncs, as in the
    #             reference. At k=1 with SGD this is exactly "step".
    #   "async" — the jitted step returns psum'd dense grads; a host
    #             AsyncDenseTable thread applies Adam and workers pull
    #             fresh params each step (BoxPSAsynDenseTable role).
    dense_sync_mode: str = "step"
    dense_sync_interval: int = 8
    # Forward/backward compute precision (role of paddle.amp / the AMP
    # meta-optimizer): "bfloat16" casts params + activations for the
    # model fwd/bwd so matmuls hit the MXU at native rate; master params,
    # optimizer state, loss, AUC, and the sparse push stay float32.
    compute_dtype: str = "float32"
    # DataNorm over the concatenated dense features (role of the
    # reference's data_norm op in CTR models, data_norm_op.cc): global
    # decayed statistics, synced across dp every step, threaded through
    # the step as state (f32 regardless of compute_dtype).
    data_norm: bool = False
    data_norm_slot_dim: int = -1
    data_norm_decay: float = 0.9999999
    # Scale sparse grads by the global batch size before the push (role
    # of scale_sparse_gradient_with_batch_size, trainer_desc.proto:64
    # default true, applied in fleet_wrapper.cc:294): the loss carries a
    # 1/global_batch factor, so without the scale each key's
    # per-occurrence gradient is O(1/batch) and the sparse optimizer
    # cannot move a key meaningfully within one pass; scaling restores
    # per-occurrence O(1) grads, which is the regime the sparse adagrad
    # defaults (initial_g2sum=3, lr=0.05, optimizer.cuh.h:31) are tuned
    # for.
    scale_sparse_grad_by_batch: bool = True
    # Global-norm clip on the dense gradients before the optimizer
    # (role of paddle.nn.ClipGradByGlobalNorm in fleet configs);
    # 0 disables. In "step" mode it is applied AFTER the cross-replica
    # psum — the clip sees the true global gradient, as the reference's
    # post-allreduce clip does. In "kstep" (local-SGD) mode the clip is
    # deliberately PER-REPLICA: between syncs each worker owns a local
    # trajectory (grads are the ndev-scaled local estimate, optimizer
    # state worker-local), so the clip bounds that local step; replicas
    # may make different clip decisions until the next param average —
    # accepted local-SGD semantics, not the "step"-mode global clip.
    grad_clip_norm: float = 0.0


class CTRTrainer:
    """Owns PassEngine + dense params + the fused train step.

    Usage (mirrors the BoxPS day/pass loop, SURVEY.md §3.1):

        trainer = CTRTrainer(model, feed_cfg, table_cfg, mesh=mesh)
        trainer.init(seed=0)
        for pass_files in day:
            dataset.set_filelist(pass_files); dataset.load_into_memory()
            stats = trainer.train_pass(dataset)
        trainer.engine.store.save_base(path)
    """

    def __init__(self, model, feed_config: DataFeedConfig,
                 table_config: TableConfig, *,
                 mesh: Optional[Mesh] = None, axis: str = "dp",
                 config: TrainerConfig = TrainerConfig(),
                 store=None, store_factory=None):
        self.model = model
        self.feed_config = feed_config
        self.config = config
        self.mesh = mesh
        self.axis = axis
        # Multi-slice (DCN) topology: the pass table is sharded over
        # `axis` INSIDE each slice and replicated across slices; the
        # batch splits over slice × axis. dcn_axis drives the
        # hierarchical dense sync and the sparse push's one DCN stage.
        self.dcn_axis = None
        if (mesh is not None and "slice" in mesh.axis_names
                and int(mesh.shape["slice"]) > 1):
            if axis == "slice":
                raise ValueError("table axis cannot be the DCN slice axis")
            self.dcn_axis = "slice"
        n_slices = (int(mesh.shape["slice"])
                    if self.dcn_axis is not None else 1)
        # ndev = REPLICA count (batch shards) = slice * table axis size;
        # the table itself has mesh.shape[axis] shards regardless.
        self.ndev = (int(mesh.shape[axis]) * n_slices
                     if mesh is not None else 1)
        if feed_config.batch_size % self.ndev:
            raise ValueError(
                f"batch_size {feed_config.batch_size} must be divisible by "
                f"the replica count {self.ndev} (slice x {axis})")
        # Per-slot mf widths (dynamic mf, role of CtrDymfAccessor): slots
        # declaring SlotConf.emb_dim get that width; the rest use the
        # table default. Slots are grouped by width — one PassEngine,
        # store, and fused pull/push per width group.
        slot_dims = {s.name: (s.emb_dim or table_config.dim)
                     for s in feed_config.sparse_slots}
        if self.num_tasks > 1 and feed_config.num_labels < self.num_tasks:
            raise ValueError(
                f"model has {self.num_tasks} tasks but the feed parses "
                f"only {feed_config.num_labels} label columns")
        # store: optional FeatureStore-shaped backing tier instance — a
        # TieredFeatureStore (RAM+SSD) or a distributed.ps.PSBackedStore
        # (remote CPU PS, the BuildPull flow). Single-width models only;
        # multi-width models pass store_factory(cfg) -> store instead.
        if store is not None:
            if store_factory is not None:
                raise ValueError("pass store or store_factory, not both")
            if len(set(slot_dims.values())) > 1:
                raise ValueError(
                    "a single store instance cannot back multiple widths "
                    "— pass store_factory instead")
            store_factory = lambda cfg: store  # noqa: E731
        self.table_config = table_config
        self.engine = GroupedEngine(table_config, slot_dims, mesh=mesh,
                                    table_axis=axis,
                                    store_factory=store_factory)
        self.sparse_opt = make_sparse_optimizer(table_config)
        self.params: Any = None
        self.opt_state: Any = None
        self.auc_state: Optional[AucState] = None
        self._async_dense = None
        self._sync_params_cache = None
        self._eval_fn = None
        self.timers = timers.TimerGroup()
        # Per-pass prefetch segment-cache observability (reset per pass;
        # surfaced as seg_cache_hit_rate in the pass report).
        self._seg_cache_hits = 0
        self._seg_cache_misses = 0
        self._step_fn = None
        # K-step scanned megastep (FLAGS_trainer_steps_per_dispatch > 1):
        # the compiled fn and the K it was built at — invalidated together
        # with _step_fn whenever the measured bucket caps change.
        self._mega_fn = None
        self._mega_k = 0
        self._eval_k = 0
        # Pass-loop observability (reset per pass, surfaced in stats):
        # dispatches = compiled-program launches; host_syncs = blocking
        # device fetches INSIDE the loop (the check_nan_inf finite-vector
        # reads — pass-end stat reductions are O(1) and not counted).
        self._dispatch_blocks = 0
        self._host_syncs = 0
        # Test hook: when True the pass loop retains per-step loss device
        # arrays (K=1: scalars, K>1: [K] blocks) in _debug_losses so
        # parity tests can compare per-step losses bitwise. Off by
        # default — retaining O(steps) arrays is exactly what the
        # running-sum path exists to avoid.
        self._debug_collect_losses = False
        self._debug_losses: List[Tuple[int, jax.Array, int]] = []
        # Measured bucket-capacity overrides the current _step_fn was
        # traced with (None = default n-based capacity).
        self._step_caps: Optional[Tuple[Optional[int], ...]] = None
        self._slot_names = [s.name for s in feed_config.sparse_slots]
        # Sharded capacities: always divisible by ndev (matches
        # SlotBatch.pack_sharded / Dataset.batches_sharded shapes).
        self._slot_caps = {
            s.name: feed_config.sparse_capacity(s, num_shards=self.ndev)
            for s in feed_config.sparse_slots}
        if self.config.dense_optimizer == "adam":
            self._optax = optax.adam(self.config.dense_learning_rate)
        elif self.config.dense_optimizer == "sgd":
            self._optax = optax.sgd(self.config.dense_learning_rate)
        else:
            raise ValueError(self.config.dense_optimizer)
        # The ZeRO-sharded step decomposes the chain by hand: the clip
        # must see the FULL gradient tree (its global norm spans every
        # leaf), then the elementwise inner optimizer runs on the local
        # shards — so keep the parts addressable next to the chain.
        self._optax_base = self._optax
        self._clip_tx = None
        if self.config.grad_clip_norm > 0:
            if self.config.dense_sync_mode == "async":
                # The async path applies updates in the host
                # AsyncDenseTable, not through self._optax — chaining
                # the clip there would be silently ignored.
                raise NotImplementedError(
                    "grad_clip_norm with dense_sync_mode='async' is not "
                    "supported (the host dense table applies updates)")
            self._clip_tx = optax.clip_by_global_norm(
                self.config.grad_clip_norm)
            self._optax = optax.chain(self._clip_tx, self._optax_base)
        # FLAGS_dense_zero placement, resolved at init() (the mesh and
        # sync mode decide whether 'shard' is meaningful); the offload
        # wrapper is built lazily.
        self._dense_zero = "off"
        self._offload_tx: Optional[zero_lib.OffloadedOptimizer] = None
        self._zero_warned = False

    # -- init -------------------------------------------------------------

    @property
    def num_tasks(self) -> int:
        """Multi-task models (SharedBottomMultiTask) advertise num_tasks
        and return [B, T] logits; the trainer then trains per-task BCE
        over labels[:, :T] with a stacked per-task AUC state (role of
        the MultiTaskMetricMsg AUC family, fleet/metrics.h:346)."""
        return int(getattr(self.model, "num_tasks", 1))

    def _auc_init(self):
        nb = self.config.auc_num_buckets
        if self.num_tasks == 1:
            return auc_state_init(nb)
        return jax.vmap(lambda _: auc_state_init(nb))(
            jnp.arange(self.num_tasks))

    def _make_loss_auc(self, axis):
        """One implementation of the masked (multi-)task BCE and the
        (stacked) AUC accumulation, shared by the train and eval steps —
        the two must never drift."""
        num_tasks = self.num_tasks

        def squeeze1(t):
            # A multi-task ARCHITECTURE configured with num_tasks=1
            # still emits [B, 1]; without the squeeze the single-task
            # BCE would broadcast [B, 1] against [B] into a [B, B]
            # matrix — finite loss, silently garbage training.
            return t[:, 0] if t.ndim == 2 else t

        def loss_of(logits, labels, validf):
            # Local masked sum over the GLOBAL valid count; callers psum
            # the result to finish the cross-replica mean.
            total_valid = lax.psum(jnp.sum(validf), axis)
            if num_tasks > 1:   # [B, T]: mean over tasks
                bce = optax.sigmoid_binary_cross_entropy(
                    logits, labels[:, :num_tasks])
                return (jnp.sum(bce * validf[:, None])
                        / jnp.maximum(total_valid * num_tasks, 1.0))
            bce = optax.sigmoid_binary_cross_entropy(squeeze1(logits),
                                                     labels[:, 0])
            return jnp.sum(bce * validf) / jnp.maximum(total_valid, 1.0)

        def auc_of(auc, probs, labels, valid):
            if num_tasks > 1:
                return jax.vmap(
                    lambda st, p, l: auc_accumulate(st, p, l, valid,
                                                    axis=axis),
                    in_axes=(0, 1, 1))(auc, probs, labels[:, :num_tasks])
            return auc_accumulate(auc, squeeze1(probs), labels[:, 0],
                                  valid, axis=axis)

        return loss_of, auc_of

    def _auc_stats(self, auc) -> Dict[str, float]:
        if self.num_tasks == 1:
            return auc_compute(auc)
        per_task = [auc_compute(jax.tree.map(lambda x: x[t], auc))
                    for t in range(self.num_tasks)]
        stats = dict(per_task[0])  # task 0 (click) is the headline
        for t, st in enumerate(per_task):
            for k, v in st.items():
                stats[f"{k}_task{t}"] = v
        return stats

    def init(self, seed: int = 0) -> None:
        rng = jax.random.PRNGKey(seed)
        self.params = self.model.init(rng)
        if self.config.data_norm:
            dense_dim = sum(s.dim for s in self.feed_config.dense_slots)
            if not dense_dim:
                raise ValueError("data_norm=True but the feed declares "
                                 "no dense slots")
            # Lives in the params tree (checkpointed with the dense
            # model) but is updated by the decayed summary path, not the
            # optimizer — _build_step overwrites it after the update.
            self.params["data_norm"] = data_norm_init(dense_dim)
        self._init_dense()
        self.auc_state = self._auc_init()
        if self.mesh is not None:
            rep = NamedSharding(self.mesh, P())
            self.auc_state = jax.device_put(self.auc_state, rep)

    # -- dense placement (FLAGS_dense_zero) -------------------------------

    def _dense_zero_mode(self) -> str:
        """Resolve FLAGS_dense_zero against the mesh and sync mode.

        'shard' + 'kstep' degrades to 'off' with one warning: ZeRO
        removes REDUNDANCY, and k-step optimizer state is worker-local
        (intentionally divergent between syncs) — there is no replicated
        copy to shard away, and an all-gather would mix per-device
        trajectories. 'offload' requires the in-step grads of
        dense_sync_mode='step' ('async' already has its own host
        updater; 'kstep' state must stay device-local per step)."""
        z = str(flags.flag("dense_zero"))
        if z not in ("off", "shard", "offload"):
            raise ValueError(
                f"dense_zero must be off|shard|offload, got {z!r}")
        if z == "off" or self.mesh is None:
            return "off"
        if z == "offload" and self.config.dense_sync_mode != "step":
            raise ValueError(
                "dense_zero='offload' requires dense_sync_mode='step' "
                f"(got {self.config.dense_sync_mode!r})")
        if z == "shard" and self.config.dense_sync_mode == "kstep":
            if not self._zero_warned:
                self._zero_warned = True
                log.warning(
                    "dense_zero='shard' ignored under "
                    "dense_sync_mode='kstep': k-step optimizer state is "
                    "worker-local (no replicated copy to shard) — "
                    "running with replicated placement")
            return "off"
        return z

    def _init_dense(self) -> None:
        """Init + place the dense params/optimizer state. Params stay
        replicated (ZeRO-1/2, not ZeRO-3 — the CTR dense half is MBs,
        the state is the redundancy worth removing); opt_state placement
        follows FLAGS_dense_zero. Checkpoints stay layout-agnostic: the
        GLOBAL shapes are identical under every mode (sharding is
        placement, not format), so save gathers to the host format and
        :meth:`place_dense` re-shards on load."""
        self._dense_zero = self._dense_zero_mode()
        if self.mesh is None:
            self.opt_state = self._optax.init(self.params)
            return
        rep = NamedSharding(self.mesh, P())
        self.params = jax.device_put(self.params, rep)
        if self._dense_zero == "offload":
            self._offload_tx = zero_lib.OffloadedOptimizer(
                self._optax, self.mesh, axis=self.axis,
                min_size=int(flags.flag("dense_zero_min_size")))
            self.opt_state = self._offload_tx.init(self.params)
        else:
            self.opt_state = self._optax.init(self.params)
            self.opt_state = jax.tree.map(
                jax.device_put, self.opt_state,
                self._opt_shardings(self.opt_state))
        self.dense_memory_stats()

    def _opt_shardings(self, state: Any):
        """Per-leaf NamedShardings of the NON-offload opt_state
        placement: replicated under 'off', zero_shardings over the table
        axis under 'shard' (replicated across slices on a multi-slice
        mesh — the hierarchical psum keeps slice replicas bit-equal, so
        only intra-slice redundancy is worth removing)."""
        if self._dense_zero == "shard":
            return zero_lib.zero_shardings(
                state, self.mesh, axis=self.axis,
                min_size=int(flags.flag("dense_zero_min_size")))
        rep = NamedSharding(self.mesh, P())
        return jax.tree.map(lambda _: rep, state)

    def place_dense(self, params: Any, opt_state: Any) -> Tuple[Any, Any]:
        """device_put HOST-format dense state into this trainer's live
        placement — the checkpoint-load half of layout agnosticism
        (save is plain device_get: global shapes are mode-invariant)."""
        if self.mesh is None:
            return params, opt_state
        rep = NamedSharding(self.mesh, P())
        params = jax.device_put(params, rep)
        if self._dense_zero == "offload":
            assert self._offload_tx is not None
            opt_state = jax.tree.map(
                jax.device_put, opt_state,
                self._offload_tx._state_shardings(opt_state))
        else:
            opt_state = jax.tree.map(jax.device_put, opt_state,
                                     self._opt_shardings(opt_state))
        return params, opt_state

    def dense_memory_stats(self) -> Dict[str, Any]:
        """Measured per-device HBM bytes of the dense half (live array
        shardings, not flag arithmetic) + placement provenance; also
        lands the dense/*_hbm_bytes gauges the benches record."""
        pb = zero_lib.tree_hbm_bytes_per_device(self.params)
        ob = zero_lib.tree_hbm_bytes_per_device(self.opt_state)
        monitor.set_gauge("dense/params_hbm_bytes", pb)
        monitor.set_gauge("dense/opt_state_hbm_bytes", ob)
        return {"params_hbm_bytes": pb, "opt_state_hbm_bytes": ob,
                "dense_zero": self._dense_zero}

    # -- the fused step ----------------------------------------------------

    def _group_layout(self) -> Tuple[List[Tuple[str, ...]],
                                     List[Dict[str, slice]]]:
        """Width groups (dynamic mf): group g's slots share one PassTable
        and one fused pull/push; slot slices index into the group's fused
        arrays."""
        caps_local = {n: self._slot_caps[n] // self.ndev
                      for n in self._slot_names}
        group_slots: List[Tuple[str, ...]] = [
            g.slots for g in self.engine.groups]
        group_sl: List[Dict[str, slice]] = []
        for slots in group_slots:
            offs = np.cumsum([0] + [caps_local[n] for n in slots])
            group_sl.append({n: slice(int(offs[i]), int(offs[i + 1]))
                             for i, n in enumerate(slots)})
        return group_slots, group_sl

    def _make_forward(self, group_slots, group_sl):
        """Shared train/eval forward: slice each width group's fused pull
        into per-slot arrays and call the model. ``emb_alls``/``w_alls``
        override the pulled emb/w so the train step can differentiate
        with respect to them."""
        model = self.model
        bs_local = self.feed_config.batch_size // self.ndev
        has_dense = bool(self.feed_config.dense_slots)
        cdt = dict(float32=jnp.float32,
                   bfloat16=jnp.bfloat16)[self.config.compute_dtype]

        def cast(tree):
            if cdt == jnp.float32:
                return tree
            return jax.tree.map(
                lambda x: x.astype(cdt)
                if x.dtype == jnp.float32 else x, tree)

        dn_slot_dim = self.config.data_norm_slot_dim

        def forward(params, pulled, segments, dense_feats,
                    emb_alls=None, w_alls=None):
            # Normalize dense features by the global stats BEFORE the
            # bf16 cast (the ~1e4-scale accumulators must stay f32);
            # the stats update happens in the train body, not here.
            params, dense_feats = normalize_dense_and_strip(
                params, dense_feats, slot_dim=dn_slot_dim)
            params = cast(params)
            dense_feats = cast(dense_feats)
            if emb_alls is not None:
                emb_alls, w_alls = cast(emb_alls), cast(w_alls)
            emb: Dict[str, jax.Array] = {}
            w: Dict[str, jax.Array] = {}
            for gi, slots in enumerate(group_slots):
                src_e = (emb_alls[gi] if emb_alls is not None
                         else cast(pulled[gi]["emb"]))
                src_w = (w_alls[gi] if w_alls is not None
                         else cast(pulled[gi]["w"]))
                for n in slots:
                    emb[n] = src_e[group_sl[gi][n]]
                    w[n] = src_w[group_sl[gi][n]]
            kwargs = dict(batch_size=bs_local,
                          dense_feats=dense_feats if has_dense else None)
            if hasattr(model, "use_cvm"):  # Wide&Deep takes show/click
                show = {n: cast(pulled[gi]["show"])[group_sl[gi][n]]
                        for gi, slots in enumerate(group_slots)
                        for n in slots}
                click = {n: cast(pulled[gi]["click"])[group_sl[gi][n]]
                         for gi, slots in enumerate(group_slots)
                         for n in slots}
                logits = model.apply(params, emb, w, show, click,
                                     segments, **kwargs)
            else:
                logits = model.apply(params, emb, w, segments, **kwargs)
            return logits.astype(jnp.float32)

        return forward

    def _build_step(self, caps: Optional[Tuple[Optional[int], ...]] = None,
                    k_steps: int = 1):
        """The fused device step. ``k_steps == 1`` (default) builds the
        per-step program with its legacy signature; ``k_steps > 1``
        wraps the SAME per-step body in a ``lax.scan`` over a stacked
        [K, ...] batch block — one XLA dispatch runs K steps, with the
        kstep sync_flag derived from an in-scan global step counter and
        loss/overflow/finite-ness accumulated on device into [K]
        outputs (one host fetch per block, not per step). A partial
        tail block is handled by ``n_active``: steps with in-block
        index >= n_active compute on the padded (repeated) batch but
        their state updates are masked out, so padding never reaches
        the tables/params/AUC."""
        axis = self.axis
        dcn = self.dcn_axis
        # Per-width-group bucket-capacity overrides (measured
        # auto-capacity, FLAGS_embedding_auto_capacity): trace-time
        # constants, so a cap change means a rebuild — train_pass
        # pow2-buckets the measurement to keep steady-state passes on
        # the same compiled step.
        caps_list = (list(caps) if caps is not None
                     else [None] * len(self.engine.groups))
        # Replica-wide reductions (loss, AUC, stats) span slice x axis;
        # table collectives (all_to_all in pull/push) stay on `axis`
        # (intra-slice ICI) with the one accumulator psum over `dcn`.
        raxes = (dcn, axis) if dcn else axis
        ndev = self.ndev
        bs_local = self.feed_config.batch_size // ndev
        optimizer = self._optax
        sparse_opt = self.sparse_opt
        group_slots, group_sl = self._group_layout()
        forward = self._make_forward(group_slots, group_sl)

        mode = self.config.dense_sync_mode
        if mode not in ("step", "kstep", "async"):
            raise ValueError(f"unknown dense_sync_mode {mode!r}")
        # FLAGS_dense_zero (resolved at init): 'shard' decomposes the
        # in-step dense update — clip on the FULL psum'd grad tree (its
        # global norm spans every leaf), elementwise inner optimizer on
        # this device's zero_slice shard (bit-identical per element),
        # tiled all-gather of the updated param shards (the psum+slice/
        # all-gather pair is exactly the reduce-scatter/all-gather
        # schedule of the weight-update-sharding paper, compiler-
        # scheduled). 'offload' makes the dense update EXTERNAL like
        # async: the step returns psum'd grads and train_pass routes
        # them through OffloadedOptimizer.
        zmode = self._dense_zero
        zmin = int(flags.flag("dense_zero_min_size"))
        z_shard = zmode == "shard" and mode == "step"
        external_dense = mode == "async" or zmode == "offload"
        if z_shard:
            pz_specs = zero_lib.zero_specs(self.params, self.mesh,
                                           axis=axis, min_size=zmin)
            z_nsh = int(self.mesh.shape[axis])
        if zmode == "shard":
            opt_spec = zero_lib.zero_specs(self.opt_state, self.mesh,
                                           axis=axis, min_size=zmin)
        else:
            opt_spec = P()
        clip_tx = self._clip_tx
        base_tx = self._optax_base
        scale_sparse = self.config.scale_sparse_grad_by_batch
        sparse_scale = float(self.feed_config.batch_size)
        loss_of, auc_of = self._make_loss_auc(raxes)
        # Dense-grad wire dtype (FLAGS_dense_allreduce_dtype): trace-time
        # constant — 'f32' keeps the sync a verbatim lax.psum /
        # hierarchical tree (bit-parity pinned); 'bf16'/'int8' narrow
        # the allreduce wire with f32 accumulation (quantized_psum).
        dense_wire = str(flags.flag("dense_allreduce_dtype"))
        if dense_wire not in ("f32", "bf16", "int8"):
            raise ValueError(
                f"dense_allreduce_dtype must be f32|bf16|int8, "
                f"got {dense_wire!r}")
        dense_qblock = int(flags.flag("embedding_quant_block"))
        monitor.set_gauge("dense/allreduce_wire_bits",
                          {"f32": 32, "bf16": 16, "int8": 8}[dense_wire])
        dn_on = self.config.data_norm
        if dn_on and mode == "async":
            # The reference routes data_norm stats through the async
            # dense table with update_norm=False (data_norm_op.cu:253);
            # this build updates them in-step, which the async host
            # table would overwrite.
            raise NotImplementedError(
                "data_norm with dense_sync_mode='async' is not supported")
        dn_slot_dim = self.config.data_norm_slot_dim
        dn_decay = self.config.data_norm_decay

        def body(tables, params, opt_state, auc, rows, segments, labels,
                 valid, dense_feats, sync_flag):
            dn_old = params.get("data_norm") if dn_on else None
            # rows[g]: [sum caps_local over group g's slots] — each width
            # group's slots fused into ONE pull (one all_to_all pair per
            # group; G = #distinct widths, typically 1-3). The
            # bucket-by-shard layout is computed ONCE per group and
            # shared by the pull and the push below (both bucket the
            # same dev_rows — CopyKeys computed once in the reference
            # too). Passing axis shares the rows exchange and the
            # sorted-stream kernels' argsort between pull and push, so
            # the step pays 3 collectives + 1 sort per group, not 4 + 2.
            bucketings = [compute_bucketing(t, r, cap=c, axis=axis)
                          for t, r, c in zip(tables, rows, caps_list)]
            # The bucketing tuples carry their capacity — pull/push mask
            # with the capacity the buckets were built at.
            pulled = [pull_local(t, r, axis=axis, bucketing=bk)
                      for t, r, bk in zip(tables, rows, bucketings)]

            labels1 = labels[:, 0]
            validf = valid.astype(jnp.float32)

            def loss_fn(params, emb_alls, w_alls):
                logits = forward(params, pulled, segments, dense_feats,
                                 emb_alls=emb_alls, w_alls=w_alls)
                # Exact global logloss: local sum / global valid count.
                return loss_of(logits, labels, validf), logits

            grad_fn = jax.value_and_grad(loss_fn, argnums=(0, 1, 2),
                                         has_aux=True)
            (loss, logits), (g_params, g_embs, g_ws) = grad_fn(
                params, tuple(p["emb"] for p in pulled),
                tuple(p["w"] for p in pulled))

            # Dense sync (see TrainerConfig.dense_sync_mode).
            if external_dense:
                # async / offload: the host applies the update — the
                # step's job is the exact cross-replica grad sum.
                g_params = quantized_psum(g_params, raxes,
                                          wire_dtype=dense_wire,
                                          block=dense_qblock)
            elif mode == "step":
                # Grads already carry the global 1/N via the global
                # denominator — the sum over replicas completes the
                # reduction (role of SyncParam / c_allreduce_sum). On a
                # multi-slice mesh the sum is hierarchical: reduce-
                # scatter on ICI, psum the 1/dp shard over DCN,
                # all-gather back (SyncParam's exact shape,
                # boxps_worker.cc:584-645).
                if dcn:
                    # Only the slow DCN hop narrows under a reduced
                    # dense wire; the ICI hops stay f32.
                    g_params = hierarchical_psum_tree(
                        g_params, inner_axis=axis, outer_axis=dcn,
                        outer_wire_dtype=dense_wire,
                        quant_block=dense_qblock)
                else:
                    g_params = quantized_psum(g_params, axis,
                                              wire_dtype=dense_wire,
                                              block=dense_qblock)
                if z_shard:
                    if clip_tx is not None:
                        clip_state, inner_state = opt_state
                        g_params, clip_state = clip_tx.update(
                            g_params, clip_state, params)
                    else:
                        inner_state = opt_state
                    g_sl = zero_lib.zero_slice(g_params, pz_specs, axis,
                                               z_nsh)
                    p_sl = zero_lib.zero_slice(params, pz_specs, axis,
                                               z_nsh)
                    updates, inner_state = base_tx.update(g_sl,
                                                          inner_state,
                                                          p_sl)
                    p_new = optax.apply_updates(p_sl, updates)
                    params = zero_lib.zero_all_gather(p_new, pz_specs,
                                                      axis)
                    opt_state = ((clip_state, inner_state)
                                 if clip_tx is not None else inner_state)
                else:
                    updates, opt_state = optimizer.update(
                        g_params, opt_state, params)
                    params = optax.apply_updates(params, updates)
            elif mode == "kstep":
                # Local step with the unbiased full-grad estimate
                # (local grad x world size, since the loss denominator is
                # global); params pmean'd when sync_flag fires.
                g_local = jax.tree.map(lambda g: g * float(ndev), g_params)
                updates, opt_state = optimizer.update(g_local, opt_state,
                                                      params)
                params = optax.apply_updates(params, updates)
                params = lax.cond(
                    sync_flag > 0,
                    lambda p: jax.tree.map(
                        lambda x: lax.pmean(x, raxes), p),
                    lambda p: p, params)
            if dn_on:
                # Decayed summary update from the SAME stats the forward
                # normalized with (the optimizer saw zero grads for them
                # — stop_gradient — so post-update stats are unchanged);
                # psum over dp = the sync_stats allreduce.
                _, dn_new = data_norm_apply(
                    dn_old, dense_feats.astype(jnp.float32),
                    slot_dim=dn_slot_dim, summary_decay_rate=dn_decay,
                    axis_name=raxes)
                params = {**params, "data_norm": {
                    **params["data_norm"],
                    **{k: dn_new[k] for k in (
                        "batch_size", "batch_sum", "batch_square_sum")}}}

            # Sparse push per group: show=1 per occurrence, click=its
            # row's label (role of show/click stats in PushSparseGrad).
            if scale_sparse:
                g_embs = tuple(g * sparse_scale for g in g_embs)
                g_ws = tuple(g * sparse_scale for g in g_ws)
            new_tables = []
            for gi, slots in enumerate(group_slots):
                seg_g = jnp.concatenate([segments[n] for n in slots])
                occ_valid = (seg_g < bs_local).astype(jnp.float32)
                clicks = jnp.where(
                    seg_g < bs_local,
                    labels1[jnp.minimum(seg_g, bs_local - 1)],
                    0.0) * occ_valid
                new_tables.append(push_local(
                    tables[gi], rows[gi], g_embs[gi], g_ws[gi], occ_valid,
                    clicks, axis=axis, opt=sparse_opt, dcn_axis=dcn,
                    bucketing=bucketings[gi]))

            probs = jax.nn.sigmoid(logits)
            auc = auc_of(auc, probs, labels, valid)
            loss_global = lax.psum(loss, raxes)
            # Sparse-path observability, one [3] device vector so the
            # pass fetches all with one sync: bucket-overflow ids that
            # degraded to zero-embedding pulls and dropped grads this
            # step, width groups whose pull gather gave way to XLA at run
            # time (a block asked for more distinct rows than the kernel
            # budget), and width groups in which the sorted-stream
            # kernels served a hot row's over-budget run themselves —
            # each summed over devices and width groups.
            overflow_global = lax.psum(jnp.stack([
                sum(p["overflow"][0] for p in pulled),
                sum(kernel_fallback(bk) for bk in bucketings),
                sum(kernel_hot_served(bk) for bk in bucketings)]), raxes)
            out = (tuple(new_tables), params, opt_state, auc, loss_global,
                   overflow_global)
            if external_dense:
                out = out + (g_params,)
            return out

        if self.mesh is None:
            raise RuntimeError("CTRTrainer requires a mesh (1-device is a "
                               "1-axis mesh)")
        # P(axis) on the tables/rows tuples is a pytree PREFIX spec:
        # every leaf of every group shards its leading dim over axis
        # (replicated across slices on a multi-slice mesh — the push
        # keeps the replicas bit-equal). Batch args shard over the
        # full replica set (slice-major matches pack_sharded order).
        dspec = P((dcn, axis)) if dcn else P(axis)
        if k_steps == 1:
            out_specs = (P(axis), P(), opt_spec, P(), P(), P())
            if external_dense:
                out_specs = out_specs + (P(),)
            body_sm = jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(P(axis), P(), opt_spec, P(), dspec, dspec,
                          dspec, dspec, dspec, P()),
                out_specs=out_specs,
                check_vma=False)
            return jax.jit(body_sm, donate_argnums=(0, 1, 2, 3))

        # K-step megastep: scan the per-step body over the stacked block
        # INSIDE shard_map (collectives run per scan iteration exactly as
        # in the K=1 program — the per-step op budget is unchanged ×K).
        if k_steps < 1:
            raise ValueError(f"k_steps must be >= 1, got {k_steps}")
        if external_dense:
            # The host updater (async dense table / offload optimizer)
            # needs a pull/push around EVERY step; train_pass forces
            # K=1 for these modes before building.
            raise ValueError("steps_per_dispatch > 1 requires a device-"
                             "side dense update ('step'/'kstep'), not "
                             "'async' or dense_zero='offload'")
        k_sync = max(1, self.config.dense_sync_interval)

        def mega(tables, params, opt_state, auc, step0, n_active, rows,
                 segments, labels, valid, dense_feats):
            def scan_step(carry, xs):
                tables_c, params_c, opt_c, auc_c = carry
                ki, rows_k, segs_k, labels_k, valid_k, dense_k = xs
                # Per-step sync_flag from the in-scan step counter: the
                # SAME (global_step + 1) % interval the host computes on
                # the K=1 path — a dense-sync boundary may fall anywhere
                # inside a block.
                if mode == "kstep":
                    sync_flag = (((step0 + ki + 1) % k_sync) == 0
                                 ).astype(jnp.int32)
                else:
                    sync_flag = jnp.zeros((), jnp.int32)
                out = body(tables_c, params_c, opt_c, auc_c, rows_k,
                           segs_k, labels_k, valid_k, dense_k, sync_flag)
                new_tables, new_params, new_opt, new_auc = out[:4]
                loss, overflow = out[4], out[5]
                # Tail-block mask: padded steps (repeat of the last real
                # batch) run the math but write NOTHING — carry passes
                # through untouched, and their loss/overflow report as
                # zero / finite so the per-block outputs stay clean.
                active = ki < n_active
                carry = (_tree_select(active, new_tables, tables_c),
                         _tree_select(active, new_params, params_c),
                         _tree_select(active, new_opt, opt_c),
                         _tree_select(active, new_auc, auc_c))
                return carry, (jnp.where(active, loss, 0.0),
                               jnp.where(active, overflow,
                                         jnp.zeros_like(overflow)),
                               jnp.where(active, jnp.isfinite(loss), True))

            ks = jnp.arange(k_steps, dtype=jnp.int32)
            (tables, params, opt_state, auc), outs = lax.scan(
                scan_step, (tables, params, opt_state, auc),
                (ks, rows, segments, labels, valid, dense_feats))
            losses, overflows, finites = outs
            return tables, params, opt_state, auc, losses, overflows, finites

        sdspec = P(None, (dcn, axis)) if dcn else P(None, axis)
        mega_sm = jax.shard_map(
            mega, mesh=self.mesh,
            in_specs=(P(axis), P(), opt_spec, P(), P(), P(), sdspec,
                      sdspec, sdspec, sdspec, sdspec),
            out_specs=(P(axis), P(), opt_spec, P(), P(), P(), P()),
            check_vma=False)
        return jax.jit(mega_sm, donate_argnums=(0, 1, 2, 3))

    def _build_eval_step(self, k_steps: int = 1):
        """Read-only twin of the train step: pull + forward + AUC, no
        pushes, no param updates (role of the AUC-runner test mode,
        box_wrapper.h:900-989 / SetTestMode). ``k_steps > 1`` scans the
        same body over a stacked [K, ...] block (one dispatch per K
        eval steps), with the tail mask of the train megastep."""
        axis = self.axis
        dcn = self.dcn_axis
        raxes = (dcn, axis) if dcn else axis
        group_slots, group_sl = self._group_layout()
        forward = self._make_forward(group_slots, group_sl)
        loss_of, auc_of = self._make_loss_auc(raxes)

        def body(tables, params, auc, rows, segments, labels, valid,
                 dense_feats):
            pulled = [pull_local(t, r, axis=axis)
                      for t, r in zip(tables, rows)]
            logits = forward(params, pulled, segments, dense_feats)
            validf = valid.astype(jnp.float32)
            loss = lax.psum(loss_of(logits, labels, validf), raxes)
            auc = auc_of(auc, jax.nn.sigmoid(logits), labels, valid)
            return auc, loss

        dspec = P((dcn, axis)) if dcn else P(axis)
        if k_steps == 1:
            body_sm = jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(P(self.axis), P(), P(), dspec, dspec,
                          dspec, dspec, dspec),
                out_specs=(P(), P()),
                check_vma=False)
            return jax.jit(body_sm, donate_argnums=(2,))

        def mega(tables, params, auc, n_active, rows, segments, labels,
                 valid, dense_feats):
            def scan_step(auc_c, xs):
                ki, rows_k, segs_k, labels_k, valid_k, dense_k = xs
                new_auc, loss = body(tables, params, auc_c, rows_k,
                                     segs_k, labels_k, valid_k, dense_k)
                active = ki < n_active
                return (_tree_select(active, new_auc, auc_c),
                        jnp.where(active, loss, 0.0))

            ks = jnp.arange(k_steps, dtype=jnp.int32)
            auc, losses = lax.scan(
                scan_step, auc,
                (ks, rows, segments, labels, valid, dense_feats))
            return auc, losses

        sdspec = P(None, (dcn, axis)) if dcn else P(None, axis)
        mega_sm = jax.shard_map(
            mega, mesh=self.mesh,
            in_specs=(P(self.axis), P(), P(), P(), sdspec, sdspec,
                      sdspec, sdspec, sdspec),
            out_specs=(P(), P()),
            check_vma=False)
        return jax.jit(mega_sm, donate_argnums=(2,))

    def eval_pass(self, dataset: Dataset, *, feed_keys: bool = True
                  ) -> Dict[str, float]:
        """Evaluate one pass: AUC/loss only — the store is left exactly
        as-is (no write-back, no new keys persisted, nothing dirtied)."""
        if self.params is None:
            raise RuntimeError("call init() first")
        report.init_telemetry_from_flags()
        faults.init_from_flags()
        pass_t0 = time.perf_counter()
        stage_base = self.timers.snapshot_ms()
        boundary_base = self.engine.boundary_ms()
        disp_q_base = monitor.GLOBAL.quantile_digest("trainer/dispatch_ms")
        self._seg_cache_hits = 0
        self._seg_cache_misses = 0
        n_blocks = 0
        k_disp = max(1, int(flags.flag("trainer_steps_per_dispatch")))
        if self._eval_fn is None or self._eval_k != k_disp:
            self._eval_fn = self._build_eval_step(k_steps=k_disp)
            self._eval_k = k_disp
        eng = self.engine
        if feed_keys:
            eng.feed_pass([dataset.pass_keys(slots=g.slots)
                           for g in eng.groups], readonly=True)
        with trace.span("pass/begin_pass"):
            tables = eng.begin_pass()
        auc = self._auc_init()
        rep = (NamedSharding(self.mesh, P())
               if self.mesh is not None else None)
        if self.mesh is not None:
            auc = jax.device_put(auc, rep)
        # Running device-side loss sum: no O(steps) retained arrays and
        # no per-step host sync — one fetch at pass end.
        loss_sum = None
        nact_full = (_put_global(np.int32(k_disp), rep)
                     if k_disp > 1 else None)
        nsteps = 0
        try:
            for args in self._prefetch_batches(dataset, k=k_disp):
                t_disp0 = time.perf_counter()
                with self.timers.scope("dispatch"), \
                        trace.span("pass/dispatch", kind="eval",
                                   block=n_blocks, k=k_disp):
                    if k_disp == 1:
                        rows, segs, labels, valid, dense = args
                        auc, loss = self._eval_fn(tables, self.params,
                                                  auc, rows, segs, labels,
                                                  valid, dense)
                        n_active = 1
                    else:
                        rows, segs, labels, valid, dense, n_active = args
                        nact = (nact_full if n_active == k_disp
                                else _put_global(np.int32(n_active), rep))
                        auc, losses = self._eval_fn(tables, self.params,
                                                    auc, nact, rows, segs,
                                                    labels, valid, dense)
                        loss = jnp.sum(losses)
                n_blocks += 1
                watchdog.beat()
                disp_ms = (time.perf_counter() - t_disp0) * 1e3
                monitor.observe("trainer/dispatch_ms", disp_ms)
                monitor.observe_quantile("trainer/dispatch_ms", disp_ms)
                loss_sum = loss if loss_sum is None else loss_sum + loss
                nsteps += n_active
        finally:
            eng.abort_pass()
        with self.timers.scope("sync"), \
                trace.span("pass/final_fetch"):
            stats = self._auc_stats(auc)
            # graftlint: allow-sync(pass-end stat fetch inside the sync scope)
            stats["loss"] = (float(loss_sum) / nsteps if nsteps
                             else float("nan"))
        stats["steps"] = nsteps
        stats["dispatch_blocks"] = n_blocks
        stats["steps_per_dispatch"] = k_disp
        stats["seg_cache_hit_rate"] = self._seg_cache_rate()
        stats["boundary"] = self._boundary_delta(boundary_base)
        wall_s = time.perf_counter() - pass_t0
        stats["dispatch_ms_quantiles"] = self._dispatch_quantiles(
            disp_q_base)
        stats["pass_report"] = report.emit_pass_report(
            "eval", steps=nsteps,
            samples=nsteps * self.feed_config.batch_size,
            wall_s=wall_s,
            stage_ms=report.stage_delta(self.timers, stage_base),
            stats=stats,
            extra={"steps_per_dispatch": k_disp,
                   "seg_cache_hit_rate": stats["seg_cache_hit_rate"]})
        self._observe_quality("eval", stats, dataset, auc_state=auc)
        return stats

    def _sync_params_fn(self):
        """Jitted cross-replica param average for k-step pass boundaries."""
        if self._sync_params_cache is None:
            axis = self.axis
            raxes = ((self.dcn_axis, axis) if self.dcn_axis is not None
                     else axis)

            @jax.jit
            @functools.partial(
                jax.shard_map, mesh=self.mesh, in_specs=P(),
                out_specs=P(), check_vma=False)
            def sync(params):
                return jax.tree.map(lambda x: lax.pmean(x, raxes), params)

            self._sync_params_cache = sync
        return self._sync_params_cache

    def _prefetch_batches(self, dataset: Dataset, k: int = 1):
        """Producer thread packs + host-maps batch k+1 while batch k's
        device step executes (role of the reference's pipelined batch
        packing + preload threads, MiniBatchGpuPack data_feed.cc:4611,
        PreLoadIntoMemory box_wrapper.h:1140). The host work (numpy pack,
        native keymap lookup — both GIL-releasing) runs concurrently with
        the asynchronously-dispatched device computation; a small bounded
        queue keeps the device fed without unbounded host memory.

        Transfer thrift: per-slot segment arrays are
        usually IDENTICAL between consecutive full batches of fixed-length
        slots (identity layout), so the producer reuses the previous
        device copy when the host bytes match instead of re-transferring
        ~2 MB per batch; dense features ship in the compute dtype (bf16
        halves them under AMP).

        ``k > 1`` (FLAGS_trainer_steps_per_dispatch): the producer stacks
        K packed batches into ONE leading-axis block — yields 6-tuples
        ``(rows, segs, labels, valid, dense, n_active)`` with [K, ...]
        device arrays for the scanned megastep. The segment cache works
        on the stacked host arrays (consecutive full blocks of
        fixed-length slots are still byte-identical) and a partial tail
        block is padded by repeating the last real batch with
        ``n_active < K`` (the scan masks the padding out). ``k == 1``
        yields the legacy per-batch 5-tuples."""
        import queue
        import threading

        q: "queue.Queue" = queue.Queue(
            maxsize=max(1, int(flags.flag("trainer_prefetch_depth"))))
        _DONE = object()
        stop = threading.Event()
        seg_cache: Dict[str, Tuple[np.ndarray, jax.Array]] = {}
        dense_bf16 = self.config.compute_dtype == "bfloat16"
        # Explicit global placement: every process passes the SAME host
        # array and owns only its addressable shards — which is what makes
        # the identical code run under multi-process (jax.distributed)
        # clusters, where bare jnp.asarray would produce non-addressable
        # single-device arrays.
        dspec = (P((self.dcn_axis, self.axis))
                 if self.dcn_axis is not None else P(self.axis))
        data_sh = (NamedSharding(self.mesh, dspec)
                   if self.mesh is not None else None)
        # Stacked blocks shard dim 1 (dim 0 is the K steps axis).
        stk_spec = (P(None, (self.dcn_axis, self.axis))
                    if self.dcn_axis is not None else P(None, self.axis))
        stk_sh = (NamedSharding(self.mesh, stk_spec)
                  if self.mesh is not None else None)

        def _dev(host):
            return _put_global(host, data_sh)

        def _dev_stk(host):
            return _put_global(host, stk_sh)

        def _seg_dev(name: str, host: np.ndarray,
                     put=None) -> jax.Array:
            hit = seg_cache.get(name)
            if hit is not None and np.array_equal(hit[0], host):
                # Single-writer counters: only the producer thread
                # touches them mid-pass; the pass reader consumes after
                # the queue drains (and the reset happens pre-start).
                # graftlint: allow-lock(single producer; read post-drain)
                self._seg_cache_hits += 1
                return hit[1]
            # graftlint: allow-lock(single producer; read post-drain)
            self._seg_cache_misses += 1
            dev = (put or _dev)(host)
            seg_cache[name] = (host.copy(), dev)
            return dev

        def _put(item) -> bool:
            # Polls with a timeout: a consumer that left early sets
            # ``stop`` and never drains the full queue.
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        n_groups = len(self.engine.groups)
        # Map-ahead worker (FLAGS_trainer_map_ahead): the host keymap
        # lookup of batch i+1 runs on this ONE worker while the producer
        # packs + transfers batch i — the CopyKeys host map leaves the
        # prefetch critical path entirely (the native hash probe and the
        # sharded numpy fallback both release the GIL, so the two
        # threads genuinely overlap).
        mapper = None
        if flags.flag("trainer_map_ahead"):
            from concurrent.futures import ThreadPoolExecutor
            mapper = ThreadPoolExecutor(max_workers=1,
                                        thread_name_prefix="pbx-map-ahead")

        def _map_rows_timed(batch):
            # Stage split (PrintSyncTimer vocabulary): "pull" is the host
            # half of PullSparse (feasign -> device-row keymap, the
            # CopyKeys role); "pack" is batch assembly + dtype prep.
            faults.faultpoint("trainer/map_ahead")
            with self.timers.scope("pull"), trace.span("prefetch/keymap"):
                return self._map_batch_rows_host(batch)

        def _pack_host(batch, rows_h):
            faults.faultpoint("trainer/pack")
            with self.timers.scope("pack"):
                dense_h = _concat_dense_host(batch)
                if dense_bf16:
                    import ml_dtypes
                    dense_h = dense_h.astype(ml_dtypes.bfloat16)
                return (rows_h,
                        {n: batch.segments[n] for n in self._slot_names},
                        batch.labels, batch.valid, dense_h)

        def _stack_block(blk):
            with self.timers.scope("pack"):
                n_active = len(blk)
                # static-shape tail pad
                blk = blk + [blk[-1]] * (k - n_active)
                rows = tuple(_dev_stk(np.stack([b[0][g] for b in blk]))
                             for g in range(n_groups))
                segs = {n: _seg_dev(n, np.stack([b[1][n] for b in blk]),
                                    put=_dev_stk)
                        for n in self._slot_names}
                return (rows, segs,
                        _dev_stk(np.stack([b[2] for b in blk])),
                        _dev_stk(np.stack([b[3] for b in blk])),
                        _dev_stk(np.stack([b[4] for b in blk])),
                        n_active)

        _EOF = object()

        def producer():
            buf: List[tuple] = []
            it = iter(dataset.batches_sharded(self.ndev))

            def read_next():
                # "read" = waiting on the dataset iterator (columnar
                # slice/channel pop — the reference's ReadInstance
                # timer); separate from pack/pull so a starved pass
                # is distinguishable from a slow keymap.
                faults.faultpoint("trainer/prefetch")
                with self.timers.scope("read"):
                    return next(it, _EOF)

            try:
                batch = read_next()
                fut = (mapper.submit(_map_rows_timed, batch)
                       if mapper is not None and batch is not _EOF
                       else None)
                while batch is not _EOF:
                    # Kick batch i+1's keymap map NOW: it runs on the
                    # mapper worker while this thread packs + transfers
                    # batch i below.
                    nxt = read_next()
                    fut_n = (mapper.submit(_map_rows_timed, nxt)
                             if mapper is not None and nxt is not _EOF
                             else None)
                    rows_h = (fut.result() if fut is not None
                              else _map_rows_timed(batch))
                    if k == 1:
                        faults.faultpoint("trainer/pack")
                        with self.timers.scope("host_map"), \
                                trace.span("prefetch/host_map"):
                            with self.timers.scope("pack"):
                                dense_h = _concat_dense_host(batch)
                                if dense_bf16:
                                    import ml_dtypes
                                    dense_h = dense_h.astype(
                                        ml_dtypes.bfloat16)
                                args = (tuple(_dev(h) for h in rows_h),
                                        {n: _seg_dev(n,
                                                     batch.segments[n])
                                         for n in self._slot_names},
                                        _dev(batch.labels),
                                        _dev(batch.valid),
                                        _dev(dense_h))
                        if not _put(args):
                            return  # consumer bailed early
                        batch, fut = nxt, fut_n
                        continue
                    with self.timers.scope("host_map"), \
                            trace.span("prefetch/host_map", k=k):
                        buf.append(_pack_host(batch, rows_h))
                        args = (_stack_block(buf) if len(buf) == k
                                else None)
                        if args is not None:
                            buf = []
                    if args is not None and not _put(args):
                        return
                    batch, fut = nxt, fut_n
                if buf:
                    with self.timers.scope("host_map"):
                        args = _stack_block(buf)
                    if not _put(args):
                        return
            except BaseException as e:
                _put(e)
                return
            _put(_DONE)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                with trace.span("pass/feed_wait"):
                    item = q.get()
                if item is _DONE:
                    break
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            # Unblock the producer if we exited early (error mid-pass).
            stop.set()
            t.join(timeout=60.0)
            if mapper is not None:
                mapper.shutdown(wait=False)

    def _map_batch_rows_host(self, batch: SlotBatch) -> List[np.ndarray]:
        """Host map: batch feasigns → per-width-group fused device-row
        arrays (role of CopyKeys' host side, one array per dim group) —
        host side only, so the K-stacking prefetcher can np.stack K
        batches before the one device transfer."""
        rows = []
        for gi, g in enumerate(self.engine.groups):
            all_ids = np.concatenate([batch.ids[n] for n in g.slots])
            r = self.engine.lookup_rows(gi, all_ids)
            # Interleave per-device: [dev, slot, cap_local] flatten.
            rows.append(_interleave_slots(r, list(g.slots),
                                          self._slot_caps, self.ndev))
        return rows

    def _map_batch_rows(self, batch: SlotBatch) -> Tuple[jax.Array, ...]:
        dspec = (P((self.dcn_axis, self.axis))
                 if self.dcn_axis is not None else P(self.axis))
        data_sh = (NamedSharding(self.mesh, dspec)
                   if self.mesh is not None else None)
        return tuple(_put_global(h, data_sh)
                     for h in self._map_batch_rows_host(batch))

    def export_serving(self, path: str) -> Dict[str, object]:
        """One-call serving export: the xbox sparse model (emb + w, no
        optimizer state — save_xbox_base_model role, fleet_util.py:774)
        plus a BARE dense-params snapshot and a ``meta.json`` naming the
        table and the data_norm configuration — everything
        ``serving.load_serving_predictor(model, feed, path)`` needs to
        stand a predictor up (the meta matters: a hand-built fresh
        template would silently DROP the trainer-added data_norm stats
        and serve un-normalized probabilities). Training-resume
        snapshots (params + optimizer state) are the checkpoint
        protocol's job, not this artifact's."""
        import json
        import os

        from paddlebox_tpu.checkpoint.dense import save_pytree

        if self.params is None:
            raise RuntimeError("call init() (and train) before exporting")
        os.makedirs(path, exist_ok=True)
        xbox = os.path.join(path, "xbox")
        n = int(self.engine.store.save_xbox(xbox))
        dense = os.path.join(path, "dense.npz")
        save_pytree(jax.device_get(self.params), dense)
        meta = {
            "table": self.table_config.name,
            "data_norm": bool(self.config.data_norm),
            "dense_dim": int(sum(s.dim
                                 for s in self.feed_config.dense_slots)),
            "data_norm_slot_dim": int(self.config.data_norm_slot_dim),
            "compute_dtype": self.config.compute_dtype,
        }
        with open(os.path.join(path, "meta.json"), "w") as f:
            json.dump(meta, f, indent=1)
        return {"xbox": xbox, "dense": dense, "features": n,
                "meta": os.path.join(path, "meta.json")}

    def _measure_caps(self, tables, rows) -> List[Optional[int]]:
        """Per-group measured bucket capacity: the first batch's worst
        per-(device, shard) row count — UNIQUE rows when dedup is on (a
        cell holds a unique id), occurrences otherwise — with the
        shard-slack headroom, rounded up to a power of two
        (compile-stability bucketing) and clamped to the per-device id
        count. Role of the reference sizing its shard buffers from the
        actual batch (heter_comm_inl.h:273 walks real counts) — here the
        shapes must be static, so measure once per pass and retrace only
        when the pow2 bucket grows."""
        slack = float(flags.flag("embedding_shard_slack"))
        dedup = bool(flags.flag("embedding_dedup"))
        caps: List[Optional[int]] = []
        for t, r in zip(tables, rows):
            if t.num_shards == 1:
                caps.append(None)
                continue
            block = t.rows_per_shard + 1
            # r is flat [n] (per-step) or stacked [K, n] (megastep first
            # block): either way measure every (step, device) row set —
            # the scanned fn compiles ONCE for the block, so its caps
            # must cover the worst batch in it.
            arr = np.asarray(r)
            rr = arr.reshape(-1, arr.shape[-1] // self.ndev)
            worst = 1
            for d in range(rr.shape[0]):
                vals = np.unique(rr[d]) if dedup else rr[d]
                shard = np.clip(vals // block, 0, t.num_shards - 1)
                worst = max(worst, int(np.bincount(
                    shard, minlength=t.num_shards).max()))
            n_local = rr.shape[1]
            c = min(max(int(slack * worst) + 8, 8), n_local)
            c = min(1 << (c - 1).bit_length(), n_local)
            caps.append(c)
        return caps

    # -- pass loop ---------------------------------------------------------

    def train_pass(self, dataset: Dataset, *, feed_keys: bool = True
                   ) -> Dict[str, float]:
        """Train one pass over the dataset (role of train_from_dataset +
        begin_pass/end_pass, SURVEY.md §3.1)."""
        if self.params is None:
            raise RuntimeError("call init() first")
        # Telemetry is host-side only: flag-armed sinks, a per-pass stage
        # baseline (the TimerGroup is cumulative across passes), and
        # seg-cache counters. NOTHING below adds
        # ops or syncs to the jitted step.
        report.init_telemetry_from_flags()
        faults.init_from_flags()
        pass_t0 = time.perf_counter()
        stage_base = self.timers.snapshot_ms()
        boundary_base = self.engine.boundary_ms()
        disp_q_base = monitor.GLOBAL.quantile_digest("trainer/dispatch_ms")
        self._seg_cache_hits = 0
        self._seg_cache_misses = 0
        eng = self.engine
        mode = self.config.dense_sync_mode
        k = max(1, self.config.dense_sync_interval)
        profiling = bool(flags.flag("profile_trainer"))
        check_nan = (self.config.check_nan_inf
                     or flags.flag("check_nan_inf"))
        # K-step megastep (FLAGS_trainer_steps_per_dispatch): one scanned
        # XLA dispatch per K steps. Two configs force K=1: async dense
        # sync needs a host pull/push around every step, and the profiler
        # needs per-step dispatch boundaries to time.
        k_disp = max(1, int(flags.flag("trainer_steps_per_dispatch")))
        if k_disp > 1 and mode == "async":
            log.vlog(0, "trainer_steps_per_dispatch=%d ignored: "
                     "dense_sync_mode='async' pulls/pushes the host dense "
                     "table around every step — running K=1", k_disp)
            k_disp = 1
        # dense_zero='offload' is the other external-update mode: the
        # host-resident optimizer needs the grads around every step.
        offload = self._dense_zero == "offload"
        if k_disp > 1 and offload:
            log.vlog(0, "trainer_steps_per_dispatch=%d ignored: "
                     "dense_zero='offload' routes the dense update "
                     "through the host-pinned optimizer every step — "
                     "running K=1", k_disp)
            k_disp = 1
        if k_disp > 1 and profiling:
            log.vlog(0, "trainer_steps_per_dispatch=%d ignored under "
                     "FLAGS_profile_trainer (per-step timing needs "
                     "per-step dispatch) — running K=1", k_disp)
            k_disp = 1
        if feed_keys:
            with self.timers.scope("feed_pass"), \
                    trace.span("pass/feed_pass"):
                eng.feed_pass([dataset.pass_keys(slots=g.slots)
                               for g in eng.groups])
        with trace.span("pass/begin_pass"):
            tables = eng.begin_pass()
        params, opt_state = self.params, self.opt_state
        auc = self.auc_state
        if mode == "async" and self._async_dense is None:
            from paddlebox_tpu.train.async_dense import AsyncDenseTable
            self._async_dense = AsyncDenseTable(
                # graftlint: allow-sync(async mode seeds the HOST dense table once)
                jax.device_get(params),
                learning_rate=self.config.dense_learning_rate)
        rep = (NamedSharding(self.mesh, P())
               if self.mesh is not None else None)
        # Pre-built replicated step flags: creating them per step would
        # issue host->device transfers (with cross-process consistency
        # collectives under jax.distributed) racing the prefetch thread's.
        flags_01 = (_put_global(np.int32(0), rep),
                    _put_global(np.int32(1), rep))
        nact_full = (_put_global(np.int32(k_disp), rep)
                     if k_disp > 1 else None)
        # Device-side running sums: the pass keeps TWO device scalars
        # alive instead of O(steps) retained loss/overflow arrays, and
        # nothing here blocks the dispatch pipeline.
        loss_sum = None
        overflow_sum = None
        group_n: Optional[List[int]] = None
        first_batch_dup = None
        nsteps = 0
        self._dispatch_blocks = 0
        self._host_syncs = 0
        if self._debug_collect_losses:
            self._debug_losses = []
        # check_nan_inf without the per-step float(loss) sync: each
        # dispatch also yields a device-side finite-ness vector; the host
        # fetches block i-1's verdict while block i executes (one sync
        # per BLOCK, one block late mid-pass, exact at pass end).
        pending_finite = None

        def _check_pending():
            nonlocal pending_finite
            if pending_finite is None:
                return
            base, fin, na = pending_finite
            pending_finite = None
            self._host_syncs += 1
            with self.timers.scope("sync"), \
                    trace.span("pass/sync_finite"):
                fv = np.asarray(fin)[:na]
            if not fv.all():
                bad = base + int(np.argmin(fv)) + 1
                raise FloatingPointError(f"NaN/Inf loss at step {bad}")

        for args in self._prefetch_batches(dataset, k=k_disp):
            if k_disp == 1:
                rows, segs, labels, valid, dense = args
                n_active = 1
            else:
                rows, segs, labels, valid, dense, n_active = args
            if group_n is None:
                # Per-device id count per width group — static across the
                # pass, feeds the exchange-bytes observable below. The
                # duplication factor (occurrences per unique id in the
                # first batch) tells the operator how much headroom
                # FLAGS_embedding_unique_frac could reclaim: dedup means
                # bucket cells hold UNIQUE ids, so unique_frac can drop
                # toward 1/duplication before overflow risk returns.
                group_n = [int(r.shape[-1]) // max(self.ndev, 1)
                           for r in rows]
                addressable = all(getattr(r, "is_fully_addressable", True)
                                  for r in rows)
                if addressable:
                    # Duplication is a first-BATCH signal: slice step 0
                    # out of a stacked [K, n] block.
                    firsts = [np.asarray(r)[0] if k_disp > 1
                              else np.asarray(r) for r in rows]
                    occ = sum(int(f.shape[0]) for f in firsts)
                    uniq = sum(len(np.unique(f)) for f in firsts)
                    first_batch_dup = occ / max(uniq, 1)
                if addressable and flags.flag("embedding_auto_capacity"):
                    # Measured capacity (pow2-bucketed): size each
                    # group's bucket to the first batch's worst
                    # per-(device, shard) cell demand instead of the
                    # n-based binomial bound. Caps only RATCHET UP: a
                    # pass measuring smaller keeps the compiled (larger,
                    # still-safe) step, so re-measurement jitter across
                    # passes can never recompile mid-run — only a batch
                    # genuinely exceeding the warmed capacity does.
                    meas = self._measure_caps(tables, rows)
                    cur = self._step_caps
                    merged = tuple(
                        c if cur is None or cur[i] is None
                        else (None if c is None else max(c, cur[i]))
                        for i, c in enumerate(meas))
                    if merged != cur:
                        self._step_caps = merged
                        self._step_fn = None
                        self._mega_fn = None
                        log.vlog(0, "auto-capacity: bucket caps %s "
                                 "(measured from first %s)",
                                 list(merged),
                                 "stacked block" if k_disp > 1
                                 else "batch")
                else:
                    if (flags.flag("embedding_auto_capacity")
                            and not addressable
                            and not getattr(self, "_autocap_warned",
                                            False)):
                        # Multi-host: rows span processes, so the host
                        # cannot measure them — say so ONCE (per
                        # trainer) instead of silently delivering zero
                        # byte reduction every pass.
                        self._autocap_warned = True
                        log.warning(
                            "auto-capacity requested but batch rows are "
                            "not fully addressable (multi-host run) — "
                            "using the default n-based capacity")
                    if self._step_caps is not None:
                        # Flag turned off (or data not addressable):
                        # drop back to the default-capacity step.
                        self._step_caps = None
                        self._step_fn = None
                        self._mega_fn = None
                # Build (or reuse) the compiled fn for this pass's K —
                # AFTER the capacity measurement above, so the scanned
                # megastep is traced at the measured caps (caps only
                # ratchet up; a steady-state pass reuses the warm fn).
                if k_disp == 1:
                    if self._step_fn is None:
                        self._step_fn = self._build_step(
                            caps=self._step_caps)
                elif self._mega_fn is None or self._mega_k != k_disp:
                    self._mega_fn = self._build_step(
                        caps=self._step_caps, k_steps=k_disp)
                    self._mega_k = k_disp
            if mode == "async":
                # PullDense role: freshest host params each step.
                params = jax.device_put(self._async_dense.pull_dense(), rep)
            block_base = nsteps
            t_disp0 = time.perf_counter()
            # "dispatch" = the host-side enqueue wall of the (async)
            # compiled-program launch; under FLAGS_profile_trainer the
            # per-step sync runs inside, so the same scope degenerates to
            # the synced step wall (credited to fwd_bwd below).
            with self.timers.scope("device_step"), \
                    self.timers.scope("dispatch"), \
                    trace.span("pass/dispatch",
                               block=self._dispatch_blocks, k=k_disp):
                if k_disp == 1:
                    sync_flag = flags_01[
                        1 if (mode == "kstep" and (nsteps + 1) % k == 0)
                        else 0]
                    out = self._step_fn(
                        tables, params, () if offload else opt_state,
                        auc, rows, segs, labels, valid, dense, sync_flag)
                    tables, params, opt_out, auc, loss, overflow = out[:6]
                    if not offload:
                        opt_state = opt_out
                    blk_losses, blk_overflow = loss, overflow
                    if profiling:
                        # Completion INSIDE the scope so device_step
                        # records the real step wall time, not async
                        # dispatch. Profiling trades the pipelining away
                        # on purpose (TrainFilesWithProfiler does the
                        # same).
                        # graftlint: allow-sync(FLAGS_profile_trainer syncs per step by design)
                        float(loss)
                else:
                    # ONE dispatch runs n_active steps; the in-scan step
                    # counter starts at this block's first global step.
                    step0 = _put_global(np.int32(nsteps), rep)
                    nact = (nact_full if n_active == k_disp
                            else _put_global(np.int32(n_active), rep))
                    out = self._mega_fn(
                        tables, params, opt_state, auc, step0, nact,
                        rows, segs, labels, valid, dense)
                    (tables, params, opt_state, auc, blk_losses,
                     blk_overflows, blk_finites) = out
                    blk_overflow = jnp.sum(blk_overflows, axis=0)
            self._dispatch_blocks += 1
            # Stall-watchdog heartbeat: per-block dispatch progress is
            # the liveness signal (one cached-bool no-op when disarmed).
            watchdog.beat()
            disp_s = time.perf_counter() - t_disp0
            # Step-latency distribution (host-observed block enqueue
            # wall): the pass report's histogram feed, plus the
            # log-bucketed digest behind the per-pass p50/p90/p99/p999.
            monitor.observe("trainer/dispatch_ms", disp_s * 1e3)
            monitor.observe_quantile("trainer/dispatch_ms", disp_s * 1e3)
            if profiling and k_disp == 1:
                # Profiling syncs per step, so the block wall IS the
                # fused device step (pull+fwd-bwd+push) — the closest
                # host-observable stand-in for the fwd_bwd stage.
                self.timers["fwd_bwd"].add_elapsed(disp_s)
            if mode == "async":
                # PushDense role: hand psum'd grads to the host updater.
                # graftlint: allow-sync(async dense pulls grads to the host each step by design)
                self._async_dense.push_dense(jax.device_get(out[6]))
            elif offload:
                # The offload round-trip: stage host state -> HBM, run
                # the jitted update, stream the new state back to its
                # host pinning, apply updates to the replicated params.
                # All transfers are async dispatches — nothing here
                # blocks on the device.
                params, opt_state = self._offload_tx.update_apply(
                    out[6], opt_state, params)
            nsteps += n_active
            if profiling and k_disp == 1:
                # graftlint: allow-sync(FLAGS_profile_trainer per-step log)
                log.vlog(0, "step %d: loss=%.5f %s", nsteps, float(loss),
                         self.timers.report())
            blk_loss = (blk_losses if k_disp == 1
                        else jnp.sum(blk_losses))
            loss_sum = blk_loss if loss_sum is None else loss_sum + blk_loss
            overflow_sum = (blk_overflow if overflow_sum is None
                            else overflow_sum + blk_overflow)
            if self._debug_collect_losses:
                self._debug_losses.append((block_base, blk_losses,
                                           n_active))
            if check_nan:
                # Fetch block i-1's verdict while block i executes —
                # the device never idles waiting on the host check.
                _check_pending()
                fin = (jnp.isfinite(blk_losses).reshape(1)
                       if k_disp == 1 else blk_finites)
                pending_finite = (block_base, fin, n_active)
        if check_nan:
            _check_pending()
        if mode == "kstep" and nsteps % k != 0:
            # Pass boundary: leave params synchronized regardless of
            # where the last sync fell (the reference's pass-end
            # SyncParam does the same).
            params = self._sync_params_fn()(params)
        if mode == "async":
            self._async_dense.flush()
            params = jax.device_put(self._async_dense.pull_dense(), rep)
        eng.update_tables(tables)
        self.params, self.opt_state, self.auc_state = params, opt_state, auc
        # "push" = the host-visible half of PushSparse: the pass-end
        # table write-back into the persistent store (the in-step push
        # is fused into the jitted program and rides "dispatch").
        with self.timers.scope("end_pass"), self.timers.scope("push"), \
                trace.span("pass/end_pass"):
            eng.end_pass()
        # "sync" = blocking device fetches: the pass-end stat reductions
        # (plus any deferred finite-vector fetches counted above).
        with self.timers.scope("sync"), \
                trace.span("pass/final_fetch"):
            stats = self._auc_stats(self.auc_state)
            # graftlint: allow-sync(pass-end stat fetch inside the sync scope)
            stats["loss"] = (float(loss_sum) / nsteps if nsteps
                             else float("nan"))
        stats["steps"] = nsteps
        stats["steps_per_dispatch"] = k_disp
        stats["dispatch_blocks"] = self._dispatch_blocks
        stats["host_syncs"] = self._host_syncs
        with self.timers.scope("sync"):
            # graftlint: allow-sync(pass-end stat fetch inside the sync scope)
            of = (np.asarray(overflow_sum) if overflow_sum is not None
                  else (0, 0, 0))
            stats["lookup_overflow"] = int(of[0])
            stats["kernel_fallback"] = int(of[1])
            stats["kernel_hot_served"] = int(of[2])
        # Static per-device all-to-all bytes for one pull+push round —
        # what dedup + FLAGS_embedding_unique_frac shrink (the dedup-
        # before-exchange observable; heter_comm.h:192 transfers merged
        # keys for the same reason). record_exchange_stats also lands
        # it in the metric registry + trace counter.
        caps_now = (list(self._step_caps) if self._step_caps is not None
                    else [None] * len(group_n or []))
        stats["lookup_exchange_bytes"] = (
            record_exchange_stats(tables, group_n, caps_now)
            if group_n else 0)
        # Occurrences per unique id in the pass's first batch: the
        # operator's sizing signal for FLAGS_embedding_unique_frac
        # (safe floor ~= 1/duplication).
        stats["lookup_duplication"] = (
            round(first_batch_dup, 3) if group_n and first_batch_dup
            else None)
        stats["scale_sparse_grad_by_batch"] = bool(
            self.config.scale_sparse_grad_by_batch)
        if stats["lookup_overflow"]:
            monitor.add("embedding/lookup_overflow",
                        stats["lookup_overflow"])
            log.warning("pass had %d overflowed sparse lookups (dropped "
                        "pull+grad) — raise FLAGS_embedding_shard_slack "
                        "if the key distribution is skewed",
                        stats["lookup_overflow"])
        if stats["kernel_fallback"]:
            monitor.add("embedding/kernel_fallback",
                        stats["kernel_fallback"])
            log.warning("the sorted-stream gather gave way to XLA in %d "
                        "(step, width group, device) cells this pass — a "
                        "table block was asked for more distinct rows "
                        "than the kernel's per-block budget",
                        stats["kernel_fallback"])
        if stats["kernel_hot_served"]:
            monitor.add("embedding/kernel_hot_served",
                        stats["kernel_hot_served"])
        stats["seg_cache_hit_rate"] = self._seg_cache_rate()
        stats["boundary"] = self._boundary_delta(boundary_base)
        wall_s = time.perf_counter() - pass_t0
        # The dispatch-latency digest window -> p50/p90/p99/p999.
        stats["dispatch_ms_quantiles"] = self._dispatch_quantiles(
            disp_q_base)
        # The PrintSyncTimer moment: ONE structured per-pass summary
        # line + registry/JSONL publish (core.report).
        stats["pass_report"] = report.emit_pass_report(
            "train", steps=nsteps,
            samples=nsteps * self.feed_config.batch_size,
            wall_s=wall_s,
            stage_ms=report.stage_delta(self.timers, stage_base),
            stats=stats,
            extra={"steps_per_dispatch": k_disp,
                   "seg_cache_hit_rate": stats["seg_cache_hit_rate"],
                   "lookup_duplication": stats["lookup_duplication"]})
        self._observe_quality("train", stats, dataset)
        log.vlog(0, "pass done: steps=%d loss=%.5f auc=%.5f (%s)",
                 nsteps, stats["loss"], stats["auc"], self.timers.report())
        return stats

    def _observe_quality(self, kind: str, stats: Dict[str, float],
                         dataset, auc_state=None) -> None:
        """Fold the finished pass into the model-quality plane
        (FLAGS_quality_collect, core/quality.py): the host copy of the
        device AUC histogram localizes a COPC excursion into prediction
        buckets, the dataset's load-time slot-health snapshot carries
        coverage/churn/skew, and the tracker raises the drift alarms +
        the quality_report line beside the pass_report. Host-side only
        — one extra pass-end table fetch, zero device ops."""
        if not quality.enabled():
            return
        auc = auc_state if auc_state is not None else self.auc_state
        q_table = None
        if self.num_tasks == 1 and auc is not None:
            with self.timers.scope("sync"):
                # graftlint: allow-sync(pass-end quality table fetch inside the sync scope)
                q_table = np.asarray(auc.table, np.float64)
        # Slot health rides TRAIN passes only: eval re-walks the same
        # dataset (slot_replacement_eval runs many evals per load), and
        # feeding the churn/coverage baselines duplicate snapshots of
        # one load would dilute the drift signal with zeros.
        health_fn = (getattr(dataset, "quality_health", None)
                     if kind == "train" else None)
        summary = quality.GLOBAL.observe_pass(
            kind, stats=stats, auc_table=q_table,
            health=health_fn() if health_fn is not None else None)
        if summary is not None:
            stats["quality_report"] = summary

    def _seg_cache_rate(self) -> Optional[float]:
        total = self._seg_cache_hits + self._seg_cache_misses
        return round(self._seg_cache_hits / total, 4) if total else None

    def _boundary_delta(self, base: Dict[str, float]) -> Dict[str, float]:
        """Per-pass pass-boundary breakdown: deltas of the engine's
        cumulative boundary timers over this pass's window. In a
        pipelined day loop the NEXT pass's (overlapped) build lands in
        this window — exactly the boundary this pass paid for.
        ``overlap_frac`` = the fraction of the build that ran while
        training still owned the store (1.0 = fully hidden; 0.0 = the
        r04 serial boundary)."""
        now = self.engine.boundary_ms()
        d = {key: round(now[key] - base.get(key, 0.0), 3) for key in now}
        build = d.get("build_ms", 0.0)
        wait = d.get("feed_wait_ms", 0.0)
        d["overlap_frac"] = (round(min(1.0, max(0.0, 1.0 - wait / build)),
                                   4)
                             if build > 1e-6 else None)
        # Background DCN exchange (MultiHostStore worker): the fraction
        # of exchange bytes that moved while the caller was doing other
        # work. No exchange work this pass -> no row (the gauge would
        # lie at 1.0 on single-host tiers).
        xbusy = d.get("exchange_busy_ms", 0.0)
        xwait = d.get("exchange_wait_ms", 0.0)
        if xbusy > 1e-6:
            d["exchange_overlap_frac"] = round(
                min(1.0, max(0.0, 1.0 - xwait / xbusy)), 4)
        return d

    def _dispatch_quantiles(self, base) -> Optional[Dict[str, float]]:
        """This pass's dispatch-latency p50/p90/p99/p999 from the
        cumulative registry digest, windowed by count subtraction."""
        d = monitor.GLOBAL.quantile_digest("trainer/dispatch_ms")
        if d is None:
            return None
        w = d.delta(base)
        if not w.count:
            return None
        out = {k: (round(v, 3) if v is not None else None)
               for k, v in w.quantiles().items()}
        out["count"] = w.count
        return out

    def reset_metrics(self) -> None:
        self.auc_state = self._auc_init()
        if self.mesh is not None:
            self.auc_state = jax.device_put(
                self.auc_state, NamedSharding(self.mesh, P()))


def _interleave_slots(rows_concat: np.ndarray, names: List[str],
                      caps: Dict[str, int], ndev: int) -> np.ndarray:
    """Reorder [slotA(all devs), slotB(all devs), ...] into per-device
    groups [dev0: slotA,slotB..., dev1: ...] so sharding the flat array
    over dp gives each device its own slots' local ids contiguously."""
    parts = []
    off = 0
    per_slot = {}
    for n in names:
        per_slot[n] = rows_concat[off:off + caps[n]].reshape(ndev, -1)
        off += caps[n]
    for d in range(ndev):
        for n in names:
            parts.append(per_slot[n][d])
    return np.concatenate(parts)


def _tree_select(pred, new, old):
    """Per-leaf ``where(pred, new, old)`` over matching pytrees — the
    megastep's tail mask (a padded scan step computes ``new`` but must
    leave the carried state byte-identical to ``old``)."""
    return jax.tree.map(lambda a, b: jnp.where(pred, a, b), new, old)


def _put_global(host, sharding) -> jax.Array:
    """Host array -> global device array under ``sharding``, WITHOUT any
    cross-process collective (jax.device_put to a multi-process sharding
    runs an assert-equal allgather, which would race other threads'
    collectives; make_array_from_callback materializes only this
    process's addressable shards). Single-process it is equivalent."""
    if sharding is None:
        return jnp.asarray(host)
    host = np.asarray(host)
    return jax.make_array_from_callback(host.shape, sharding,
                                        lambda idx: host[idx])


def _concat_dense_host(batch: SlotBatch) -> np.ndarray:
    if batch.dense:
        return np.concatenate([batch.dense[k] for k in sorted(batch.dense)],
                              axis=-1)
    return np.zeros((batch.labels.shape[0], 0), np.float32)
