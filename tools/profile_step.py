"""Micro-profile of the CTR device step's components at bench shapes.

VERDICT r02 task 2 asked for a recorded profile of the jitted step naming
the dominant op. This measures each stage as its own jitted function at the
exact bench shapes (4M-key x 16-dim table, 16384-sample batch, 26 slots),
plus raw D2H/H2D bandwidth (the end_pass/feed_pass transfer path). Run on
the bench chip:

    python tools/profile_step.py

Results recorded in PROFILE.md.

A stall watchdog (PBX_PROFILE_WATCHDOG_S, default 600 s; 0 disables)
guards against a wedged device call: if no probe completes within the
limit, it prints one JSON
line with faulthandler thread stacks + the trace ring tail and exits 3 —
a hung probe run is diagnosable post-mortem instead of silent.
"""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from paddlebox_tpu.core import report as _report
from paddlebox_tpu.core import trace as _trace

_WD = {"t": time.monotonic(), "phase": "start"}


def _tick(phase: str) -> None:
    _WD["t"] = time.monotonic()
    _WD["phase"] = phase
    if _trace.GLOBAL.enabled:
        _trace.instant("profile/" + phase)


def _watchdog_loop(limit: float) -> None:
    while True:
        time.sleep(5)
        if time.monotonic() - _WD["t"] > limit:
            try:
                tail = _trace.stall_forensics()
            except Exception as e:  # noqa: BLE001 - keep the record
                tail = {"error": f"forensics unavailable: {e!r}"}
            print(json.dumps({
                "metric": "profile_step_FAILED",
                "error": (f"watchdog: no probe progress in phase "
                          f"{_WD['phase']!r} for {limit:.0f}s"),
                "tail": tail,
            }, default=str), flush=True)
            os._exit(3)


def _start_watchdog() -> None:
    limit = float(os.environ.get("PBX_PROFILE_WATCHDOG_S", "600"))
    if limit <= 0:
        return
    import threading
    threading.Thread(target=_watchdog_loop, args=(limit,),
                     daemon=True).start()


# Sync on a 4-byte slice of the result: forces completion of the dispatch
# chain without transferring the (possibly hundreds of MB) result to the
# host, which would swamp the op being measured.
_tiny = jax.jit(lambda x: lax.slice(x.ravel(), (0,), (1,)))


def sync(r):
    leaf = jax.tree_util.tree_leaves(r)[0]
    out = np.asarray(_tiny(leaf))
    _WD["t"] = time.monotonic()  # every completed probe feeds the dog
    return out


def timeit(fn, *args, n=10, warmup=2):
    for _ in range(warmup):
        r = fn(*args)
    sync(r)
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn(*args)
    sync(r)
    return (time.perf_counter() - t0) / n


def _ingest_probes():
    """Host-ingest stage probes (round 13): each row isolates ONE stage
    of the disk→chunk→store path — parse only (all three parser tiers),
    shm handoff only (frame write + zero-copy attach), store build only
    (incremental vs sorted-run vs the dict fallback baseline) — so a
    PROFILE.md cost model can attribute the ingest wall per stage."""
    from paddlebox_tpu.data.parser import parse_block_numpy, parse_lines
    from paddlebox_tpu.data.columnar import instances_to_chunk
    from paddlebox_tpu.data.slots import DataFeedConfig, SlotConf
    from paddlebox_tpu.data import shm_channel
    from paddlebox_tpu.native.parser_py import parse_chunk_native
    from paddlebox_tpu.native.store_py import bench_index_build

    _tick("ingest-parse")
    n_lines, n_slots, dense_dim = 100_000, 26, 13
    slots = tuple(SlotConf(f"s{i}", avg_len=1.0) for i in range(n_slots))
    slots += (SlotConf("d", is_dense=True, dim=dense_dim),)
    cfg = DataFeedConfig(slots=slots, batch_size=1024,
                         slot_capacity_slack=1.0)
    rng = np.random.default_rng(0)
    ids = rng.integers(1, 1 << 40, (n_lines, n_slots))
    parts = [(np.char.add(np.char.add(
        (ids[:, 0] % 2).astype("U1"), " s0:"), ids[:, 0].astype("U20")))]
    line = parts[0]
    for j in range(1, n_slots):
        line = np.char.add(line, f" s{j}:")
        line = np.char.add(line, ids[:, j].astype("U20"))
    line = np.char.add(line, " d:" + ",".join(["0.5"] * dense_dim))
    block = ("\n".join(line.tolist()) + "\n").encode()

    t0 = time.perf_counter()
    chunk = parse_chunk_native(block, cfg)
    dt = time.perf_counter() - t0
    if chunk is not None:
        print(f"ingest parse native [{n_lines}]   {dt*1e3:8.1f} ms "
              f"({n_lines/dt:,.0f} rows/s)")
    else:
        print("ingest parse native          unavailable (no native lib)")
    t0 = time.perf_counter()
    chunk_np = parse_block_numpy(block, cfg)
    dt = time.perf_counter() - t0
    print(f"ingest parse numpy-bulk      {dt*1e3:8.1f} ms "
          f"({n_lines/dt:,.0f} rows/s)")
    t0 = time.perf_counter()
    instances_to_chunk(parse_lines(block.decode().split("\n"), cfg), cfg)
    dt = time.perf_counter() - t0
    print(f"ingest parse per-line        {dt*1e3:8.1f} ms "
          f"({n_lines/dt:,.0f} rows/s)")

    _tick("ingest-shm")
    chunk = chunk if chunk is not None else chunk_np
    nbytes = chunk.nbytes
    name = shm_channel.seg_name(os.getpid(), shm_channel.next_load_id(),
                                0, 0)
    t0 = time.perf_counter()
    shm_channel.write_chunk(chunk, name)
    got, release = shm_channel.read_chunk(name)
    dt = time.perf_counter() - t0
    assert got.num_rows == chunk.num_rows
    release()
    print(f"ingest shm roundtrip {nbytes/1e6:6.1f} MB {dt*1e3:8.1f} ms "
          f"({nbytes/dt/1e9:.2f} GB/s write+attach)")

    _tick("ingest-build")
    for mode in ("upsert", "bulk", "dict"):
        r = bench_index_build(4_000_000, chunk=1_000_000, mode=mode)
        print(f"store build {mode:7s} [4M]     "
              f"{4e6/r*1e3:8.1f} ms ({r:,.0f} keys/s)")


def main():
    # Ring-only tracing (file export when FLAGS_trace_path is set) +
    # the stall watchdog — same forensics discipline as bench.py.
    _report.init_telemetry_from_flags()
    _trace.GLOBAL.enable()
    _start_watchdog()
    _ingest_probes()
    _tick("setup")
    N_ROWS = 4 * 1024 * 1024        # pass table rows (pow2 bucket)
    D = 16
    BATCH = 16384
    SLOTS = 26
    n = BATCH * SLOTS               # ids per step = 425984

    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.integers(0, N_ROWS, n), jnp.int32)
    emb = jnp.asarray(rng.normal(size=(N_ROWS, D)), jnp.float32)
    state = jnp.asarray(np.abs(rng.normal(size=(N_ROWS, D))), jnp.float32)
    grads = jnp.asarray(rng.normal(size=(n, D)), jnp.float32)
    payload = jnp.asarray(rng.normal(size=(n, D + 3)), jnp.float32)
    fused = jnp.asarray(rng.normal(size=(N_ROWS, 2 * D + 8)), jnp.float32)
    sync(fused)

    print(f"shapes: table [{N_ROWS},{D}] ids [{n}]")
    _tick("dispatch-rtt")

    # Dispatch-latency probe (empty-step RTT): one trivial jitted
    # program, dispatched AND synced per iteration — the pure host-side
    # enqueue + completion round-trip with ~zero device work. This is
    # the per-step overhead FLAGS_trainer_steps_per_dispatch amortizes
    # (K steps ride one dispatch, so the hot loop pays RTT/K).
    tiny = jnp.zeros((8,), jnp.float32)
    empty = jax.jit(lambda x: x + 1.0)
    np.asarray(empty(tiny))  # compile + warm
    iters = 20
    t0 = time.perf_counter()
    for _ in range(iters):
        np.asarray(empty(tiny))
    t = (time.perf_counter() - t0) / iters
    print(f"empty-step dispatch RTT      {t*1e3:8.2f} ms "
          f"(amortized by steps_per_dispatch)")

    _tick("sort-gather-scatter")
    t = timeit(jax.jit(lambda r: jnp.argsort(r)), rows)
    print(f"argsort[{n}]                 {t*1e3:8.2f} ms")

    t = timeit(jax.jit(lambda r: jnp.sort(r)), rows)
    print(f"sort[{n}]                    {t*1e3:8.2f} ms")

    t = timeit(jax.jit(lambda e, r: e[r]), emb, rows)
    print(f"gather [{n}x{D}]             {t*1e3:8.2f} ms")

    t = timeit(jax.jit(lambda f, r: f[r]), fused, rows)
    print(f"gather fused [{n}x{2*D+8}]   {t*1e3:8.2f} ms")

    # Pull-side sorted-stream kernel (CopyForPull role) vs the XLA
    # gather at both bench pull widths — includes the kernel's argsort,
    # which the real step AMORTIZES by sharing it with the push scatter
    # (compute_bucketing), so the steady-state cost is lower than this
    # standalone row by ~the argsort line above.
    _tick("sorted-gather")
    from paddlebox_tpu.ops.pallas_kernels.sorted_gather import sorted_gather
    for pw in (16, 40):
        tbl = jnp.asarray(rng.normal(size=(N_ROWS, pw)), jnp.float32)
        sync(tbl)
        t = timeit(jax.jit(lambda t_, r: t_[r, :pw]), tbl, rows)
        print(f"gather xla [{n}x{pw}]        {t*1e3:8.2f} ms")
        t = timeit(jax.jit(
            lambda r, t_: sorted_gather(r, t_, width=pw)), rows, tbl)
        print(f"sorted_gather [{n}x{pw}]     {t*1e3:8.2f} ms "
              f"(incl. its own argsort)")

    t = timeit(jax.jit(lambda e, r, g: e.at[r].add(g)), emb, rows, grads)
    print(f"scatter-add [{n}x{D}]        {t*1e3:8.2f} ms")

    sorted_rows = jnp.sort(rows)
    t = timeit(jax.jit(lambda e, r, g: e.at[r].add(g)),
               emb, sorted_rows, grads)
    print(f"scatter-add sorted ids       {t*1e3:8.2f} ms")

    t = timeit(jax.jit(
        lambda e, r, g: e.at[r].add(g, unique_indices=True)),
        emb, sorted_rows, grads)
    print(f"scatter-add sorted+unique    {t*1e3:8.2f} ms")

    donating = jax.jit(lambda e, r, g: e.at[r].add(g), donate_argnums=(0,))
    e2 = jnp.array(emb)
    t = timeit(donating, e2, rows, grads, n=1, warmup=0)
    print(f"scatter-add donated (1x)     {t*1e3:8.2f} ms")

    _tick("segment-sum")
    # segment_sum path (the merge): ids -> full table-sized accumulator
    t = timeit(jax.jit(lambda p, r: jax.ops.segment_sum(
        p, r, num_segments=N_ROWS)), payload, rows)
    print(f"segment_sum->table [{n}]     {t*1e3:8.2f} ms")

    # segment_sum into a small (batch-sized) accumulator after sort-rank
    t = timeit(jax.jit(lambda p, r: jax.ops.segment_sum(
        p, r % n, num_segments=n)), payload, rows)
    print(f"segment_sum->batch [{n}]     {t*1e3:8.2f} ms")

    # dense optimizer sweep over full table (adagrad-style)
    @jax.jit
    def dense_update(e, s, acc):
        g = acc[:, :D]
        s2 = s + g * g
        return e - 0.05 * g * lax.rsqrt(s2 + 1e-8), s2
    acc = jnp.zeros((N_ROWS, D), jnp.float32)
    t = timeit(dense_update, emb, state, acc)
    print(f"dense adagrad sweep [{N_ROWS}x{D}]  {t*1e3:8.2f} ms")

    # one-hot matmul alternative for the pull (gather as matmul)? At
    # 426K x 4M that is infeasible; skip.

    _tick("mlp")
    # the MLP fwd+bwd at bench size, f32 and bf16
    dims = [SLOTS * D + 13, 400, 400, 400, 1]
    for dt_ in (jnp.float32, jnp.bfloat16):
        ws = [jnp.asarray(rng.normal(size=(a, b)) * 0.05, dt_)
              for a, b in zip(dims[:-1], dims[1:])]
        x = jnp.asarray(rng.normal(size=(BATCH, dims[0])), dt_)
        y = jnp.asarray(rng.random(BATCH) < 0.3, jnp.float32)

        def loss_fn(ws, x, y):
            h = x
            for w in ws[:-1]:
                h = jax.nn.relu(h @ w)
            logit = (h @ ws[-1])[:, 0].astype(jnp.float32)
            p = jax.nn.sigmoid(logit)
            return -jnp.mean(y * jnp.log(p + 1e-7)
                             + (1 - y) * jnp.log(1 - p + 1e-7))
        t = timeit(jax.jit(jax.grad(loss_fn)), ws, x, y)
        print(f"MLP fwd+bwd {dt_.__name__} [{BATCH}]    {t*1e3:8.2f} ms")

    # AUC histogram accumulate
    probs = jnp.asarray(rng.random(BATCH), jnp.float32)
    labels = jnp.asarray(rng.random(BATCH) < 0.3, jnp.float32)
    NB = 1 << 16

    @jax.jit
    def auc_acc(hist, probs, labels):
        b = jnp.clip((probs * NB).astype(jnp.int32), 0, NB - 1)
        idx = b + (labels.astype(jnp.int32)) * NB
        return hist.at[idx].add(1.0)
    hist = jnp.zeros((2 * NB,), jnp.float32)
    t = timeit(auc_acc, hist, probs, labels)
    print(f"AUC hist scatter [{BATCH}]   {t*1e3:8.2f} ms")

    _tick("pass-boundary")
    # Fused end/begin boundary program (FLAGS_pass_boundary_fuse) at
    # bench pass shapes: 4M-row resident store, 20K-key next pass, half
    # the pass shared with the ending one. Three rows: the end_pass
    # scatter alone, the remainder merge-gather alone (the two-dispatch
    # boundary), and the fused single-dispatch program. Compare the
    # fused win with the dispatch RTT (the empty-step row above) to see
    # how much of it is the saved dispatch rather than device time.
    W = 2 * D + 8
    PASS = 20_000
    rps = 1 << (PASS - 1).bit_length()          # pow2 rows_per_shard
    scratch = N_ROWS                            # store scratch row
    store_vals = jnp.asarray(
        rng.normal(size=(N_ROWS + 1, W)), jnp.float32)
    prev_block = jnp.asarray(rng.normal(size=(rps + 1, W)), jnp.float32)
    next_block = jnp.zeros((rps + 1, W), jnp.float32)
    prev_idx_h = np.full((rps,), scratch, np.int32)
    prev_idx_h[:PASS] = rng.choice(N_ROWS, PASS, replace=False)
    prev_idx = jnp.asarray(prev_idx_h)
    m = PASS // 2                               # shared remainder
    m_cap = 1 << (m - 1).bit_length()
    idx_h = np.full((m_cap,), scratch, np.int32)
    idx_h[:m] = rng.choice(N_ROWS, m, replace=False)
    place_h = np.full((m_cap,), rps, np.int32)
    place_h[:m] = rng.choice(PASS, m, replace=False)
    nidx, nplace = jnp.asarray(idx_h), jnp.asarray(place_h)

    # Non-donating probe twins of device_store's boundary programs (the
    # real ones donate the store/block, which a repeat-timing loop
    # cannot feed; op structure is identical).
    scat = jax.jit(lambda v, b, i: v.at[i].set(b[:rps]))
    merge = jax.jit(lambda b, v, i, p: b.at[p].set(v[i]).at[rps].set(0.0))

    @jax.jit
    def fused(v, pb, pi, nb, ni, pl):
        v = v.at[pi].set(pb[:rps])
        out = nb.at[pl].set(v[ni])
        return v, out.at[rps].set(0.0)

    t = timeit(scat, store_vals, prev_block, prev_idx)
    print(f"boundary scatter [{PASS}x{W}]    {t*1e3:8.2f} ms")
    t = timeit(merge, next_block, store_vals, nidx, nplace)
    print(f"boundary merge [{m}x{W}]     {t*1e3:8.2f} ms")
    t = timeit(fused, store_vals, prev_block, prev_idx, next_block,
               nidx, nplace)
    print(f"boundary fused (1 dispatch)  {t*1e3:8.2f} ms "
          f"(vs scatter+merge = 2 dispatches)")

    _tick("quantized-psum")
    # int8 dense-grad codec probe (FLAGS_dense_allreduce_dtype): the
    # blocked quantize -> dequantize round-trip at fused dense-grad
    # size — the per-step device cost quantized_psum adds on TOP of
    # the DCN byte win (the collective itself needs a multi-device
    # mesh; bench multihost carries the byte accounting).
    from paddlebox_tpu.multihost.quant import (dequantize_blocked,
                                               quantize_blocked)
    GRAD = 1 << 20                             # ~1M-param dense block
    QB = 128
    g8 = jnp.asarray(rng.normal(size=(8, GRAD // 8)), jnp.float32)

    @jax.jit
    def qdq(x):
        q, s = quantize_blocked(x, QB)
        return dequantize_blocked(q, s, x.shape[1], QB)

    t = timeit(qdq, g8)
    print(f"int8 grad codec round-trip [{GRAD}] {t*1e3:8.2f} ms "
          f"(block {QB})")

    _tick("bandwidth")
    # D2H bandwidth at end_pass sizes (np.asarray = the write-back path)
    for arr in (emb, jnp.asarray(rng.normal(size=(N_ROWS,)), jnp.float32)):
        sync(arr)
        t0 = time.perf_counter()
        h = np.asarray(arr)
        dt = time.perf_counter() - t0
        print(f"D2H {h.nbytes/1e6:7.1f} MB          {dt*1e3:8.2f} ms "
              f"({h.nbytes/dt/1e9:.3f} GB/s)")

    # H2D bandwidth (feed_pass path): device_put + 4-byte readback
    h = np.asarray(emb)
    t0 = time.perf_counter()
    d = jax.device_put(h)
    sync(d)
    dt = time.perf_counter() - t0
    print(f"H2D {h.nbytes/1e6:7.1f} MB          {dt*1e3:8.2f} ms "
          f"({h.nbytes/dt/1e9:.3f} GB/s)")


if __name__ == "__main__":
    main()
