"""Follow-up probes: scatter cost scaling + merge-as-dense-sweep feasibility
+ transfer bandwidths. See tools/profile_step.py; results in PROFILE.md."""
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_tiny = jax.jit(lambda x: lax.slice(x.ravel(), (0,), (1,)))


def sync(r):
    return np.asarray(_tiny(jax.tree_util.tree_leaves(r)[0]))


def timeit(fn, *args, n=10, warmup=2):
    for _ in range(warmup):
        r = fn(*args)
    sync(r)
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn(*args)
    sync(r)
    return (time.perf_counter() - t0) / n


def main():
    N_ROWS = 4 * 1024 * 1024
    D = 16
    n = 425984
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.integers(0, N_ROWS, n), jnp.int32)
    srows = jnp.sort(rows)
    emb = jnp.asarray(rng.normal(size=(N_ROWS, D)), jnp.float32)
    grads = jnp.asarray(rng.normal(size=(n, D)), jnp.float32)
    W = 40
    gradsW = jnp.asarray(rng.normal(size=(n, W)), jnp.float32)
    fused = jnp.asarray(rng.normal(size=(N_ROWS, W)), jnp.float32)
    sync(fused)

    # scatter width scaling: 1 wide scatter vs several narrow
    t = timeit(jax.jit(lambda e, r, g: e.at[r].add(g)), fused, rows, gradsW)
    print(f"scatter-add [{n}x{W}]           {t*1e3:8.2f} ms")
    scalar = jnp.asarray(rng.normal(size=(N_ROWS,)), jnp.float32)
    gs = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    t = timeit(jax.jit(lambda e, r, g: e.at[r].add(g)), scalar, rows, gs)
    print(f"scatter-add [{n}x1]             {t*1e3:8.2f} ms")

    # scatter into SMALL table (row count scaling)
    small = jnp.asarray(rng.normal(size=(n, D)), jnp.float32)
    rsmall = jnp.asarray(rng.integers(0, n, n), jnp.int32)
    t = timeit(jax.jit(lambda e, r, g: e.at[r].add(g)), small, rsmall, grads)
    print(f"scatter-add into [{n}] rows     {t*1e3:8.2f} ms")

    # scatter .set vs .add
    t = timeit(jax.jit(lambda e, r, g: e.at[r].set(g)), emb, srows, grads)
    print(f"scatter-SET sorted [{n}x{D}]    {t*1e3:8.2f} ms")

    # gather with many indices from SMALL source (the aligned-merge path)
    big_idx = jnp.asarray(rng.integers(0, n, N_ROWS), jnp.int32)
    src = jnp.asarray(rng.normal(size=(n, 20)), jnp.float32)  # 34MB
    t = timeit(jax.jit(lambda s, i: s[i]), src, big_idx)
    print(f"gather [{N_ROWS}] from [{n}x20] {t*1e3:8.2f} ms")

    # searchsorted: 4M queries into sorted 426K keys
    skeys = jnp.sort(jnp.asarray(
        rng.choice(np.arange(N_ROWS, dtype=np.int32), n, replace=False)))
    queries = jnp.arange(N_ROWS, dtype=jnp.int32)
    t = timeit(jax.jit(lambda k, q: jnp.searchsorted(k, q)), skeys, queries)
    print(f"searchsorted 4M into 426K       {t*1e3:8.2f} ms")

    # searchsorted small into big (bucketing by shard boundary alternative)
    t = timeit(jax.jit(lambda k, q: jnp.searchsorted(k, q)),
               jnp.sort(queries), skeys)
    print(f"searchsorted 426K into 4M       {t*1e3:8.2f} ms")

    # cumsum-based alternatives: segment boundaries via diff of sorted ids
    @jax.jit
    def seg_merge(sr, g):
        is_start = jnp.concatenate([jnp.ones((1,), bool), sr[1:] != sr[:-1]])
        seg = jnp.cumsum(is_start) - 1
        return jax.ops.segment_sum(g, seg, num_segments=n)
    t = timeit(seg_merge, srows, grads)
    print(f"merge segment_sum->[{n}]        {t*1e3:8.2f} ms")

    # full dense-sweep merge: searchsorted + small-gather + where
    @jax.jit
    def dense_merge(table, urow, uval):
        # urow: sorted unique update rows [m] (padded with N_ROWS)
        # uval: merged updates [m, D]
        pos = jnp.searchsorted(urow, jnp.arange(N_ROWS, dtype=jnp.int32))
        pos_c = jnp.minimum(pos, urow.shape[0] - 1)
        hit = urow[pos_c] == jnp.arange(N_ROWS, dtype=jnp.int32)
        upd = uval[pos_c]
        return table + jnp.where(hit[:, None], upd, 0.0)
    urow = srows
    t = timeit(dense_merge, emb, urow, grads)
    print(f"dense-sweep merge total         {t*1e3:8.2f} ms")

    # D2H / H2D bandwidths (finishing what profile_step.py crashed before)
    for arr in (emb, scalar):
        sync(arr)
        t0 = time.perf_counter()
        h = np.asarray(arr)
        dt = time.perf_counter() - t0
        print(f"D2H {h.nbytes/1e6:7.1f} MB            {dt*1e3:8.2f} ms "
              f"({h.nbytes/dt/1e9:.3f} GB/s)")
    h = np.asarray(emb)
    for _ in range(2):
        t0 = time.perf_counter()
        d = jax.device_put(h)
        sync(d)
        dt = time.perf_counter() - t0
        print(f"H2D {h.nbytes/1e6:7.1f} MB            {dt*1e3:8.2f} ms "
              f"({h.nbytes/dt/1e9:.3f} GB/s)")

    # D2H in parallel chunks (does the host link parallelize?)
    from concurrent.futures import ThreadPoolExecutor
    chunks = [emb[i * (N_ROWS // 8):(i + 1) * (N_ROWS // 8)]
              for i in range(8)]
    for c in chunks:
        sync(c)
    t0 = time.perf_counter()
    with ThreadPoolExecutor(8) as ex:
        res = list(ex.map(np.asarray, chunks))
    dt = time.perf_counter() - t0
    tot = sum(r.nbytes for r in res)
    print(f"D2H {tot/1e6:7.1f} MB x8 threads   {dt*1e3:8.2f} ms "
          f"({tot/dt/1e9:.3f} GB/s)")


if __name__ == "__main__":
    main()
