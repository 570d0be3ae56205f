"""AOT Mosaic-compile every Pallas kernel at its bench shapes — no TPU needed.

The interpret-mode tests prove the kernels' math; they prove nothing
about whether Mosaic accepts their memory ops (alignment/tiling rules
only the real TPU pipeline enforces — r03 shipped two kernels that were
interpret-correct and Mosaic-rejected: the sorted scatter's unaligned
DMA offsets and the flash attention's (1, block_q) row-stat blocks).
jax's compile-only PJRT topology compiles for TPU with no TPU attached:

    python tools/aot_check_kernels.py

Runs as its own process (tests/test_aot_step.py) because libtpu admits
one process at a time. Compiling is not executing: chip_smoke.py phase 2
runs the same kernels at the same shapes on the chip.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from tools._aot_common import tpu_topology  # noqa: E402

# (updates, payload width, rows incl. trash) — the deepfm_criteo cells'
# push, a Wide&Deep-sized push, and the tiny probe shape.
SCATTER_SHAPES = [
    (425_984, 20, 4_194_305),
    (163_840, 12, 1_048_577),
    (64, 8, 9000),
]

# (requests, pull width, table width, rows incl. trash) — the
# deepfm_criteo cells' pull (426K ids from the [4M, W] fused table; rows
# NOT a multiple of the kernel BLOCK, so this also pins Mosaic's padded
# tail-block fetch) and the tiny probe shape.
GATHER_SHAPES = [
    (425_984, 16, 20, 4_194_305),
    (425_984, 40, 40, 4_194_305),
    (64, 8, 9, 9000),
]


def main() -> None:
    from paddlebox_tpu.ops.pallas_kernels.flash_attention import (
        BlockDiffusionMask, flash_attention)
    from paddlebox_tpu.ops.pallas_kernels.grouped_matmul import (
        grouped_matmul, grouped_weight_grad, scatter_add_rows)
    from paddlebox_tpu.ops.pallas_kernels.seqpool_cvm import (
        seqpool_cvm_pallas)
    from paddlebox_tpu.ops.pallas_kernels.sorted_gather import sorted_gather
    from paddlebox_tpu.ops.pallas_kernels.sorted_scatter import (
        sorted_scatter_accumulate)
    from paddlebox_tpu.ops.pallas_kernels.ssd_scan import ssd_scan

    topo = tpu_topology("v5e:2x2x1")
    if topo is None:
        return
    sh = NamedSharding(Mesh([topo.devices[0]], ("d",)), P())

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    for n, aw, rows_n in SCATTER_SHAPES:
        jax.jit(lambda r, p: sorted_scatter_accumulate(r, p, rows_n)).lower(
            sds((n,), jnp.int32), sds((n, aw), jnp.float32)).compile()
        print(f"AOT sorted_scatter [{n} x {aw}] -> {rows_n}: OK", flush=True)

    for n, pw, w, rows_n in GATHER_SHAPES:
        jax.jit(lambda r, t: sorted_gather(r, t, width=pw)).lower(
            sds((n,), jnp.int32), sds((rows_n, w), jnp.float32)).compile()
        print(f"AOT sorted_gather [{n}] <- [{rows_n} x {w}] width {pw}: OK",
              flush=True)

    # A GPT-2-medium-wide batch: [4, 1024, 16, 64], causal, with gradients.
    q = sds((4, 1024, 16, 64), jnp.float32)
    jax.jit(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        use_pallas=True).sum(),
        argnums=(0, 1, 2))).lower(q, q, q).compile()
    print("AOT flash_attention fwd+bwd [4, 1024, 16, 64]: OK", flush=True)

    # The hybrid stack's kernels at published widths and the benchmark
    # cell's 8,192 positions (benchmarks/configs/nemotron3_super_120b.json):
    # 32 query heads over 2 key/value heads of 128, and the Mamba-2 scan
    # with 128 heads of 64, state 128, 8 groups, chunks of 128, at both
    # operand precisions.
    q = sds((1, 8192, 32, 128), jnp.float32)
    kv = sds((1, 8192, 2, 128), jnp.float32)
    jax.jit(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        use_pallas=True).sum(),
        argnums=(0, 1, 2))).lower(q, kv, kv).compile()
    print("AOT flash_attention grouped fwd+bwd [1, 8192, 32/2, 128]: OK",
          flush=True)
    # The looped stack's attention at published widths and the benchmark
    # cell's 4,096 positions (benchmarks/configs/ouro_2_6b.json): 16 query
    # heads over 16 key/value heads of 128.
    q = sds((1, 4096, 16, 128), jnp.float32)
    jax.jit(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        use_pallas=True).sum(),
        argnums=(0, 1, 2))).lower(q, q, q).compile()
    print("AOT flash_attention fwd+bwd [1, 4096, 16/16, 128]: OK", flush=True)
    # The block-diffusion stack's attention at published widths and the
    # benchmark cell's 4,096 positions (benchmarks/configs/sdar_30b_a3b.json):
    # 8,192 rows, 32 query heads over 4 key/value heads of 128, under the
    # block mask (blocks of 4; of 6: a block length that is no power of two
    # divides in the edge tiles).
    for seq, block in ((4096, 4), (3072, 6)):
        q = sds((1, 2 * seq, 32, 128), jnp.float32)
        kv = sds((1, 2 * seq, 4, 128), jnp.float32)
        rule = BlockDiffusionMask(seq, block)
        jax.jit(jax.grad(
            lambda q, k, v: flash_attention(q, k, v, mask=rule,
                                            use_pallas=True).sum(),
            argnums=(0, 1, 2))).lower(q, kv, kv).compile()
        print(f"AOT flash_attention block-diffusion fwd+bwd "
              f"[1, {2 * seq}, 32/4, 128] blocks of {block}: OK", flush=True)
    scan_args = (sds((1, 8192, 128, 64), jnp.float32),
                 sds((1, 8192, 128), jnp.float32), sds((128,), jnp.float32),
                 sds((1, 8192, 8, 128), jnp.float32),
                 sds((1, 8192, 8, 128), jnp.float32),
                 sds((128,), jnp.float32))
    for mxu in (jnp.bfloat16, jnp.float32):
        jax.jit(jax.grad(
            lambda *a: ssd_scan(*a, chunk=128, use_pallas=True,
                                mxu_dtype=mxu).sum(),
            argnums=tuple(range(6)))).lower(*scan_args).compile()
        print(f"AOT ssd_scan fwd+bwd [1, 8192, 128, 64] "
              f"{jnp.dtype(mxu).name}: OK", flush=True)

    # The two expert stacks' products at published widths over the rows a
    # trip of the dispatch's loop may hold, at both operand precisions:
    # the rows' product and its transposed-weight twin, the weights'
    # gradient summed into its argument's buffer, and a trip's rows added
    # to their tokens' (the cell's 8,192 rows). Block diffusion: 16 held
    # experts, gate and up-projection side by side (2048 -> 1536) and 768
    # -> 2048 out, trips of one to three shares of 8,192 rows; the hybrid
    # stack: 8 held squared-ReLU experts, 1024 -> 2688 -> 1024, trips of
    # one and two shares of 2,816 rows.
    for held, widths, trips, width in (
            (16, ((2048, 1536), (768, 2048)), (8192, 16384, 24576), 2048),
            (8, ((1024, 2688), (2688, 1024)), (2816, 5632), 1024)):
        sizes = sds((held,), jnp.int32)
        for rows in trips:
            for mxu in (jnp.bfloat16, jnp.float32):
                for k, n in widths:
                    for transpose_w in (False, True):
                        w = sds((held, n, k) if transpose_w
                                else (held, k, n), mxu)
                        jax.jit(lambda x, w, s: grouped_matmul(
                            x, w, s, transpose_w=transpose_w,
                            use_pallas=True)).lower(
                            sds((rows, k), mxu), w, sizes).compile()
                    jax.jit(lambda x, dy, s, into: grouped_weight_grad(
                        x, dy, s, into, use_pallas=True),
                        donate_argnums=3).lower(
                        sds((rows, k), mxu), sds((rows, n), mxu), sizes,
                        sds((held, k, n), jnp.float32)).compile()
                shapes = ", ".join(f"{k} -> {n}" for k, n in widths)
                print(f"AOT grouped_matmul fwd+transposed+weights [{rows}, "
                      f"{shapes}] x {held} {jnp.dtype(mxu).name}: OK",
                      flush=True)
            jax.jit(lambda into, index, values: scatter_add_rows(
                into, index, values, use_pallas=True),
                donate_argnums=0).lower(
                sds((8192, width), jnp.float32), sds((rows,), jnp.int32),
                sds((rows, width), jnp.float32)).compile()
            print(f"AOT scatter_add_rows [{rows}, {width}] -> [8192, "
                  f"{width}]: OK", flush=True)

    n, d, rows = 65536, 16, 16384
    sc = sds((n,), jnp.float32)
    jax.jit(lambda e, s, c, g: seqpool_cvm_pallas(
        e, s, c, g, rows, use_pallas=True)).lower(
        sds((n, d), jnp.float32), sc, sc, sds((n,), jnp.int32)).compile()
    print(f"AOT seqpool_cvm [{n} x {d}] -> {rows}: OK", flush=True)

    print("PALLAS KERNELS TPU AOT COMPILE: OK")


if __name__ == "__main__":
    main()
