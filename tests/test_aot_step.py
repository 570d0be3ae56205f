"""Everything the chip compiles must compile through the real XLA:TPU +
Mosaic pipeline (compile-only PJRT topology): each Pallas kernel at its
bench shapes, and the full CTR train/eval step — program-level insurance
the per-kernel checks can't give (shard_map + donation + Pallas
custom-call interactions). Each check is a tools/aot_check_*.py run in
its own subprocess, one at a time: libtpu admits one process at a time,
so no test here (or anywhere in the suite) loads it in the pytest
process."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_tool(name, timeout, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", name), *args],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=timeout)
    # The tools print this sentinel (and exit cleanly) only where libtpu
    # is not installed. Any other failure to reach the compile-only
    # topology — the multi-process lockfile included — is a failure.
    if "TPU-AOT-NO-LIBTPU" in proc.stdout:
        pytest.skip("libtpu is not installed")
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


@pytest.mark.slow
def test_pallas_kernels_aot_compile_for_tpu():
    """sorted_scatter, sorted_gather, flash_attention fwd+bwd (the three
    dense cells' shapes: equal heads of 64 and of 128, grouped heads),
    ssd_scan fwd+bwd, the two expert stacks' grouped products and
    seqpool_cvm at the shapes the benchmarks use."""
    out = _run_tool("aot_check_kernels.py", 900)
    assert out.count("AOT sorted_scatter") == 3
    assert out.count("AOT sorted_gather") == 3
    assert "AOT flash_attention fwd+bwd [4, 1024, 16, 64]" in out
    assert "AOT flash_attention fwd+bwd [1, 4096, 16/16, 128]" in out
    assert "AOT flash_attention grouped fwd+bwd" in out
    assert out.count("AOT flash_attention block-diffusion fwd+bwd") == 2
    assert out.count("AOT ssd_scan fwd+bwd") == 2
    assert out.count("AOT grouped_matmul fwd+transposed+weights") == 10
    assert out.count("AOT scatter_add_rows") == 5
    assert "AOT seqpool_cvm" in out
    assert "PALLAS KERNELS TPU AOT COMPILE: OK" in out


@pytest.mark.slow
def test_full_ctr_step_aot_compiles_for_tpu():
    out = _run_tool("aot_check_step.py", 900)
    assert "FULL-STEP TPU AOT COMPILE: OK" in out
    assert "EVAL-STEP TPU AOT COMPILE: OK" in out
    # K-step scanned megastep (train + eval), Pallas kernels inside the
    # scan body, through the same Mosaic pipeline.
    assert "MEGASTEP(K=4) TPU AOT COMPILE: OK" in out
    assert "MEGASTEP-EVAL(K=4) TPU AOT COMPILE: OK" in out
    # Fused end/begin pass-boundary program (FLAGS_pass_boundary_fuse):
    # one dispatch per boundary must keep compiling for TPU, single-chip
    # and sharded-all_to_all variants both.
    assert "FUSED-BOUNDARY(local) TPU AOT COMPILE: OK" in out
    assert "FUSED-BOUNDARY(sharded S=" in out
    # Slot-column split store (FLAGS_table_slot_placement=split|host):
    # the two-part scatter/boundary programs are distinct from the
    # fused 1-tuple layout and must lower for TPU on their own.
    assert "SPLIT-SLOT-PUSH(sharded S=" in out
    # ZeRO-sharded dense update (FLAGS_dense_zero=shard): psum ->
    # zero_slice -> shard update -> all-gather inside the full dp=4
    # shard_map'd step, clip-decomposed adam included.
    assert "ZERO-STEP(dp=4, adam+clip) TPU AOT COMPILE: OK" in out


@pytest.mark.slow
def test_multichip_steps_aot_compile_for_tpu():
    """GPT hybrid (pp x sp, 1F1B, ring attention) and CTR dp=4 (sharded
    table all-to-all) through the real TPU pipeline on a 4-device
    compile-only topology — ICI collective lowering included."""
    out = _run_tool("aot_check_multichip.py", 900)
    assert "MULTICHIP TPU AOT COMPILE: OK" in out


@pytest.mark.slow
def test_dense_bench_steps_aot_compile_for_tpu():
    """resnet50 (bf16 conv fwd+transpose under autodiff) and BERT-base
    train steps at their bench shapes."""
    out = _run_tool("aot_check_dense.py", 900)
    assert "DENSE BENCH TPU AOT COMPILE: OK" in out


@pytest.mark.slow
def test_hybrid_cell_programs_fit_the_v5e():
    """Tier-1 has no chip: this is what guards the hybrid stack's memory
    plan. The benchmark cell's timed step and its set-up's ``highest``
    gradient function, compiled for the v5e at published widths and 8,192
    positions, each under the tool's stated share of the chip, under a
    plan that keeps the attention layer's and every expert layer's
    values (how many Mamba in-projections fit beside them is the
    plan's to say)."""
    out = _run_tool("aot_check_dense.py", 1500, "--hybrid")
    plan = out.split("hybrid plan: ", 1)[1].splitlines()[0]
    assert "E:5,*:1" in plan, plan
    assert "AOT hybrid step: " in out
    assert "AOT hybrid setup gradient: " in out
    assert "AOT hybrid step and setup gradient fit: OK" in out


@pytest.mark.slow
def test_looped_cell_programs_fit_the_v5e():
    """The looped cell's timed step and its set-up's ``highest`` gradient
    function, compiled for the v5e at published widths, 12 layers x 4
    passes and 4,096 positions, each under the tool's stated share of the
    chip, under a plan that keeps the flash kernel's output in every pass
    (what else fits is the plan's to say)."""
    out = _run_tool("aot_check_dense.py", 1500, "--looped")
    plan = out.split("looped plan: ", 1)[1].splitlines()[0]
    assert "flash_out:12/12/12/12" in plan, plan
    assert "AOT looped step: " in out
    assert "AOT looped setup gradient: " in out
    assert "AOT looped step and setup gradient fit: OK" in out


@pytest.mark.slow
def test_block_diffusion_cell_programs_fit_the_v5e():
    """The block-diffusion cell's timed step and its set-up's ``highest``
    gradient function, compiled for the v5e at published widths, 12
    layers, 16 held experts and 4,096 positions (8,192 rows), each under
    the tool's stated share of the chip, under a plan that keeps the
    router's values in every piece (which pieces keep the flash output is
    the plan's to say); 80 of the mask's 256 tiles a head are live."""
    out = _run_tool("aot_check_dense.py", 1500, "--blockdiff")
    plan = out.split("blockdiff plan: ", 1)[1].splitlines()[0]
    assert '"layers_kept": "L:4"' in plan and "moe_logits" in plan, plan
    assert '"tiles_live": 80' in plan and '"tiles_edge": 24' in plan
    # the expert dispatch's loop: blocks of one even router's share over
    # grouped products in 128-row tiles
    assert '"dispatch_block_rows": 8192' in plan, plan
    assert '"dispatch_row_tile": 128' in plan, plan
    assert "AOT blockdiff step: " in out
    assert "AOT blockdiff setup gradient: " in out
    assert "AOT blockdiff step and setup gradient fit: OK" in out


@pytest.mark.slow
def test_scale_steps_aot_compile_for_tpu_256_chips():
    """The 8->256-chip scaling evidence one chip or one four-chip host
    can't give: the multislice CTR step (slice=4 x dp=64) and the
    hybrid GPT step (slice x dp x pp x sp x mp) lower + compile against
    a real 16x16 v5e compile-only topology — XLA schedules the full
    256-chip collective program (slice axis logical on the single-slice
    compile topology; DCN semantics pinned by test_multislice)."""
    out = _run_tool("aot_check_scale.py", 1500, "--chips", "256")
    assert "SCALE TPU AOT COMPILE (256 chips): OK" in out
