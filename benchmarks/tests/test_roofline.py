"""Operations and bytes of each kernel against counts made by hand."""

import json
import os

import pytest

from benchmarks.trace.roofline import (dense_train, flash_attention,
                                       least_seconds, sorted_gather,
                                       sorted_scatter)

HERE = os.path.dirname(__file__)
with open(os.path.join(HERE, "..", "trace", "peaks.json")) as f:
    V5E = json.load(f)["TPU v5 lite"]


def test_peaks_are_the_published_v5e_figures():
    assert V5E["bf16_flops_per_s"] == 197e12
    assert V5E["hbm_bytes_per_s"] == 819e9
    assert V5E["ici_bits_per_s"] == 1600e9


def test_least_seconds_picks_the_larger_bound():
    got = least_seconds(197e12, 819e9 / 2, V5E)
    assert got["seconds"] == 1.0 and got["bound"] == "flops"
    got = least_seconds(197e12 / 4, 819e9, V5E)
    assert got["seconds"] == 1.0 and got["bound"] == "bytes"


def test_sorted_gather_bytes():
    # 425,984 ids, 19 float32 columns: ids + rows read + rows written
    shapes = {"ids_per_step_per_chip": 425984, "emb_dim": 16}
    got = sorted_gather.parts(shapes, V5E, {"columns_beside_emb": 3})["call"]
    assert got["bytes"] == 425984 * 4 + 2 * 425984 * 19 * 4 == 66453504
    assert got["bound"] == "bytes"
    assert got["seconds"] == pytest.approx(66453504 / 819e9)


def test_sorted_scatter_bytes():
    shapes = {"ids_per_step_per_chip": 425984, "emb_dim": 16,
              "pass_keys_per_chip": 4000000}
    got = sorted_scatter.parts(shapes, V5E, {"columns_beside_emb": 4})["call"]
    assert got["bytes"] == (425984 * 4 + 425984 * 20 * 4
                            + 4000000 * 20 * 4) == 355782656
    assert got["flops"] == 425984 * 20
    assert got["bound"] == "bytes"


def test_flash_attention_counts():
    shapes = {"batch_per_chip": 2, "seq": 1024, "n_head": 16,
              "head_dim": 64, "dtype_bytes": 4}
    got = flash_attention.parts(shapes, V5E, {})
    # one S x S x D product per (batch, head), causal share 1025 / 2048
    product = 2 * 2 * 16 * 1024 * 1024 * 64 * 1025 / 2048
    assert got["fwd"]["flops"] == pytest.approx(2 * product)
    assert got["dq"]["flops"] + got["dkv"]["flops"] == pytest.approx(
        5 * product)                    # backward = 2.5 x forward
    tensor = 2 * 1024 * 16 * 64 * 4
    assert got["fwd"]["bytes"] == 4 * tensor + 2 * 16 * 1024 * 4
    # float32 tensors at S = 1024, D = 64: the forward is bytes-bound
    assert got["fwd"]["bound"] == "bytes"


def test_dense_flops_per_token_gpt2_medium():
    shapes = {"n_embd": 1024, "n_inner": 4096, "n_layer": 24,
              "vocab_size": 50257, "seq": 1024}
    weights = 24 * (4 * 1024 * 1024 + 2 * 1024 * 4096) + 1024 * 50257
    assert weights == 353_453_056       # no embedding, no position table
    attention = 24 * 2 * 2 * 1024 * 1025 / 2
    assert dense_train.flops_per_token(shapes) == pytest.approx(
        3 * (2 * weights + attention))
    assert 2.2e9 < dense_train.flops_per_token(shapes) < 2.4e9
