"""The day-loop traffic generator: key sets, determinism, the text it
prints (through the program's own parser) and the AUC ceiling."""

import json
import os

import numpy as np

from benchmarks.generators import ctr_pass_files as gen

HERE = os.path.dirname(__file__)


def _plan(name="day_uniform", chips=1, **over):
    with open(os.path.join(HERE, "..", "traffic", name + ".json")) as f:
        traffic = json.load(f)
    with open(os.path.join(HERE, "..", "configs", "deepfm_criteo.json")) as f:
        config = json.load(f)
    traffic.update(traffic["rehearse"])
    traffic.update(over)
    config["store"] = config["rehearse"]["store"]
    config["batch_per_chip"] = config["rehearse"]["batch_per_chip"]
    return gen.plan(traffic, config, chips)


def test_real_sizes_fit_the_store():
    for name, chips in (("day_uniform", 1), ("day_zipf", 1),
                        ("day_uniform_dp4", 4)):
        with open(os.path.join(HERE, "..", "traffic", name + ".json")) as f:
            traffic = json.load(f)
        with open(os.path.join(HERE, "..", "configs",
                               "deepfm_criteo.json")) as f:
            config = json.load(f)
        p = gen.plan(traffic, config, chips)
        assert p["pass_keys"] == 4_000_000 * chips
        assert p["files_per_pass"] == 32 * chips
        grown = p["n_resident"] + p["n_passes"] * p["unseen"]
        assert grown < (1 << config["store"]["rows_log2_per_chip"]) * chips


def test_pass_key_sets():
    p = _plan()
    ranks = np.arange(p["pass_keys"])
    sets = [gen.keys_of_ranks(p, 3, k, ranks) for k in range(p["n_passes"])]
    for k, keys in enumerate(sets):
        assert np.unique(keys).size == p["pass_keys"] and keys.min() >= 1
        beyond = keys > p["n_resident"]
        assert beyond.sum() == p["unseen"]
        if k:
            shared = np.intersect1d(keys, sets[k - 1])
            assert shared.size == p["hot"] + p["core"]
            assert not np.intersect1d(keys[beyond],
                                      sets[k - 1]).size  # unseen are new
    # another seed scatters the same ranks over other resident keys
    assert not np.array_equal(sets[0], gen.keys_of_ranks(p, 4, 0, ranks))


def test_same_seed_same_bytes_and_parser_reads_them_back(tmp_path):
    from paddlebox_tpu.data.parser import parse_lines
    from benchmarks.runners.ctr_day import _feed
    p = _plan()
    a = gen.draw_block(p, 7, 1, 2, 64)
    b = gen.draw_block(p, 7, 1, 2, 64)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], gen.draw_block(p, 8, 1, 2, 64)[0])
    ids, labels, dense = a
    text = gen.format_lines(ids, labels, dense).decode()
    config = {"model": {"slots": p["slots"], "dense_dim": p["dense_dim"]}}
    rows = parse_lines(text.splitlines(), _feed(config, 64))
    assert len(rows) == 64
    for i, row in enumerate(rows):
        assert row.labels[0] == labels[i]
        assert [int(row.sparse[f"s{j}"][0]) for j in range(p["slots"])] \
            == ids[i].tolist()
        np.testing.assert_allclose(row.dense["d"], dense[i] / 1e4, rtol=1e-6)
    # slot 0 is the planted hot head, and no other slot draws from it
    hot = set(gen.keys_of_ranks(p, 7, 1, np.arange(p["hot"])).tolist())
    assert set(ids[:, 0].tolist()) <= hot
    assert not hot & set(ids[:, 1:].ravel().tolist())


def test_zipf_head_is_the_shared_core():
    p = _plan("day_zipf")
    ids, _, _ = gen.draw_block(p, 1, 2, 0, 4096)
    values, counts = np.unique(ids[:, 1:], return_counts=True)
    top = values[np.argmax(counts)]
    assert 0.15 < counts.max() / counts.sum() < 0.21     # Zipf(1.2): ~18%
    first_body = gen.keys_of_ranks(p, 1, 2, np.array([p["hot"]]))[0]
    assert top == first_body
    assert first_body == gen.keys_of_ranks(p, 1, 5, np.array([p["hot"]]))[0]


def test_auc_ceiling_matches_a_simulation():
    p = _plan()
    ceiling = gen.auc_ceiling(p, 3)
    assert 0.84 < ceiling < 0.87
    rng = np.random.default_rng(0)
    hot = gen.keys_of_ranks(p, 3, 0, rng.integers(0, p["hot"], 200_000))
    prob = gen.planted_probability(hot, p["label_rate"], p["label_strength"])
    labels = rng.random(prob.size) < prob
    from benchmarks.reference.deepfm_criteo import rank_auc
    assert abs(rank_auc(prob, labels.astype(float)) - ceiling) < 0.004
