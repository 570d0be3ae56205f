"""AutoInt through CTRTrainer end-to-end + a numpy attention oracle."""

import numpy as np
import pytest

from paddlebox_tpu.data.dataset import Dataset
from paddlebox_tpu.data.slots import DataFeedConfig, SlotConf
from paddlebox_tpu.embedding import TableConfig
from paddlebox_tpu.models import AutoInt
from paddlebox_tpu.parallel import HybridTopology, build_mesh
from paddlebox_tpu.train import CTRTrainer, TrainerConfig

SLOTS = ("a", "b")


@pytest.mark.slow  # 6 s learn loop; the numpy-oracle parity test stays tier-1; tier-1 keeps its 870 s window (PR 21)
def test_autoint_learns_interaction(tmp_path):
    mesh = build_mesh(HybridTopology(dp=8))
    feed = DataFeedConfig(
        slots=tuple(SlotConf(s, avg_len=1.0) for s in SLOTS),
        batch_size=64)
    model = AutoInt(slot_names=SLOTS, emb_dim=8, att_dim=16, num_heads=2,
                    num_layers=2, hidden=(32,))
    tr = CTRTrainer(model, feed, TableConfig(dim=8, learning_rate=0.2),
                    mesh=mesh,
                    config=TrainerConfig(auc_num_buckets=1 << 10,
                                         dense_learning_rate=3e-3))
    tr.init(seed=0)
    rng = np.random.default_rng(9)
    p = str(tmp_path / "part")
    with open(p, "w") as f:
        for _ in range(512):
            a, b = rng.integers(1, 60), rng.integers(1, 60)
            # Pure interaction signal (same planting as the DCN/CIN
            # tests): neither field alone predicts the label.
            label = int(((a % 2) == (b % 2)) == (rng.random() < 0.85))
            f.write(f"{label} a:{a} b:{b}\n")
    losses = []
    for _ in range(7):
        ds = Dataset(feed, num_reader_threads=1)
        ds.set_filelist([p])
        ds.load_into_memory()
        stats = tr.train_pass(ds)
        losses.append(stats["loss"])
    assert losses[-1] < losses[0]
    assert stats["auc"] > 0.62, stats["auc"]


def test_autoint_matches_numpy_oracle():
    """apply() against an independently written numpy attention tower
    with TWO layers and att_dim != emb_dim, so any head/field axis mixup
    or residual-projection slip changes the answer."""
    import jax
    import jax.numpy as jnp

    model = AutoInt(slot_names=SLOTS, emb_dim=4, att_dim=6, num_heads=3,
                    num_layers=2, hidden=())
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    bs = 3
    emb = {s: jnp.asarray(rng.normal(size=(bs, 4)), jnp.float32)
           for s in SLOTS}
    w = {s: jnp.asarray(rng.normal(size=(bs,)), jnp.float32)
         for s in SLOTS}
    segs = {s: jnp.arange(bs, dtype=jnp.int32) for s in SLOTS}
    got = np.asarray(model.apply(params, emb, w, segs, batch_size=bs))

    def softmax(z):
        e = np.exp(z - z.max(axis=-1, keepdims=True))
        return e / e.sum(axis=-1, keepdims=True)

    x = np.stack([np.asarray(emb[s]) for s in SLOTS], axis=1)  # [B,2,4]
    dh = 2
    for layer in params["att"]:
        wq, wk, wv = (np.asarray(layer[k]) for k in ("wq", "wk", "wv"))
        heads = []
        for hh in range(3):
            q = x @ wq[hh]                                  # [B,m,dh]
            k = x @ wk[hh]
            v = x @ wv[hh]
            s = q @ np.swapaxes(k, 1, 2) / np.sqrt(dh)
            heads.append(softmax(s) @ v)
        o = np.concatenate(heads, axis=-1)                  # [B,m,6]
        x = np.maximum(o + x @ np.asarray(layer["wr"]), 0.0)
    head = params["head"]
    logits = (x.reshape(bs, -1) @ np.asarray(head["w"])
              )[:, 0] + np.asarray(head["b"])[0]
    wide = sum(np.asarray(w[s]) for s in SLOTS)
    ref = logits + wide + float(params["bias"])
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_autoint_rejects_mixed_widths():
    with pytest.raises(ValueError, match="uniform emb_dim"):
        AutoInt(slot_names=SLOTS, emb_dim={"a": 4, "b": 8}).init(
            __import__("jax").random.PRNGKey(0))
    with pytest.raises(ValueError, match="must divide"):
        AutoInt(slot_names=SLOTS, emb_dim=4, att_dim=5,
                num_heads=2).init(__import__("jax").random.PRNGKey(0))
    with pytest.raises(ValueError, match="num_layers"):
        AutoInt(slot_names=SLOTS, emb_dim=4, num_layers=0).init(
            __import__("jax").random.PRNGKey(0))
