"""Shard-host kill -9 drill: survive host loss under live traffic.

The acceptance bar of the replicated tier (MULTIHOST.md "replicated
tier"): two REAL shard-host processes hold a replicas=2 world; a
DayRunner trains against them while serving-style readers hammer the
``pull_serving`` miss path. One host is SIGKILL'd between passes:

- every concurrent serving read keeps succeeding (reads fail over to
  the surviving replica — ZERO failed client RPCs);
- the interrupted training pass costs one self-heal retry: the
  pass-retry hook PROMOTES the surviving backup to primary, the
  rollback reloads the published chain from live servers only, and the
  replay is bit-identical — final losses, dense params, and store
  contents equal a never-killed single-host reference;
- a fresh host joins through the elastic rank table and the
  pass-boundary hook RE-REPLICATES the thinned slots to it, restoring
  the replication factor, with content digests matching the survivor.
"""

import hashlib
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from paddlebox_tpu.embedding.store import _FIELDS
from paddlebox_tpu.embedding.table import TableConfig
from paddlebox_tpu.launch.elastic import read_rank_table
from paddlebox_tpu.multihost import MultiHostStore, ReplicaMap, ShardClient
from paddlebox_tpu.multihost.reshard import ElasticReshardController
from paddlebox_tpu.serving.fleet import ShardBackedStore
from tests.test_multihost_ctr import (DAY, _make_runner, _store_rows,
                                      _write_day)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = TableConfig(name="emb", dim=8, learning_rate=0.1)


def _spawn_host(root: str, host_id: str, index: int, world: int = 2):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable,
         os.path.join(REPO, "tests", "shard_host_worker.py"),
         root, host_id, str(index), str(world)],
        env=env, cwd=REPO,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    ep_file = os.path.join(root, f"{host_id}.ep")
    deadline = time.time() + 60
    while time.time() < deadline:
        if os.path.exists(ep_file):
            with open(ep_file) as f:
                return proc, json.load(f)["endpoint"]
        if proc.poll() is not None:
            raise RuntimeError(f"worker {host_id} died rc={proc.returncode}")
        time.sleep(0.05)
    raise TimeoutError(f"worker {host_id} never advertised an endpoint")


class _ServingReaders:
    """Concurrent pull_serving traffic: the fleet's shard-miss path.
    Counts every failed read — the drill pins the count at ZERO."""

    def __init__(self, backed: ShardBackedStore, keys: np.ndarray,
                 threads: int = 3):
        self._backed = backed
        self._keys = keys
        self._stop = threading.Event()
        self.failures = []
        self.reads = 0
        self._lock = threading.Lock()
        self._threads = [threading.Thread(target=self._loop, daemon=True)
                         for _ in range(threads)]

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                found, vals = self._backed.read(self._keys)
                assert vals.shape == (self._keys.size,
                                      self._backed.dim + 1)
                with self._lock:
                    self.reads += 1
            except Exception as e:  # noqa: BLE001 — the drill records all
                with self._lock:
                    self.failures.append(repr(e))
            time.sleep(0.01)

    def __enter__(self):
        for t in self._threads:
            t.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=10)


def _digest(arrs) -> str:
    h = hashlib.sha256()
    for a in arrs:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.slow  # multi-process kill -9 drill; tier-1 keeps its 870 s window
def test_shard_host_kill9_under_train_and_predict_traffic(tmp_path):
    data = str(tmp_path / "data")
    _write_day(data, rows_per_split=96)

    # Never-killed reference: the flat single-host run (bit-identical
    # to the multihost f32 wire by the PR-10 parity pins).
    ref = _make_runner(data, str(tmp_path / "out_ref"))
    ref_stats = ref.train_day(DAY)
    ref_keys, _ = ref.trainer.engine.store.key_stats()

    root = str(tmp_path / "hosts")
    os.makedirs(root, exist_ok=True)
    elroot = os.path.join(root, "elastic")
    proc_a, ep_a = _spawn_host(root, "hostA", 0)
    proc_b, ep_b = _spawn_host(root, "hostB", 1)
    proc_c = None
    try:
        rmap = ReplicaMap.ring([ep_a, ep_b], 2)
        for ep in (ep_a, ep_b):
            c = ShardClient(ep)
            c.call("set_replication", map=rmap.to_dict())
            c.close()

        store = MultiHostStore(CFG, [ep_a, ep_b], replica_map=rmap)
        ctl = ElasticReshardController(
            store, None, table_fn=lambda: read_rank_table(elroot))
        runner = _make_runner(
            data, str(tmp_path / "out_drill"), store=store,
            hook=lambda day, pid: ctl.maybe_apply(day, pid))
        ctl.ckpt = runner.ckpt
        runner.pass_retry_hook = (
            lambda day, pid, e: ctl.repair(reason=repr(e)))

        traffic_keys = np.sort(np.unique(np.random.default_rng(7)
                               .integers(1, 120, 64, dtype=np.uint64)))
        backed = ShardBackedStore([ep_a, ep_b], CFG.dim,
                                  replica_map=store.replica_map)
        files = [runner.filelist_fn(DAY, s) for s in runner.pass_splits]
        stats = []
        with _ServingReaders(backed, traffic_keys) as readers:
            stats.append(runner.train_pass(DAY, 1, files[0]))

            # kill -9 one host of the replicated pair, mid-traffic.
            proc_b.send_signal(signal.SIGKILL)
            proc_b.wait(timeout=30)
            proc_c, ep_c = _spawn_host(root, "hostC", 0)

            # The interrupted pass: push hits the dead primary → loud
            # transient → retry hook PROMOTES → rollback+replay.
            stats.append(runner.train_pass(DAY, 2, files[1]))
            # The dead host is out of the map (promotion); pass 2's own
            # boundary hook may ALREADY have re-replicated to hostC if
            # the rank table settled that fast — both are legal here.
            assert ep_b not in store.replica_map.all_endpoints()
            backed.set_replica_map(store.replica_map)

            # Boundary repair: once the rank table settles on
            # {hostA, hostC}, the hook re-replicates to the fresh host.
            stats.append(runner.train_pass(DAY, 3, files[2]))
            deadline = time.time() + 30
            while (store.replica_map.replication < 2
                   and time.time() < deadline):
                ctl.maybe_apply(DAY, 3)       # the boundary-hook path
                time.sleep(0.25)
            assert store.replica_map.replication == 2, \
                "boundary repair never restored the replication factor"
            backed.set_replica_map(store.replica_map)
            found, _ = backed.read(traffic_keys)   # reads span old+new

        assert not readers.failures, readers.failures[:5]
        assert readers.reads > 0
        # Close the day the same way the reference's train_day did
        # (lifecycle shrink + base dump — forwarded to the new backup).
        runner.day_end(DAY)

        # Zero lost updates: the drilled run equals the reference.
        assert len(stats) == 3
        for sa, sb in zip(stats, ref_stats):
            np.testing.assert_array_equal(sa["loss"], sb["loss"])
            np.testing.assert_array_equal(sa["auc"], sb["auc"])
        import jax
        assert _digest(jax.tree_util.tree_leaves(
            jax.device_get(runner.trainer.params))) == _digest(
            jax.tree_util.tree_leaves(jax.device_get(ref.trainer.params)))
        rows_d = _store_rows(store, ref_keys)
        rows_r = _store_rows(ref.trainer.engine.store, ref_keys)
        for f in _FIELDS:
            np.testing.assert_array_equal(rows_d[f], rows_r[f],
                                          err_msg=f)

        # Replication factor restored WITH matching bytes: the fresh
        # host's replica stores mirror the survivor's primaries.
        ca, cc = ShardClient(ep_a), ShardClient(ep_c)
        try:
            st_a = ca.call("replica_status")
            st_c = cc.call("replica_status")
            assert st_a["replication"] == 2
            assert {s: d["role"] for s, d in st_a["slots"].items()} == \
                {"0": "primary", "1": "primary"}
            assert {s: d["role"] for s, d in st_c["slots"].items()} == \
                {"0": "backup", "1": "backup"}
            for slot in ("0", "1"):
                assert st_c["slots"][slot]["rows"] == \
                    st_a["slots"][slot]["rows"]
                assert st_c["slots"][slot]["seq"] == \
                    st_a["slots"][slot]["seq"]
        finally:
            ca.close()
            cc.close()
        backed.close()
        store.close()
    finally:
        for p in (proc_a, proc_b, proc_c):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait(timeout=10)
