"""Real-process serving fleet drill (the ISSUE-11 acceptance drill):
replica PROCESSES over one shared ShardServer tier behind a FleetRouter
discovered through elastic heartbeat meta — kill -9 one replica under
concurrent client traffic with ZERO failed client RPCs, and join a
replica mid-traffic that serves bit-identical probabilities to the
incumbents (everyone resolves the same shard tier with the same init
seed, so the model IS the same model).
"""

import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from paddlebox_tpu.core import flags as flagmod
from paddlebox_tpu.embedding.table import TableConfig
from paddlebox_tpu.multihost.shard_service import (start_local_shards,
                                                   stop_shards)
from paddlebox_tpu.multihost.store import MultiHostStore
from paddlebox_tpu.serving import PredictClient
from paddlebox_tpu.serving.router import FleetRouter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "fleet_replica_worker.py")

DIM = 8
N_KEYS = 400


def _spawn(elastic_root, host_id, shard_eps, ready_file):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("PBX_RANK", None)
    return subprocess.Popen(
        [sys.executable, WORKER, elastic_root, host_id,
         ",".join(shard_eps), ready_file],
        cwd=REPO, env=env, start_new_session=True)


def _wait_file(path, timeout=180.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if os.path.exists(path):
            with open(path) as f:
                return f.read().strip()
        time.sleep(0.1)
    raise TimeoutError(f"worker never wrote {path}")


def _wait_healthy(router, want, timeout=120.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if router.fleet.size() >= want:
            return
        time.sleep(0.1)
    raise TimeoutError(
        f"fleet never reached {want} healthy replicas: "
        f"{router.fleet.replicas()}")


@pytest.mark.slow  # multi-process kill -9 drill; tier-1 keeps its 870 s window
def test_fleet_kill9_and_join_drill(tmp_path):
    # Shared shard tier, populated with a deterministic trained-model
    # stand-in every replica resolves against.
    cfg = TableConfig(name="emb", dim=DIM, learning_rate=0.1)
    shard_servers, shard_eps = start_local_shards(2, cfg)
    store = MultiHostStore(cfg, shard_eps)
    rng = np.random.default_rng(3)
    keys = np.arange(1, N_KEYS + 1, dtype=np.uint64)
    rows = store.pull_for_pass(keys)
    rows["emb"] = rng.normal(size=(N_KEYS, DIM)).astype(np.float32) * .02
    rows["w"] = rng.normal(size=(N_KEYS,)).astype(np.float32) * .02
    store.push_from_pass(keys, rows)

    root = str(tmp_path / "elastic")
    procs = {}
    router = None
    clients = []
    prev_hb = flagmod.flag("fleet_health_interval_s")
    flagmod.set_flags({"fleet_health_interval_s": 0.2})
    try:
        # Two incumbents, spawned in parallel (jax import dominates).
        for hid in ("repA", "repB"):
            procs[hid] = _spawn(root, hid, shard_eps,
                                str(tmp_path / f"{hid}.ep"))
        eps = {hid: _wait_file(str(tmp_path / f"{hid}.ep"))
               for hid in ("repA", "repB")}
        router = FleetRouter("127.0.0.1:0", elastic_root=root)
        _wait_healthy(router, 2)

        # Concurrent clients through the router. EVERY RPC must
        # succeed across the kill and the join below.
        stop = threading.Event()
        failures = []
        done = [0] * 4
        crng = np.random.default_rng(77)
        lines_per_cli = [
            [[f"0 u:{crng.integers(1, N_KEYS)} "
              f"i:{crng.integers(1, N_KEYS)}" for _ in range(2)]
             for _ in range(8)]
            for _ in range(4)]

        def run(i):
            cli = PredictClient(router.endpoint)
            j = 0
            try:
                while not stop.is_set():
                    try:
                        out = cli.predict(
                            lines_per_cli[i][j % 8])
                        assert out.shape == (2,)
                        done[i] += 1
                    except Exception as e:  # noqa: BLE001 - the drill count
                        failures.append((i, repr(e)))
                    j += 1
            finally:
                cli.close()

        threads = [threading.Thread(target=run, args=(i,), daemon=True)
                   for i in range(4)]
        for t in threads:
            t.start()
        time.sleep(1.0)

        # JOIN mid-traffic: the third replica registers through the
        # same elastic meta and is admitted by the health loop.
        procs["repC"] = _spawn(root, "repC", shard_eps,
                               str(tmp_path / "repC.ep"))
        eps["repC"] = _wait_file(str(tmp_path / "repC.ep"))
        _wait_healthy(router, 3)

        # Bit-identical: the joiner answers exactly what an incumbent
        # answers (direct clients, fixed lines).
        probe = [f"0 u:{k} i:{k + 5}" for k in (3, 77, 250, 390)]
        c_new = PredictClient(eps["repC"])
        c_old = PredictClient(eps["repB"])
        np.testing.assert_array_equal(c_new.predict(probe),
                                      c_old.predict(probe))
        c_new.close()
        c_old.close()

        # KILL -9 one incumbent under traffic.
        os.kill(procs["repA"].pid, signal.SIGKILL)
        procs["repA"].wait(timeout=30)
        deadline = time.time() + 60
        while time.time() < deadline:
            r = router.fleet.get("repA")
            if r is None or r.state == "ejected":
                break
            time.sleep(0.1)
        else:
            raise AssertionError(
                f"repA never left the fleet: {router.fleet.replicas()}")
        time.sleep(1.0)     # keep traffic flowing post-eject
        stop.set()
        for t in threads:
            t.join(timeout=60)

        assert failures == [], failures[:5]
        assert all(d > 0 for d in done), done
        # The survivors (incl. the joiner) carried the traffic.
        st_cli = PredictClient(router.endpoint)
        st = st_cli.stats()
        st_cli.close()
        assert st["fleet_size"] == 2
        assert st["predict_rpcs"] > 0
    finally:
        flagmod.set_flags({"fleet_health_interval_s": prev_hb})
        stop_evt = locals().get("stop")
        if stop_evt is not None:
            stop_evt.set()
        for c in clients:
            c.close()
        if router is not None:
            router.stop()
        for p in procs.values():
            if p.poll() is None:
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except (OSError, ProcessLookupError):
                    p.kill()
                p.wait(timeout=30)
        store.close()
        stop_shards(shard_servers)
