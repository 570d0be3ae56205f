"""Process launcher: ``python -m paddlebox_tpu.launch <opts> script.py``.

Role of the reference launch stack (``python/paddle/distributed/launch/
main.py:18`` + ``controllers/collective.py``): spawn one training process
per host/worker with the cluster env injected, watch them, and restart on
failure (role of ``controllers/watcher.py`` + the elastic manager's
fault-tolerant restart, ``fleet/elastic/manager.py``).

TPU-first: one process per HOST (jax owns all local chips), env contract
``PBX_COORDINATOR/PBX_NUM_PROCESSES/PBX_PROCESS_ID`` consumed by
``paddlebox_tpu.distributed.initialize``. ``--nproc N`` spawns N local
processes for CPU runs with forced host-platform device counts (tests);
it is refused unless ``JAX_PLATFORMS=cpu``, because every local child
would claim every local chip and a chip belongs to one process.
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time
from typing import Dict, List, Optional

from paddlebox_tpu.core import log


def build_env(rank: int, world: int, coordinator: str,
              base: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    env = dict(base if base is not None else os.environ)
    env["PBX_COORDINATOR"] = coordinator
    env["PBX_NUM_PROCESSES"] = str(world)
    env["PBX_PROCESS_ID"] = str(rank)
    return env


class Watcher:
    """Spawn + monitor worker processes; restart failed ranks up to
    ``max_restarts`` (role of launch watcher + elastic restart)."""

    def __init__(self, cmds: List[List[str]], envs: List[Dict[str, str]],
                 *, max_restarts: int = 0, poll_sec: float = 0.5):
        self.cmds = cmds
        self.envs = envs
        self.max_restarts = max_restarts
        self.poll_sec = poll_sec
        self.procs: List[Optional[subprocess.Popen]] = [None] * len(cmds)
        self.restarts = [0] * len(cmds)
        # terminate() sets this so run() stops respawning SIGTERM'd ranks
        # (an elastic restart must not race the failure-restart logic).
        self._stopping = False

    def _spawn(self, i: int) -> None:
        self.procs[i] = subprocess.Popen(self.cmds[i], env=self.envs[i])
        log.vlog(0, "launched rank %d (pid %d)", i, self.procs[i].pid)

    def run(self) -> int:
        for i in range(len(self.cmds)):
            self._spawn(i)
        try:
            while True:
                all_done = True
                for i, p in enumerate(self.procs):
                    if p is None:
                        continue
                    ret = p.poll()
                    if ret is None:
                        all_done = False
                        continue
                    if ret == 0:
                        self.procs[i] = None
                        continue
                    if self._stopping:
                        self.procs[i] = None
                        continue
                    if self.restarts[i] < self.max_restarts:
                        self.restarts[i] += 1
                        log.warning("rank %d exited %d; restart %d/%d", i,
                                    ret, self.restarts[i], self.max_restarts)
                        self._spawn(i)
                        all_done = False
                    else:
                        log.error("rank %d failed (%d); terminating job",
                                  i, ret)
                        self.terminate()
                        return ret
                if all_done:
                    return 0
                time.sleep(self.poll_sec)
        except KeyboardInterrupt:
            self.terminate()
            return 130

    def terminate(self) -> None:
        self._stopping = True
        for p in self.procs:
            if p is not None and p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.time() + 5
        for p in self.procs:
            if p is None:
                continue
            while p.poll() is None and time.time() < deadline:
                time.sleep(0.1)
            if p.poll() is None:
                p.kill()


def run_elastic(args) -> int:
    """Elastic mode: membership from an ElasticManager over a shared dir;
    rank/world derive from the published rank table and workers restart on
    membership generation changes (role of `paddle.distributed.run
    --elastic` wiring ElasticManager into the launch controllers)."""
    import socket
    import threading

    from paddlebox_tpu.launch.elastic import ElasticManager

    host_id = args.host_id or socket.gethostname()
    em = ElasticManager(args.elastic_dir, host_id,
                        min_hosts=args.min_hosts, max_hosts=args.max_hosts)
    em.start()
    try:
        while True:
            try:
                table = em.wait_for_quorum(timeout=args.elastic_timeout)
            except TimeoutError:
                log.error("elastic: quorum of %d hosts not reached in %.0fs",
                          args.min_hosts, args.elastic_timeout)
                return 3
            gen = table.generation
            host_rank = table.rank_of(host_id)
            world = table.world_size * args.nproc
            cmds, envs = [], []
            for i in range(args.nproc):
                rank = host_rank * args.nproc + i
                cmds.append([sys.executable, args.script] + args.script_args)
                env = build_env(rank, world, args.coordinator)
                env["PBX_ELASTIC_GENERATION"] = str(gen)
                envs.append(env)
            log.vlog(0, "elastic gen %d: host %s rank %d world %d", gen,
                     host_id, host_rank, world)
            watcher = Watcher(cmds, envs, max_restarts=args.max_restarts)
            result: List[Optional[int]] = [None]
            t = threading.Thread(target=lambda: result.__setitem__(
                0, watcher.run()), daemon=True)
            t.start()
            while t.is_alive():
                t.join(0.5)
                cur = em.current_table()
                if cur is not None and cur.generation != gen:
                    log.warning("elastic: membership gen %d -> %d; "
                                "restarting workers", gen, cur.generation)
                    watcher.terminate()
                    t.join(10.0)
                    break
            else:
                return result[0] if result[0] is not None else 1
            # membership changed: loop — wait for the new table and relaunch
    finally:
        em.stop()


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m paddlebox_tpu.launch",
        description="launch distributed training processes")
    ap.add_argument("--nproc", type=int, default=1,
                    help="local processes to spawn (hosts in prod: 1)")
    ap.add_argument("--coordinator", default="127.0.0.1:8476",
                    help="coordinator address for jax.distributed")
    ap.add_argument("--rank-offset", type=int, default=0,
                    help="global rank of this host's first process")
    ap.add_argument("--world-size", type=int, default=0,
                    help="total processes across hosts (default: nproc)")
    ap.add_argument("--max-restarts", type=int, default=0,
                    help="per-rank restart budget on failure (elastic)")
    ap.add_argument("--elastic-dir", default="",
                    help="shared dir for elastic membership (enables "
                         "elastic mode: ranks come from the lease table)")
    ap.add_argument("--host-id", default="",
                    help="elastic host identity (default: hostname)")
    ap.add_argument("--min-hosts", type=int, default=1,
                    help="elastic quorum size")
    ap.add_argument("--max-hosts", type=int, default=0,
                    help="elastic max hosts (0 = unbounded)")
    ap.add_argument("--elastic-timeout", type=float, default=300.0,
                    help="seconds to wait for elastic quorum")
    ap.add_argument("script", help="training script")
    ap.add_argument("script_args", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)

    if args.nproc > 1 and os.environ.get(
            "JAX_PLATFORMS", "").lower() != "cpu":
        ap.error("--nproc > 1 needs JAX_PLATFORMS=cpu: each local child "
                 "would initialise every local accelerator chip, and a "
                 "chip belongs to one process (the second child fails or "
                 "hangs). On a TPU host run one process; it drives all "
                 "local chips")

    if args.elastic_dir:
        return run_elastic(args)

    world = args.world_size or args.nproc
    cmds, envs = [], []
    for i in range(args.nproc):
        rank = args.rank_offset + i
        cmds.append([sys.executable, args.script] + args.script_args)
        envs.append(build_env(rank, world, args.coordinator))
    return Watcher(cmds, envs, max_restarts=args.max_restarts).run()


if __name__ == "__main__":
    sys.exit(main())
