"""Event-loop multiplexed RPC plane tests (PR 16, RPC.md): wire v2
frames (request ids, scatter/gather zero-copy array segments, shm
shortcut), the single-poller server, N-outstanding connection
multiplexing, server-side pull coalescing, and the drill half —
out-of-order soak on ONE socket, kill -9 mid-flight with
idempotent-retry + resolve failover, and v1 interop both ways."""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from paddlebox_tpu.core import flags, monitor
from paddlebox_tpu.distributed import rpc, wire

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class EchoServer(rpc.FramedRPCServer):
    service_name = "mux-test"

    def handle_echo(self, req):
        sleep_ms = float(req.get("sleep_ms", 0.0))
        if sleep_ms > 0:
            time.sleep(sleep_ms / 1e3)
        return {"a": np.asarray(req["a"], np.float32) * 2.0,
                "i": int(req.get("i", -1))}

    def handle_boom(self, req):
        raise ValueError("in-band boom")


def _conn(ep, **kw):
    kw.setdefault("service_name", "mux-test")
    kw.setdefault("idempotent", ("echo",))
    return rpc.FramedRPCConn(ep, timeout=30.0, **kw)


@pytest.fixture
def flag_reset():
    keep = {k: flags.flag(k) for k in
            ("rpc_mux", "rpc_sg_min_bytes", "rpc_shm",
             "multihost_coalesce_window_ms")}
    yield
    flags.set_flags(keep)


# -- wire v1: memoryview-segment encode stays bit-identical ----------------

def test_v1_ndarray_frames_bit_identical_and_roundtrip():
    """The v1 LEGACY-tag ndarray encode now feeds memoryview segments
    to the frame join instead of materializing ``tobytes()`` copies —
    the bytes on the wire must be IDENTICAL (v1 peers parse them), and
    a non-contiguous input must normalize exactly like
    ``ascontiguousarray`` always did."""
    rng = np.random.default_rng(0)
    dtypes = (np.float32, np.float64, np.float16, np.int8, np.uint8,
              np.int16, np.int32, np.int64, np.uint16, np.uint32,
              np.uint64, np.bool_)
    obj = {f"a{i}": rng.integers(0, 2, size=(3, 5)).astype(dt)
           for i, dt in enumerate(dtypes)}
    obj["nested"] = {"x": [np.arange(7, dtype=np.float32), "s", 3, None],
                     "empty": np.empty((0, 4), np.float64)}
    frame = wire.pack_frame(obj)
    # Reference layout: header + payload; v1, flags 0.
    assert frame[:2] == b"PB"
    ln = wire.read_frame_header(frame[:wire.HEADER.size])
    payload = frame[wire.HEADER.size:]
    assert len(payload) == ln
    back = wire.loads(payload)
    for i, dt in enumerate(dtypes):
        got = back[f"a{i}"]
        assert got.dtype == dt and np.array_equal(got, obj[f"a{i}"])
    assert np.array_equal(back["nested"]["x"][0], obj["nested"]["x"][0])
    assert back["nested"]["empty"].shape == (0, 4)
    # Deterministic bytes (same object -> same frame), and a strided
    # view encodes exactly like its contiguous copy — the
    # ascontiguousarray normalization the tobytes path performed.
    assert wire.pack_frame(obj) == frame
    big = rng.standard_normal((8, 6)).astype(np.float32)
    assert (wire.pack_frame({"v": big[::2, ::3]})
            == wire.pack_frame({"v": np.ascontiguousarray(big[::2, ::3])}))


# -- wire v2: plain, sg, shm ------------------------------------------------

def test_v2_plain_frame_roundtrip():
    obj = {"method": "echo", "x": [1, 2.5, "s"], "b": b"\x00\x01"}
    frame = wire.pack_frame_v2(obj, 41)
    ver, fl, ln = wire.read_any_header(frame[:wire.HEADER.size])
    assert (ver, fl) == (wire.WIRE_VERSION_MUX, 0)
    rid, back = wire.loads_v2(frame[wire.HEADER.size:])
    assert rid == 41 and back == obj


def test_sg_frame_roundtrip_zero_copy_and_edges():
    rng = np.random.default_rng(1)
    obj = {"ok": True,
           "result": {"emb": rng.standard_normal((64, 16)).astype(
                          np.float32),
                      "keys": np.arange(64, dtype=np.uint64),
                      "empty": np.empty((0, 3), np.float32),
                      "note": "mixed tree"}}
    bufs = wire.sg_frame_buffers(obj, 7)
    frame = b"".join(bytes(b) for b in bufs)
    ver, fl, ln = wire.read_any_header(frame[:wire.HEADER.size])
    assert ver == wire.WIRE_VERSION_MUX and fl & wire.FLAG_SG
    payload = memoryview(frame)[wire.HEADER.size:]
    assert len(payload) == ln
    rid, back = wire.loads_sg(payload)
    assert rid == 7
    assert np.array_equal(back["result"]["emb"], obj["result"]["emb"])
    assert back["result"]["keys"].dtype == np.uint64
    assert back["result"]["empty"].shape == (0, 3)
    assert back["result"]["note"] == "mixed tree"
    # Zero-copy: decoded arrays are VIEWS over the receive buffer.
    assert back["result"]["emb"].base is not None
    # Segments are 64-byte aligned in the payload.
    arrs = wire.dumps_sg(obj)[1]
    offs, _total = wire.sg_plan(arrs)
    assert all(o % 64 == 0 for o in offs)
    # No-array and 0-d edges: a frame with no segments round-trips, and
    # a 0-d array promotes to shape (1,) exactly like the v1 path.
    bufs2 = wire.sg_frame_buffers({"just": "tree"}, 9)
    f2 = b"".join(bytes(b) for b in bufs2)
    rid2, b2 = wire.loads_sg(memoryview(f2)[wire.HEADER.size:])
    assert (rid2, b2) == (9, {"just": "tree"})
    v1_back = wire.loads(wire.pack_frame(
        {"z": np.asarray(3.0, np.float32)})[wire.HEADER.size:])
    bufs3 = wire.sg_frame_buffers({"z": np.asarray(3.0, np.float32)}, 1)
    f3 = b"".join(bytes(b) for b in bufs3)
    _, b3 = wire.loads_sg(memoryview(f3)[wire.HEADER.size:])
    assert b3["z"].shape == v1_back["z"].shape == (1,)


def test_v1_reader_rejects_v2_and_flags():
    v2 = wire.pack_frame_v2({"m": 1}, 1)
    with pytest.raises(wire.WireError):
        wire.read_frame_header(v2[:wire.HEADER.size])
    # read_any_header refuses a v1 frame carrying v2 flags (corruption).
    hdr = bytearray(wire.pack_frame({"m": 1})[:wire.HEADER.size])
    hdr[3] |= wire.FLAG_SG
    with pytest.raises(wire.WireError):
        wire.read_any_header(bytes(hdr))


# -- mux dispatch: soak, ordering, inline handlers -------------------------

def test_mux_soak_out_of_order_bit_identical(flag_reset):
    """8 threads x 16 outstanding on ONE connection: replies arrive out
    of order (the server sleeps longer on even request ids) yet every
    future resolves to ITS request's payload, bit-identical to a serial
    reference run."""
    flags.set_flags({"rpc_mux": True})
    srv = EchoServer("127.0.0.1:0")
    conn = _conn(srv.endpoint)
    fb0 = monitor.get("rpc/mux_fallbacks")
    try:
        serial = {}
        for i in range(8):
            a = np.full((32,), float(i), np.float32)
            serial[i] = conn.call("echo", a=a, i=i)["a"]
        failures = []

        def worker(t):
            try:
                for _round in range(4):
                    futs = []
                    for j in range(16):
                        i = (t * 16 + j) % 8
                        a = np.full((32,), float(i), np.float32)
                        futs.append((i, conn.call_async(
                            "echo", a=a, i=i,
                            sleep_ms=2.0 if i % 2 == 0 else 0.0)))
                    for i, f in futs:
                        out = f.result()
                        if out["i"] != i or not np.array_equal(
                                out["a"], serial[i]):
                            failures.append((t, i))
            except BaseException as e:  # noqa: BLE001 - surface in test
                failures.append((t, repr(e)))

        ts = [threading.Thread(target=worker, args=(t,))
              for t in range(8)]
        [t.start() for t in ts]
        [t.join(60) for t in ts]
        assert not failures, failures[:5]
        # One socket did all of it: no fallback, no reconnect churn.
        assert monitor.get("rpc/mux_fallbacks") == fb0
    finally:
        conn.close()
        srv.stop()
        srv.close_connections()


def test_mux_inband_errors_and_sg_arrays_server_side(flag_reset):
    """In-band handler errors cross the mux wire as error replies (not
    stream teardown), and large array payloads ride SG frames in both
    directions when enabled."""
    flags.set_flags({"rpc_mux": True, "rpc_sg_min_bytes": 1024})
    srv = EchoServer("127.0.0.1:0")
    conn = _conn(srv.endpoint)
    try:
        sg0 = monitor.get("rpc/sg_frames")
        big = np.arange(4096, dtype=np.float32)
        out = conn.call("echo", a=big)
        assert np.array_equal(out["a"], big * 2.0)
        assert monitor.get("rpc/sg_frames") >= sg0 + 2  # request + reply
        with pytest.raises(RuntimeError, match="in-band boom"):
            conn.call("boom")
        # The conn survives an in-band error: same socket keeps working.
        assert conn.call("echo", a=np.ones(4, np.float32))["i"] == -1
    finally:
        conn.close()
        srv.stop()
        srv.close_connections()


def test_v1_interop_both_directions(flag_reset):
    """Version negotiation: a v1-pinned client (``--norpc_mux``) speaks
    legacy frames to the new server; a mux client against a pre-mux
    server (wire_caps answered with an in-band error) falls back to v1
    and counts ``rpc/mux_fallbacks`` — mixed-version clusters
    interoperate instead of desyncing."""
    srv = EchoServer("127.0.0.1:0")
    try:
        flags.set_flags({"rpc_mux": False})
        legacy = _conn(srv.endpoint)
        out = legacy.call("echo", a=np.arange(4, dtype=np.float32))
        assert np.array_equal(out["a"],
                              np.arange(4, dtype=np.float32) * 2.0)
        legacy.close()
    finally:
        srv.stop()
        srv.close_connections()

    class OldServer(EchoServer):
        def _wire_caps(self, cs, req):
            return {"max_version": 1}  # a pre-mux peer's best answer

    old = OldServer("127.0.0.1:0")
    try:
        flags.set_flags({"rpc_mux": True})
        fb0 = monitor.get("rpc/mux_fallbacks")
        conn = _conn(old.endpoint)
        out = conn.call("echo", a=np.ones(8, np.float32))
        assert np.array_equal(out["a"], np.full(8, 2.0, np.float32))
        assert monitor.get("rpc/mux_fallbacks") == fb0 + 1
        # call_async still works on the fallback conn (helper thread).
        f = conn.call_async("echo", a=np.ones(2, np.float32), i=5)
        assert f.result()["i"] == 5
        conn.close()
    finally:
        old.stop()
        old.close_connections()


# -- forensics tables -------------------------------------------------------

def test_inflight_and_poller_tables(flag_reset):
    flags.set_flags({"rpc_mux": True})
    srv = EchoServer("127.0.0.1:0")
    conn = _conn(srv.endpoint)
    try:
        futs = [conn.call_async("echo", a=np.ones(4, np.float32),
                                sleep_ms=300.0) for _ in range(3)]
        time.sleep(0.1)
        rows = rpc.inflight_table()
        mine = [r for r in rows if r["endpoint"] == srv.endpoint]
        assert mine and mine[0]["outstanding"] >= 3
        assert mine[0]["method"] == "echo"
        pol = rpc.poller_table()
        me = [p for p in pol if p["endpoint"] == srv.endpoint]
        assert me and me[0]["service"] == "mux-test"
        assert "poller" in me[0]["thread"]
        assert me[0]["conns"] >= 1 and me[0]["running"]
        for f in futs:
            f.result()
        assert not [r for r in rpc.inflight_table()
                    if r["endpoint"] == srv.endpoint]
    finally:
        conn.close()
        srv.stop()
        srv.close_connections()


# -- server-side pull coalescing -------------------------------------------

def test_pull_coalescing_bit_identical_and_counted(flag_reset):
    from paddlebox_tpu.embedding.table import TableConfig
    from paddlebox_tpu.multihost.keyrange import ShardRangeTable
    from paddlebox_tpu.multihost.shard_service import (ShardClient,
                                                       ShardServer)
    cfg = TableConfig(name="emb", dim=8, learning_rate=0.1)
    srv = ShardServer("127.0.0.1:0", 0, ShardRangeTable.for_world(1),
                      cfg)
    rng = np.random.default_rng(3)
    universe = np.unique(rng.integers(1, 1 << 40, 512, dtype=np.uint64))
    try:
        # Reference: direct (coalescing disabled) pulls per key set.
        flags.set_flags({"multihost_coalesce_window_ms": -1.0})
        sets = [np.unique(rng.choice(universe, 64)) for _ in range(16)]
        c0 = ShardClient(srv.endpoint)
        ref = [c0.call("pull", keys=k) for k in sets]
        base_rounds = srv.metrics.get("multihost/coalesce_rounds")
        assert base_rounds == 0  # disabled path never coalesces
        # Coalesced: concurrent pulls inside a window fold into fewer
        # store lookups; every slice stays bit-identical.
        flags.set_flags({"multihost_coalesce_window_ms": 5.0})
        got = [None] * len(sets)
        errs = []

        def puller(i):
            try:
                c = ShardClient(srv.endpoint)
                got[i] = c.call("pull", keys=sets[i])
                c.close()
            except BaseException as e:  # noqa: BLE001
                errs.append(repr(e))

        ts = [threading.Thread(target=puller, args=(i,))
              for i in range(len(sets))]
        [t.start() for t in ts]
        [t.join(30) for t in ts]
        assert not errs, errs[:3]
        for i in range(len(sets)):
            for f in ref[i]:
                assert np.array_equal(got[i][f], ref[i][f]), f
        assert srv.metrics.get("multihost/coalesced_pulls") > 0
        assert (srv.metrics.get("multihost/coalesce_rounds")
                < len(sets))  # fewer lookups than requests
        c0.close()
    finally:
        srv.stop()
        srv.close_connections()


# -- kill -9 drill ----------------------------------------------------------

def _spawn_echo(root, name):
    proc = subprocess.Popen(
        [sys.executable,
         os.path.join(REPO, "tests", "rpc_echo_worker.py"),
         str(root), name],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    ep_file = os.path.join(root, f"{name}.ep")
    for _ in range(200):
        if os.path.exists(ep_file):
            with open(ep_file) as f:
                meta = json.load(f)
            return proc, meta["endpoint"]
        time.sleep(0.05)
    proc.kill()
    raise RuntimeError(f"echo worker {name} never advertised")


@pytest.mark.slow  # 8 s multi-process kill -9 drill; tier-1 keeps its 870 s window (PR 21)
def test_mux_kill9_idempotent_retry_and_resolve_failover(
        tmp_path, flag_reset):
    """kill -9 the server while mux calls are provably in flight: the
    idempotent ``echo`` futures re-issue through the conn's
    retry/reconnect machinery, the reconnect-time ``resolve`` hook
    re-points at the surviving replica, and every call completes with
    correct bytes — the PR-5/PR-11 drill contract, unchanged on the
    mux plane."""
    flags.set_flags({"rpc_mux": True})
    proc_a, ep_a = _spawn_echo(tmp_path, "a")
    proc_b, ep_b = _spawn_echo(tmp_path, "b")
    live = {"ep": ep_a}
    conn = rpc.FramedRPCConn(
        ep_a, timeout=30.0, service_name="rpc-drill",
        idempotent=("echo",), resolve=lambda cur: live["ep"])
    try:
        re0 = monitor.get("rpc/retries")
        a = np.arange(16, dtype=np.float32)
        assert conn.call("echo", a=a)["who"] == "a"
        futs = [conn.call_async("echo", a=a, sleep_ms=400.0)
                for _ in range(8)]
        time.sleep(0.1)          # calls are mid-handler on A
        live["ep"] = ep_b
        proc_a.send_signal(signal.SIGKILL)
        outs = [f.result() for f in futs]
        for out in outs:
            assert np.array_equal(out["a"], a * 2.0)
            assert out["who"] == "b"  # failover actually moved hosts
        assert monitor.get("rpc/retries") > re0
        # The conn is settled on B: a plain call works, no new retry.
        assert conn.call("echo", a=a)["who"] == "b"
    finally:
        conn.close()
        for p in (proc_a, proc_b):
            p.kill()
            p.wait(timeout=10)


# -- shm shortcut (flag-gated off by default) ------------------------------

def test_shm_frames_roundtrip_same_host(flag_reset):
    if not os.path.isdir("/dev/shm"):
        pytest.skip("no /dev/shm")
    flags.set_flags({"rpc_mux": True, "rpc_shm": True,
                     "rpc_shm_min_bytes": 1024,
                     "rpc_sg_min_bytes": 1024})
    srv = EchoServer("127.0.0.1:0")
    conn = _conn(srv.endpoint)
    try:
        s0 = monitor.get("rpc/shm_frames")
        big = np.arange(65536, dtype=np.float32)
        out = conn.call("echo", a=big)
        assert np.array_equal(out["a"], big * 2.0)
        assert monitor.get("rpc/shm_frames") > s0
        # One-shot segments: nothing pbx-rpc-* leaks in /dev/shm.
        time.sleep(0.1)
        assert not [e for e in os.listdir("/dev/shm")
                    if e.startswith(f"pbx-rpc-{os.getpid()}")]
    finally:
        conn.close()
        srv.stop()
        srv.close_connections()
