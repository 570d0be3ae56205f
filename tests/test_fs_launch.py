"""Filesystem abstraction + elastic launch tests. HadoopFS is exercised
against a fake `hadoop` CLI shim (the reference's hdfs paths shell out the
same way, fs.cc:224), so no real cluster is needed — mirroring the
reference's localhost-fake-cluster test philosophy."""

import os
import stat
import sys
import textwrap

import pytest

from paddlebox_tpu.utils.fs import HadoopFS, LocalFS, fs_for

FAKE_HADOOP = textwrap.dedent("""\
    #!/bin/sh
    # fake 'hadoop' CLI: maps 'fs -<op> args...' onto a local root dir
    ROOT="$FAKE_HDFS_ROOT"
    shift  # drop 'fs'
    op="$1"; shift
    strip() { echo "$1" | sed 's|hdfs://fake||'; }
    case "$op" in
      -test) [ -e "$ROOT$(strip "$2")" ] ;;
      -mkdir) shift; mkdir -p "$ROOT$(strip "$1")" ;;
      -cat) cat "$ROOT$(strip "$1")" ;;
      -put)
        force="$1"; [ "$force" = "-f" ] && shift
        src="$1"; dst="$ROOT$(strip "$2")"
        mkdir -p "$(dirname "$dst")"
        if [ "$src" = "-" ]; then cat > "$dst"; else cp "$src" "$dst"; fi ;;
      -get) cp "$ROOT$(strip "$1")" "$2" ;;
      -rm) shift; shift; rm -rf "$ROOT$(strip "$1")" ;;
      -mv) mv "$ROOT$(strip "$1")" "$ROOT$(strip "$2")" ;;
      -ls)
        d="$ROOT$(strip "$1")"
        [ -d "$d" ] || { echo "ls: no such file: $1" >&2; exit 1; }
        for f in "$d"/*; do
          [ -e "$f" ] || continue
          echo "-rw-r--r-- 1 u g 0 2026-01-01 00:00 hdfs://fake${f#$ROOT}"
        done ;;
      *) echo "unknown op $op" >&2; exit 1 ;;
    esac
    """)


@pytest.fixture
def fake_hdfs(tmp_path, monkeypatch):
    shim = tmp_path / "hadoop"
    shim.write_text(FAKE_HADOOP)
    shim.chmod(shim.stat().st_mode | stat.S_IEXEC)
    root = tmp_path / "hdfs_root"
    root.mkdir()
    monkeypatch.setenv("PATH", f"{tmp_path}:{os.environ['PATH']}")
    monkeypatch.setenv("FAKE_HDFS_ROOT", str(root))
    return root


def test_local_fs_roundtrip(tmp_path):
    fs = LocalFS()
    p = str(tmp_path / "a" / "b.txt")
    with fs.open_write(p) as f:
        f.write(b"hello")
    assert fs.exists(p)
    with fs.open_read(p) as f:
        assert f.read() == b"hello"
    fs.rename(p, str(tmp_path / "a" / "c.txt"))
    assert not fs.exists(p)
    assert [os.path.basename(x) for x in fs.ls(str(tmp_path / "a"))] \
        == ["c.txt"]
    fs.remove(str(tmp_path / "a"))
    assert not fs.exists(str(tmp_path / "a"))


def test_fs_for_scheme_routing():
    assert isinstance(fs_for("/tmp/x"), LocalFS)
    assert isinstance(fs_for("hdfs://ns1/user/x"), HadoopFS)
    assert isinstance(fs_for("afs://cluster/x"), HadoopFS)


def test_hadoop_fs_against_shim(fake_hdfs, tmp_path):
    fs = HadoopFS()
    base = "hdfs://fake/warehouse"
    fs.mkdir(base)
    assert fs.exists(base)
    # streaming write -> read roundtrip via pipes; close() is durable so
    # the file exists as soon as the with-block exits
    with fs.open_write(f"{base}/part-0") as f:
        f.write(b"line1\nline2\n")
    assert fs.exists(f"{base}/part-0")
    with fs.open_read(f"{base}/part-0") as f:
        assert f.read() == b"line1\nline2\n"
    # reading a missing path raises at close, not an empty stream
    with pytest.raises(IOError):
        s = fs.open_read(f"{base}/nonexistent")
        s.read()
        s.close()
    # put/get files
    local = tmp_path / "up.txt"
    local.write_text("payload")
    fs.put(str(local), f"{base}/up.txt")
    fs.get(f"{base}/up.txt", str(tmp_path / "down.txt"))
    assert (tmp_path / "down.txt").read_text() == "payload"
    # ls / mv / rm
    names = [p.rsplit("/", 1)[-1] for p in fs.ls(base)]
    assert set(names) == {"part-0", "up.txt"}
    fs.rename(f"{base}/up.txt", f"{base}/moved.txt")
    assert fs.exists(f"{base}/moved.txt")
    # a deliberate partial read must NOT raise (SIGPIPE on the CLI)
    with fs.open_write(f"{base}/big") as f:
        f.write(b"x" * (1 << 20))
    with fs.open_read(f"{base}/big") as f:
        assert f.read(10) == b"x" * 10
    fs.remove(base)
    assert not fs.exists(base)


def test_hadoop_fs_error_surfaces(fake_hdfs):
    fs = HadoopFS()
    with pytest.raises(IOError):
        fs.ls("hdfs://fake/definitely/missing/dir/x")


# ---------------------------------------------------------------------------
# elastic launch
# ---------------------------------------------------------------------------

def test_launch_refuses_local_procs_off_cpu(tmp_path, monkeypatch):
    """A chip belongs to one process: --nproc > 1 is refused unless the
    children are pinned to CPU."""
    from paddlebox_tpu.launch.main import main
    script = tmp_path / "worker.py"
    script.write_text("raise SystemExit(7)\n")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(SystemExit) as e:
        main(["--nproc", "2", str(script)])
    assert e.value.code == 2
    assert main(["--nproc", "1", str(script)]) == 7


def test_launch_elastic_single_host(tmp_path):
    """Elastic mode end-to-end on one host: ranks come from the lease
    table; the worker script records its env and exits."""
    from paddlebox_tpu.launch.main import main
    script = tmp_path / "worker.py"
    out = tmp_path / "out"
    out.mkdir()
    script.write_text(textwrap.dedent(f"""\
        import os
        rank = os.environ["PBX_PROCESS_ID"]
        with open(r"{out}" + "/r" + rank, "w") as f:
            f.write(os.environ["PBX_NUM_PROCESSES"] + ":" +
                    os.environ["PBX_ELASTIC_GENERATION"])
        """))
    rc = main(["--elastic-dir", str(tmp_path / "es"), "--host-id", "h0",
               "--nproc", "2", "--min-hosts", "1",
               "--elastic-timeout", "30", str(script)])
    assert rc == 0
    assert sorted(os.listdir(out)) == ["r0", "r1"]
    assert (out / "r0").read_text().startswith("2:")
