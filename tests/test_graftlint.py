"""graftlint (tools/graftlint) as a tier-1 gate.

Two halves:

1. **Planted-violation fixtures** — tiny synthetic projects, one per
   pass, each asserting: the violation is caught, the matching
   ``# graftlint: allow-*`` pragma suppresses it, and a clean variant
   produces nothing. Plus baseline suppression / ``--fail-on new``
   semantics and the near-miss metric-name warning.
2. **The real tree** — ``run_passes(default_config(REPO))`` over
   ``paddlebox_tpu/`` and ``tools/`` must produce ZERO
   non-baselined error findings: a PR that introduces a hot-path sync,
   an undocumented flag/metric, a faultpoint/doc drift, an unlocked
   cross-thread write, or replay-path wall-clock FAILS this suite.

No jax import needed by the suite itself — graftlint is stdlib-only.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.graftlint import (Baseline, DEFAULT_BASELINE,  # noqa: E402
                             RunResult, default_config, fixture_config,
                             run_passes)
from tools.graftlint.passes import registry_drift  # noqa: E402


def _write(root, rel, text):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        f.write(textwrap.dedent(text))
    return path


def _by_code(findings, code):
    return [f for f in findings if f.code == code]


def _active(result, code=None):
    out = [f for f in result.active]
    if code is not None:
        out = [f for f in out if f.code == code]
    return out


# ---------------------------------------------------------------------------
# pass 1: hot-path sync detector
# ---------------------------------------------------------------------------

HOT_FIXTURE = """
    import jax
    import jax.numpy as jnp
    import numpy as np

    def hot_root(x):
        y = jnp.sum(x)
        helper(y)
        bad = float(y)                      # HS001
        if y > 0:                           # HS005
            pass
        np.asarray(y)                       # HS003
        y.item()                            # HS002
        jax.device_get(y)                   # HS004
        return bad

    def helper(v):
        w = v + jnp.ones(3)
        return int(w)                       # HS001 (reached via root)

    def allowed_root(x):
        y = jnp.sum(x)
        # graftlint: allow-sync(fixture says this one is fine)
        return float(y)

    def clean_root(x):
        y = jnp.sum(x)
        z = y + 1
        if x is not None:                   # identity check: no finding
            z = z * 2
        return z

    def cold(x):
        return float(jnp.sum(x))            # unreachable: no finding
"""


def test_hot_sync_fixture(tmp_path):
    _write(str(tmp_path), "hot.py", HOT_FIXTURE)
    cfg = fixture_config(str(tmp_path), hot_roots=(
        "hot:hot_root", "hot:allowed_root", "hot:clean_root"))
    res = run_passes(cfg, ["hot_sync"])
    codes = sorted(f.code for f in res.active)
    assert codes == ["HS001", "HS001", "HS002", "HS003", "HS004",
                     "HS005"], [f.message for f in res.findings]
    # the helper finding proves call-graph reachability
    assert any("helper" in f.key for f in res.active)
    # the pragma'd float() is recorded as allowed, not active
    allowed = [f for f in res.findings if f.suppressed_by is not None]
    assert len(allowed) == 1
    assert "fixture says" in allowed[0].suppressed_by
    # nothing anchored in clean_root or the unreachable cold()
    assert not any("clean_root" in f.key or ":cold" in f.key
                   for f in res.active)


def test_hot_sync_traced_body_params_are_tracers(tmp_path):
    _write(str(tmp_path), "hot.py", """
        def _build_step(self):
            def body(tables, n):
                if n:                       # tracer truth-test
                    return tables
                return tables
            return body
    """)
    cfg = fixture_config(str(tmp_path), hot_roots=("hot:_build_step",))
    res = run_passes(cfg, ["hot_sync"])
    assert [f.code for f in res.active] == ["HS005"]


# ---------------------------------------------------------------------------
# pass 2: flag hygiene
# ---------------------------------------------------------------------------

FLAGS_FIXTURE = """
    def define_flag(name, default, help="", type=None):
        pass

    def validate_all():
        return ["bad_default does not parse"]

    define_flag("used_documented", 1)
    define_flag("orphan_flag", 2)                 # FH002: never referenced
    define_flag("undocumented_flag", 3)           # FH003: not in DOCS.md
    define_flag("bad_default", "nope", type=int)  # FH005 (static)
"""

FLAG_CODE_FIXTURE = """
    def flag(name):
        return name

    def f():
        flag("used_documented")
        flag("undocumented_flag")
        flag("bad_default")
        flag("missing_flag")                      # FH001
"""

FLAG_DOCS = """
    # Docs
    `FLAGS_used_documented` does things. `FLAGS_orphan_flag` too, and
    `FLAGS_bad_default`. But `FLAGS_ghost_flag` was renamed away.  <!-- FH004 -->
"""


def test_flag_hygiene_fixture(tmp_path):
    _write(str(tmp_path), "flags.py", FLAGS_FIXTURE)
    _write(str(tmp_path), "code.py", FLAG_CODE_FIXTURE)
    _write(str(tmp_path), "DOCS.md", FLAG_DOCS)
    cfg = fixture_config(str(tmp_path))
    res = run_passes(cfg, ["flag_hygiene"])
    assert [f.key for f in _active(res, "FH001")] == ["missing_flag"]
    assert [f.key for f in _active(res, "FH002")] == ["orphan_flag"]
    assert [f.key for f in _active(res, "FH003")] == ["undocumented_flag"]
    assert [f.key for f in _active(res, "FH004")] == ["ghost_flag"]
    # FH005 twice: the static type/default mismatch AND the module's own
    # validate_all() report
    fh5 = _active(res, "FH005")
    assert any(f.key == "bad_default" for f in fh5)
    assert any("bad_default does not parse" in f.message for f in fh5)


def test_flag_hygiene_pragma_on_define(tmp_path):
    _write(str(tmp_path), "flags.py", """
        def define_flag(name, default, help="", type=None): pass
        def validate_all(): return []
        # graftlint: allow-flag(kept for operator compat)
        define_flag("deliberate_orphan", 1)
    """)
    _write(str(tmp_path), "DOCS.md", "`FLAGS_deliberate_orphan`\n")
    cfg = fixture_config(str(tmp_path))
    res = run_passes(cfg, ["flag_hygiene"])
    assert not res.active
    assert any(f.suppressed_by for f in res.findings)


# ---------------------------------------------------------------------------
# pass 3: registry drift (+ near-miss warning)
# ---------------------------------------------------------------------------

REGISTRY_CODE = """
    from x import monitor, faults

    def f(site):
        faults.faultpoint("eng/build")
        faults.faultpoint("eng/missing_from_doc")   # RD001
        monitor.add("ns/good_metric", 1)
        monitor.add("ns/typo_metrc", 1)             # RD004 near-miss
        monitor.add("ns/very_undocumented", 1)      # RD003
        monitor.add(f"dyn/{site}_done", 1)          # pattern: doc has dyn/<s>_done
"""

REGISTRY_DOCS = """
    # Docs

    metrics: `ns/good_metric`, `ns/typo_metric`, `dyn/<site>_done`,
    and `ns/stale_gone` (RD005).

    ## Faultpoint site table

    | Site | Where |
    |---|---|
    | `eng/build` | the build |
    | `eng/stale_site` | removed long ago |
"""


def test_registry_drift_fixture(tmp_path):
    _write(str(tmp_path), "code.py", REGISTRY_CODE)
    _write(str(tmp_path), "DOCS.md", REGISTRY_DOCS)
    cfg = fixture_config(str(tmp_path))
    res = run_passes(cfg, ["registry_drift"])
    assert [f.key for f in _active(res, "RD001")] == ["eng/missing_from_doc"]
    assert [f.key for f in _active(res, "RD002")] == ["eng/stale_site"]
    assert [f.key for f in _active(res, "RD003")] == ["ns/very_undocumented"]
    near = _active(res, "RD004")
    assert [f.key for f in near] == ["ns/typo_metrc"]
    assert near[0].severity == "warn"
    assert "ns/typo_metric" in near[0].message     # the did-you-mean
    assert [f.key for f in _active(res, "RD005")] == ["ns/stale_gone"]
    # the f-string pattern matched the <site> doc form: no finding for it
    assert not any("dyn/" in f.key for f in res.active)


def test_registry_transient_contract(tmp_path):
    _write(str(tmp_path), "faults_mod.py", """
        _TRANSIENT_TYPES = (OSError,)
        class InjectedFault(RuntimeError):
            pass
        def is_transient(e):
            return isinstance(e, _TRANSIENT_TYPES)
    """)
    _write(str(tmp_path), "DOCS.md", "## Faultpoint site table\n")
    cfg = fixture_config(str(tmp_path))
    res = run_passes(cfg, ["registry_drift"])
    assert [f.code for f in res.active] == ["RD006"]


def test_globs_intersect():
    gi = registry_drift.globs_intersect
    assert gi("pass/*_steps", "pass/train_*")
    assert gi("a/b", "a/b")
    assert not gi("a/b", "a/c")
    assert gi("fault/*_injected", "fault/eng/build_injected")
    assert not gi("pass/*_steps", "day/*")
    assert gi("*", "anything/at/all")


# ---------------------------------------------------------------------------
# pass 4: lock discipline
# ---------------------------------------------------------------------------

LOCK_FIXTURE = """
    import threading

    class Racy:
        def __init__(self):
            self._lock = threading.Lock()
            self._evt = threading.Event()
            self.counter = 0
            self._t = threading.Thread(target=self._work)

        def _work(self):
            self.counter += 1          # LD001: unlocked thread write
            self._evt.wait()           # LD003: untimed wait off main

        def read(self):
            return self.counter

    class Clean:
        def __init__(self):
            self._lock = threading.Lock()
            self.n = 0
            self._t = threading.Thread(target=self._work)

        def _work(self):
            with self._lock:
                self.n += 1

        def read(self):
            with self._lock:
                return self.n

    class Pragmad:
        def __init__(self):
            self.flagv = False
            self._t = threading.Thread(target=self._work)

        def _work(self):
            # graftlint: allow-lock(monotonic latch, torn read fine)
            self.flagv = True

        def read(self):
            return self.flagv

    class DeadlockA:
        def __init__(self):
            self.la = threading.Lock()
            self.lb = threading.Lock()
            self._t = threading.Thread(target=self.one)

        def one(self):
            with self.la:
                with self.lb:
                    pass

        def two(self):
            with self.lb:
                with self.la:      # LD002: cycle la->lb->la
                    pass
"""


def test_lock_discipline_fixture(tmp_path):
    _write(str(tmp_path), "locks.py", LOCK_FIXTURE)
    cfg = fixture_config(str(tmp_path))
    res = run_passes(cfg, ["lock_discipline"])
    ld1 = _active(res, "LD001")
    assert [f.key for f in ld1] == ["Racy.counter"], \
        [f.message for f in res.findings]
    assert _active(res, "LD002"), "lock-order cycle not detected"
    ld3 = _active(res, "LD003")
    assert len(ld3) == 1 and ld3[0].severity == "warn"
    assert "_evt.wait" in ld3[0].key
    # the pragma'd latch is suppressed, the clean class silent
    assert any(f.suppressed_by and "Pragmad.flagv" in f.key
               for f in res.findings)
    assert not any("Clean." in f.key for f in res.active)


# ---------------------------------------------------------------------------
# pass 5: replay purity
# ---------------------------------------------------------------------------

REPLAY_FIXTURE = """
    import time
    import random
    import numpy as np

    def replay_root():
        t = time.time()                  # RP001
        r = random.random()              # RP002
        z = np.random.shuffle([1, 2])    # RP002
        s = {1, 2, 3}
        for x in s:                      # RP003 (warn)
            pass
        time.sleep(0.001)                # allowed
        ok = time.monotonic()            # allowed
        rng = np.random.default_rng(42)  # allowed (seeded)
        return sorted(s)                 # allowed

    def pragma_root():
        # graftlint: allow-replay(timestamp metadata only)
        return time.time()

    def cold():
        return time.time()               # unreachable: no finding
"""


def test_replay_purity_fixture(tmp_path):
    _write(str(tmp_path), "replay.py", REPLAY_FIXTURE)
    cfg = fixture_config(str(tmp_path), replay_roots=(
        "replay:replay_root", "replay:pragma_root"))
    res = run_passes(cfg, ["replay_purity"])
    assert [f.code for f in _active(res, "RP001")] == ["RP001"]
    assert len(_active(res, "RP002")) == 2
    rp3 = _active(res, "RP003")
    assert len(rp3) == 1 and rp3[0].severity == "warn"
    assert any(f.suppressed_by == "timestamp metadata only"
               for f in res.findings)
    assert not any(":cold" in f.key for f in res.active)


# ---------------------------------------------------------------------------
# baseline + fail-on semantics
# ---------------------------------------------------------------------------

def _flag_fixture_result(tmp_path) -> RunResult:
    _write(str(tmp_path), "flags.py", FLAGS_FIXTURE)
    _write(str(tmp_path), "code.py", FLAG_CODE_FIXTURE)
    _write(str(tmp_path), "DOCS.md", FLAG_DOCS)
    return run_passes(fixture_config(str(tmp_path)), ["flag_hygiene"])


def test_baseline_suppression_and_fail_on(tmp_path):
    res = _flag_fixture_result(tmp_path)
    assert res.failures("new"), "fixture must fail with no baseline"
    # baseline every current finding -> fail-on new passes, any fails
    bl = Baseline({f.fingerprint(res.root): "reviewed: fixture"
                   for f in res.active})
    res.apply_baseline(bl)
    assert res.failures("new") == []
    assert res.failures("any"), "--fail-on any ignores the baseline"
    assert res.failures("none") == []
    s = res.summary()
    assert s["new"] == 0 and s["baselined"] == len(res.active)


def test_baseline_is_line_number_stable(tmp_path):
    res1 = _flag_fixture_result(tmp_path)
    bl = Baseline({f.fingerprint(res1.root): "ok" for f in res1.active})
    # shift every line down; fingerprints must not move
    for rel in ("flags.py", "code.py"):
        p = os.path.join(str(tmp_path), rel)
        with open(p) as f:
            src = f.read()
        with open(p, "w") as f:
            f.write("# shifted\n# shifted\n" + src)
    res2 = run_passes(fixture_config(str(tmp_path)), ["flag_hygiene"])
    res2.apply_baseline(bl)
    assert res2.failures("new") == []


def test_baseline_save_load_roundtrip(tmp_path):
    path = os.path.join(str(tmp_path), "bl.json")
    bl = Baseline({"a:b:c:d": "why"})
    bl.save(path)
    assert Baseline.load(path).entries == {"a:b:c:d": "why"}
    assert Baseline.load(os.path.join(str(tmp_path), "nope.json")).entries \
        == {}


# ---------------------------------------------------------------------------
# the real tree: the adoption gate
# ---------------------------------------------------------------------------

def test_real_tree_has_no_new_findings():
    """The tier-1 contract: graftlint over paddlebox_tpu/ and tools/
    yields zero non-baselined errors. If this fails, either fix
    the finding, add an inline pragma with a reason, or (for a reviewed
    intentional case) add a baseline entry with a reason."""
    cfg = default_config(REPO)
    res = run_passes(cfg)
    res.apply_baseline(Baseline.load(DEFAULT_BASELINE))
    failures = res.failures("new")
    msg = "\n".join(
        f"{os.path.relpath(f.path, REPO)}:{f.lineno} [{f.pass_id}/"
        f"{f.code}] {f.message}" for f in failures)
    assert not failures, f"new graftlint findings:\n{msg}"
    assert res.files_scanned > 100  # the walker really saw the tree


def test_real_tree_every_pragma_has_a_reason():
    """Pragmas are the inline escape hatch; an empty reason defeats the
    review trail."""
    res = run_passes(default_config(REPO))
    for f in res.findings:
        if f.suppressed_by is not None:
            assert f.suppressed_by.strip() not in ("", "allowed by pragma"), \
                f"{f.path}:{f.lineno} pragma without a reason"


def test_cli_end_to_end(tmp_path):
    """python -m tools.graftlint over the real tree: exit 0, JSON and
    summary artifacts parse, planted regression exits 1."""
    summary_path = os.path.join(str(tmp_path), "s.json")
    proc = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--json",
         "--summary", summary_path],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout)
    assert out["summary"]["new"] == 0
    with open(summary_path) as f:
        summary = json.load(f)
    assert summary["findings_total"] >= summary["baselined"]
    assert set(summary["per_pass"]) == {
        "hot_sync", "flag_hygiene", "registry_drift",
        "lock_discipline", "replay_purity"}


def test_cli_fails_on_planted_violation(tmp_path):
    """A fixture tree with a violation + the CLI --fail-on new exits
    nonzero; --write-baseline then adopts it and the rerun exits 0."""
    root = str(tmp_path)
    _write(root, "flags.py",
           "def define_flag(n, d, help='', type=None): pass\n"
           "def validate_all(): return []\n")
    _write(root, "DOCS.md", "nothing\n")
    _write(root, "code.py",
           "def flag(n): return n\n"
           "def f(): flag('nonexistent_flag')\n")
    bl = os.path.join(root, "bl.json")
    args = [sys.executable, "-m", "tools.graftlint", "--root", root,
            "--baseline", bl, "--passes", "flag_hygiene", ""]
    proc = subprocess.run(args, cwd=REPO, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "nonexistent_flag" in proc.stdout
    adopt = subprocess.run(
        args[:-1] + ["--write-baseline", ""],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert adopt.returncode == 0, adopt.stdout + adopt.stderr
    proc2 = subprocess.run(args, cwd=REPO, capture_output=True,
                           text=True, timeout=120)
    assert proc2.returncode == 0, proc2.stdout + proc2.stderr


# ---------------------------------------------------------------------------
# flags.validate_all (the small-fix satellite)
# ---------------------------------------------------------------------------

def test_validate_all_clean_and_dirty():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_flags_probe_test", os.path.join(
            REPO, "paddlebox_tpu", "core", "flags.py"))
    flags = importlib.util.module_from_spec(spec)
    sys.modules["_flags_probe_test"] = flags
    try:
        spec.loader.exec_module(flags)
    finally:
        sys.modules.pop("_flags_probe_test", None)
    # the live registry's defaults all round-trip
    assert flags.validate_all() == []
    # a planted bad default is caught
    reg = flags.FlagRegistry()
    reg.define("fine", 3)
    reg.define("bad", "xyz", type=int)
    errs = reg.validate_all()
    assert len(errs) == 1 and "bad" in errs[0]
    # bool/int confusion is caught (True is an int at isinstance level)
    reg2 = flags.FlagRegistry()
    reg2.define("sneaky", True, type=int)
    assert any("sneaky" in e for e in reg2.validate_all())
