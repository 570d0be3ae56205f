"""Thread-safe span tracer buffering Chrome-trace events.

Role of the per-stage ``platform::Timer`` blocks the reference prints via
``PrintSyncTimer`` (``fleet/box_wrapper.h:395-420``) and its nvprof range
annotations — re-expressed as a process-global tracer whose spans land in
a bounded ring buffer and export to a ``chrome://tracing`` / Perfetto
loadable JSON file (``FLAGS_trace_path``).

Design constraints (the CTR hot loop runs through here):

- **Zero hot-loop cost when disabled.** ``span()`` checks ONE cached bool
  and returns a shared ``nullcontext`` — no flag-registry lock, no
  allocation. Enabling is explicit (``enable()`` or ``init_from_flags()``
  reading ``FLAGS_trace_path``), never inferred per event.
- **Host-side only.** Spans wrap dispatch/fetch boundaries and host
  stages; nothing here may add ops or syncs to a jitted program.
- **Bounded.** Events live in a ring (``FLAGS_trace_ring_events``); a
  multi-hour run cannot OOM the host, and ``snapshot()`` hands the tail
  to crash/stall dumps (``stall_forensics``).

Distributed tracing (OBSERVABILITY.md "Distributed tracing"): a
compact TRACE CONTEXT — ``{tid, sid, origin}`` = trace id, sending
span id, origin host:pid — rides the framed RPC header
(``distributed/rpc.py``), so every server-side span across the fleet
records the trace id of the request that caused it. Context is
thread-local (``use_context``); span/trace ids come from a process
counter salted with the pid (no wall clock, no randomness — the replay
closure stays pure). Each trace file carries a WALL-CLOCK ANCHOR
(``otherData.wall_anchor_ns`` = the unix ns at ring ts 0) plus the
per-connection clock offsets measured by the RPC handshake
(``note_peer_offset``), which is what lets ``tools/trace_report.py
--merge`` stitch N per-process rings onto ONE global timeline with
cross-process flow arrows.

Usage::

    from paddlebox_tpu.core import trace
    trace.enable("/tmp/run.trace.json")
    with trace.span("pull", k=4):
        ...
    trace.export()           # or automatic at process exit

The device half (end of this module): the dense train steps open
``jax.named_scope`` s of one vocabulary (``SCOPE_TOPS``, ``SCOPE_PARTS``),
which XLA keeps as each compiled instruction's ``op_name``. A step compiled
through ``models/train_step.make_train_step`` is recorded
(``record_program``: a reference, nothing parsed), and
``device_scope_table`` lays a device trace's per-instruction times on the
scopes and phases of the recorded program that ran.
"""

from __future__ import annotations

import atexit
import itertools
import json
import os
import re
import threading
import time
from collections import deque
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple)

from paddlebox_tpu.core import flags


def _json_safe(v: Any) -> Any:
    """Clamp span args to JSON scalars — a jax array or object captured
    into an event must not make the whole export unserializable."""
    if isinstance(v, (bool, int, float, str)) or v is None:
        return v
    return repr(v)


class _NullSpan:
    """Shared no-op context for the disabled path (allocation-free)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()
NULL_SPAN = _NULL_SPAN   # public alias (callers pre-picking a span)

# -- distributed trace context ------------------------------------------------

# Per-thread active context: {"tid": trace id, "sid": this hop's span id,
# "origin": "host:pid" of the trace root, optional "parent": the sending
# span id}. Set by the RPC server loop for the handler's duration, by
# fan-out helpers that carry a caller's context into worker threads, and
# by the serving micro-batcher for the batch it coalesced.
_CTX = threading.local()

# Monotonic span-id source. next() on itertools.count is atomic under
# the GIL; ids are salted with the pid so two processes never collide.
_SPAN_IDS = itertools.count(1)


def _new_id() -> str:
    return f"{os.getpid():x}.{next(_SPAN_IDS):x}"


def current_context() -> Optional[Dict[str, str]]:
    """The calling thread's active trace context (None when no traced
    request is in scope — including always when tracing is off, since
    only traced RPCs install one)."""
    return getattr(_CTX, "ctx", None)


class _CtxScope:
    """Push/pop one context on the calling thread (re-entrant; restores
    whatever was active on exit, including None)."""

    __slots__ = ("_ctx", "_prev")

    def __init__(self, ctx: Optional[Dict[str, str]]):
        self._ctx = ctx

    def __enter__(self):
        self._prev = getattr(_CTX, "ctx", None)
        _CTX.ctx = self._ctx
        return self._ctx

    def __exit__(self, *exc):
        _CTX.ctx = self._prev
        return False


def use_context(ctx: Optional[Dict[str, str]]):
    """``with trace.use_context(ctx): ...`` — activate a captured
    context on this thread (fan-out worker threads, the micro-batcher
    dispatcher). ``None`` is legal and deactivates for the scope."""
    return _CtxScope(ctx)


def wire_context() -> Optional[Dict[str, str]]:
    """The context an outgoing RPC should carry, or None when tracing
    is off (the one-cached-bool discipline: a disabled process attaches
    nothing and pays one attribute check). A fresh root is minted when
    no context is active — the client edge is where a trace starts."""
    if not GLOBAL._enabled:
        return None
    cur = getattr(_CTX, "ctx", None)
    sid = _new_id()
    if cur is None:
        return {"tid": _new_id(), "sid": sid,
                "origin": f"{GLOBAL.host}:{GLOBAL._pid}"}
    return {"tid": cur["tid"], "sid": sid,
            "origin": cur.get("origin", "")}


def server_context(wire_ctx: Dict[str, Any]) -> Dict[str, str]:
    """The server-side child of a context received off the wire: same
    trace id, a fresh local span id, ``parent`` = the client's span id
    (what the merge tool draws the cross-process flow arrow from)."""
    return {"tid": str(wire_ctx.get("tid", "")),
            "sid": _new_id(),
            "parent": str(wire_ctx.get("sid", "")),
            "origin": str(wire_ctx.get("origin", ""))}


class _Span:
    """One live span: records a Chrome 'X' (complete) event on exit —
    including exit-via-exception, with the exception recorded in the
    event args so a crash dump names the failing stage."""

    __slots__ = ("_tracer", "name", "args", "_t0")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.args = args

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, etype, evalue, tb):
        t1 = time.perf_counter_ns()
        args = self.args
        if etype is not None:
            args = dict(args or {})
            args["error"] = f"{etype.__name__}: {evalue!r}"
        self._tracer._record("X", self.name, self._t0, args,
                             dur_ns=t1 - self._t0)
        return False


class Tracer:
    """Process-global span tracer with a bounded event ring."""

    def __init__(self, capacity: int = 65536):
        self._lock = threading.Lock()
        self._events: deque = deque(maxlen=max(1, int(capacity)))
        self._enabled = False          # the ONE hot-path check
        self._path: Optional[str] = None
        self._epoch_ns = time.perf_counter_ns()
        # Wall-clock anchor: the unix ns corresponding to ring ts 0.
        # Captured back-to-back with the perf epoch so cross-process
        # merge (trace_report --merge) can place this ring on a global
        # timeline. Constructed once per process, outside any replay
        # closure.
        self._wall_anchor_ns = time.time_ns()
        self._pid = os.getpid()
        try:
            self.host = os.uname().nodename
        except (AttributeError, OSError):  # pragma: no cover - non-posix
            self.host = "localhost"
        # endpoint -> {"offset_ms", "rtt_ms"} from the RPC clock
        # handshake (rpc.FramedRPCConn): how far each peer's wall clock
        # sits from ours, embedded in the export for merge refinement.
        self._peer_offsets: Dict[str, Dict[str, float]] = {}
        self._atexit_registered = False
        self._dropped = 0

    # -- lifecycle --------------------------------------------------------

    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self, path: Optional[str] = None,
               ring_events: Optional[int] = None) -> None:
        """Turn tracing on; ``path`` (if given) is where ``export()`` and
        the process-exit hook write the Chrome trace JSON."""
        with self._lock:
            if ring_events and ring_events != self._events.maxlen:
                self._events = deque(self._events,
                                     maxlen=max(1, int(ring_events)))
            if path:
                self._path = path
            self._enabled = True
            if self._path and not self._atexit_registered:
                self._atexit_registered = True
                atexit.register(self._export_at_exit)

    def disable(self) -> None:
        self._enabled = False

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._dropped = 0

    def init_from_flags(self) -> bool:
        """Idempotent flag-driven enable: a non-empty ``FLAGS_trace_path``
        turns tracing on (called at pass/service entry points, so
        env-set flags work without code changes). Returns enabled."""
        if not self._enabled:
            path = flags.flag("trace_path")
            if path:
                self.enable(path, int(flags.flag("trace_ring_events")))
        return self._enabled

    # -- recording --------------------------------------------------------

    def _record(self, ph: str, name: str, t_ns: int,
                args: Optional[Dict[str, Any]], dur_ns: int = 0) -> None:
        if not self._enabled:
            return  # span opened just as tracing was disabled
        th = threading.current_thread()
        ev: Dict[str, Any] = {
            "name": name, "ph": ph, "pid": self._pid,
            "tid": th.ident or 0,
            "ts": (t_ns - self._epoch_ns) / 1e3,   # Chrome wants us
        }
        if ph == "X":
            ev["dur"] = dur_ns / 1e3
        ctx = getattr(_CTX, "ctx", None)
        if ctx is not None:
            # Every span recorded under a traced request carries its
            # caller's trace id — the cross-process correlation key.
            args = dict(args or {})
            args.setdefault("trace", ctx["tid"])
        if args:
            ev["args"] = {k: _json_safe(v) for k, v in args.items()}
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self._dropped += 1
            self._events.append(ev)

    def span(self, name: str, **args: Any):
        """``with trace.span("pull", k=4): ...`` — a null context when
        disabled, a recorded Chrome complete-event otherwise."""
        if not self._enabled:
            return _NULL_SPAN
        return _Span(self, name, args)

    def instant(self, name: str, **args: Any) -> None:
        """Point-in-time marker (phase transitions, watchdog ticks)."""
        if not self._enabled:
            return
        self._record("i", name, time.perf_counter_ns(), args)

    def counter(self, name: str, **values: float) -> None:
        """Chrome counter event — graphs a named value over time."""
        if not self._enabled:
            return
        self._record("C", name, time.perf_counter_ns(), values)

    # -- output -----------------------------------------------------------

    def snapshot(self) -> List[Dict[str, Any]]:
        """The current ring contents, oldest first — the crash/stall dump
        surface (``stall_forensics``)."""
        with self._lock:
            return list(self._events)

    def trace_object(self) -> Dict[str, Any]:
        """The full Chrome-trace JSON object (thread-name metadata +
        events) — what ``export`` serializes."""
        events = self.snapshot()
        meta = []
        seen = set()
        for th in threading.enumerate():
            if th.ident is None or th.ident in seen:
                continue
            seen.add(th.ident)
            meta.append({"name": "thread_name", "ph": "M",
                         "pid": self._pid, "tid": th.ident,
                         "args": {"name": th.name}})
        with self._lock:
            peer_offsets = {ep: dict(v)
                            for ep, v in self._peer_offsets.items()}
        return {"traceEvents": meta + events,
                "displayTimeUnit": "ms",
                "otherData": {
                    # graftlint: allow-lock(approximate stat; torn read ok)
                    "dropped_events": self._dropped,
                    # The merge anchors: unix ns of ring ts 0, this
                    # process's identity, and measured peer clock
                    # offsets (trace_report --merge).
                    "wall_anchor_ns": int(self._wall_anchor_ns),
                    "host": self.host,
                    "pid": int(self._pid),
                    "peer_offsets_ms": peer_offsets}}

    def note_peer_offset(self, endpoint: str, offset_ms: float,
                         rtt_ms: float = 0.0) -> None:
        """Record one clock-handshake result (rpc.FramedRPCConn calls
        this per connect while tracing is on)."""
        with self._lock:
            self._peer_offsets[endpoint] = {
                "offset_ms": round(float(offset_ms), 3),
                "rtt_ms": round(float(rtt_ms), 3)}

    def export(self, path: Optional[str] = None) -> str:
        """Write the Perfetto/chrome://tracing-loadable JSON file.
        Returns the path written."""
        path = path or self._path
        if not path:
            raise ValueError("no trace path: pass one or enable(path=...)")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.trace_object(), f, default=str)
        os.replace(tmp, path)
        return path

    def _export_at_exit(self) -> None:
        if self._enabled and self._path:
            try:
                self.export()
            except OSError:
                pass


GLOBAL = Tracer()

enable = GLOBAL.enable
disable = GLOBAL.disable
clear = GLOBAL.clear
enabled = lambda: GLOBAL.enabled  # noqa: E731
init_from_flags = GLOBAL.init_from_flags
span = GLOBAL.span
instant = GLOBAL.instant
counter = GLOBAL.counter
snapshot = GLOBAL.snapshot
export = GLOBAL.export
note_peer_offset = GLOBAL.note_peer_offset

# Extra stall-forensics sections contributed by other modules (the rpc
# layer registers its in-flight call table here — trace cannot import
# rpc without a cycle). Each provider must be cheap and non-raising.
_FORENSICS_PROVIDERS: Dict[str, Callable[[], Any]] = {}


def register_forensics_provider(name: str, fn: Callable[[], Any]) -> None:
    _FORENSICS_PROVIDERS[name] = fn


def stall_forensics(max_events: int = 256) -> Dict[str, Any]:
    """Post-mortem payload for a hung run: every thread's Python stack
    (faulthandler), the trace ring tail, and every registered provider
    section (e.g. ``inflight_rpcs`` — the in-flight RPC table, so a
    hang names the REMOTE it is stuck on, not just local frames).
    The pass watchdog logs this so a 'no progress in phase X' stall
    names the blocked frame, not just the phase."""
    import faulthandler
    import tempfile
    try:
        with tempfile.TemporaryFile(mode="w+") as f:
            faulthandler.dump_traceback(file=f, all_threads=True)
            f.seek(0)
            stacks = f.read().splitlines()
    except Exception as e:  # noqa: BLE001 - forensics must never raise
        stacks = [f"<faulthandler failed: {e!r}>"]
    out: Dict[str, Any] = {"thread_stacks": stacks,
                           "trace_tail": GLOBAL.snapshot()[-max_events:]}
    for name, fn in _FORENSICS_PROVIDERS.items():
        try:
            out[name] = fn()
        except Exception as e:  # noqa: BLE001 - forensics must never raise
            out[name] = f"<provider failed: {e!r}>"
    return out


# -- the device half: compiled steps read by scope ----------------------------

# The named scopes of a dense train step (``jax.named_scope``). Top level:
# the token embedding, the layers, the final norm / head / loss, and the
# optimizer's update and apply. Inside ``stack`` one part a layer part;
# time under ``stack`` in no part is the stack's own work (scan carries,
# stacking of kept values, gradient sums between passes).
SCOPE_TOPS = ("embed", "stack", "head", "optimizer")
SCOPE_PARTS = ("attention", "mlp", "moe", "mamba")
# operations that only hold others: their time is their children's
CONTAINERS = frozenset(("while", "conditional", "call"))

_TRANSFORM = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = (.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_CALLED = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation"
                     r"|false_computation)=%?([\w.\-]+)")
_CALLED_SET = re.compile(
    r"\b(?:branch_computations|called_computations)=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")

Scope = Tuple[Optional[str], Optional[str], str]    # (top, part, phase)

_PROGRAMS: deque = deque(maxlen=8)      # compiled steps, newest last
# id of a recorded program -> (its scopes, the seconds reading them took)
_PARSED: Dict[int, Tuple[Dict[str, Tuple[str, Scope]], float]] = {}


def record_program(compiled: Any) -> None:
    """Keep a compiled step (``jax.stages.Compiled``) for
    ``device_scope_table``: a reference to code that holds no device
    buffers. Nothing is read from it until a table is asked for."""
    _PROGRAMS.append(compiled)


def scope_of(op_name: str) -> Scope:
    """An instruction's ``op_name`` -> ``(top, part, phase)``: the first
    segment of its name stack, transforms (``jvp(...)``, ``transpose(...)``,
    ``vmap(...)``) taken off, that is one of ``SCOPE_TOPS`` (None: no
    top-level scope), the first segment after it that is one of
    ``SCOPE_PARTS`` (None: the top's own work), and the phase JAX's own
    marks give: ``recompute`` under ``rematted_computation``, else
    ``backward`` under a ``transpose(``, else ``forward``."""
    top = part = None
    for seg in op_name.split("/"):
        m = _TRANSFORM.match(seg)
        while m:
            seg = m.group(1)
            m = _TRANSFORM.match(seg)
        if top is None:
            if seg in SCOPE_TOPS:
                top = seg
        elif seg in SCOPE_PARTS:
            part = seg
            break
    if "rematted_computation" in op_name:
        phase = "recompute"
    elif "transpose(" in op_name:
        phase = "backward"
    else:
        phase = "forward"
    return top, part, phase


def _closing(text: str, start: int) -> int:
    """Index just past the parenthesis that closes the one at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        depth += (text[i] == "(") - (text[i] == ")")
        if depth == 0:
            return i + 1
    return len(text)


def _opcode(rest: str) -> Tuple[str, str]:
    """``shape opcode(operands), ...`` -> (opcode, operands); a tuple
    shape is skipped to its closing parenthesis."""
    rest = rest.lstrip()
    if rest.startswith("("):
        rest = rest[_closing(rest, 0):]
    else:
        rest = rest.partition(" ")[2]
    rest = rest.lstrip()
    opcode, paren, _ = rest.partition("(")
    if not paren:
        return opcode.strip(), ""
    return opcode.strip(), rest[len(opcode):_closing(rest, len(opcode))]


def program_scopes(text: str) -> Dict[str, Tuple[str, Scope]]:
    """A compiled module's text (``Compiled.as_text()``) -> ``{instruction:
    (opcode, (top, part, phase))}`` for every instruction it holds, those
    of fused computations too: HLO names are unique within a module,
    inside loop bodies as well, and a device trace names an operation by
    its instruction.

    An instruction whose ``op_name`` names no top-level scope (one XLA
    made: a cast of stacked weights for the MXU hoisted out of its loop,
    a copy or prefetch at a loop's edge; or one JAX built outside the
    scope it serves, as a scan's partial evaluation does) takes the scope
    of the root of the computation it fuses, else of the first of its
    users that has one, else of the instruction that runs its
    computation. What is left has none: it is unscoped."""
    own: Dict[str, Scope] = {}
    opcode: Dict[str, str] = {}
    home: Dict[str, str] = {}           # instruction -> its computation
    root: Dict[str, str] = {}           # computation -> its ROOT
    caller: Dict[str, str] = {}         # computation -> who runs it
    fuses: Dict[str, str] = {}          # fusion -> computation it fuses
    users: Dict[str, List[str]] = {}
    computation = ""
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            computation = head.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        name, rest = m.groups()
        meta = _OP_NAME.search(rest)
        own[name] = scope_of(meta.group(1) if meta else "")
        opcode[name], operands = _opcode(rest)
        for operand in _OPERAND.findall(operands):
            users.setdefault(operand, []).append(name)
        home[name] = computation
        if line.lstrip().startswith("ROOT "):
            root[computation] = name
        called = _CALLED.findall(rest)
        for group in _CALLED_SET.findall(rest):
            called += [c.strip().lstrip("%") for c in group.split(",")]
        for c in called:
            caller.setdefault(c, name)
        if opcode[name] == "fusion" and called:
            fuses[name] = called[0]

    resolved: Dict[str, Scope] = {}

    def scope(name: str, depth: int = 0) -> Scope:
        if own[name][0] is not None or depth > 256:
            return own[name]
        if name not in resolved:
            resolved[name] = own[name]          # a cycle ends here
            inner = root.get(fuses.get(name, ""))
            found = [own[inner]] if inner and own[inner][0] else []
            for user in ([] if found else users.get(name, ())):
                got = scope(user, depth + 1)
                if got[0] is not None:
                    found = [got]
                    break
            up = caller.get(home[name])
            if not found and up:
                found = [scope(up, depth + 1)]
            resolved[name] = found[0] if found else own[name]
        return resolved[name]
    return {name: (opcode[name], scope(name)) for name in own}


class ScopeTable(NamedTuple):
    """A traced window's leaf time on the scopes of the step that ran."""
    # (top, part, phase) -> (seconds, calls) of the step's instructions;
    # top None: no top-level scope
    rows: Dict[Scope, Tuple[float, float]]
    # leaf time of instructions the step does not hold (other programs)
    elsewhere: Tuple[float, float]
    parse_s: float      # what reading the step's text took (once a process)

    def seconds(self, **where: Optional[str]) -> float:
        """Leaf seconds of the rows whose ``top``, ``part`` and ``phase``
        equal those given (a key left out matches any)."""
        keys = ("top", "part", "phase")
        return sum(v[0] for k, v in self.rows.items()
                   if all(k[keys.index(n)] == want
                          for n, want in where.items()))


def device_scope_table(op_seconds: Dict[str, Sequence[float]]
                       ) -> Optional[ScopeTable]:
    """``op_seconds``: a device trace's ``{"name (opcode)": (seconds,
    calls)}`` (``benchmarks/trace/reduce.py``'s ``ops``) -> its leaf time
    by the scopes of the recorded program whose instructions cover the most
    of it: the step, where the step is the one program of the window that
    matters. Containers (``CONTAINERS``) are left out: their children are
    what they cost. None where no program was recorded or none ran."""
    live = {id(c) for c in _PROGRAMS}
    for stale in set(_PARSED) - live:
        del _PARSED[stale]
    for compiled in _PROGRAMS:
        if id(compiled) not in _PARSED:
            t0 = time.perf_counter()
            scopes = program_scopes(compiled.as_text())
            _PARSED[id(compiled)] = (scopes, time.perf_counter() - t0)
    leaves = []
    for key, (seconds, calls) in op_seconds.items():
        name, opcode = key, ""
        if key.endswith(")") and " (" in key:
            name, opcode = key[:-1].rsplit(" (", 1)
        if opcode not in CONTAINERS:
            leaves.append((name, opcode, float(seconds), float(calls)))

    def held(scopes, name, opcode):
        got = scopes.get(name)
        return (got is not None and got[0] not in CONTAINERS
                and (not opcode or got[0] == opcode))
    best, parse_s, covered = None, 0.0, 0.0
    for compiled in _PROGRAMS:
        scopes, seconds = _PARSED[id(compiled)]
        cover = sum(s for n, o, s, _ in leaves if held(scopes, n, o))
        if cover > covered:
            best, parse_s, covered = scopes, seconds, cover
    if best is None:
        return None
    rows: Dict[Scope, List[float]] = {}
    elsewhere = [0.0, 0.0]
    for name, opcode, seconds, calls in leaves:
        row = (rows.setdefault(best[name][1], [0.0, 0.0])
               if held(best, name, opcode) else elsewhere)
        row[0] += seconds
        row[1] += calls
    return ScopeTable({k: (v[0], v[1]) for k, v in rows.items()},
                      (elsewhere[0], elsewhere[1]), parse_s)
