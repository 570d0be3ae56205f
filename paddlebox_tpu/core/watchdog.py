"""Heartbeat stall watchdog: abort a hung pass instead of hanging forever.

A device call once blocked for 30+ minutes with zero progress and the
run recorded nothing. This module is the watchdog for the training
loop: the day runner arms it around each pass, the trainer's per-block
dispatch path feeds it, and a stall (``FLAGS_stall_timeout_s`` with no
heartbeat) dumps ``trace.stall_forensics()`` — every thread's Python
stack + the span-ring tail — into the log, then aborts the pass by
raising :class:`StallError` *in the armed thread* so the failure flows
through the same cancel/rollback/retry machinery as any other transient
fault.

The async raise (``PyThreadState_SetAsyncExc``) lands when the target
thread next executes Python bytecode. A thread blocked inside a C call
(a dead socket read with no timeout) won't see it until the call
returns — which is why the forensic dump happens FIRST: even if the
abort cannot land, the log names the blocked frame.

Zero cost when disarmed: ``beat()`` checks ONE cached bool
(the ``core/trace.py`` discipline). Nothing here touches jitted code.
"""

from __future__ import annotations

import ctypes
import sys
import threading
import time
from typing import Optional

from paddlebox_tpu.core import flags, log, monitor, trace


class StallError(RuntimeError):
    """No heartbeat within the stall timeout. Classified transient: the
    observed stalls (wedged device call, dead socket) are exactly the
    faults a pass retry recovers from."""

    transient = True


def _async_raise(thread_ident: int, exc_type: type) -> bool:
    """Raise ``exc_type`` in the thread with ``thread_ident`` the next
    time it runs Python bytecode. Returns whether the raise was armed."""
    res = ctypes.pythonapi.PyThreadState_SetAsyncExc(
        ctypes.c_ulong(thread_ident), ctypes.py_object(exc_type))
    if res > 1:  # pragma: no cover - interpreter-level invariant
        # Undo: >1 means we hit multiple states (stale ident) — leaving
        # the exception pending there would corrupt an innocent thread.
        ctypes.pythonapi.PyThreadState_SetAsyncExc(
            ctypes.c_ulong(thread_ident), None)
        return False
    return res == 1


class Watchdog:
    """One armed window at a time: ``arm()`` starts (or re-targets) the
    monitor thread, ``beat()`` feeds it, ``disarm()`` closes the window.

    A stall dumps forensics and async-raises :class:`StallError` in the
    armed thread, once per armed window."""

    def __init__(self, timeout_s: float, *, name: str = "watchdog",
                 poll_s: float = 0.0,
                 heartbeat_s: float = 0.0):
        self.name = name
        self._timeout = float(timeout_s)
        self._poll = float(poll_s) if poll_s > 0 else None
        self._heartbeat_s = float(heartbeat_s)
        self._armed = False            # the ONE beat() check
        self._lock = threading.Lock()
        self._t = time.monotonic()
        self._t0 = self._t
        self._phase = ""
        self._target: Optional[int] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._fired = False

    # -- arm/feed ----------------------------------------------------------

    @property
    def armed(self) -> bool:
        return self._armed

    def set_timeout(self, timeout_s: float) -> None:
        """Change the limit; an open window sees it at its next poll."""
        self._timeout = float(timeout_s)

    def arm(self, *, thread: Optional[threading.Thread] = None,
            phase: str = "armed") -> None:
        """Open a watch window targeting ``thread`` (default: the calling
        thread — the one a stall should abort). Re-arming re-targets and
        resets the heartbeat; the monitor thread is started once."""
        with self._lock:
            t = thread if thread is not None else threading.current_thread()
            self._target = t.ident
            self._t = time.monotonic()
            self._phase = phase
            self._fired = False
            self._armed = True
            if self._thread is None or not self._thread.is_alive():
                self._stop.clear()
                self._thread = threading.Thread(
                    target=self._loop, name=f"{self.name}-monitor",
                    daemon=True)
                self._thread.start()

    def beat(self, phase: Optional[str] = None) -> None:
        if not self._armed:
            return
        self._t = time.monotonic()
        if phase is not None:
            self._phase = phase

    def disarm(self) -> None:
        self._armed = False

    def close(self) -> None:
        """Stop the monitor thread (tests; long-lived runners just
        disarm between windows)."""
        self._armed = False
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=5.0)
        self._thread = None

    def idle_s(self) -> float:
        return time.monotonic() - self._t

    @property
    def phase(self) -> str:
        return self._phase

    # -- the monitor -------------------------------------------------------

    def _loop(self) -> None:
        last_hb = time.monotonic()
        while not self._stop.is_set():
            poll = self._poll or max(0.05, min(1.0, self._timeout / 8.0))
            if self._stop.wait(poll):
                return
            if not self._armed:
                continue
            now = time.monotonic()
            if self._heartbeat_s > 0 and now - last_hb >= self._heartbeat_s:
                last_hb = now
                print(f"[{self.name} hb] phase={self._phase} "
                      f"idle={now - self._t:.0f}s "
                      f"elapsed={now - self._t0:.0f}s",
                      file=sys.stderr, flush=True)
            # Check-and-set under the lock: arm() resets _fired from the
            # training thread, and an unguarded race here could double-
            # fire (two async raises) into a freshly re-armed window.
            with self._lock:
                idle = now - self._t
                fire = (self._armed and idle > self._timeout
                        and not self._fired)
                if fire:
                    self._fired = True
            if fire:
                self._fire(idle)

    def _fire(self, idle: float) -> None:
        monitor.add("watchdog/stalls", 1)
        monitor.set_gauge("watchdog/last_stall_idle_s", round(idle, 3))
        phase = self._phase
        # Forensics into the log, then abort the armed
        # thread through the normal exception path. The RPC plane leads
        # (rpc.poller_table / rpc.inflight_table via the forensics
        # providers): a stall in the event-loop plane should name the
        # POLLER THREAD and its deepest worker queue first — a wedged
        # poller or a backed-up worker pool stalls every conn it owns —
        # then the in-flight remotes (a stall blocked on a dead peer
        # should name the REMOTE, not bury it under thread stacks).
        fx = trace.stall_forensics()
        pollers = fx.get("rpc_pollers") or []
        plane = "; ".join(
            f"{p['service']}@{p['endpoint']} thread={p['thread']} "
            f"queue={p['worker_queue_depth']} "
            f"lag={p['loop_lag_ms']:.1f}ms conns={p['conns']}"
            for p in pollers if isinstance(p, dict)) or "none"
        inflight = fx.get("inflight_rpcs") or []
        remote = "; ".join(
            f"{e['service']}.{e['method']} -> {e['endpoint']} "
            f"(in flight {e['age_s']:.1f}s, "
            f"{e.get('outstanding', 1)} outstanding)"
            for e in inflight if isinstance(e, dict)) or "none"
        log.warning(
            "%s: no progress in phase %r for %.0fs — rpc pollers "
            "(deepest queue first): %s — in-flight RPCs: %s — dumping "
            "stall forensics and aborting the pass:\n%s",
            self.name, phase, idle, plane, remote,
            "\n".join(fx.get("thread_stacks", [])))
        # Flight recorder (core/incident.py): persist the forensics
        # just gathered — a stall at 3am should leave a bundle, not
        # only a log line. Contained + rate-limited inside trigger.
        from paddlebox_tpu.core import incident
        incident.trigger("watchdog_stall",
                         context={"watchdog": self.name,
                                  "phase": phase,
                                  "idle_s": round(idle, 3)},
                         forensics=fx)
        target = self._target
        if target is not None and _async_raise(target, StallError):
            monitor.add("watchdog/aborts", 1)
            trace.instant("watchdog/abort", phase=phase,
                          idle_s=round(idle, 3))
        else:  # pragma: no cover - target already gone
            log.warning("%s: armed thread %s is gone; nothing to abort",
                        self.name, target)


# Process-global instance for the training loop: the day runner arms it
# per pass (FLAGS_stall_timeout_s), the trainer's dispatch path feeds it.
GLOBAL = Watchdog(timeout_s=0.0, name="pass-watchdog")

beat = GLOBAL.beat


def arm_from_flags(*, phase: str = "pass",
                   thread: Optional[threading.Thread] = None) -> bool:
    """Arm the global pass watchdog when FLAGS_stall_timeout_s > 0.
    Returns whether it armed (caller pairs with ``disarm()``)."""
    timeout = float(flags.flag("stall_timeout_s"))
    if timeout <= 0:
        return False
    GLOBAL.set_timeout(timeout)
    GLOBAL.arm(thread=thread, phase=phase)
    return True


def disarm() -> None:
    GLOBAL.disarm()
