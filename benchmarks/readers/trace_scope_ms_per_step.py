"""Device time, in ms per traced step, of the step's operations under one
named scope (``core/trace.py``, ``device_scope_table``): ``top``, and where
given ``part`` (null: the top's own work, in no part) and ``phase``; a key
left out matches any. ``share: unscoped`` reads instead, in %, the leaf
time with no top-level scope over all the step's leaf time, and prints the
whole table to standard error once (``run.py: scopes: {...}``). Nothing to
read untraced, or where no compiled step was recorded (a program without
the table)."""

import json
import sys


def _table(traced):
    from paddlebox_tpu.core import trace
    table_of = getattr(trace, "device_scope_table", None)
    if traced is None or not traced["steps"] or table_of is None:
        return None
    return table_of(traced["ops"])


def _print(table, steps):
    def ms(seconds):
        return seconds / steps * 1e3
    rows = {"/".join(k or "-" for k in key): ms(v[0])
            for key, v in sorted(table.rows.items(),
                                 key=lambda kv: -kv[1][0])}
    print("run.py: scopes: " + json.dumps({
        "steps": steps, "parse_s": table.parse_s,
        "step_leaf_ms": ms(table.seconds()),
        "unscoped_ms": ms(table.seconds(top=None)),
        "other_programs_ms": ms(table.elsewhere[0]),
        "rows_ms": rows}), file=sys.stderr)


def read(params, observed, traced, peaks):
    table = _table(traced)
    if table is None:
        return None
    if params.get("share") == "unscoped":
        step = table.seconds()
        if not step:
            return None
        _print(table, traced["steps"])
        return 100.0 * table.seconds(top=None) / step
    where = {k: params[k] for k in ("top", "part", "phase") if k in params}
    return table.seconds(**where) / traced["steps"] * 1e3
