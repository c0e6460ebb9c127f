"""Device time of the traced window by physical operator.

The program names each operator's device code with ``jax.named_scope``
and keeps, for each executable it compiled, which operator each HLO op ran
for (``repro.tracing.executables()``). The trace reduction gives device
seconds per HLO op name (``rec["trace"]["ops"]``); this module maps those
names through the executable that served the window's batch size. Where
several did, the one whose ops cover most of the window's device time is
taken, the newest on a tie. A program without that record (one that
predates it) gives ``None``.
"""
from __future__ import annotations

import sys
from collections import defaultdict

_logged = set()


def by_operator(rec) -> dict | None:
    """``{"operators": {operator: s}, "unscoped": s, "cross": s,
    "module": name}``: device seconds of the window by the op's innermost
    operator scope, those of ops with none, and those of fusions whose
    fused ops belong to more than one operator (counted under the
    fusion's root operator too)."""
    tr = rec.get("trace")
    if tr is None or not tr["ops"]:
        return None
    try:
        from repro import tracing
    except ImportError:
        return None
    sizes = set(rec.get("batch_sizes") or ())
    found = [e for e in reversed(tracing.executables())
             if e.batch_size in sizes]

    def covered(e):
        return sum(s for name, s in tr["ops"].items() if name in e.ops)

    if not found or covered(exe := max(found, key=covered)) <= 0:
        return None
    ops, unscoped, cross = defaultdict(float), 0.0, 0.0
    for name, s in tr["ops"].items():
        op = exe.ops.get(name)
        if op is None or op.operator is None:
            unscoped += s
        else:
            ops[op.operator] += s
            cross += s if op.cross else 0.0
    out = {"operators": dict(ops), "unscoped": unscoped, "cross": cross,
           "module": exe.module}
    if id(rec) not in _logged:
        _logged.add(id(rec))
        print(f"device s by operator ({exe.module}): " + ", ".join(
            f"{k} {v:.6f}" for k, v in sorted(ops.items(), key=lambda kv:
                                             -kv[1]))
            + f"; unscoped {unscoped:.6f}; in fusions across operators "
            f"{cross:.6f}", file=sys.stderr, flush=True)
    return out


def ms_per_query(rec, operator: str) -> float | None:
    """Device milliseconds of ``operator`` per completed request."""
    ops = by_operator(rec)
    if ops is None or not rec["completed"]:
        return None
    return ops["operators"].get(operator, 0.0) / rec["completed"] * 1e3
