"""Online request times on the program's wall stamps (``EngineRequest``'s
``*_wall_ns``, nanoseconds on one host clock) and the quantum records'
``wall_ns`` intervals, for the readers of ``metrics/``.  A program without
the stamps reads as nothing."""
from __future__ import annotations

import numpy as np


def window_end_ns(spans: list):
    """End of the window's last quantum record on the wall clock, or None."""
    for ev in reversed(spans):
        if ev.get("type") == "quantum" and "wall_ns" in ev.get("args", {}):
            return ev["args"]["wall_ns"][1]
    return None


def online_p95_ms(w, stamp: str):
    """p95 of ``stamp - arrival_wall_ns`` (ms) over the online requests
    submitted before the window's last quantum record ended; None where
    nothing was stamped."""
    end = window_end_ns(w.spans)
    if end is None:
        return None
    ms = []
    for cr in w.requests.values():
        if getattr(cr.priority, "value", cr.priority) != "online":
            continue
        t0, t1 = getattr(cr, "arrival_wall_ns", None), getattr(cr, stamp, None)
        if t0 is not None and t1 is not None and t0 <= end:
            ms.append((t1 - t0) / 1e6)
    if not ms:
        return None
    return float(np.percentile(np.asarray(ms, np.float64), 95))
