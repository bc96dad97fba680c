"""Bounded execution of one named section/phase in a daemon thread.

The core of ``__graft_entry__``'s dryrun ``_phase``: run ``fn`` in a
daemon thread, join for ``timeout_s``, and report ``{status: ok|error|timeout, seconds[, result|error]}`` — so
one hung or crashing section forfeits its own numbers instead of eating
the whole run's budget (a dryrun once died at rc=124 with no way to tell
which phase hung).

Best effort by design: a truly wedged thread may hold jax's dispatch
lock and time out the sections behind it too, but each of those is
bounded the same way and the run still emits its partial status table.
"""

from __future__ import annotations

import threading
import time
import traceback
from typing import Any, Dict


def run_bounded(
    fn, *args, name: str = "section", timeout_s: float = 300.0, **kw
) -> Dict[str, Any]:
    """Run ``fn(*args, **kw)`` in a daemon thread joined for
    ``timeout_s`` seconds.  Returns ``{"status": "ok", "seconds": s,
    "result": r}``, ``{"status": "error", "seconds": s, "error": msg}``
    (exception repr, truncated), or ``{"status": "timeout",
    "seconds": s}`` when the thread is still alive at the deadline."""
    t0 = time.perf_counter()
    box: Dict[str, Any] = {}

    def target():
        try:
            box["result"] = fn(*args, **kw)
        except Exception as e:  # noqa: BLE001 - the outcome IS the data
            traceback.print_exc()
            box["error"] = f"{type(e).__name__}: {e}"[:300]

    th = threading.Thread(target=target, daemon=True, name=name)
    th.start()
    th.join(timeout_s)
    out: Dict[str, Any] = {
        "seconds": round(time.perf_counter() - t0, 1)
    }
    if th.is_alive():
        out["status"] = "timeout"
    elif "error" in box:
        out["status"] = "error"
        out["error"] = box["error"]
    else:
        out["status"] = "ok"
        out["result"] = box.get("result")
    return out
