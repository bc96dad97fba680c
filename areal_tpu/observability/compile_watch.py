"""XLA recompile sentinel: count, attribute, and alarm on compiles.

Recompiles are the serving loop's silent latency killer — the bucket
ladders in ``engine/batching.py`` exist solely to bound compile count,
yet nothing counted or alarmed on an unexpected compile until now.  This
module watches two signals:

* **jitted-entry cache polling** (the deterministic, per-fn signal): the
  engine's jitted entry points (``paged_decode_chunk``,
  ``paged_fill_chunk``, the dense ``_decode_chunk``/``_admit_rows``)
  each expose a compiled-variant cache; a poll that finds the cache
  grown means new (shape, dtype) signatures compiled since the last
  poll.  Each detected compile increments
  ``areal_xla_compiles_total{fn=}`` and records an ``xla.compile`` trace
  span carrying the caller-provided shape/dtype signature.
* **jax.monitoring durations** (the process-wide timing signal): the
  ``backend_compile`` duration events feed the
  ``areal_xla_compile_seconds`` histogram plus an ``fn="backend"``
  counter row.  One module-level listener dispatches to every live
  watch — jax offers registration but no unregistration, so instances
  enroll in a WeakSet instead of stacking dead listeners.  It also keeps
  the process's own pair (:func:`backend_compiles`), which a
  ``PhaseClock`` differences over each lap: WHICH step compiled.

**Steady-state guard**: after ``GenServerConfig.compile_quiet_after_steps``
engine steps the watch is marked steady; any compile on a watched
decode/fill entry from then on fires
``areal_trace_stall_total{kind="recompile"}`` ONCE PER EPISODE (the
stall watchdog's fire-once/re-arm discipline: a burst of compiles is one
alarm; a quiet poll re-arms) and invokes the ``on_steady_compile``
callback so the worker can force-sample the trace roots the compile
stalled.
"""

from __future__ import annotations

import threading
import weakref
from typing import Callable, Dict, List, Optional, Tuple

from areal_tpu.observability.registry import get_registry
from areal_tpu.observability.tracing import get_tracer

#: live CompileWatch instances the module-level jax.monitoring listener
#: dispatches to (weak: a dropped watch unenrolls itself)
_active_watches: "weakref.WeakSet[CompileWatch]" = weakref.WeakSet()
_listener_lock = threading.Lock()
_listener_installed = False
#: [backend compiles, their seconds] of the process since the listener
#: went in (written by whichever thread compiles; read without a lock)
_process_compiles = [0, 0.0]


def _on_jax_event_duration(name: str, secs: float, **kw) -> None:
    if "backend_compile" not in name:
        return
    _process_compiles[0] += 1
    _process_compiles[1] += float(secs)
    for watch in list(_active_watches):
        watch._note_backend_compile(float(secs))


def _install_monitoring_listener() -> bool:
    """Register the process-wide duration listener once.  Returns False
    when jax.monitoring is unavailable (the cache-polling signal still
    works)."""
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return True
        try:
            import jax.monitoring as jmon

            jmon.register_event_duration_secs_listener(
                _on_jax_event_duration
            )
        except Exception:
            return False
        _listener_installed = True
        return True


def backend_compiles() -> Tuple[int, float]:
    """(count, seconds) of the process's backend compiles so far; installs
    the listener on first use ((0, 0.0) for good without jax.monitoring)."""
    if not _listener_installed:
        _install_monitoring_listener()
    return _process_compiles[0], _process_compiles[1]


class CompileWatch:
    """Per-worker compile counter + steady-state recompile sentinel.

    ``quiet_after_steps``: engine steps before the steady-state guard
    arms (0 disables the sentinel; counting always runs).
    ``on_steady_compile(fns)``: called once per episode with the entry
    points that compiled, so the owner can force-sample the stalled
    trace roots."""

    def __init__(
        self,
        registry=None,
        tracer=None,
        quiet_after_steps: int = 0,
        on_steady_compile: Optional[Callable[[List[str]], None]] = None,
        monitoring: bool = True,
    ):
        self._registry = registry if registry is not None else get_registry()
        self._tracer = tracer if tracer is not None else get_tracer()
        self.quiet_after_steps = max(0, int(quiet_after_steps))
        self._on_steady_compile = on_steady_compile
        self._lock = threading.Lock()
        self._entries: Dict[str, Dict] = {}
        self._steady = False
        self._episode_fired = False
        # cumulative plain counters (mirrored onto the metrics RPC)
        self.compiles_total: Dict[str, int] = {}
        self.steady_compiles_total = 0
        self.sentinel_fires_total = 0
        self.monitoring_active = bool(
            monitoring and _install_monitoring_listener()
        )
        if self.monitoring_active:
            _active_watches.add(self)

    # -- registration -------------------------------------------------------

    @staticmethod
    def _cache_size(fn) -> Optional[int]:
        try:
            return int(fn._cache_size())
        except Exception:
            return None

    def watch(
        self,
        fn_name: str,
        jitted_fn,
        signature: Optional[Callable[[], str]] = None,
    ) -> bool:
        """Track a jitted entry point by compiled-cache size.
        ``signature()`` (optional) renders the current shape/dtype
        signature for the ``xla.compile`` span attrs.  Returns False
        when the fn exposes no cache (nothing to poll)."""
        size = self._cache_size(jitted_fn)
        if size is None:
            return False
        with self._lock:
            self._entries[fn_name] = {
                "fn": jitted_fn,
                "last": size,
                "signature": signature,
            }
            self.compiles_total.setdefault(fn_name, 0)
        return True

    # -- state --------------------------------------------------------------

    def note_step(self, step: int) -> None:
        """Arm the steady-state guard once the engine step counter
        clears ``quiet_after_steps`` (0 = never arms)."""
        if (
            not self._steady
            and self.quiet_after_steps > 0
            and int(step) >= self.quiet_after_steps
        ):
            self._steady = True

    def set_steady(self, steady: bool) -> None:
        self._steady = bool(steady)
        if not steady:
            self._episode_fired = False

    @property
    def steady(self) -> bool:
        return self._steady

    @property
    def armed(self) -> bool:
        """True when the next steady-state compile will fire the
        sentinel (steady and not mid-episode)."""
        return self._steady and not self._episode_fired

    # -- signals ------------------------------------------------------------

    def _note_backend_compile(self, secs: float) -> None:
        """jax.monitoring backend_compile event (process-wide; no per-fn
        attribution — the polled entries carry that)."""
        self._registry.counter("areal_xla_compiles_total").inc(
            fn="backend"
        )
        self._registry.histogram("areal_xla_compile_seconds").observe(
            secs
        )

    def poll(self) -> Dict[str, int]:
        """Diff every watched entry's compiled-cache size; count, trace,
        and (when steady) run the sentinel.  Returns the new compiles by
        fn for this poll (empty = quiet)."""
        fresh: Dict[str, int] = {}
        with self._lock:
            for fn_name, ent in self._entries.items():
                cur = self._cache_size(ent["fn"])
                if cur is None:
                    continue
                n = cur - ent["last"]
                ent["last"] = cur
                if n > 0:
                    fresh[fn_name] = n
                    self.compiles_total[fn_name] = (
                        self.compiles_total.get(fn_name, 0) + n
                    )
        counter = self._registry.counter("areal_xla_compiles_total")
        for fn_name, n in fresh.items():
            counter.inc(float(n), fn=fn_name)
            ent = self._entries.get(fn_name) or {}
            sig_fn = ent.get("signature")
            sig = ""
            if sig_fn is not None:
                try:
                    sig = str(sig_fn())
                except Exception:
                    sig = "?"
            root = f"xla-{fn_name}"
            # compiles are rare and fleet-relevant: always record them
            self._tracer.force(root)
            self._tracer.span_begin(
                root, "xla.compile", root=root,
                fn=fn_name, new_entries=n, signature=sig,
            )
            self._tracer.span_end(root, "xla.compile", root=root)
        if self._steady:
            if fresh:
                self.steady_compiles_total += sum(fresh.values())
                if not self._episode_fired:
                    self._episode_fired = True
                    self.sentinel_fires_total += 1
                    self._registry.counter("areal_trace_stall_total").inc(
                        kind="recompile"
                    )
                    if self._on_steady_compile is not None:
                        try:
                            self._on_steady_compile(sorted(fresh))
                        except Exception:
                            pass
            else:
                # a clean poll ends the episode: the next steady-state
                # compile is a NEW alarm
                self._episode_fired = False
        return fresh

    def stats(self) -> Dict[str, float]:
        """Plain cumulative counters for the metrics RPC."""
        out: Dict[str, float] = {
            f"xla_compiles/{fn}": float(n)
            for fn, n in sorted(self.compiles_total.items())
        }
        out["xla_steady_compiles_total"] = float(self.steady_compiles_total)
        out["xla_sentinel_fires_total"] = float(self.sentinel_fires_total)
        return out

    def close(self) -> None:
        _active_watches.discard(self)
        self.monitoring_active = False
