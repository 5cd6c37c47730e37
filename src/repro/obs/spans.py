"""Self-instrumentation spans: JSONL telemetry for the tool's own hot paths.

The simulator is itself a performance artifact — trace import, cluster
build/retune, sweep points, calibration rounds, serving graphgen and the
training loop all have budgets, but regressions in the field are invisible
without timing in situ.  ``span()`` wraps those sections:

    from repro.obs import span
    with span("cluster.retune", records=len(prov)) as s:
        ...
        s.note(touched=n)

Emission is **off by default** and costs one module-global ``None`` check
(bench-gated <= 1.05x in ``benchmarks/bench_obs.py``).  Set
``REPRO_TELEMETRY=<path>`` in the environment (read once at import) or
call :func:`configure` (the ``--telemetry PATH`` CLI flag) to record one
JSON object per completed span::

    {"span": "scenario.sweep.scenario.sweep_point", "name": "...",
     "ts": <wall-clock start>, "t0": <perf_counter start>,
     "dur_s": <perf_counter duration>, "attrs": {...}, "error": "ValueError"?}

``span`` is the dotted path of the contextvar-stacked enclosing spans, so
nested sections reconstruct a call tree without ids; ``contextvars`` keeps
the stack correct across threads and async tasks.  ``t0`` and ``dur_s``
are on one clock, so the records of one process can be intersected.

**Buffering.**  While enabled, records are kept in memory and appended to
the path in bulk: whenever ``_FLUSH_AT`` records are held, at
:func:`flush` (``Trainer.fit`` calls it as it returns), when
:func:`configure` is called again (with ``None`` or another path), and at
interpreter exit.  No span writes its own record, memory stays bounded,
and a process that is killed loses at most the last ``_FLUSH_AT``.

**Profiler bridge.**  Once ``jax`` has been imported, each span also enters
``jax.profiler.TraceAnnotation(name, **attrs)``.  An active
``jax.profiler`` capture then holds the spans on the host's Python thread,
on the profiler's clock, beside the device's ``XLA Ops``; without a capture
the annotation is a no-op.

**Compile records.**  While enabled, and once ``jax`` has been imported,
JAX's own monitoring events add one record each:

    ========================  ==============================================
    ``jax.trace``             ``/jax/core/compile/jaxpr_trace_duration``
    ``jax.lower``             ``/jax/core/compile/jaxpr_to_mlir_module_duration``
    ``jax.compile``           ``/jax/core/compile/backend_compile_duration``
    ``jax.cache_load``        ``/jax/compilation_cache/cache_retrieval_time_sec``
    ========================  ==============================================

Their ``span`` is the enclosing span path plus the event's name; ``attrs``
hold ``fun_name`` where JAX gives one (``jit(f)`` recorded as ``f``) and
the ``step`` of the innermost enclosing span that has one, so a compile
inside ``Trainer.fit``'s loop names its step and its function.

Stdlib-only at import: importable from anywhere in the package without
cycles, and without JAX.
"""

from __future__ import annotations

import atexit
import contextvars
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["span", "configure", "enabled", "flush", "telemetry_path"]

_ENV = "REPRO_TELEMETRY"
_FLUSH_AT = 512             # records held before they are written in bulk
_path: Optional[str] = None
_records: List[Dict[str, Any]] = []
_lock = threading.Lock()
_stack: "contextvars.ContextVar[Tuple[Span, ...]]" = contextvars.ContextVar(
    "repro_obs_span_stack", default=())
# set once jax is imported and telemetry is on: the profiler annotation
# each span enters, and whether JAX's monitoring listeners are registered
_annotation: Any = None
_listening = False

_JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}
_CACHE_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


def enabled() -> bool:
    """True when spans are being recorded."""
    return _path is not None


def telemetry_path() -> Optional[str]:
    """The active JSONL sink path, or None when disabled."""
    return _path


def configure(path: Optional[str]) -> None:
    """Record spans for ``path`` (JSONL, appended); ``None``/empty disables.
    Writes out what was recorded for the previous path first.  Overrides
    ``REPRO_TELEMETRY``; safe to call repeatedly."""
    global _path
    with _lock:
        _flush_locked()
        _path = path or None
    if _path is None:
        _unlisten()
    else:
        _listen()


def _flush_locked() -> None:
    global _records
    recs, _records = _records, []
    if _path is None or not recs:
        return
    with open(_path, "a", encoding="utf-8") as f:
        f.write("".join(json.dumps(r, default=str) + "\n" for r in recs))


def flush() -> None:
    """Write out the records held so far (a no-op when disabled)."""
    with _lock:
        _flush_locked()


atexit.register(flush)


def _append(record: Dict[str, Any]) -> None:
    with _lock:
        if _path is not None:        # disabled between span start and end
            _records.append(record)
            if len(_records) >= _FLUSH_AT:
                _flush_locked()


# ------------------------------------------------------------- JAX hooks
def _listen() -> None:
    """Bridge to the profiler and register JAX's compile listeners, once
    ``jax`` is imported (never importing it here)."""
    global _annotation, _listening
    if "jax" not in sys.modules:
        return
    import jax.monitoring as monitoring
    import jax.profiler as profiler
    with _lock:
        if _listening:
            return
        monitoring.register_event_time_span_listener(_on_time_span)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _annotation = profiler.TraceAnnotation
        _listening = True


def _unlisten() -> None:
    global _annotation, _listening
    with _lock:
        if not _listening:
            return
        import jax.monitoring as monitoring
        monitoring.unregister_event_time_span_listener(_on_time_span)
        monitoring.unregister_event_duration_listener(_on_duration)
        _annotation = None
        _listening = False


def _on_time_span(event: str, start: float, end: float, **kw: Any) -> None:
    name = _JAX_EVENTS.get(event)
    if name is not None:
        # JAX times these on the wall clock and reports them as they end
        lag = time.time() - start
        _jax_record(name, start, time.perf_counter() - lag, end - start, kw)


def _on_duration(event: str, secs: float, **kw: Any) -> None:
    if event == _CACHE_EVENT:       # reported as the load ends
        _jax_record("jax.cache_load", time.time() - secs,
                    time.perf_counter() - secs, secs, kw)


def _jax_record(name: str, wall: float, t0: float, dur: float,
                kw: Dict[str, Any]) -> None:
    stack = _stack.get()
    attrs: Dict[str, Any] = {}
    fun = kw.get("fun_name")
    if fun is not None:
        fun = str(fun)
        attrs["fun_name"] = (fun[4:-1] if fun.startswith("jit(")
                             and fun.endswith(")") else fun)
    for s in reversed(stack):
        if "step" in s.attrs:
            attrs["step"] = s.attrs["step"]
            break
    _append({"span": ".".join([s.name for s in stack] + [name]),
             "name": name, "ts": wall, "t0": t0, "dur_s": dur,
             "attrs": attrs})


# ----------------------------------------------------------------- spans
class _NullSpan:
    """Shared no-op span: the entire disabled-path cost."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False

    def note(self, **attrs: Any) -> None:
        pass


_NULL = _NullSpan()


class Span:
    """Context manager recording one timed section (see module doc)."""

    __slots__ = ("name", "attrs", "_t0", "_wall", "_token", "_annot")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "Span":
        self._token = _stack.set(_stack.get() + (self,))
        self._annot = (None if _annotation is None
                       else _annotation(self.name, **self.attrs))
        if self._annot is not None:
            self._annot.__enter__()
        self._wall = time.time()
        self._t0 = time.perf_counter()
        return self

    def note(self, **attrs: Any) -> None:
        """Attach attributes discovered mid-section to the record."""
        self.attrs.update(attrs)

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> bool:
        dur = time.perf_counter() - self._t0
        if self._annot is not None:
            self._annot.__exit__(exc_type, exc, tb)
        path = _stack.get()
        _stack.reset(self._token)
        rec: Dict[str, Any] = {"span": ".".join(s.name for s in path),
                               "name": self.name, "ts": self._wall,
                               "t0": self._t0, "dur_s": dur}
        if exc_type is not None:
            rec["error"] = exc_type.__name__
        if self.attrs:
            rec["attrs"] = self.attrs
        _append(rec)
        return False


def span(name: str, **attrs: Any) -> Any:
    """A timed section named ``name``; no-op unless telemetry is enabled."""
    if _path is None:
        return _NULL
    if not _listening:
        _listen()
    return Span(name, attrs)


configure(os.environ.get(_ENV))
