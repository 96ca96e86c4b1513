"""Host spans of the serving path, on the profiler's clock.

Every span is a ``jax.profiler.TraceAnnotation`` named ``serve:<phase>``.
A profiler started with ``jax.profiler.start_trace`` records it on the
host plane beside the device's operations, with its keyword arguments (a
tick id, a program name) as the event's stats; with no profiler running
a span costs about a microsecond.

``install`` adds two process-wide hooks, once: a ``serve:gc`` span around
every pause of Python's garbage collector, and a count of the programs
XLA compiles or loads from the persistent cache (``compiles``).  Both
belong to the process, not to one engine: a scheduler reads the count's
change across its own ``step``.
"""
from __future__ import annotations

import gc

import jax

PREFIX = "serve:"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_hooks = {"installed": False, "compiles": 0, "gc": []}


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span ``serve:<name>`` carrying ``args`` as its stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)


def compiles() -> int:
    """Programs compiled or loaded in this process since ``install``."""
    return _hooks["compiles"]


def _on_event(event: str, duration: float, **kw):
    if event == _COMPILE_EVENT:
        _hooks["compiles"] += 1


def _on_gc(phase: str, info: dict):
    if phase == "start":
        ann = span("gc", generation=info["generation"])
        ann.__enter__()
        _hooks["gc"].append(ann)
    elif _hooks["gc"]:
        _hooks["gc"].pop().__exit__(None, None, None)


def install():
    """Register the compile listener and the collector's callback (once
    per process)."""
    if _hooks["installed"]:
        return
    _hooks["installed"] = True
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    gc.callbacks.append(_on_gc)
