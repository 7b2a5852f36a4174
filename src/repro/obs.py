"""Host spans of the serving path, on the profiler's clock and in memory.

``span(name, **attrs)`` marks one stretch of host work at a layer boundary
(``gateway.step``, ``server.tick``, ``model.decode_dispatch``, ...; the
tree is in ``docs/serving.md``, "Tracing").  It records only while
recording is on: while a ``jax.profiler`` session is active, or between
:func:`enable` and :func:`disable`.  Otherwise it returns one shared null
context after a single check, and allocates and appends nothing.

While on, a span goes to two places:

  * the profiler's trace, through ``jax.profiler.TraceAnnotation``: the
    same clock as the device planes, so a capture names each gap between
    device ops by the span the host was in;
  * a bounded in-memory ring of ``(name, t0, t1, parent, attrs)``, read
    with :func:`records`.  Times are ``time.perf_counter()`` seconds;
    ``parent`` is the index in :func:`records` of the enclosing span
    (None at the top, or once the ring has dropped it).

The serving loop is single-threaded, and so is the nesting kept here.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque

import jax

__all__ = ["span", "recording", "records", "clear", "enable", "disable"]

RING = 1 << 16                       # spans kept: several minutes of serving

_NULL = contextlib.nullcontext()
_active = jax.profiler.TraceAnnotation.is_enabled
_ring: deque = deque(maxlen=RING)    # [name, t0, t1, parent seq, attrs]
_stack: list[int] = []               # seq of each open span, outermost first
_seq = 0                             # spans ever opened
_enabled = False


class _Span:
    __slots__ = ("_name", "_attrs", "_ann", "_rec")

    def __init__(self, name: str, attrs: dict):
        self._name, self._attrs = name, attrs

    def __enter__(self):
        global _seq
        self._ann = jax.profiler.TraceAnnotation(self._name, **self._attrs)
        self._ann.__enter__()
        self._rec = [self._name, time.perf_counter(), None,
                     _stack[-1] if _stack else None, self._attrs]
        _ring.append(self._rec)
        _stack.append(_seq)
        _seq += 1
        return self

    def __exit__(self, *exc):
        self._rec[2] = time.perf_counter()
        _stack.pop()
        return self._ann.__exit__(*exc)


def recording() -> bool:
    """Whether spans are being recorded now."""
    return _enabled or _active()


def span(name: str, **attrs):
    """A context manager timing one stretch of host work (module docs)."""
    if not (_enabled or _active()):
        return _NULL
    return _Span(name, attrs)


def records() -> list[tuple]:
    """The ring's spans in the order they opened, as ``(name, t0, t1,
    parent, attrs)``; ``t1`` is None while a span is still open."""
    first = _seq - len(_ring)
    return [(name, t0, t1,
             None if parent is None or parent < first else parent - first,
             attrs)
            for name, t0, t1, parent, attrs in _ring]


def clear() -> None:
    """Drop every recorded span (open spans still close cleanly)."""
    _ring.clear()


def enable() -> None:
    """Record spans without a profiler session (tests, command lines)."""
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False
