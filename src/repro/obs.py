"""In-program tracer: spans, counters and iteration tags.

    from repro import obs
    with obs.span("gen.decode") as sp:
        ...
    sp.seconds                    # always measured: two clock reads

    obs.enable()                  # off by default; turning it on is a call
    obs.step(i)                   # tag the spans of PPO iteration i
    obs.count("gen.decode_steps", 256)
    obs.snapshot()                # {"spans": [Span, ...], "counters": {...}}

A span always measures its own host duration.  Only while the tracer is
enabled does it also record ``(name, start_ns, end_ns, parent, step,
attrs)`` in a bounded ring, take its parent from a per-thread stack, and
open a ``jax.profiler.TraceAnnotation`` of its name, so that a profiler
trace shows the span on the host plane, on the same clock as the device
ops.  No span waits for the device: device time comes from a profiler
trace, where each program of the stage-3 path runs under the name
:func:`named` gives it.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax

RING = 4096


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[str]
    step: Optional[int]
    attrs: Dict[str, Any]


_enabled = False
_ring: "collections.deque[Span]" = collections.deque(maxlen=RING)
_counters: Dict[str, float] = {}
_lock = threading.Lock()
_local = threading.local()


def _stack() -> list:
    s = getattr(_local, "stack", None)
    if s is None:
        s = _local.stack = []
    return s


class span:
    """Context manager timing one step of the program; ``.seconds`` is
    its duration (the time so far while it is open)."""
    __slots__ = ("name", "attrs", "_t0", "_t1", "_parent", "_ann")

    def __init__(self, name: str, **attrs):
        self.name, self.attrs = name, attrs
        self._t1 = None
        self._ann = None

    def __enter__(self) -> "span":
        if _enabled:
            stack = _stack()
            self._parent = stack[-1] if stack else None
            stack.append(self.name)
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self._t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
            _stack().pop()
            sp = Span(self.name, self._t0, self._t1, self._parent,
                      getattr(_local, "step", None), self.attrs)
            with _lock:
                _ring.append(sp)

    @property
    def seconds(self) -> float:
        t1 = self._t1 if self._t1 is not None else time.perf_counter_ns()
        return (t1 - self._t0) * 1e-9


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to counter ``name`` (while enabled)."""
    if _enabled:
        with _lock:
            _counters[name] = _counters.get(name, 0) + n


def step(i: Optional[int]) -> None:
    """Tag the spans that this thread ends from now on with iteration
    ``i``.  The tag is per thread: a rollout producer thread tags its own
    spans with the iteration it generates for."""
    _local.step = i


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def reset() -> None:
    """Forget the recorded spans, the counters and this thread's
    iteration tag."""
    with _lock:
        _ring.clear()
        _counters.clear()
    _local.step = None


def snapshot() -> dict:
    """The recorded spans, oldest first, and a copy of the counters."""
    with _lock:
        return {"spans": list(_ring), "counters": dict(_counters)}


def named(name: str, fn: Callable) -> Callable:
    """``fn`` under the name ``name``, for ``jax.jit``: the compiled
    program is then ``jit_<name>`` in its HLO and on a profiler trace's
    ``XLA Modules`` line."""
    def program(*args, **kwargs):
        return fn(*args, **kwargs)
    program.__name__ = program.__qualname__ = name
    return program
