"""Named host spans at the HDArray runtime's layer boundaries.

``span(name)`` is a context manager around one phase of the runtime
(``hdarray.run_pipeline``, ``hdarray.plan``, ``hdarray.commit``,
``hdarray.dispatch``, ``hdarray.capture``, ``hdarray.replay``,
``hdarray.write``, ``hdarray.read``; see docs/architecture.md,
"Tracing").  Every span enters a ``jax.profiler.TraceAnnotation``, so
under a profiler it lands on the host thread's line of the trace, on
the same clock as the device planes; with no profiler running the
annotation costs about a microsecond.

With recording on (``record(True)``) each span is also kept, when it
closes, in one bounded in-memory buffer as a :class:`Span` on
``time.perf_counter``: ``snapshot()`` returns the buffer, for reading
phase times without a profiler.  Recording is off by default.

jax is imported on the first span, not with this module, so the sim
and null backends import without it.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Deque, List, NamedTuple, Optional

#: spans the buffer keeps; the oldest are dropped first
CAPACITY = 65536


class Span(NamedTuple):
    name: str
    start: float                 # time.perf_counter seconds
    end: float
    parent: Optional[int]        # ``sid`` of the enclosing span, if any
    sid: int                     # this span's id, unique in the process


_buffer: Deque[Span] = collections.deque(maxlen=CAPACITY)
_recording = False
_ids = itertools.count()
_open = threading.local()        # per thread: the stack of open span ids
_annotation = None               # jax.profiler.TraceAnnotation, once found


def record(on: bool = True) -> None:
    """Keep closed spans in the buffer (``on``) or stop keeping them;
    what the buffer holds stays until :func:`clear`."""
    global _recording
    _recording = bool(on)


def snapshot() -> List[Span]:
    """The recorded spans, oldest first (in the order they closed)."""
    return list(_buffer)


def clear() -> None:
    _buffer.clear()


def _annotation_type():
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


class span:
    """``with span("hdarray.plan"): ...`` — one named phase."""
    __slots__ = ("name", "_ann", "_sid", "_parent", "_t0")

    def __init__(self, name: str) -> None:
        self.name = name

    def __enter__(self) -> "span":
        self._ann = _annotation_type()(self.name)
        self._ann.__enter__()
        self._sid = None
        if _recording:
            stack = getattr(_open, "stack", None)
            if stack is None:
                stack = _open.stack = []
            self._parent = stack[-1] if stack else None
            self._sid = next(_ids)
            stack.append(self._sid)
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._sid is not None:
            t1 = time.perf_counter()
            _open.stack.pop()
            _buffer.append(Span(self.name, self._t0, t1, self._parent,
                                self._sid))
        self._ann.__exit__(*exc)
