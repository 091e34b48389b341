"""The program's own spans in a reduced trace.

The HDArray runtime marks its phases with ``hdarray.*`` spans
(``repro.core.spans``): profiler annotations on the main thread's line
of the host plane, which :func:`trace.reduce` keeps, clipped to the
window, in ``Reduced.spans`` beside the Python profiler's frames.  The
readers of ``plan_ms``, ``replay_ms``, ``dispatch_ms`` and
``host_pace_pct`` take them from here, per ``hdarray.run_pipeline``
span in the window.  A program without such spans gives None.
"""
from __future__ import annotations

from typing import Iterable, List, Optional, Tuple

PREFIX = "hdarray."
CALL = "hdarray.run_pipeline"

Event = Tuple[str, float, float]


def program_spans(reduced) -> List[Event]:
    if reduced is None:
        return []
    return [s for s in reduced.spans if s[0].startswith(PREFIX)]


def self_seconds(spans: List[Event]) -> List[float]:
    """Each span's time less that of the spans nested directly inside
    it (one thread's spans nest or are disjoint)."""
    own = [b - a for _, a, b in spans]
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][1], -spans[i][2], i))
    open_: List[int] = []
    for i in order:
        _, a, b = spans[i]
        while open_ and not (spans[open_[-1]][1] <= a
                             and b <= spans[open_[-1]][2]):
            open_.pop()
        if open_:
            own[open_[-1]] -= b - a
        open_.append(i)
    return own


def per_call_ms(reduced, names: Iterable[str]) -> Optional[float]:
    """Milliseconds of self time per ``run_pipeline`` call in the spans
    ``names``, or None where the window holds no call span."""
    spans = program_spans(reduced)
    calls = sum(n == CALL for n, _, _ in spans)
    if not calls:
        return None
    names = set(names)
    return 1e3 * sum(s for (n, _, _), s in zip(spans, self_seconds(spans))
                     if n in names) / calls


def call_seconds(reduced) -> float:
    """Seconds inside ``run_pipeline`` calls in the window."""
    return sum(b - a for n, a, b in program_spans(reduced) if n == CALL)

