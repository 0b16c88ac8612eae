"""Host spans the benchmark records around its calls into the program.

Each span is (name, start, end) on ``time.perf_counter``.  In a traced run
the same span is also a ``jax.profiler.TraceAnnotation`` named
``bench/<name>``, so the device trace and the host spans share a clock.
``wrap`` puts a span around a module attribute of the program (a function
or a method) for the traced run only, and fails loudly if the attribute is
gone.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    info: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.end - self.start


class Spans:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.items: List[Span] = []
        self._undo: List[Callable[[], None]] = []

    @contextlib.contextmanager
    def span(self, name: str, **info):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench/{name}")
            ann.__enter__()
        t0 = time.perf_counter()
        rec = Span(name, t0, t0, dict(info))
        try:
            yield rec
        finally:
            rec.end = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.items.append(rec)

    def named(self, name: str, lo: float = float("-inf"),
              hi: float = float("inf")) -> List[Span]:
        return [s for s in self.items
                if s.name == name and s.start >= lo and s.end <= hi]

    def wrap(self, owner: Any, attr: str, name: str,
             keep: Optional[Callable[[Any], dict]] = None) -> None:
        """Record a span ``name`` around every call of ``owner.attr``;
        ``keep(result)`` may add facts about the result to the span."""
        if not hasattr(owner, attr):
            raise AttributeError(
                f"{getattr(owner, '__name__', owner)!r} has no {attr!r}: the "
                f"span {name!r} has nothing to wrap")
        orig = getattr(owner, attr)
        spans = self

        def wrapped(*args, **kwargs):
            with spans.span(name) as rec:
                out = orig(*args, **kwargs)
                if keep is not None:
                    rec.info.update(keep(out))
                return out

        setattr(owner, attr, wrapped)
        self._undo.append(lambda: setattr(owner, attr, orig))

    def unwrap(self) -> None:
        while self._undo:
            self._undo.pop()()


def nested(spans: Spans, outer: str, inner: str, lo: float, hi: float):
    """(outer span, inner span) pairs, the inner inside the outer, with
    the outer inside [lo, hi]."""
    outs = spans.named(outer, lo, hi)
    return [(o, i) for o in outs for i in spans.named(inner, o.start, o.end)]
