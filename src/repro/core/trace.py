"""Host spans on the profiler's clock.

``span(name, acc=None, **ids)`` opens a ``jax.profiler.TraceAnnotation``
named ``name``: under ``jax.profiler.trace`` it is a host event on the
same clock as the device planes, carrying ``ids`` (``job``, ``group``,
``chunk``) as event stats.  A span inherits the ids of the spans it is
nested in, so a wait inside a resolve names the dispatch it waits on.
With ``acc=(obj, field)`` the span's ``perf_counter`` duration is also
added to ``obj.field``: the engines' host timers are these sums.

Spans mark chunk, drain-group and job boundaries only, never a pair or
an itemset.  Outside a trace a span costs one annotation enter and exit,
plus two ``perf_counter`` calls when it has an ``acc``.
"""

from __future__ import annotations

from contextvars import ContextVar
from time import perf_counter
from typing import Any, Dict, Optional, Tuple

import jax

__all__ = ["span"]

_IDS: ContextVar[Dict[str, int]] = ContextVar("span_ids", default={})


class span:
    __slots__ = ("_name", "_acc", "_ids", "_token", "_ann", "_t0")

    def __init__(self, name: str, acc: Optional[Tuple[Any, str]] = None,
                 **ids: int):
        self._name = name
        self._acc = acc
        self._ids = ids

    def __enter__(self) -> "span":
        ids = {**_IDS.get(), **self._ids} if self._ids else _IDS.get()
        self._token = _IDS.set(ids)
        self._ann = jax.profiler.TraceAnnotation(self._name, **ids)
        self._ann.__enter__()
        if self._acc is not None:
            self._t0 = perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._acc is not None:
            obj, field = self._acc
            setattr(obj, field,
                    getattr(obj, field) + perf_counter() - self._t0)
        self._ann.__exit__(*exc)
        _IDS.reset(self._token)
