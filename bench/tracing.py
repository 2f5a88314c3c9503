"""The benchmark's observability bundle for a traced run.

The program's spans (``gateway.admit``, ``session.dispatch``,
``device.execute``, ``retire.decode``, ``rescue.rung``) are recorded by its
``repro.obs`` tracer on the host clock.  Injected through ``plan(obs=...)``,
this tracer also opens a ``jax.profiler.TraceAnnotation`` for each span, so
the same spans land in the profiler's trace on the device's clock, where
the trace reduction can name what the host was doing in each idle gap."""
from __future__ import annotations

import jax

from repro.obs import MetricsRegistry, Obs, Span, Tracer


class _AnnotatedSpan(Span):
    __slots__ = ("_ann",)

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        return super().__enter__()

    def __exit__(self, exc_type, exc, tb):
        try:
            return super().__exit__(exc_type, exc, tb)
        finally:
            self._ann.__exit__(exc_type, exc, tb)


class AnnotatingTracer(Tracer):
    """A ``repro.obs`` tracer whose spans are also profiler annotations."""

    def span(self, name: str, **attrs) -> Span:
        return _AnnotatedSpan(self, name, attrs)


def traced_obs(maxlen: int = 1 << 20) -> Obs:
    return Obs(MetricsRegistry(), AnnotatingTracer(maxlen=maxlen))
