"""Named host spans, recorded on the program's own reports and emitted as
profiler annotations.

``with span("cleave.fleet.stage", phases):`` times its body on the host
clock, adds the seconds to ``phases["stage"]`` (the name's last dotted
part), and runs the body inside ``jax.profiler.TraceAnnotation(name)``, so
the span lands on the device trace's clock whenever a profiler session is
active (``jax.profiler.trace``) and costs about a microsecond when none is.
A span never waits on the device: where it should cover device work, the
code inside it already blocks on the result.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Optional

from jax.profiler import TraceAnnotation


@contextlib.contextmanager
def span(name: str, phases: Optional[Dict[str, float]] = None):
    """Time the body into ``phases[<last part of name>]`` (when given) and
    annotate it as ``name`` on the profiler's trace."""
    t0 = time.perf_counter()
    try:
        with TraceAnnotation(name):
            yield
    finally:
        if phases is not None:
            key = name.rsplit(".", 1)[-1]
            phases[key] = phases.get(key, 0.0) + time.perf_counter() - t0
