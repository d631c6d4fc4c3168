"""Runtime observability: metrics registry, trace spans, step telemetry,
flight recorder, perf-evidence harness.

Parts (ISSUE 1 + ISSUE 2 tentpoles):

* :mod:`.metrics` — process-wide Counter / Gauge / Histogram registry
  with labels; ``snapshot()`` / ``export_json()`` for readout, flag-gated
  (``FLAGS_enable_metrics``) so disabled instruments cost one boolean
  check.
* :func:`span` — THE span primitive of the program (the serve loop's
  phases, ``to_static``'s capture stages).  Always on: it enters a
  ``jax.profiler.TraceAnnotation`` for its duration, so while
  ``jax.profiler`` is tracing the span lies on ``/host:CPU`` on the
  device trace's clock, and keeps count / total / max seconds per name in
  memory (:func:`span_totals`) for spans that end before any profiler
  starts.  While a :class:`paddle_tpu.profiler.Profiler` is recording it
  also lands on that profiler's host timeline (``_HostTracer``).
* :mod:`.telemetry` — per-training-step :class:`~.telemetry.StepTimeline`
  records (wall/compile/comm split, compute/comm/host fractions,
  tokens/sec, MFU via the shared :mod:`.flops` helper).
* :mod:`.flight_recorder` — bounded ring of the last K step records +
  events, dumped to JSON on demand, on an unhandled train-step
  exception, or when the NaN/Inf watchdog
  (``FLAGS_enable_nan_watchdog``) trips.  CLI:
  ``python -m paddle_tpu.observability.dump``.
* :mod:`.flops` — the ONE FLOPs/MFU accounting helper (models, the
  auto-tuner cost model, bench and telemetry all use it).
* :mod:`.harness` — registered benchmark rungs with backend probing and
  degradation: every rung always emits a schema-stable JSON record
  ``{rung, ok, value|error, device, elapsed_s}`` instead of a run-killing
  stack trace (`bench.py` drives it).

Usage::

    from paddle_tpu import observability as obs

    tl = obs.telemetry.StepTimeline(flops_per_token=fpt,
                                    device_kind="tpu v5e")
    with tl.step(tokens=B * S) as st:
        loss = step(x, y)
    st.annotate(loss=float(loss))
    tl.summary()                            # fractions, tokens/s, MFU

    obs.metrics.snapshot()                  # dict of every live metric
    obs.metrics.export_json("metrics.json")
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from jax.profiler import TraceAnnotation

from ..profiler import profiler as _prof

from . import metrics  # noqa: F401
from . import descriptions  # noqa: F401
from . import flops  # noqa: F401
from . import flight_recorder  # noqa: F401
from . import telemetry  # noqa: F401
from . import quantiles  # noqa: F401
from . import compile_tracker  # noqa: F401
from . import xray  # noqa: F401
from .metrics import (  # noqa: F401
    counter, gauge, histogram, quantile, snapshot, reset, export_json,
)

__all__ = ["metrics", "harness", "span", "span_totals", "telemetry",
           "flight_recorder",
           "flops", "quantiles", "compile_tracker", "xray", "chrome",
           "descriptions", "export", "http",
           "counter", "gauge", "histogram", "quantile", "snapshot",
           "reset", "export_json"]

# name -> [count, total seconds, max seconds]; one small list a name,
# updated under the lock (spans end on the serve loop's thread, on the
# caller's thread in to_static, and on handler threads in tests)
_SPAN_TOTALS: dict = {}
_SPAN_LOCK = threading.Lock()


class span:
    """Timing span: context manager (or begin()/end()).  For its duration
    it holds a ``jax.profiler.TraceAnnotation(name, **attrs)`` — one
    TraceMe check when no profiler runs — and at its end it adds its wall
    time to the per-name totals (:func:`span_totals`) and leaves it in
    ``seconds``.  ``set(**attrs)`` adds attrs known only later
    (``tokens`` emitted, ``cache_hit``) to the running annotation."""

    __slots__ = ("name", "attrs", "seconds", "_t0", "_ann")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self.seconds = 0.0
        self._t0: Optional[float] = None

    def begin(self) -> "span":
        self._ann = TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def set(self, **attrs) -> None:
        self._ann.set_metadata(**attrs)

    def end(self) -> Optional[float]:
        if self._t0 is None:
            return None
        t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        t0, self._t0 = self._t0, None
        dt = self.seconds = t1 - t0
        with _SPAN_LOCK:
            rec = _SPAN_TOTALS.get(self.name)
            if rec is None:
                _SPAN_TOTALS[self.name] = [1, dt, dt]
            else:
                rec[0] += 1
                rec[1] += dt
                if dt > rec[2]:
                    rec[2] = dt
        tracer = _prof.active_tracer()
        if tracer is not None:
            tracer.add(self.name, t0, t1, category="span")
        return dt

    def __enter__(self) -> "span":
        return self.begin()

    def __exit__(self, *exc) -> bool:
        self.end()
        return False


def span_totals(prefix: str = "") -> dict:
    """``{name: {"count", "total_s", "max_s"}}`` of every span ended so
    far in this process (names starting with ``prefix``)."""
    with _SPAN_LOCK:
        return {n: {"count": c, "total_s": t, "max_s": m}
                for n, (c, t, m) in _SPAN_TOTALS.items()
                if n.startswith(prefix)}


def __getattr__(name):
    # leaf modules only bench/test/scrape flows need; kept lazy so
    # `import paddle_tpu` never pays for them
    if name in ("harness", "export", "http", "chrome"):
        import importlib
        return importlib.import_module("." + name, __name__)
    raise AttributeError(name)
