"""Serving spans on the profiler's own host tracer.

Every span of the serving path opens through :func:`span`: a
``jax.profiler.TraceAnnotation`` with a fixed name and keyword stats.
With the profiler off a span records nothing and costs about a
microsecond.  With it on (``jax.profiler.start_trace`` or
``jax.profiler.trace``, ``host_tracer_level`` 1 or more), the spans land
on the ``/host:CPU`` plane of the same trace as the chips' programs,
on the same clock.  The profiler keeps them; nothing here buffers,
and there is no switch but the profiler's.  Nesting on one thread
gives each span its parent; the ``rid``/``rids`` stats tie the spans
of one request together across threads.

Spans, by thread (stats in brackets):

- caller: ``serve.submit``, the whole of ``ServeFrontend.submit``
  (``rid``); ``serve.submit.lock``, its wait there to take the
  frontend's bookkeeping lock (``rid``).
- scheduler: ``serve.cycle``, one pass of the scheduler loop
  (``cycle``); ``serve.intake``, admission of arrivals (``admitted``,
  ``deferred``); ``serve.prefill``, one prefill call up to its first
  tokens on the host (``rids``, ``rows``, ``bucket``); ``serve.step``,
  one engine step (``rung`` of the window it dispatched; 0 when none);
  ``serve.admit``, slot admission: ladder target, cache store, page
  admission (``admitted``); ``serve.window.prep``, page mapping and,
  with no window in flight, the host-to-device copies of the window's
  inputs (``rung``, ``window``: its index since the last reset);
  ``serve.window.dispatch``, the jitted window call (``rung``,
  ``window``); ``serve.window.sync``, the wait for a window's tokens —
  in a step, the window dispatched before the step's own (``rung``,
  ``window``); ``serve.window.post``, per-slot bookkeeping and releases
  (``rung``, ``window``, ``finished``); ``serve.window.drain``, a
  forced read-back of the window in flight, holding its sync and post
  (``reason``, ``rung``, ``window``); ``serve.emit``,
  new tokens onto the backlog (``tokens``); ``serve.idle``, the wait
  for work when nothing moved.
- emit: ``serve.deliver``, one backlog item delivered, callbacks
  included (``rid``).
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **stats) -> TraceAnnotation:
    """A host span ``name`` with ``stats`` (ints, floats or strings);
    use as a context manager.  A stat known only at the end is added
    with ``set_metadata(**stats)`` before the span closes."""
    return TraceAnnotation(name, **stats)

