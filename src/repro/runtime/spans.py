"""Host spans of the serving engine, on the profiler's clock.

``span("prefill", rid=3)`` is a ``jax.profiler.TraceAnnotation`` named
``engine.prefill`` with its keyword arguments attached.  It is recorded only
while a profiler session runs (``jax.profiler.trace`` / ``start_trace``),
into the same ``.xplane.pb`` as the device's ops, so host work and device
work share one clock; with no session running a span costs about a
microsecond and records nothing.  A span never touches the device: no sync,
no transfer.  Arguments known only inside the span are attached with
``set_metadata`` on the object the ``with`` statement binds.

The engine's spans, one tick nested as shown:

    engine.tick       ServeEngine.step                 clock, mode
      engine.admit    admission, paged-page reclaim    waiting, free
      engine.prefill  one prompt: pad, dispatch        rid, prompt_len, bucket
      engine.insert   its slot-insert dispatch         rid, slot
      engine.chunk    chunk length, chunk dispatch     chunk, live
      engine.sync     the tick's one device_get
      engine.emit     ring drain, emits, measurement   tokens
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

PREFIX = "engine."


def span(name: str, **args) -> TraceAnnotation:
    """The host span ``engine.<name>`` with ``args`` attached."""
    return TraceAnnotation(PREFIX + name, **args)
