"""One reader per per-layer metric, found by the metric's name.

Each module defines ``read(run, trace)``: ``run`` holds the host-side
facts of the run (the configuration, the chip's peaks, the queue waits and
the counts of the traced slice), ``trace`` the reduction of its profile
(``bench.trace.reduce``) or None.  A reader that finds nothing to read
returns None, and the metric is left out of the result line.

The programs are found in the trace by the names the serving engine's
jitted functions carry: ``chunk_fn`` (the fused decode chunk) and
``prefill_fn`` (the bucketed batch-1 prefill).
"""
DECODE = "chunk_fn"
PREFILL = "prefill_fn"
