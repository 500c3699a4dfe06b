"""Scheduler: due time to the start of the engine tick that admitted the
request, 90th percentile over the requests due before the profiler
started (starting and stopping it stalls the host)."""
from ..common import percentile


def read(run, trace):
    waits = run["queue_wait_ms"]
    return percentile(waits, 90) if waits else None
