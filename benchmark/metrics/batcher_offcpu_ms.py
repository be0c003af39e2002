"""batcher_offcpu_ms (ms): per ``gen/step``, the step's wall time minus
the serving thread's CPU time over it (``cpu_us``) minus the wall time
of its ``/fetch`` regions: wall not on a CPU and not in a fetch — the
GIL, a lock, the scheduler, and blocking inside the runtime's
``device_put`` and dispatch included.  A lower bound: CPU time the
thread spends inside a fetch (the runtime's wait does not only sleep)
is subtracted too, so a step can read below zero."""
from benchmark import idle_leaves


def read(r):
    return idle_leaves.offcpu_ms(r)
