"""queue_wait_ms (ms): how long an admitted request had waited in the
batcher's queue, mean over the requests admitted in the traced window —
the ``wait_us_sum`` and ``admitted`` counts of the program's
``gen/admit`` regions (the batcher's own clock, submit to admission)."""
from benchmark import program_spans


def read(r):
    admits = program_spans.named(r, "gen/admit")
    admitted = program_spans.count_sum(admits, "admitted")
    if not admitted:
        return None
    return 1e-3 * program_spans.count_sum(admits, "wait_us_sum") / admitted
