"""prefill_rung_fill_pct (%): rows that hold a request / rows of the
batch rung they were padded to, over the window's prefill groups — the
``rows`` and ``rung`` counts of the program's ``gen/prefill`` regions.
Under 100 the prefill program runs padding rows."""
from benchmark import program_spans


def read(r):
    groups = program_spans.named(r, "gen/prefill")
    rung = program_spans.count_sum(groups, "rung")
    if not rung:
        return None
    return 100.0 * program_spans.count_sum(groups, "rows") / rung
