"""sample_ms (ms): time in the program's ``gen/sample`` regions (the
per-lane ``sample_token`` loops: first tokens after a prefill, one
token a lane after a decode), per ``gen/step``."""
from benchmark import program_spans


def read(r):
    steps = program_spans.named(r, "gen/step")
    if not steps:
        return None
    spent = sum(s.dur for s in program_spans.named(r, "gen/sample"))
    return 1e3 * spent / len(steps)
