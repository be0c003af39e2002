"""decode_dispatch_idle_ms (ms): device-idle time inside the program's
``gen/decode/dispatch`` regions (the executable call and the search for
each row's first maximum queued behind it), per decode step: with
``decode_stage_idle_ms`` and ``decode_fetch_idle_ms`` the decode call's
whole host side."""
from benchmark import program_spans


def read(r):
    return program_spans.idle_ms_per(r, "gen/decode/dispatch", "gen/decode")
