"""decode_stage_idle_ms (ms): device-idle time inside the program's
``gen/decode/stage`` regions (the ``device_put`` of the step's tokens
and positions), per decode step."""
from benchmark import program_spans


def read(r):
    return program_spans.idle_ms_per(r, "gen/decode/stage", "gen/decode")
