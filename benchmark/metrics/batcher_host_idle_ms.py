"""batcher_host_idle_ms (ms): device-idle time inside the batcher's own
leaf regions, those under neither ``gen/prefill`` nor ``gen/decode``
(``gen/admit``, ``gen/decode_rows``, the decode's ``gen/sample`` and
``gen/commit``, ``gen/fire``, ``gen/complete``, and the serving
thread's ``gen/between`` two steps), per ``gen/step``."""
from benchmark import idle_leaves


def read(r):
    return idle_leaves.idle_ms_per(r, None, "gen/step")
