"""prefill_host_idle_ms (ms): device-idle time inside every leaf region
under ``gen/prefill`` (each chunk's ``gen/prefill/rows``, each call's
``/stage``, ``/dispatch`` and ``/fetch``, the
group's first-token ``gen/sample`` and its ``gen/commit``), per
``gen/prefill/call``."""
from benchmark import idle_leaves


def read(r):
    return idle_leaves.idle_ms_per(r, "gen/prefill", "gen/prefill/call")
