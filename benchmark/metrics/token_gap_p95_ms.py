"""token_gap_p95_ms (ms): 95th percentile of every gap between
consecutive streamed tokens whose later token fell in the window, one
pool over all requests, by the load generator's clock.  Under a full
table it falls now among the steps that prefill one long prompt, now
among those that admit two requests, from run to run of one seed: a
reading for the serving layer, not a number to hold a PR to."""


def read(r):
    value = r.end_to_end.get("token_gap_p95_ms")
    return value if value is not None and value == value else None
