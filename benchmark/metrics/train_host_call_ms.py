"""train_host_call_ms (ms): how long ``TrainStep.__call__`` keeps the
host, mean per step — the benchmark's own clock round the call until it
returns (the enqueue, not the completion)."""


def read(r):
    calls = r.facts.get("host_call_s")
    if not calls:
        return None
    return 1e3 * sum(calls) / len(calls)
