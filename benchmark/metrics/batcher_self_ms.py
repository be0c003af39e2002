"""batcher_self_ms (ms): what ``GenerateBatcher.step`` spends outside the
runner, mean per step — the span round the step minus the prefill and
decode spans inside it (admission, sampling every lane on the host,
streaming the tokens out)."""
from benchmark import trace_reduce


def read(r):
    own = trace_reduce.self_seconds(r.trace, "batcher_step",
                                    ("prefill", "decode"))
    if not own:
        return None
    return 1e3 * sum(own) / len(own)
