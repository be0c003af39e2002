"""decode_call_ms (ms): mean length of the benchmark's span round
``GenerateRunner.decode`` — input transfer, the program, and the logits
coming back to the host."""
from benchmark import trace_reduce


def read(r):
    spans = trace_reduce.spans_named(r.trace, "decode")
    if not spans:
        return None
    return 1e3 * sum(d for _, d in spans) / len(spans)
