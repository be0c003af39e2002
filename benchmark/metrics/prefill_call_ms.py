"""prefill_call_ms (ms): mean length of the program's
``gen/prefill/call`` regions — one ``GenerateRunner.prefill``: inputs
staged, the program, whole logits back on the host."""
from benchmark import program_spans


def read(r):
    return program_spans.mean_ms(program_spans.named(r, "gen/prefill/call"))
