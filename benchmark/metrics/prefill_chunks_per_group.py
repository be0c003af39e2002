"""prefill_chunks_per_group (count): runner calls one admitted group's
prefill takes, mean over the window — the ``chunks`` count of the
program's ``gen/prefill`` regions.  1 unless a prompt is longer than the
largest prompt bucket and is prefilled a bucket's width at a time."""
from benchmark import program_spans


def read(r):
    groups = program_spans.named(r, "gen/prefill")
    if not groups:
        return None
    return program_spans.count_sum(groups, "chunks") / len(groups)
