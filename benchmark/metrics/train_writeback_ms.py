"""train_writeback_ms (ms): mean length of the program's
``train/writeback`` regions — the loops that put the new values, the
optimizer's state and the auxiliary values back into the parameters."""
from benchmark import program_spans


def read(r):
    return program_spans.mean_ms(program_spans.named(r, "train/writeback"))
