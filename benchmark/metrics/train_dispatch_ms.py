"""train_dispatch_ms (ms): mean length of the program's
``train/dispatch`` regions — the call of the step's executable alone
(the enqueue of its ~400 leaves), inside ``TrainStep.__call__``."""
from benchmark import program_spans


def read(r):
    return program_spans.mean_ms(program_spans.named(r, "train/dispatch"))
