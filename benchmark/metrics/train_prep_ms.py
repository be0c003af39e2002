"""train_prep_ms (ms): mean length of the program's ``train/prep``
regions — the batch placed, the next key, learning rates, the small
values committed, the leaves gathered.  It holds the wait for the
device's queue too: when the host runs ahead of the device, the call
blocks here for a device step (``train_host_work_ms`` leaves those
calls out)."""
from benchmark import program_spans


def read(r):
    return program_spans.mean_ms(program_spans.named(r, "train/prep"))
