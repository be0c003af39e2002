"""train_host_work_ms (ms): what one ``TrainStep.__call__`` costs the
host when it does not wait for the device — the median length of the
program's ``train/step`` regions over the window's UNBLOCKED calls.
The host runs ahead until the device's queue is full and then blocks
inside ``train/prep`` for a device step per call, so a window's calls
fall into two bunches; a call counts as blocked when it is longer than
halfway between the window's shortest and its longest call.  Where no
call blocks (a step the host bounds) that halves the one bunch and
reads its lower quartile.  This, not ``train_host_call_ms``, is the
step time under which a faster device step becomes host-bound."""
from benchmark import program_spans


def read(r):
    return program_spans.unblocked_median_ms(
        program_spans.named(r, "train/step"))
