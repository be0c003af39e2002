"""train_call_self_ms (ms): what ``TrainStep.__call__`` spends outside
the executable's call, mean per step — the program's ``train/step``
regions minus the ``train/dispatch`` inside them.  That is the host's
work there (batch placement, the key, learning rates, gathering and
writing back the leaves) AND the wait for the device's queue: once the
host runs ahead, the first operation of ``train/prep`` that needs a free
slot blocks for a device step.  It is a mean over a window that holds
both kinds of call, as ``train_host_call_ms`` is; the host's own work
is ``train_host_work_ms``."""
from benchmark import program_spans, trace_reduce


def read(r):
    steps = program_spans.named(r, "train/step")
    if not steps:
        return None
    inside = trace_reduce.Cover(trace_reduce.merged(
        (s.start, s.start + s.dur)
        for s in program_spans.named(r, "train/dispatch")))
    return 1e3 * sum(s.dur - inside.within(s.start, s.start + s.dur)
                     for s in steps) / len(steps)
