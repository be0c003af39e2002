"""optimizer_device_share_pct (%): device seconds of the train step's
operations that lie WHOLLY under the program's ``train/optimizer`` scope
(the update over the buckets, the results cut apart) / device-busy
seconds: the optimizer's share from below.  The scope is read from the
``op_name`` of every instruction in the compiled step's text, a fusion's
from the instructions of its fused computation; the fusions that
straddle the scope's edge are ``optimizer_mixed_share_pct``, and the
optimizer's share lies between this and the sum of the two."""
from benchmark import program_spans


def read(r):
    return program_spans.device_share_pct(r, "train/optimizer")
