"""decode_fetch_idle_ms (ms): device-idle time inside the program's
``gen/decode/fetch`` regions (``np.asarray(logits)``: the wait for the
decode program, then the logits copied into fresh host memory), per
decode step.  The device idles there only once the program is done, so
this is the copy; a process in the "fast state" should read less here."""
from benchmark import program_spans


def read(r):
    return program_spans.idle_ms_per(r, "gen/decode/fetch", "gen/decode")
