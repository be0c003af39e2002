"""device_idle_pct.train (%): share of the traced window in which no
operation ran on the device (1 - union of device-op intervals / window)."""
from benchmark import trace_reduce


def read(r):
    return trace_reduce.idle_pct(r.trace)
