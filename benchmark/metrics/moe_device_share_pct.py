"""moe_device_share_pct (%): the routed feed-forward's share of the
device's busy time in the window.  Device seconds of the decode and
prefill programs' operations that lie WHOLLY under the scopes ``moe/*``
(``moe/route``, ``moe/dispatch``, ``moe/experts``, ``moe/combine``; the
compiler's own grouped-product kernels among them: ``scope_ops``), each
call's events held against the text of the program it ran / the device's
busy seconds over the whole window.  The fusions that straddle a
scope's edge are noted on standard error; the share lies between this
and this plus theirs."""
from benchmark import scope_ops, trace_reduce


def read(r):
    got = scope_ops.both(r, "moe")
    if got is None:
        return None
    in_s, mixed_s, events = got
    busy_s = trace_reduce.busy_seconds(r.trace)
    r.note("moe_device_share", events=events, device_s=in_s,
           mixed_s=mixed_s, busy_s=busy_s,
           mixed_share_pct=100.0 * mixed_s / busy_s if busy_s else None)
    if not events or busy_s <= 0:
        return None
    return 100.0 * in_s / busy_s
