"""delta_device_share_pct (%): the delta-rule layers' share of the decode
program's device time.  Device seconds of the decode program's
operations that lie WHOLLY under the scopes ``delta/*``
(``delta/state_update``; read from the ``op_name`` of every instruction
of the decode program's text, a fusion's from its fused computation) /
device-busy seconds, both over the device events inside the program's
``gen/decode`` regions only.  The fusions that straddle a scope's edge
are noted on standard error; the share lies between this and this plus
theirs.  The layers' convolution (scope ``ssm/conv``, the op they share
with the Mamba layers) and their projections are not in it."""
from benchmark import region_ops


def read(r):
    got = region_ops.scope_seconds(r, "gen/decode", "delta")
    if got is None:
        return None
    in_s, mixed_s, events, busy_s, _ = got
    r.note("delta_device_share", events=events, device_s=in_s,
           mixed_s=mixed_s, busy_s=busy_s,
           mixed_share_pct=100.0 * mixed_s / busy_s if busy_s else None)
    if not events or busy_s <= 0:
        return None
    return 100.0 * in_s / busy_s
