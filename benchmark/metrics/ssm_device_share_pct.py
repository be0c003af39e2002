"""ssm_device_share_pct (%): the state-space layers' share of the decode
program's device time.  Device seconds of the decode program's
operations that lie WHOLLY under the scopes ``ssm/*`` (``ssm/conv``,
``ssm/state_update``; read from the ``op_name`` of every instruction of
the decode program's text, a fusion's from its fused computation) /
device-busy seconds, both over the device events inside the program's
``gen/decode`` regions only: instruction names repeat from program to
program, so a prefill program's events must not be counted under the
decode program's names.  The fusions that straddle a scope's edge are
noted on standard error; the share lies between this and this plus
theirs."""
from benchmark import region_ops


def read(r):
    got = region_ops.scope_seconds(r, "gen/decode", "ssm")
    if got is None:
        return None
    in_s, mixed_s, events, busy_s, _ = got
    r.note("ssm_device_share", events=events, device_s=in_s,
           mixed_s=mixed_s, busy_s=busy_s,
           mixed_share_pct=100.0 * mixed_s / busy_s if busy_s else None)
    if not events or busy_s <= 0:
        return None
    return 100.0 * in_s / busy_s
