"""delta_state_update_roofline (%): the one-token delta-rule update's
share of its roofline in the decode program.  Device time: the decode
program's operations that lie wholly under the scope
``delta/state_update`` (the program fences the update so that nothing of
its neighbours is fused into it), over the device events inside
``gen/decode`` regions only.  Least time: every linear-attention layer's
state read once and written once for all the slots the program computes,
at its unpadded size (``flops_olmo_hybrid.state_update_bytes``), over
the chip's HBM bandwidth, or its operations over the peak, whichever is
larger, a decode step."""
from benchmark import flops_olmo_hybrid as counts
from benchmark import region_ops


def read(r):
    got = region_ops.scope_seconds(r, "gen/decode", "delta/state_update")
    if got is None:
        return None
    in_s, mixed_s, events, _, spans = got
    if not events or in_s <= 0:
        return None
    slots = r.facts["slots"]
    least, _ = counts.roofline_seconds(
        counts.state_update_flops(r.cfg, slots),
        counts.state_update_bytes(r.cfg, slots),
        r.peaks["bf16_flops_per_s"], r.peaks["hbm_bytes_per_s"])
    r.note("delta_state_update_roofline", steps=len(spans), events=events,
           device_ms_per_step=1e3 * in_s / len(spans),
           mixed_ms_per_step=1e3 * mixed_s / len(spans),
           least_ms_per_step=1e3 * least)
    return 100.0 * least * len(spans) / in_s
