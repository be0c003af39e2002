"""ep_decode_step_roofline (%): the decode steps' share of their
roofline, for a device that holds a share of each routed layer's
experts.  Device time: device-busy seconds inside the program's
``gen/decode`` regions, the whole calls alone (``ep_calls``).  Least
time: what each step had to do, from its region's own counts by
``flops_trinity`` — the dense weights (projections, gates, norms, the
dense MLP, routers, shared experts, head) once, each TOUCHED held expert
once (``moe_experts_touched``, counted on the device and summed over the
layers), the keys and values the lanes read (``context_tokens +
active`` positions in the full layer, ``window_tokens`` = ``min(p + 1,
4096)`` summed over the lanes in each sliding one), and the operations
of a token a lane with its held assignments (``moe_assignments_held``) —
over the chip's HBM bandwidth or its bf16 peak, whichever is larger,
summed over the steps.  It counts the work, not the implementation: an
idle slot the program computes, a table or a ring read whole, an
untouched expert read earn nothing."""
from benchmark import ep_calls, program_spans
from benchmark import flops_trinity as counts

NEEDS = ("moe_experts_touched", "moe_assignments_held", "window_tokens",
         "active", "context_tokens")


def read(r):
    calls = ep_calls.whole_calls(r, ep_calls.DECODE)
    if calls is None or any(n not in s.stats for s, *_ in calls
                            for n in NEEDS):
        return None
    el = 2 if r.cfg.get("param_dtype") == "bfloat16" else 4
    kv_el = 2 if r.cfg.get("kv_cache_dtype") == "bfloat16" else 4
    least = busy = 0.0
    for s, _, busy_s, _ in calls:
        st = {n: int(s.stats[n]) for n in NEEDS}
        full = st["context_tokens"] + st["active"]
        least += counts.roofline_seconds(
            counts.decode_step_flops(r.cfg, st["active"],
                                     st["moe_assignments_held"], full,
                                     st["window_tokens"]),
            counts.decode_step_bytes(r.cfg, full, st["window_tokens"],
                                     st["moe_experts_touched"], el, kv_el),
            r.peaks["bf16_flops_per_s"], r.peaks["hbm_bytes_per_s"])[0]
        busy += busy_s
    if busy <= 0:
        return None
    n = len(calls)
    r.note("ep_decode_step_roofline", steps=n,
           steps_in_trace=len(program_spans.named(r, ep_calls.DECODE)),
           device_ms_per_step=1e3 * busy / n,
           least_ms_per_step=1e3 * least / n,
           experts_touched_per_step=sum(
               int(s.stats["moe_experts_touched"]) for s, *_ in calls) / n,
           held_assignments_per_step=sum(
               int(s.stats["moe_assignments_held"]) for s, *_ in calls) / n)
    return 100.0 * least / busy
