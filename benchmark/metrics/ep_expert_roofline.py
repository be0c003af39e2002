"""ep_expert_roofline (%): the routed experts' grouped products' share
of their roofline, over the decode AND the prefill calls of a device
that holds a share of each routed layer's experts.  Device time: the
operations wholly under the scope ``moe/experts`` of the program each
call ran (the grouped products and the gate between them), among the
device events inside the whole calls of ``gen/decode`` and
``gen/prefill/call`` (``ep_calls``).  Least time, a call: its touched
held experts' weights (``moe_experts_touched`` x 56.6 MB) over the
chip's HBM bandwidth, or its held assignments' operations
(``moe_assignments_held`` x gate, up and down) over its bf16 peak,
whichever is larger — both the program's own counts of the call,
summed over the layers, so a kernel that reads an untouched expert or a
touched one twice reads lower."""
from benchmark import ep_calls
from benchmark import flops_trinity as counts

NEEDS = ("moe_experts_touched", "moe_assignments_held")


def read(r):
    el = 2 if r.cfg.get("param_dtype") == "bfloat16" else 4
    least = spent = 0.0
    seen = {}
    for region in (ep_calls.DECODE, ep_calls.PREFILL):
        calls = ep_calls.whole_calls(r, region) or []
        if any(n not in s.stats for s, *_ in calls for n in NEEDS):
            return None
        n = in_s = 0
        touched = 0
        for s, events, _, text in calls:
            names = ep_calls.scope_names(r, text, "moe/experts")
            t = sum(d for name, _, d in events if name in names)
            if t <= 0:
                continue
            least += counts.experts_roofline_seconds(
                r.cfg, int(s.stats["moe_assignments_held"]),
                int(s.stats["moe_experts_touched"]), r.peaks, el)
            spent, in_s, n = spent + t, in_s + t, n + 1
            touched += int(s.stats["moe_experts_touched"])
        if n:
            seen[region] = {"calls": n, "device_ms_per_call": 1e3 * in_s / n,
                            "experts_touched_per_call": touched / n}
    if spent <= 0:
        return None
    r.note("ep_expert_roofline", **{
        f"{region.split('/')[1]}_{k}": v for region, d in seen.items()
        for k, v in d.items()})
    return 100.0 * least / spent
