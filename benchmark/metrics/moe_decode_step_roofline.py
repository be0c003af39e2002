"""moe_decode_step_roofline (%): the decode steps' share of their
roofline, for a model of routed experts with sliding and full attention.
Device time: device-busy seconds inside the program's ``gen/decode``
regions.  Least time: what those steps had to do, from each region's own
counts — the dense weights (projections, routers, norms, head) once a
step, each TOUCHED expert's weights once (``moe_experts_touched``,
counted on the device and summed over the layers), the keys and values
the ``active`` lanes read (``context_tokens`` positions in a full layer,
``min(p, sliding_window)`` a lane in a sliding one), the operations of
one token a lane through its 8 experts (``flops_mellum2``) — over the
chip's HBM bandwidth or its bf16 peak, whichever is larger, summed over
the steps.  It counts the work, not the implementation: an idle slot the
program computes, a table or a ring it reads whole, an untouched expert
it reads earn nothing."""
from benchmark import flops_mellum2 as counts
from benchmark import region_ops


def read(r):
    got = region_ops.inside_regions(r, "gen/decode")
    if got is None:
        return None
    _, busy_s, spans = got
    if busy_s <= 0 or any("moe_experts_touched" not in s.stats
                          for s in spans):
        return None
    el = 2 if r.cfg.get("param_dtype") == "bfloat16" else 4
    kv_el = 2 if r.cfg.get("kv_cache_dtype") == "bfloat16" else 4
    least = 0.0
    for s in spans:
        active = int(s.stats["active"])
        context = int(s.stats["context_tokens"])
        least += counts.roofline_seconds(
            counts.decode_step_flops(r.cfg, active, context),
            counts.decode_step_bytes(r.cfg, active, context,
                                     int(s.stats["moe_experts_touched"]),
                                     el, kv_el),
            r.peaks["bf16_flops_per_s"], r.peaks["hbm_bytes_per_s"])[0]
    r.note("moe_decode_step_roofline", steps=len(spans),
           device_ms_per_step=1e3 * busy_s / len(spans),
           least_ms_per_step=1e3 * least / len(spans),
           experts_touched_per_step=sum(
               int(s.stats["moe_experts_touched"]) for s in spans)
           / len(spans))
    return 100.0 * least / busy_s
