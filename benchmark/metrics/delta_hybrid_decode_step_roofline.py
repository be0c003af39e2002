"""delta_hybrid_decode_step_roofline (%): the decode steps' share of
their roofline, for a model that carries a delta-rule state beside keys
and values.  Device time: device-busy seconds inside the program's
``gen/decode`` regions.  Least time: what those steps had to do, from
each region's own counts — the weights once a step, the delta-rule state
and convolution window of the ``active`` lanes read and written, the
keys and values of the ``context_tokens`` positions those lanes hold,
the operations of one token a lane (``flops_olmo_hybrid``) — over the
chip's HBM bandwidth or its bf16 peak, whichever is larger, summed over
the steps.  It counts the work, not the implementation: an idle slot the
program computes, a table it reads whole, or the tiles a state is padded
to earn nothing."""
from benchmark import flops_olmo_hybrid as counts
from benchmark import region_ops


def read(r):
    got = region_ops.inside_regions(r, "gen/decode")
    if got is None:
        return None
    _, busy_s, spans = got
    if busy_s <= 0 or any("active" not in s.stats for s in spans):
        return None
    el = 2 if r.cfg.get("param_dtype") == "bfloat16" else 4
    kv_el = 2 if r.cfg.get("kv_cache_dtype") == "bfloat16" else 4
    least = 0.0
    for s in spans:
        active = int(s.stats["active"])
        context = int(s.stats["context_tokens"])
        least += counts.roofline_seconds(
            counts.decode_step_flops(r.cfg, active, context),
            counts.decode_step_bytes(r.cfg, active, context, el, kv_el),
            r.peaks["bf16_flops_per_s"], r.peaks["hbm_bytes_per_s"])[0]
    r.note("delta_hybrid_decode_step_roofline", steps=len(spans),
           device_ms_per_step=1e3 * busy_s / len(spans),
           least_ms_per_step=1e3 * least / len(spans))
    return 100.0 * least / busy_s
