"""diffusion_step_roofline (%): the block-diffusion passes' share of
their roofline.  Device time: device-busy seconds inside the program's
``gen/decode`` regions (one pass each).  Least time: what those passes
had to do, from each region's own counts — the dense weights
(projections, routers, norms, head) once a pass, each TOUCHED expert's
weights once (``moe_experts_touched``, counted on the device and summed
over the layers), each active lane's keys and values up to its block's
end (``context_tokens`` + ``positions``), and the operations of the
``positions`` fed, each through its 8 experts and against the keys it
reads (``attended``), the head over each active row's block
(``flops_sdar``) — over the chip's HBM bandwidth or its bf16 peak,
whichever is larger, summed over the passes.  It counts the work, not
the implementation: an idle slot the program computes, a table it reads
whole, an untouched expert it reads earn nothing; every position a pass
feeds earns its share, recomputed or not (``positions_per_token`` says
how many that is)."""
from benchmark import flops_sdar as counts
from benchmark import region_ops

NEEDS = ("moe_experts_touched", "positions", "attended", "context_tokens",
         "active")


def read(r):
    got = region_ops.inside_regions(r, "gen/decode")
    if got is None:
        return None
    _, busy_s, spans = got
    if busy_s <= 0 or any(k not in s.stats for s in spans for k in NEEDS):
        return None
    el = 2 if r.cfg.get("param_dtype") == "bfloat16" else 4
    kv_el = 2 if r.cfg.get("kv_cache_dtype") == "bfloat16" else 4
    least = 0.0
    for s in spans:
        st = {k: int(s.stats[k]) for k in NEEDS}
        least += counts.roofline_seconds(
            counts.step_flops(r.cfg, st["active"], st["positions"],
                              st["attended"]),
            counts.step_bytes(r.cfg, st["context_tokens"], st["positions"],
                              st["moe_experts_touched"], el, kv_el),
            r.peaks["bf16_flops_per_s"], r.peaks["hbm_bytes_per_s"])[0]
    r.note("diffusion_step_roofline", passes=len(spans),
           device_ms_per_pass=1e3 * busy_s / len(spans),
           least_ms_per_pass=1e3 * least / len(spans),
           positions_per_pass=sum(int(s.stats["positions"]) for s in spans)
           / len(spans),
           experts_touched_per_pass=sum(
               int(s.stats["moe_experts_touched"]) for s in spans)
           / len(spans))
    return 100.0 * least / busy_s
