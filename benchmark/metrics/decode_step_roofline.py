"""decode_step_roofline (%): the decode program's share of its roofline.
Device time: device-busy seconds inside the benchmark's spans round
``GenerateRunner.decode`` (the call waits for its logits, so the
program's run lies inside its span), per step.  Least time: the bytes a
step has to read (every product's weights and the whole key/value table,
``flops.decode_step_bytes``) over the chip's HBM bandwidth, or its
operations over the peak, whichever is larger."""
from benchmark import flops, trace_reduce


def read(r):
    f = r.facts
    seconds, steps = trace_reduce.device_seconds_within(r.trace, "decode")
    if not steps or seconds <= 0:
        return None
    el = 4 if r.cfg.get("param_dtype", "float32") == "float32" else 2
    kv_el = 4 if r.cfg.get("kv_cache_dtype", "float32") == "float32" else 2
    nbytes = flops.decode_step_bytes(r.cfg, f["slots"], f["kv_capacity"],
                                     el, kv_el)
    ops = f["slots"] * flops.decode_flops_per_token(r.cfg, f["kv_capacity"])
    least, _ = flops.roofline_seconds(ops, nbytes,
                                      r.peaks["bf16_flops_per_s"],
                                      r.peaks["hbm_bytes_per_s"])
    return 100.0 * least * steps / seconds
