"""flash_attention_roofline (%): the flash-attention kernels' share of
their roofline in the train step.

Device time: the trace's events named after the compiled step's TPU
custom calls that were traced from ``flash_attention.py`` (found through
the program text's stack frames, ``trace_reduce.custom_calls_by_file``).
Least time: what THOSE calls have to do, from shapes
(``flops.flash_attention_call``) against the chip's bf16 peak and HBM
bandwidth, whichever bounds.  The program holds one such call per layer
where only the forward is a kernel (its backward at sequence 512 is
plain XLA, ``MXTPU_FLASH_BWD=auto``) and three (forward, dq, dk/dv)
where the backward is too; any other count is nothing this reader knows
how to read."""
from benchmark import flops, trace_reduce

SOURCE = "flash_attention.py"


def read(r):
    text = r.facts.get("hlo_text")
    if not text or not r.trace.devices:
        return None
    by_file = trace_reduce.custom_calls_by_file(text)
    names = set(by_file.get(SOURCE, ()))
    seconds, events = trace_reduce.op_seconds(r.trace, names)
    cfg, mix = r.cfg, r.mix
    layers = cfg["num_hidden_layers"]
    directions = {layers: (False,), 3 * layers: (False, True)}.get(len(names))
    # every kernel's calls, by the file they were traced from: the
    # instruction names alone (jvp__.N) do not tell the kernels apart
    r.note("flash_attention_roofline", calls_in_program=len(names),
           events=events, device_s=seconds,
           device_s_by_file={f: trace_reduce.op_seconds(r.trace, set(n))
                             for f, n in by_file.items()})
    if not events or seconds <= 0 or directions is None:
        return None
    heads = cfg["num_attention_heads"]
    shape = (int(mix["batch"]), heads, int(mix["seq"]), int(mix["seq"]),
             cfg["hidden_size"] // heads, bool(cfg.get("causal")))
    el = 2 if mix.get("compute_dtype") == "bfloat16" else 4
    least = 0.0
    for backward in directions:
        ops, nbytes = flops.flash_attention_call(*shape, backward, el)
        least += layers * flops.roofline_seconds(
            ops, nbytes, r.peaks["bf16_flops_per_s"],
            r.peaks["hbm_bytes_per_s"])[0]
    # events per step = calls in the program; steps seen = events / that
    steps_seen = events / len(names)
    r.note("flash_attention_roofline", steps_seen=steps_seen,
           backward_is_a_kernel=len(directions) == 2,
           device_ms_per_step=1e3 * seconds / steps_seen,
           least_ms_per_step=1e3 * least)
    return 100.0 * least * steps_seen / seconds
