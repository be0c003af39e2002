"""window_attention_roofline (%): the sliding layers' decode attention's
share of its roofline.  Device time: the decode program's operations
under the scope ``window_attention`` (scores, mask, softmax and values
of the sliding layers), wholly AND partly, over the device events inside
``gen/decode`` regions.  A fusion that lies partly under the scope
counts whole: the compiler fuses the cut of a layer's planes out of the
table (``kv_cache_read``, an operation of no scope) into the product
that reads them — the read this metric is about — and may fuse a
neighbour's small work (the queries' rotation before, the cast after)
in as well; that reads the share a little lower, never higher, and the
program is not shaped for the reader's sake.  Least time, a step: both planes of ``min(p, sliding_window)``
positions a lane and sliding layer (4 key/value heads of 128, the
table's dtype) over the chip's HBM bandwidth, from the step's own
``active`` and ``context_tokens``: the columns of the ring past the
window, which the program reads and masks, earn nothing."""
from benchmark import flops_mellum2 as counts
from benchmark import scope_ops


def read(r):
    got = scope_ops.seconds(r, scope_ops.DECODE, "window_attention")
    if got is None:
        return None
    in_s, mixed_s, events, spans = got
    in_s += mixed_s
    if not events or in_s <= 0 or any("active" not in s.stats
                                      for s in spans):
        return None
    kv_el = 2 if r.cfg.get("kv_cache_dtype") == "bfloat16" else 4
    least = sum(counts.window_attention_bytes(
        r.cfg, int(s.stats["active"]), int(s.stats["context_tokens"]),
        kv_el) for s in spans) / r.peaks["hbm_bytes_per_s"]
    r.note("window_attention_roofline", steps=len(spans), events=events,
           device_ms_per_step=1e3 * in_s / len(spans),
           of_which_partly_under_the_scope_ms=1e3 * mixed_s / len(spans),
           least_ms_per_step=1e3 * least / len(spans))
    return 100.0 * least / in_s
