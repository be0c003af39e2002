"""delta_chunk_roofline (%): the chunked delta rule's share of its
roofline in the prefill programs.  Device time: the prefill programs'
operations that lie wholly under the scope ``delta/chunk``, over the
device events inside ``gen/prefill/call`` regions; instruction names
repeat from program to program, so each region's events are held against
the text of the program it called (``rows`` x ``bucket``, from the
region's counts).  Least time: the chunked form's own operations
(``flops_olmo_hybrid.chunk_flops``) over the chip's bf16 peak, or its
bytes over the HBM bandwidth, whichever is larger, for the rows and
positions each call computed — the padded ones too: the program computes
them, and a cell whose rungs are poorly filled reads that in
``prefill_rung_fill_pct``."""
import bisect

from benchmark import flops_olmo_hybrid as counts
from benchmark import program_spans, region_ops, trace_reduce


def _events_inside(r, spans):
    cover = trace_reduce.merged((s.start, s.start + s.dur) for s in spans)
    starts = [lo for lo, _ in cover]
    out = []
    for ev in r.trace.devices[sorted(r.trace.devices)[0]]:
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i >= 0 and ev[1] < cover[i][1]:
            out.append(ev)
    return out


def read(r):
    texts = r.facts.get("prefill_hlo_texts")
    spans = program_spans.named(r, "gen/prefill/call")
    if not texts or not spans or not r.trace.devices:
        return None
    by_program = {}
    for s in spans:
        if "rows" in s.stats and "bucket" in s.stats:
            by_program.setdefault(
                (int(s.stats["rows"]), int(s.stats["bucket"])), []).append(s)
    in_s = mixed_s = least = 0.0
    events = 0
    for (rows, bucket), called in sorted(by_program.items()):
        text = texts.get(f"{rows}x{bucket}")
        if text is None:
            return None
        inside, mixed = program_spans.ops_by_scope(
            region_ops._constants_unnamed(text), "delta/chunk")
        for name, _, dur in _events_inside(r, called):
            if name in inside:
                in_s, events = in_s + dur, events + 1
            elif name in mixed:
                mixed_s += dur
        least += len(called) * counts.roofline_seconds(
            counts.chunk_flops(r.cfg, rows, bucket),
            counts.chunk_bytes(r.cfg, rows, bucket),
            r.peaks["bf16_flops_per_s"], r.peaks["hbm_bytes_per_s"])[0]
    if not events or in_s <= 0:
        return None
    r.note("delta_chunk_roofline", calls=len(spans), events=events,
           device_ms_per_call=1e3 * in_s / len(spans),
           mixed_ms_per_call=1e3 * mixed_s / len(spans),
           least_ms_per_call=1e3 * least / len(spans))
    return 100.0 * least / in_s
