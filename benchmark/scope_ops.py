"""Device seconds under a named scope of the serving programs, for the
readers of a cell whose decode AND prefill programs carry the scope.

``region_ops.scope_seconds`` reads the decode program (``facts
["hlo_text"]``); a prefill call runs one of several programs, and
instruction names repeat from program to program, so each
``gen/prefill/call`` region's events are held against the text of the
program it called (``facts["prefill_hlo_texts"]``, by the region's own
``rows`` x ``bucket``).
"""
import bisect

from . import flops_mellum2, program_spans, region_ops, trace_reduce

DECODE, PREFILL = "gen/decode", "gen/prefill/call"


def _events_inside(r, spans):
    cover = trace_reduce.merged((s.start, s.start + s.dur) for s in spans)
    starts = [lo for lo, _ in cover]
    out = []
    for ev in r.trace.devices[sorted(r.trace.devices)[0]]:
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i >= 0 and ev[1] < cover[i][1]:
            out.append(ev)
    return out


def _programs(r, region):
    """``[(text, spans)]``: each program that ``region``'s calls ran,
    with the regions that called it; None where one is unknown."""
    spans = program_spans.named(r, region)
    if not spans or not r.trace.devices:
        return None
    if region == DECODE:
        text = r.facts.get("hlo_text")
        return [(text, spans)] if text else None
    texts = r.facts.get("prefill_hlo_texts")
    if not texts:
        return None
    by_program = {}
    for s in spans:
        if "rows" not in s.stats or "bucket" not in s.stats:
            return None
        key = f"{int(s.stats['rows'])}x{int(s.stats['bucket'])}"
        if key not in texts:
            return None
        by_program.setdefault(key, []).append(s)
    return [(texts[key], called) for key, called in sorted(by_program.items())]


def seconds(r, region, scope):
    """``(inside_s, mixed_s, events, spans)`` of the operations under
    ``scope`` among the device events inside ``region``'s calls
    (``DECODE`` or ``PREFILL``); None where there is nothing to read."""
    key = ("scope_ops.seconds", region, scope)
    if key not in r.trace.memo:
        programs = _programs(r, region)
        if programs is None:
            r.trace.memo[key] = None
        else:
            in_s = mixed_s = 0.0
            events, spans = 0, []
            for text, called in programs:
                inside, mixed = program_spans.ops_by_scope(
                    region_ops._constants_unnamed(text), scope)
                for name, _, dur in _events_inside(r, called):
                    if name in inside:
                        in_s, events = in_s + dur, events + 1
                    elif name in mixed:
                        mixed_s += dur
                spans += called
            r.trace.memo[key] = (in_s, mixed_s, events, spans)
    return r.trace.memo[key]


def both(r, scope):
    """``seconds`` summed over the decode and the prefill programs;
    a kind of call the window did not make adds nothing."""
    got = [g for g in (seconds(r, DECODE, scope), seconds(r, PREFILL, scope))
           if g is not None]
    if not got:
        return None
    return tuple(sum(g[i] for g in got) for i in range(3))


def experts_roofline(r, region, note):
    """The share (%) of their roofline that the experts' grouped
    products reach inside ``region``'s calls: device seconds under
    ``moe/experts`` against, a call, the larger of its touched experts'
    weights (``moe_experts_touched`` x an expert's bytes) over the
    chip's HBM bandwidth and its routed operations (``moe_assignments``
    pairs a layer x gate, up and down) over the bf16 peak — from the
    call's own counts, so that a kernel which reads an untouched
    expert, or a touched one twice, reads lower and none reads past
    100."""
    got = seconds(r, region, "moe/experts")
    if got is None:
        return None
    in_s, mixed_s, events, spans = got
    if not events or in_s <= 0 or any(
            "moe_experts_touched" not in s.stats for s in spans):
        return None
    el = 2 if r.cfg.get("param_dtype") == "bfloat16" else 4
    least = sum(flops_mellum2.experts_roofline_seconds(
        r.cfg, int(s.stats["moe_assignments"]),
        int(s.stats["moe_experts_touched"]), r.peaks, el) for s in spans)
    r.note(note, calls=len(spans), events=events,
           experts_touched_per_call=sum(
               int(s.stats["moe_experts_touched"]) for s in spans)
           / len(spans),
           device_ms_per_call=1e3 * in_s / len(spans),
           mixed_ms_per_call=1e3 * mixed_s / len(spans),
           least_ms_per_call=1e3 * least / len(spans))
    return 100.0 * least / in_s
