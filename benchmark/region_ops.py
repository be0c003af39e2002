"""Device operations that ran inside the program's own regions.

An instruction's name (``fusion.12``) repeats from program to program,
so a reader that wants ONE program's operations takes the device events
that lie inside that program's regions (``gen/decode``: the call waits
for its logits, so the decode program runs inside it and no other does)
and only then looks at their names.
"""
import bisect
import re

from . import program_spans, trace_reduce

_METADATA = re.compile(r", metadata=\{[^}]*\}")


def _constants_unnamed(hlo_text):
    """The program's text with the ``op_name`` taken off its constants:
    the compiler shares one constant among scopes, under the name of
    whichever wrote it first, and a constant does no work."""
    return "\n".join(_METADATA.sub("", line) if " constant(" in line
                     else line for line in hlo_text.splitlines())


def inside_regions(r, region):
    """``(events, busy_s, spans)``: the first device's events
    ``(name, start, dur)`` that start inside a region called ``region``,
    the device-busy seconds inside those regions, and the regions; None
    where the run has no such region or no device event."""
    spans = program_spans.named(r, region)
    if not spans or not r.trace.devices:
        return None
    key = ("inside_regions", region)
    if key not in r.trace.memo:
        cover = trace_reduce.merged((s.start, s.start + s.dur) for s in spans)
        starts = [lo for lo, _ in cover]
        events = []
        for ev in r.trace.devices[sorted(r.trace.devices)[0]]:
            i = bisect.bisect_right(starts, ev[1]) - 1
            if i >= 0 and ev[1] < cover[i][1]:
                events.append(ev)
        busy = trace_reduce.busy_cover(r.trace)
        r.trace.memo[key] = (events, sum(busy.within(lo, hi)
                                         for lo, hi in cover), spans)
    return r.trace.memo[key]


def scope_seconds(r, region, scope):
    """Device seconds of the operations of ``facts["hlo_text"]`` that
    lie wholly, and partly, under the named scope ``scope``, among the
    events inside ``region``: ``(inside_s, mixed_s, events, busy_s,
    spans)``, or None where there is nothing to read."""
    text = r.facts.get("hlo_text")
    got = inside_regions(r, region)
    if not text or got is None:
        return None
    events, busy_s, spans = got
    key = ("region_ops.ops_by_scope", scope)
    if key not in r.trace.memo:
        r.trace.memo[key] = program_spans.ops_by_scope(
            _constants_unnamed(text), scope)
    inside, mixed = r.trace.memo[key]
    in_s = [d for n, _, d in events if n in inside]
    mixed_s = sum(d for n, _, d in events if n in mixed)
    return sum(in_s), mixed_s, len(in_s), busy_s, spans
