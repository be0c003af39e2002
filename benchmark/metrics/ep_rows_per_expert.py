"""ep_rows_per_expert (count): rows a touched held expert takes in a
decode step — the held assignments (``moe_assignments_held``) over the
held experts given a token (``moe_experts_touched``), both counted on
the device and summed over the routed layers, summed over the window's
``gen/decode`` regions.  A touched expert's weights are read whole for
its rows, so this is how many rows share one read: what the
deployment's eight devices' lanes would multiply (PERF.md sec. 4)."""
from benchmark import program_spans


def read(r):
    spans = [s for s in program_spans.named(r, "gen/decode")
             if "moe_assignments_held" in s.stats]
    touched = sum(int(s.stats.get("moe_experts_touched", 0)) for s in spans)
    if not spans or touched <= 0:
        return None
    return sum(int(s.stats["moe_assignments_held"]) for s in spans) / touched
