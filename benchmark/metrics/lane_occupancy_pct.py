"""lane_occupancy_pct (%): lanes decoding / lanes the batcher may use,
mean over the program's ``gen/step`` regions (their ``active`` and
``max_lanes`` counts)."""
from benchmark import program_spans


def read(r):
    steps = [s for s in program_spans.named(r, "gen/step")
             if s.stats.get("max_lanes")]
    if not steps:
        return None
    return 100.0 * sum(s.stats.get("active", 0) / s.stats["max_lanes"]
                       for s in steps) / len(steps)
