"""train_step_mfu (%): the whole train step's share of the chip's peak.
Forward + backward operations per token from shapes (flops.py; optimizer
and recomputation not counted) x tokens per second of the traced window
/ the chip's bf16 peak."""
from benchmark import flops


def read(r):
    f = r.facts
    if not f.get("steps"):
        return None
    tokens_per_s = f["steps"] * f["tokens_per_step"] / f["window_s"]
    per_token = flops.train_flops_per_token(r.cfg, int(r.mix["seq"]))
    peak = r.peaks["bf16_flops_per_s"] * r.cell["chips"]
    return 100.0 * per_token * tokens_per_s / peak
