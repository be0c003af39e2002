"""serve_step_mfu (%): the serving programs' share of the chip's peak.
Forward operations the window's tokens needed (every prompt token
prefilled and every token decoded in it, against the context each had;
flops.py) / the window / the chip's bf16 peak (float32 operands at the
default matmul precision take one bf16 pass)."""


def read(r):
    f = r.facts
    if not f.get("useful_flops"):
        return None
    peak = r.peaks["bf16_flops_per_s"] * r.cell["chips"]
    return 100.0 * f["useful_flops"] / f["window_s"] / peak
