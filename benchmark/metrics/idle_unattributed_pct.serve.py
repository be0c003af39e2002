"""idle_unattributed_pct.serve (%): share of the traced window's
device-idle time that lies in no leaf region of the program (exact
overlap).  What is left here has no name: a boundary is missing."""
from benchmark import program_spans


def read(r):
    return program_spans.unattributed_idle_pct(r)
