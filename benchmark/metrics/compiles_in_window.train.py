"""compiles_in_window.train (count): the program's ``compile`` regions
(an executable loaded or compiled) inside the traced window.  Set-up
builds the one step the window calls, so this reads 0."""
from benchmark import program_spans


def read(r):
    return program_spans.compiles_in_window(r)
