"""gc_pause_ms (ms): the pauses Python's garbage collector made on the
serving thread, mean per ``gen/step`` (``gc_us``, from the one
``gc.callbacks`` hook of ``mxtpu.obs``)."""
from benchmark import idle_leaves


def read(r):
    return idle_leaves.gc_pause_ms(r)
