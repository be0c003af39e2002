"""Python's garbage-collector pauses, by thread and by generation (ISSUE 36).

One ``gc.callbacks`` hook, installed once by :mod:`mxtpu.obs` while
observability is on.  A collection runs on the thread whose bytecode
reached the collector's turn, so each thread's pauses are its own:
:func:`thread_pauses` hands the calling thread its running tally
(what ``gen/step`` counts as ``gc_us`` / ``gc_n``), and the process's
pauses by generation are the operator's counter
``mxtpu_gc_pause_seconds_total{generation}``.

The hook runs inside the collection, at whatever bytecode boundary the
thread had reached — inside another instrument's locked section too —
so it takes no lock: it adds to a thread-local tally and to one sum a
generation.  The registry turns the sums into the counter when it is
exported (:func:`export`, a collector of the process registry), outside
any collection.
"""
from __future__ import annotations

import gc
import threading
import time
from typing import Tuple

COUNTER = "mxtpu_gc_pause_seconds_total"
HELP = ("Time Python's garbage collector held the interpreter, by the "
        "generation it collected.")

_local = threading.local()
_by_gen = [0, 0, 0]        # ns paused, process-wide, per generation
_exported = [0, 0, 0]      # of those, ns already added to the counter
_installed = False
_export_lock = threading.Lock()   # never taken by the hook


def _tally():
    t = getattr(_local, "tally", None)
    if t is None:
        t = _local.tally = [0, 0, 0]    # [start ns, paused ns, collections]
    return t


def _hook(phase, info) -> None:
    t = _tally()
    if phase == "start":
        t[0] = time.perf_counter_ns()
        return
    if not t[0]:
        return      # the hook was installed during this collection
    dt = time.perf_counter_ns() - t[0]
    t[0] = 0
    t[1] += dt
    t[2] += 1
    _by_gen[info["generation"]] += dt


def install() -> None:
    """Add the hook to ``gc.callbacks`` (once per process)."""
    global _installed
    if not _installed:
        _installed = True
        gc.callbacks.append(_hook)


def thread_pauses() -> Tuple[int, int]:
    """``(ns paused, collections)`` of the calling thread since the
    hook was installed."""
    t = _tally()
    return t[1], t[2]


def export(registry) -> None:
    """Bring ``mxtpu_gc_pause_seconds_total`` up to the pauses so far."""
    with _export_lock:
        new = []
        for g in range(3):
            done = _by_gen[g]
            new.append(done - _exported[g])
            _exported[g] = done
    c = registry.counter(COUNTER, HELP, labels=("generation",))
    for g, ns in enumerate(new):
        c.labels(generation=str(g)).inc(ns * 1e-9)
