"""Tally of a kernel's call sites while a program is traced: what a
compiled program holds of the kernel, read where it is lowered."""
from __future__ import annotations

import contextlib
import threading


class CallSites:
    """One kernel's tally.  ``with sites() as tally`` counts the call
    sites traced inside the block on this thread: ``tally`` is a
    one-element list whose entry is the count so far.  The kernel's
    entry point calls ``note()`` once a call."""

    def __init__(self):
        self._open = threading.local()

    def _tallies(self):
        return self._open.__dict__.setdefault("tallies", [])

    @contextlib.contextmanager
    def __call__(self):
        tally = [0]
        self._tallies().append(tally)
        try:
            yield tally
        finally:
            # by identity: two open tallies may hold equal counts
            open_ = self._tallies()
            del open_[next(i for i, t in enumerate(open_) if t is tally)]

    def note(self):
        for tally in self._tallies():
            tally[0] += 1
