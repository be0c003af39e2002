"""Per-request tracing (ISSUE 8 tentpole b).

A trace id is minted at ``FleetRouter.submit`` (and
``InferenceServer.submit``) and rides the request object through
worker dispatch, batcher queue/assembly and runner execution.  Every
phase of the request's life — queue-wait, pad/scatter, execute,
retry/backoff, hedge, steal/requeue — is emitted as a chrome-trace
span through the existing :mod:`mxtpu.profiler` with
``args={"trace_id": ...}`` (batch-level spans carry
``args={"trace_ids": [...]}``), so one request's full story — a
mid-flight worker kill included — is reconstructible from a single
``profiler.dumps()``; :func:`trace_of` does the reconstruction
in-process.

Emission is gated on ``profiler.is_active()`` BEFORE any args dict is
built, so the profiler-off request path pays one global-bool read.

:func:`region` is the ONE writer of the program's own layer-boundary
spans (ISSUE 25): a ``jax.profiler.TraceAnnotation`` named
``mxtpu:<name>``, so the span lies in the profiler's own trace on the
device events' clock whenever a ``jax.profiler`` session runs, plus
the same interval as a chrome-trace event when ``mxtpu.profiler`` is.
"""
from __future__ import annotations

import itertools
import os
import threading
from typing import Any, Dict, List, Optional, Sequence

from jax.profiler import TraceAnnotation

from .. import profiler

__all__ = ["new_trace_id", "span", "region", "region_writer",
           "trace_of",
           "NULL_REGION", "REGION_PREFIX",
           "SPAN_SUBMIT", "SPAN_QUEUE_WAIT", "SPAN_EXECUTE",
           "SPAN_BACKOFF", "SPAN_STEAL", "SPAN_REDISPATCH",
           "SPAN_HEDGE", "SPAN_PAD_SCATTER", "SPAN_RUN",
           "SPAN_REQUEUE", "SPAN_SHED", "SPAN_SCALE",
           "SPAN_PREFILL", "SPAN_TOKEN", "SPAN_REPLAY",
           "SPAN_GEN_STEP", "SPAN_GEN_ADMIT", "SPAN_PREFILL_CALL",
           "SPAN_DECODE", "SPAN_SAMPLE", "SPAN_FIRE", "SPAN_COMPILE",
           "SPAN_TRAIN_STEP", "SPAN_TRAIN_PREP", "SPAN_TRAIN_DISPATCH",
           "SPAN_TRAIN_WRITEBACK", "SPAN_STAGE", "SPAN_DISPATCH",
           "SPAN_FETCH", "SPAN_DONE", "SPAN_DECODE_ROWS",
           "SPAN_PREFILL_ROWS", "SPAN_COMMIT", "SPAN_COMPLETE",
           "SPAN_BETWEEN", "recording"]

# Request-phase span names (the committed vocabulary; tests and the
# README's reconstruction example key off these).
SPAN_SUBMIT = "fleet/submit"
SPAN_QUEUE_WAIT = "fleet/queue_wait"
SPAN_EXECUTE = "fleet/execute"
SPAN_BACKOFF = "fleet/backoff"
SPAN_STEAL = "fleet/steal"
SPAN_REDISPATCH = "fleet/redispatch"
SPAN_HEDGE = "fleet/hedge"
SPAN_PAD_SCATTER = "serving/pad_scatter"
SPAN_RUN = "serving/execute"
SPAN_REQUEUE = "serving/requeue"
# control-plane verdicts (ISSUE 11): instant spans, cat="fleet" —
# every shed and scale decision is reconstructable from one dump
SPAN_SHED = "fleet/shed"
SPAN_SCALE = "fleet/scale"
# generation phases (ISSUE 19): prefill (prompt → KV cache + first
# token), one instant span per emitted token, and the replay marker a
# stolen generation leaves when it resumes on a surviving worker —
# trace_of() reconstructs a kill-spanning stream from these
SPAN_PREFILL = "gen/prefill"
SPAN_TOKEN = "gen/token"
SPAN_REPLAY = "gen/replay"
# layer boundaries of the generate and train paths (ISSUE 25), written
# by region(): nesting on a thread is the parent relation.  The three
# suffixes name the children of one runner call (the device_puts, the
# executable call, the logits coming back); SPAN_DONE names the short
# closing child that carries the counts known only when the work is
# done.  SPAN_PREFILL is the whole prefill of one admitted group.
SPAN_GEN_STEP = "gen/step"
SPAN_GEN_ADMIT = "gen/admit"
SPAN_PREFILL_CALL = "gen/prefill/call"
SPAN_DECODE = "gen/decode"
SPAN_SAMPLE = "gen/sample"
SPAN_FIRE = "gen/fire"
SPAN_COMPILE = "compile"
SPAN_TRAIN_STEP = "train/step"
SPAN_TRAIN_PREP = "train/prep"
SPAN_TRAIN_DISPATCH = "train/dispatch"
SPAN_TRAIN_WRITEBACK = "train/writeback"
SPAN_STAGE = "/stage"
SPAN_DISPATCH = "/dispatch"
SPAN_FETCH = "/fetch"
SPAN_DONE = "/done"
# the serve loop's host work between those, one leaf each (ISSUE 36):
# the decode's host rows, each prefill chunk's, the lane-table commits
# under the batcher's lock, the futures' completions, and the serving
# thread's work between two steps
SPAN_DECODE_ROWS = "gen/decode_rows"
SPAN_PREFILL_ROWS = "gen/prefill/rows"
SPAN_COMMIT = "gen/commit"
SPAN_COMPLETE = "gen/complete"
SPAN_BETWEEN = "gen/between"
# what every region's TraceAnnotation is named by in the xplane
REGION_PREFIX = "mxtpu:"

_SEQ = itertools.count(1)
_SEQ_LOCK = threading.Lock()


def new_trace_id() -> str:
    """Process-unique, monotonically ordered id (``r<pid>-<seq>``).
    Deterministic modulo pid — fake-clock tests get stable ids."""
    with _SEQ_LOCK:
        seq = next(_SEQ)
    return f"r{os.getpid():x}-{seq:06d}"


def span(name: str, ts_us: float, dur_us: float,
         trace_id: Optional[str] = None, cat: str = "request",
         **args: Any) -> None:
    """Emit one request-phase span (chrome-trace "X" event) tagged
    with its trace id.  No-op unless the profiler is running — call
    sites may still pre-gate on :func:`mxtpu.profiler.is_active` to
    skip computing ``ts``/``dur``."""
    if not profiler.is_active():
        return
    a: Dict[str, Any] = dict(args)
    if trace_id is not None:
        a["trace_id"] = trace_id
    profiler.record_span(name, ts_us, max(0.0, dur_us), cat=cat,
                         args=a)


class _NullRegion:
    """What an owner with observability off enters instead of a
    region: shared, no allocation, no event."""

    __slots__ = ()

    def set(self, **counts: Any) -> None:
        pass

    def __enter__(self) -> "_NullRegion":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False


NULL_REGION = _NullRegion()


class _Region(TraceAnnotation):
    """One open region: the TraceAnnotation itself (so entering and
    leaving it with no session is the C++ TraceMe's inactive check and
    little else), plus the chrome-trace event and the late counts."""

    __slots__ = ("name", "trace_id", "counts", "_t0_us", "_late")

    def __init__(self, name: str, trace_id: Optional[str],
                 counts: Dict[str, Any]):
        # TraceMe formats its keyword arguments only while a session
        # is active (measured: CHANGES.md, PR 25)
        if trace_id is None:
            super().__init__(REGION_PREFIX + name, **counts)
        else:
            super().__init__(REGION_PREFIX + name, trace_id=trace_id,
                             **counts)
        self.name = name
        self.trace_id = trace_id
        self.counts = counts
        self._late: Optional[Dict[str, Any]] = None
        self._t0_us = profiler._now_us() if profiler.is_active() \
            else None

    def set(self, **counts: Any) -> None:
        """Counts known only when the work is done: written as the
        arguments of one short closing child (``<name>/done``), since
        a TraceAnnotation takes its arguments when it opens."""
        if self._late is None:
            self._late = counts
        else:
            self._late.update(counts)

    def __exit__(self, *exc: Any) -> None:
        if self._late:
            with TraceAnnotation(
                    REGION_PREFIX + self.name + SPAN_DONE,
                    **self._late):
                pass
        super().__exit__(*exc)
        if self._t0_us is not None:
            span(self.name, self._t0_us,
                 profiler._now_us() - self._t0_us,
                 trace_id=self.trace_id,
                 cat=self.name.split("/", 1)[0],
                 **{**self.counts, **(self._late or {})})


def region(name: str, trace_id: Optional[str] = None,
           **counts: Any) -> _Region:
    """One layer-boundary span of the program, as a context manager.

    Opens a ``jax.profiler.TraceAnnotation("mxtpu:" + name,
    **counts)``: inside a ``jax.profiler`` session the span lands in
    the profiler's own trace, on the device events' clock, with its
    counts as the event's stats; with no session it costs a TraceMe's
    inactive check.  While ``mxtpu.profiler`` runs, the same interval
    is also recorded as the chrome-trace event :func:`span` writes
    (timestamps from ``profiler._now_us()``, never a scheduling clock
    a test may fake), so :func:`trace_of` rebuilds timelines from the
    same call site.  Nesting on a thread is the parent relation; spans
    of one request share ``trace_id``, and a region that works for
    several requests at once (a prefill group) carries the list of
    theirs as ``trace_ids``, which :func:`trace_of` matches too.
    ``region.set(**counts)`` adds the counts known only at the end.

    It reads no knob: an owner asks :func:`region_writer` once, at
    construction, as it asks for its instruments."""
    return _Region(name, trace_id, counts)


def recording() -> bool:
    """Is a trace being written (a ``jax.profiler`` session, or
    ``mxtpu.profiler``)?  What an owner asks once per step before it
    reads a clock only a region's counts want."""
    return TraceAnnotation.is_enabled() or profiler.is_active()


def _null_region(name: str, trace_id: Optional[str] = None,
                 **counts: Any) -> _NullRegion:
    return NULL_REGION


def region_writer(on: bool):
    """:func:`region` if ``on`` (the owner's cached
    ``obs.enabled()``), else a function of the same signature that
    hands back the shared :data:`NULL_REGION`: with ``MXTPU_OBS=0`` a
    boundary costs one call and no allocation."""
    return region if on else _null_region


def _matches(ev: Dict[str, Any], trace_id: str) -> bool:
    args = ev.get("args")
    if not args:
        return False
    if args.get("trace_id") == trace_id:
        return True
    ids: Sequence[str] = args.get("trace_ids") or ()
    return trace_id in ids


def trace_of(trace_id: str,
             events: Optional[List[Dict[str, Any]]] = None
             ) -> List[Dict[str, Any]]:
    """Timeline of one request: every recorded span whose args carry
    its trace id (directly or in a batch-level ``trace_ids`` list),
    sorted by start timestamp.  Reads the live profiler buffer by
    default; pass ``events`` (e.g. ``json.loads(dump)["traceEvents"]``)
    to reconstruct from a saved trace file instead."""
    if events is None:
        events = profiler.events()
    picked = [ev for ev in events if _matches(ev, trace_id)]
    picked.sort(key=lambda ev: (ev.get("ts", 0.0),
                                ev.get("name", "")))
    return picked
