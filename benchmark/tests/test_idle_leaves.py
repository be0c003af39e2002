"""idle_leaves.py and the five readers of the serve loop's leaves (ISSUE 36).

Constructed regions and device events, as ``test_program_spans.py``
builds them (the step's closing counts given as its own): two steps of
a serve loop, one with a prefill group of two chunks, each with a
decode call, and the thread's work between the steps.  The device runs the prefill
programs and the decode program; everything else is host time.  Times
in seconds; the idle time each reader has to give is worked out by hand
beside the events.
"""
import pytest

from benchmark import idle_leaves, program_spans
from benchmark.tests.test_program_spans import made, read

FIVE = ("decode_dispatch_idle_ms", "prefill_host_idle_ms",
        "batcher_host_idle_ms", "batcher_offcpu_ms", "gc_pause_ms")

# step 1: 0.00 - 1.00                     step 2: 1.10 - 1.60
LOOP = [
    ("gen/between", -0.05, 0.0),
    ("gen/step", 0.0, 1.0, {"step": 1, "cpu_us": 300000, "gc_us": 20000,
                            "gc_n": 2}),
    ("gen/admit", 0.0, 0.05),
    ("gen/prefill", 0.05, 0.55),
    ("gen/prefill/rows", 0.05, 0.07),
    ("gen/prefill/call", 0.07, 0.27),
    ("gen/prefill/call/stage", 0.07, 0.08),
    ("gen/prefill/call/dispatch", 0.08, 0.10),
    ("gen/prefill/call/fetch", 0.10, 0.27),
    ("gen/prefill/rows", 0.27, 0.29),
    ("gen/prefill/call", 0.29, 0.49),
    ("gen/prefill/call/stage", 0.29, 0.30),
    ("gen/prefill/call/dispatch", 0.30, 0.32),
    ("gen/prefill/call/fetch", 0.32, 0.49),
    ("gen/sample", 0.49, 0.52),
    ("gen/commit", 0.52, 0.55),
    ("gen/decode_rows", 0.55, 0.57),
    ("gen/decode", 0.57, 0.87),
    ("gen/decode/stage", 0.57, 0.59),
    ("gen/decode/dispatch", 0.59, 0.62),
    ("gen/decode/fetch", 0.62, 0.87),
    ("gen/sample", 0.87, 0.90),
    ("gen/commit", 0.90, 0.92),
    ("gen/fire", 0.92, 0.96),
    ("gen/complete", 0.96, 1.00),
    ("gen/between", 1.0, 1.10),
    ("gen/step", 1.10, 1.60, {"step": 2, "cpu_us": 100000, "gc_us": 0,
                              "gc_n": 0}),
    ("gen/admit", 1.10, 1.12),
    ("gen/decode_rows", 1.12, 1.15),
    ("gen/decode", 1.15, 1.50),
    ("gen/decode/stage", 1.15, 1.17),
    ("gen/decode/dispatch", 1.17, 1.20),
    ("gen/decode/fetch", 1.20, 1.50),
    ("gen/sample", 1.50, 1.53),
    ("gen/commit", 1.53, 1.55),
    ("gen/fire", 1.55, 1.60),
]
# the prefill programs run from each dispatch to 0.03 s before its
# fetch ends; the decode programs likewise, but the first decode's
# program starts late, 0.01 s into its dispatch's 0.03
BUSY = [(0.09, 0.24), (0.31, 0.46), (0.60, 0.83), (1.18, 1.40)]
EXTENT = (-0.05, 1.60)


def loop(spans=LOOP, busy=BUSY):
    return made(spans, busy=busy, extent=EXTENT)


def test_each_leaf_is_read_by_one_owner():
    r = loop()
    owner = idle_leaves.owner_of(r)
    by = {}
    for s in program_spans.spans_of(r):
        if s.leaf:
            by.setdefault(owner(s), set()).add(s.name)
    assert by == {
        "gen/prefill": {"gen/prefill/rows", "gen/prefill/call/stage",
                        "gen/prefill/call/dispatch",
                        "gen/prefill/call/fetch", "gen/sample",
                        "gen/commit"},
        "gen/decode": {"gen/decode/stage", "gen/decode/dispatch",
                       "gen/decode/fetch"},
        None: {"gen/between", "gen/admit", "gen/decode_rows",
               "gen/sample", "gen/commit", "gen/fire", "gen/complete"}}


def test_the_five_readers():
    r = loop()
    # dispatch: 0.59-0.60 idle (0.01), 1.17-1.18 (0.01); two decodes
    assert read("decode_dispatch_idle_ms", r) == pytest.approx(10.0)
    # the fetch, as before ISSUE 36: 0.83-0.87 (0.04), 1.40-1.50 (0.10)
    assert read("decode_fetch_idle_ms", r) == pytest.approx(70.0)
    # under gen/prefill, all host time but the programs' 0.09-0.24 and
    # 0.31-0.46: 0.50 - 0.30 = 0.20 s over two calls
    assert read("prefill_host_idle_ms", r) == pytest.approx(100.0)
    # the rest: between 0.05 + 0.10, step 1's admit 0.05, rows 0.02,
    # sample 0.03, commit 0.02, fire 0.04, complete 0.04; step 2's
    # 0.02 + 0.03 + 0.03 + 0.02 + 0.05: 0.50 s over two steps
    assert read("batcher_host_idle_ms", r) == pytest.approx(250.0)
    # step 1: 1.00 - 0.30 cpu - (0.17 + 0.17 + 0.25) fetches = 0.11;
    # step 2: 0.50 - 0.10 - 0.30 = 0.10
    assert read("batcher_offcpu_ms", r) == pytest.approx(105.0)
    assert read("gc_pause_ms", r) == pytest.approx(10.0)


def test_the_leaves_and_the_unattributed_share_make_the_idle_time():
    """Decode steps x (stage + dispatch + fetch) + prefill calls x
    prefill's host time + steps x the batcher's own + the share in no
    leaf = the window's device idle time.  The leaves abut here, so the
    identity is exact; a gap between two leaves is the unattributed
    share's."""
    for spans in (LOOP, [s for s in LOOP if s[0] != "gen/complete"]):
        r = loop(spans)
        idle = (EXTENT[1] - EXTENT[0]) - sum(b - a for a, b in BUSY)
        decodes = len(program_spans.named(r, "gen/decode"))
        calls = len(program_spans.named(r, "gen/prefill/call"))
        steps = len(program_spans.named(r, "gen/step"))
        decode = sum(read(m, r) for m in (
            "decode_stage_idle_ms", "decode_dispatch_idle_ms",
            "decode_fetch_idle_ms"))
        named = 1e-3 * (decodes * decode
                        + calls * read("prefill_host_idle_ms", r)
                        + steps * read("batcher_host_idle_ms", r))
        left = idle * read("idle_unattributed_pct.serve", r) / 100.0
        assert named + left == pytest.approx(idle, abs=1e-9)
    # with gen/complete gone its 0.04 s is left unattributed
    assert left == pytest.approx(0.04)


def test_what_a_program_without_the_leaves_gives():
    """The parent of ISSUE 36: no rows, commit, complete or between
    leaves, no CPU or collector counts.  The readers of leaves read the
    leaves it writes, which are fewer (so its first reading is not the
    change's quantity); the two that need the step's new counts give
    None; and with no region at all (the train cell) every one gives
    None."""
    old = [s[:3] + ({"step": s[3]["step"]},) if s[0] == "gen/step" else s
           for s in LOOP
           if not s[0].endswith("rows")
           and s[0] not in ("gen/commit", "gen/complete", "gen/between")]
    r = loop(old)
    assert read("decode_dispatch_idle_ms", r) == pytest.approx(10.0)
    # prefill's leaves: stage, dispatch, fetch and the first tokens'
    # sample: 0.01+0.01+0.03 + 0.01+0.01+0.03 + 0.03 = 0.13 over two
    assert read("prefill_host_idle_ms", r) == pytest.approx(65.0)
    # admit, sample, fire: 0.05+0.03+0.04 + 0.02+0.03+0.05 over two
    assert read("batcher_host_idle_ms", r) == pytest.approx(110.0)
    assert read("batcher_offcpu_ms", r) is None
    assert read("gc_pause_ms", r) is None
    train = made([("train/step", 0.0, 0.1), ("train/dispatch", 0.0, 0.09)],
                 busy=[(0.0, 0.08)])
    for name in FIVE:
        assert read(name, train) is None, name
    nothing = made([], busy=[(0.0, 0.08)])
    for name in FIVE:
        assert read(name, nothing) is None, name


def test_a_group_that_began_before_the_trace_is_still_prefill():
    """The trace opens in the middle of a prefill group of many chunks:
    its ``gen/prefill`` was never recorded, but its calls' children and
    its rows say by their names whose they are."""
    r = made([("gen/prefill/rows", 0.0, 0.1),
              ("gen/prefill/call", 0.1, 0.5),
              ("gen/prefill/call/stage", 0.1, 0.2),
              ("gen/prefill/call/dispatch", 0.2, 0.3),
              ("gen/prefill/call/fetch", 0.3, 0.5),
                                  ("gen/sample", 0.5, 0.6), ("gen/commit", 0.6, 0.7),
              ("gen/step", 0.8, 1.0, {"step": 9}),
              ("gen/admit", 0.8, 1.0)],
             busy=[(0.25, 0.4)], extent=(0.0, 1.0))
    # rows 0.1 + stage 0.1 + dispatch 0.05 + fetch 0.1
    assert read("prefill_host_idle_ms", r) == pytest.approx(350.0)
    # the group's sample and commit are the loop's, and the admit
    assert read("batcher_host_idle_ms", r) == pytest.approx(400.0)


def test_a_window_that_cuts_a_leaf_counts_what_it_holds():
    """A step that began before the trace opened: its leaves are cut to
    the window, as the unattributed share cuts them."""
    r = made([("gen/step", 0.0, 1.0, {"step": 1}),
              ("gen/fire", 0.0, 0.4), ("gen/complete", 0.4, 1.0)],
             busy=[(0.5, 0.6)], extent=(0.2, 1.0))
    assert read("batcher_host_idle_ms", r) == pytest.approx(700.0)
    assert read("idle_unattributed_pct.serve", r) == pytest.approx(0.0)
