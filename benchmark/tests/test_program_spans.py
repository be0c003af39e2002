"""program_spans.py and the eighteen readers of the program's own spans.

Two small recorded traces (``data/regions_gen.xplane.pb``,
``data/regions_train.xplane.pb``): CPU runs of a tiny causal BERT behind
``GenerateBatcher`` (three requests, two lanes, three steps, a clock
that moves 0.25 s a step) and of three calls of a tiny ``TrainStep``,
every program warmed first, recorded by a ``jax.profiler`` session and
cut down to the ``mxtpu:`` events and the events with an ``hlo_op`` stat
(which stand in as one device, as in ``small.xplane.pb``).  The numbers
each reader has to give are worked out by hand below, from the spans as
the trace holds them (ms from the trace's origin):

    gen/prefill/call   0.995733 and 0.773230 long
    gen/sample         0.031639 0.027216 0.022763 0.035694 0.029201
    gen/admit          admitted 2, 0, 1; wait_us_sum 500000, 0, 750000
    gen/step           active 2, 2, 2 of max_lanes 2
    gen/prefill        rows 2, 1 of rung 2, 1; chunks 1, 1
    train/step         2.090249 1.455766 1.372003
    train/prep         1.257209 0.764078 0.753388
    train/dispatch     0.684127 0.569217 0.494418
    train/writeback    0.078214 0.069374 0.072520

Nothing here measures: the times are a CPU's.
"""
import os
import types

import pytest

from benchmark import program_spans, run as run_mod, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def recorded(tag, **facts):
    path = os.path.join(DATA, f"regions_{tag}.xplane.pb")
    trace = trace_reduce.load(path)
    trace.memo[program_spans.MEMO] = program_spans.read_xplane(path)
    return types.SimpleNamespace(trace=trace, facts=facts,
                                 cell={"name": "recorded"},
                                 note=lambda *a, **k: None)


def made(spans, busy=(), extent=None, **facts):
    """A reading of constructed spans ``(name, start, end[, stats])``
    and device-busy intervals."""
    by_line = {"t": [(s[1], s[2] - s[1], s[0], s[3] if len(s) > 3 else {})
                     for s in spans]}
    devices = {"/device:TPU:0": [(f"op.{i}", a, b - a)
                                 for i, (a, b) in enumerate(busy)]} \
        if busy else {}
    ends = [b for _, b in busy] + [s[2] for s in spans]
    trace = trace_reduce.Trace(devices, [], extent or (0.0, max(ends)), {})
    trace.memo[program_spans.MEMO] = program_spans.fold(by_line)
    return types.SimpleNamespace(trace=trace, facts=facts,
                                 cell={"name": "made"},
                                 note=lambda *a, **k: None)


def read(name, reading):
    return run_mod.read_metric(name, reading)


# ----------------------------------------------------------------------
# the recorded traces: from the xplane to the spans
# ----------------------------------------------------------------------
def test_regions_are_read_with_their_counts_and_nesting():
    spans = recorded("gen").trace.memo[program_spans.MEMO]
    assert [s.name for s in spans].count("gen/step") == 3
    assert not [s for s in spans if s.name.endswith("/done")]
    first = spans[0]
    # counts given when the region opened, and those of its closing child
    assert first.name == "gen/step" and first.stats == {
        "step": 1, "max_lanes": 2, "queued": 1, "admitted": 2,
        "active": 2, "emitted": 4, "finished": 0}
    leaves = {s.name for s in spans if s.leaf}
    assert leaves == {"gen/admit", "gen/sample", "gen/fire",
                      "gen/prefill/call/stage", "gen/prefill/call/dispatch",
                      "gen/prefill/call/fetch", "gen/decode/stage",
                      "gen/decode/dispatch", "gen/decode/fetch"}
    assert {s.name for s in spans if not s.leaf} == {
        "gen/step", "gen/prefill", "gen/prefill/call", "gen/decode"}
    fetch = [s for s in spans if s.name == "gen/prefill/call"]
    assert [s.stats["logits_bytes"] for s in fetch] == [1024, 1024]


def test_serve_readers_on_the_recorded_trace():
    r = recorded("gen")
    assert read("prefill_call_ms", r) == pytest.approx(
        (0.995733 + 0.773230) / 2, abs=1e-6)
    assert read("sample_ms", r) == pytest.approx(
        (0.031639 + 0.027216 + 0.022763 + 0.035694 + 0.029201) / 3,
        abs=1e-6)
    assert read("queue_wait_ms", r) == pytest.approx(
        (500000 + 0 + 750000) / (2 + 0 + 1) / 1e3)
    assert read("lane_occupancy_pct", r) == 100.0
    assert read("compiles_in_window.serve", r) == 0
    assert read("prefill_rung_fill_pct", r) == 100.0
    assert read("prefill_chunks_per_group", r) == 1.0


def _naive_idle(trace, intervals):
    """Seconds of ``intervals`` not covered by a device event, by the
    plainest means: a fine walk over the device events."""
    (events,) = trace.devices.values()
    total = 0.0
    for lo, hi in intervals:
        cuts = sorted({lo, hi} | {t for _, s, d in events
                                  for t in (s, s + d) if lo < t < hi})
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            if not any(s <= mid < s + d for _, s, d in events):
                total += b - a
    return total


def test_idle_readers_on_the_recorded_trace():
    r = recorded("gen")
    spans = r.trace.memo[program_spans.MEMO]
    for metric, name in (("decode_fetch_idle_ms", "gen/decode/fetch"),
                         ("decode_stage_idle_ms", "gen/decode/stage")):
        inside = [(s.start, s.start + s.dur) for s in spans
                  if s.name == name]
        assert len(inside) == 3
        assert read(metric, r) == pytest.approx(
            1e3 * _naive_idle(r.trace, inside) / 3, rel=1e-9)
    lo, hi = r.trace.extent
    leaves = [(s.start, s.start + s.dur) for s in spans if s.leaf]
    want = 100.0 * (1.0 - _naive_idle(r.trace, leaves)
                    / _naive_idle(r.trace, [(lo, hi)]))
    assert read("idle_unattributed_pct.serve", r) == pytest.approx(want)
    assert 0.0 < want < 100.0


def test_train_readers_on_the_recorded_trace():
    r = recorded("train")
    steps = (2.090249, 1.455766, 1.372003)
    calls = (0.684127, 0.569217, 0.494418)
    assert read("train_dispatch_ms", r) == pytest.approx(
        sum(calls) / 3, abs=1e-6)
    assert read("train_call_self_ms", r) == pytest.approx(
        (sum(steps) - sum(calls)) / 3, abs=1e-6)
    assert read("compiles_in_window.train", r) == 0
    assert read("train_prep_ms", r) == pytest.approx(
        (1.257209 + 0.764078 + 0.753388) / 3, abs=1e-6)
    assert read("train_writeback_ms", r) == pytest.approx(
        (0.078214 + 0.069374 + 0.072520) / 3, abs=1e-6)
    # halfway between the shortest and the longest call is 1.731 ms:
    # the first call (the longest) is left out, the median of two
    assert read("train_host_work_ms", r) == pytest.approx(
        (1.455766 + 1.372003) / 2, abs=1e-6)
    spans = r.trace.memo[program_spans.MEMO]
    assert [s.stats for s in spans if s.name == "train/dispatch"] == \
        [{"leaves": 98}] * 3
    # the recorded run's compiled text is not kept: no scope to read
    assert read("optimizer_device_share_pct", r) is None
    assert read("optimizer_mixed_share_pct", r) is None


# ----------------------------------------------------------------------
# constructed spans: the arithmetic, by hand
# ----------------------------------------------------------------------
def test_exact_overlap_differs_from_the_middle_of_the_gap():
    """The device is busy 0-1 and 1.5-2.5.  The half second between
    begins in the tail of a decode's fetch, runs through sampling and
    ends in the next decode's staging.  ``idle_gaps`` books all of it
    to the one benchmark span over its middle; the program's regions
    take what each overlaps: fetch 0.2, sample 0.1, stage 0.1, and 0.1
    (20 %) in no region."""
    busy = [(0.0, 1.0), (1.5, 2.5)]
    r = made([("gen/step", 0.0, 1.38, {"max_lanes": 4, "active": 3}),
              ("gen/decode", 0.0, 1.2), ("gen/decode/fetch", 0.9, 1.2),
              ("gen/sample", 1.2, 1.3),
              ("gen/step", 1.39, 2.5, {"max_lanes": 4, "active": 4}),
              ("gen/decode", 1.4, 2.5), ("gen/decode/stage", 1.4, 1.6)],
             busy)
    old = trace_reduce.Trace(
        r.trace.devices, [("batcher_step", 0.0, 2.5), ("decode", 0.0, 1.2),
                          ("decode", 1.4, 1.1)], (0.0, 2.5), {})
    assert trace_reduce.idle_gaps(old) == [["batcher_step", 0.5]]
    assert read("decode_fetch_idle_ms", r) == pytest.approx(1e3 * 0.2 / 2)
    assert read("decode_stage_idle_ms", r) == pytest.approx(1e3 * 0.1 / 2)
    assert read("sample_ms", r) == pytest.approx(1e3 * 0.1 / 2)
    assert read("idle_unattributed_pct.serve", r) == pytest.approx(20.0)
    assert read("lane_occupancy_pct", r) == pytest.approx(87.5)


def test_counts_ride_on_the_closing_child():
    r = made([("gen/step", 0.0, 1.0, {"step": 1}),
              ("gen/admit", 0.1, 0.3),
              ("gen/admit/done", 0.29, 0.3,
               {"admitted": 3, "wait_us_sum": 4500}),
              ("gen/admit", 0.5, 0.6),
              ("gen/admit/done", 0.59, 0.6,
               {"admitted": 1, "wait_us_sum": 1500})])
    spans = r.trace.memo[program_spans.MEMO]
    assert [s.name for s in spans] == ["gen/step", "gen/admit", "gen/admit"]
    assert read("queue_wait_ms", r) == pytest.approx(1.5)
    assert read("compiles_in_window.serve", r) == 0
    r = made([("gen/step", 0.0, 1.0), ("compile", 0.2, 0.4),
              ("compile/done", 0.39, 0.4, {"source": "disk"})])
    assert read("compiles_in_window.serve", r) == 1
    (c,) = program_spans.named(r, "compile")
    assert c.stats == {"source": "disk"}
    # a trace that opens mid-step holds the closing child of a region
    # that opened before it: dropped, not read as a region
    r = made([("gen/decode/done", 0.0, 0.01, {"logits_bytes": 8}),
              ("gen/sample", 0.02, 0.03)])
    assert [s.name for s in r.trace.memo[program_spans.MEMO]] == \
        ["gen/sample"]


def test_train_self_time_is_the_step_minus_its_dispatch():
    r = made([("train/step", 0.0, 0.100), ("train/prep", 0.0, 0.020),
              ("train/dispatch", 0.020, 0.090),
              ("train/writeback", 0.090, 0.100),
              ("train/step", 0.2, 0.260), ("train/dispatch", 0.21, 0.24)])
    assert read("train_dispatch_ms", r) == pytest.approx(50.0)
    assert read("train_call_self_ms", r) == pytest.approx(30.0)
    assert read("train_prep_ms", r) == pytest.approx(20.0)
    assert read("train_writeback_ms", r) == pytest.approx(10.0)
    assert read("compiles_in_window.train", r) == 0


def test_host_work_is_the_median_of_the_calls_that_did_not_block():
    """Three calls run ahead of the device (5, 7, 6 ms), the fourth
    meets the full queue part way (60 ms), the rest block for a device
    step each (130, 134 ms).  Halfway between 5 and 134 is 69.5: the
    fourth call lies under it, which is why the reading is a median."""
    t, spans = 0.0, []
    for ms in (5, 7, 6, 60, 130, 134):
        spans += [("train/step", t, t + ms * 1e-3),
                  ("train/prep", t, t + (ms - 4) * 1e-3)]
        t += 0.2
    r = made(spans)
    assert read("train_host_work_ms", r) == pytest.approx(6.5)
    assert read("train_prep_ms", r) == pytest.approx(
        (1 + 3 + 2 + 56 + 126 + 130) / 6)
    # no call blocks: one bunch, halved
    r = made([("train/step", 0.0, 0.006), ("train/step", 0.1, 0.108),
              ("train/step", 0.2, 0.207)])
    assert read("train_host_work_ms", r) == pytest.approx(6.5)
    assert read("train_host_work_ms", made([("gen/step", 0, 1)])) is None


def test_prefill_groups_give_their_fill_and_their_chunks():
    r = made([("gen/step", 0.0, 1.0),
              ("gen/prefill", 0.1, 0.3,
               {"rows": 3, "rung": 4, "bucket": 64, "chunks": 1}),
              ("gen/prefill", 0.5, 0.9,
               {"rows": 1, "rung": 1, "bucket": 128, "chunks": 3})])
    assert read("prefill_rung_fill_pct", r) == pytest.approx(80.0)
    assert read("prefill_chunks_per_group", r) == pytest.approx(2.0)
    r = made([("gen/step", 0.0, 1.0)])
    assert read("prefill_rung_fill_pct", r) is None
    assert read("prefill_chunks_per_group", r) is None


HLO = '''HloModule jit_step
%f1 (p0: bf16[8]) -> bf16[8] {
  %p0 = bf16[8]{0} parameter(0)
  ROOT %dot.1 = bf16[8]{0} multiply(%p0, %p0), metadata={op_name="jit(step)/jit(main)/train/forward_backward/dot_general"}
}
%f2 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(step)/jit(main)/train/optimizer/mul"}
  ROOT %sub.1 = f32[8]{0} subtract(%mul.1, %p0), metadata={op_name="jit(step)/jit(main)/train/optimizer/sub"}
}
%f3 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %cvt.1 = f32[8]{0} convert(%p0), metadata={op_name="jit(step)/jit(main)/train/forward_backward/transpose(jvp(dense))/convert"}
  ROOT %dus.1 = f32[8]{0} dynamic-update-slice(%p0, %cvt.1), metadata={op_name="jit(step)/jit(main)/train/optimizer/stack/dynamic_update_slice"}
}
%f4 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %neg.1 = f32[8]{0} negate(%p0)
}
ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f1, metadata={op_name="jit(step)/jit(main)/train/forward_backward/dot_general" source_file="a.py" source_line=3}
  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f2, metadata={op_name="jit(step)/jit(main)/train/optimizer/mul" source_file="a.py" source_line=9}
  %bitcast_dynamic-update-slice_fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f3, metadata={op_name="jit(step)/jit(main)/train/optimizer/stack/dynamic_update_slice"}
  %copy.4 = f32[8]{0} copy(%p), metadata={op_name="jit(step)/jit(main)/my_train/optimizer_like/x"}
  %copy.5 = f32[8]{0} copy(%p)
  %copy.6 = f32[8]{0} copy(%p), metadata={op_name="jit(step)/jit(main)/train/optimizer/unstack/copy"}
  ROOT %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f4, metadata={op_name="jit(step)/jit(main)/train/optimizer/neg"}
}
'''


def test_optimizer_share_reads_the_scope_off_the_program_text():
    """A fusion is judged by the instructions of its fused computation:
    the one that writes a gradient into the bucket is named by an
    operation under the scope and holds one outside it, so it is mixed
    and counts for neither side; a fused computation that names no
    operation falls back on the fusion's own name."""
    inside, mixed = program_spans.ops_by_scope(HLO, "train/optimizer")
    assert mixed == {"bitcast_dynamic-update-slice_fusion.3"}
    # the fused computations' own instructions are under the scope too,
    # but no device event carries their names
    assert inside == {"fusion.2", "copy.6", "fusion.7", "mul.1", "sub.1",
                      "dus.1"}
    events = [("fusion.1", 0.0, 0.5), ("fusion.2", 0.5, 0.1),
              ("bitcast_dynamic-update-slice_fusion.3", 0.6, 0.2),
              ("copy.4", 0.8, 0.05), ("copy.5", 0.85, 0.05),
              ("copy.6", 0.9, 0.04), ("fusion.7", 0.94, 0.06)]
    trace = trace_reduce.Trace({"/device:TPU:0": events}, [], (0.0, 1.0),
                               {program_spans.MEMO: []})
    notes = []
    r = types.SimpleNamespace(trace=trace, facts={"hlo_text": HLO},
                              cell={"name": "made"},
                              note=lambda *a, **k: notes.append(k))
    assert read("optimizer_device_share_pct", r) == pytest.approx(20.0)
    assert read("optimizer_mixed_share_pct", r) == pytest.approx(20.0)
    (note,) = notes
    assert note["mixed_s"] == pytest.approx(0.2)
    assert ("bitcast_dynamic-update-slice_fusion", 0.0, 0.2, 0.0) in \
        [tuple(f) for f in note["families_in_mixed_out_s"]]
    # a program with no such scope: nothing to read
    r.trace.memo.clear()
    r.trace.memo[program_spans.MEMO] = []
    r.facts = {"hlo_text": HLO.replace("train/optimizer", "opt")}
    assert read("optimizer_device_share_pct", r) is None
    assert read("optimizer_mixed_share_pct", r) is None


NEW = ("decode_fetch_idle_ms", "decode_stage_idle_ms", "prefill_call_ms",
       "sample_ms", "queue_wait_ms", "lane_occupancy_pct",
       "idle_unattributed_pct.serve", "compiles_in_window.serve",
       "train_dispatch_ms", "train_call_self_ms",
       "compiles_in_window.train", "optimizer_device_share_pct",
       "optimizer_mixed_share_pct", "train_prep_ms", "train_writeback_ms",
       "train_host_work_ms", "prefill_rung_fill_pct",
       "prefill_chunks_per_group")
SERVE = set(NEW[:8]) | set(NEW[-2:])
TRAIN = set(NEW) - SERVE


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_regions_gives_none(name):
    """The parent of the PR that added the regions writes none: every
    reader returns None and raises nothing, device events or not."""
    trace = trace_reduce.load(os.path.join(DATA, "small.xplane.pb"))
    trace.memo[program_spans.MEMO] = []
    r = types.SimpleNamespace(trace=trace, facts={"hlo_text": "ENTRY {}"},
                              cell={"name": "parent"},
                              note=lambda *a, **k: None)
    assert read(name, r) is None


def test_spans_of_finds_the_runs_xplane_by_the_cells_name(tmp_path,
                                                          monkeypatch):
    import shutil
    cell = tmp_path / ".trace" / "some-cell" / "plugins" / "profile" / "t"
    cell.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, "regions_train.xplane.pb"),
                cell / "host.xplane.pb")
    monkeypatch.setattr(program_spans, "HERE", str(tmp_path))
    trace = trace_reduce.Trace({}, [], (0.0, 0.0), {})
    r = types.SimpleNamespace(trace=trace, cell={"name": "some-cell"})
    assert len(program_spans.named(r, "train/step")) == 3
    assert program_spans.MEMO in trace.memo
    r = types.SimpleNamespace(trace=trace_reduce.Trace({}, [], (0, 0), {}),
                              cell={"name": "no-such-cell"})
    assert program_spans.spans_of(r) == []


def test_every_new_metric_is_declared_with_a_reader():
    from benchmark import harness
    bench = harness.load_json(os.path.join(run_mod.ROOT, "BENCHMARK.json"))
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert name in declared, name
        assert "workloads" not in declared[name]
        assert os.path.exists(os.path.join(run_mod.HERE, "metrics",
                                           name + ".py"))
    serve = {m["name"] for m in run_mod.metrics_of(
        bench, "per_layer", "bertgen-large-fusion-backlog",
        {"serve_tokens_per_s", "setup_s"})}
    train = {m["name"] for m in run_mod.metrics_of(
        bench, "per_layer", "bert-large-mlm-s512",
        {"train_tokens_per_s", "setup_s"})}
    assert SERVE <= serve and not TRAIN & serve
    assert TRAIN <= train and not SERVE & train


# ----------------------------------------------------------------------
# a whole traced run at a tiny size: the regions the program writes now
# ----------------------------------------------------------------------
def _traced_run(cell_name, cfg, mix, tmp_path, monkeypatch):
    """As ``run.py --trace 1`` lays it out: the xplane under
    ``<benchmark>/.trace/<cell>``, here with a temp dir for the
    benchmark's."""
    import test_correct
    monkeypatch.setattr(program_spans, "HERE", str(tmp_path))
    return test_correct.drive(
        cell_name, cfg, mix, trace=1,
        tmp_path=tmp_path / ".trace" / cell_name)


def test_a_traced_train_run_reports_the_new_metrics(tmp_path, monkeypatch):
    from conftest import TINY, TINY_TRAIN_MIX
    res = _traced_run("bert-large-mlm-s512", TINY, TINY_TRAIN_MIX,
                      tmp_path, monkeypatch)
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert res["correct"] is True
    assert TRAIN <= set(got)
    assert got["compiles_in_window.train"] == 0
    assert 0 < got["train_dispatch_ms"]
    # the two parts make up the call the benchmark's own clock times
    assert got["train_dispatch_ms"] + got["train_call_self_ms"] == \
        pytest.approx(got["train_host_call_ms"], rel=0.2)
    assert 0 < got["optimizer_device_share_pct"] < 100
    assert 0 <= got["optimizer_mixed_share_pct"] < 100
    assert got["optimizer_device_share_pct"] + \
        got["optimizer_mixed_share_pct"] <= 100
    assert got["train_prep_ms"] + got["train_dispatch_ms"] + \
        got["train_writeback_ms"] <= got["train_dispatch_ms"] + \
        got["train_call_self_ms"] + 1e-9
    assert 0 < got["train_host_work_ms"] <= got["train_dispatch_ms"] + \
        got["train_call_self_ms"]


def test_a_traced_serve_run_reports_the_new_metrics(tmp_path, monkeypatch):
    import test_correct
    from conftest import TINY_GEN
    res = _traced_run("bertgen-large-fusion-backlog", TINY_GEN,
                      test_correct.TINY_GEN_MIX, tmp_path, monkeypatch)
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert res["correct"] is True
    assert SERVE <= set(got)
    assert got["compiles_in_window.serve"] == 0
    assert 0 < got["lane_occupancy_pct"] <= 100
    assert 0 <= got["idle_unattributed_pct.serve"] < 100
    assert got["queue_wait_ms"] > 0 and got["prefill_call_ms"] > 0
    assert 0 < got["prefill_rung_fill_pct"] <= 100
    assert got["prefill_chunks_per_group"] >= 1
