"""``obs.region`` — the program's own layer-boundary spans (ISSUE 25).

A real ``jax.profiler`` session into a temp dir, a tiny causal BERT
behind ``GenerateBatcher`` and a tiny ``TrainStep``: every region of
the generate and train paths must land in the xplane, on the device
events' clock, with its counts and its nesting; the chrome-trace view
(``trace_of``) must keep working from the same call sites; with no
session, or ``MXTPU_OBS=0``, nothing is written and nothing computed
changes; and the names the device side carries (kernel ``name=``,
``jax.named_scope`` round the step phases) are in the lowered text.
"""
import glob
import os

import numpy as np
import pytest

import jax
import mxtpu as mx
from mxtpu import obs, parallel, profiler
from mxtpu.gluon import loss as gloss
from mxtpu.models.transformer import BERTModel
from mxtpu.serving import (GenerateBatcher, GenerateRunner,
                           InferenceServer)
from mxtpu.serving.stats import ServingStats

# not test_generate.py's sizes: a program compiled inside a profiler
# session takes long enough for JAX's persistent cache to keep it, and an
# executable JAX loaded from there does not survive ExecutableCache's
# own store/load on the CPU (test_warmed_worker_has_zero_cold_compiles)
V, U, HID, NL, NH, L = 40, 16, 32, 2, 2, 16
LANES = 2
P = obs.trace.REGION_PREFIX


class FakeClock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


@pytest.fixture(scope="module")
def export(tmp_path_factory):
    net = BERTModel(V, U, HID, NL, NH, max_length=L, dropout=0.0,
                    use_token_type=False, causal=True)
    net.initialize()
    net.hybridize()
    net(mx.nd.array(np.ones((1, 3))), mx.nd.array(np.zeros(1)),
        mx.nd.array(np.zeros(net.kv_cache_spec(1), np.float32)))
    d = tmp_path_factory.mktemp("regions")
    return net.export(str(d / "genbert")) + (
        net.kv_cache_spec(LANES, L),)


def _runner(export, **kw):
    sym_file, param_file, spec = export
    kw.setdefault("prompt_buckets", (4, 8))
    kw.setdefault("cache", None)
    return GenerateRunner.from_export(sym_file, param_file, spec, **kw)


@pytest.fixture(scope="module")
def runner(export):
    r = _runner(export)
    r.warmup()
    return r


def _serve(runner, prompts=((1, 2, 3), (4, 5, 6, 7)), max_tokens=4,
           clock=None, stats=None):
    """Two requests through a fresh batcher to completion; returns
    (token streams, batcher)."""
    b = GenerateBatcher(runner, clock=clock or FakeClock(),
                        stats=stats)
    reqs = [b.submit(list(p), max_tokens=max_tokens, trace_id=f"t{i}")
            for i, p in enumerate(prompts)]
    for _ in range(20):
        b.step()
        if all(r.done() for r in reqs):
            break
    return [r.result(0) for r in reqs], b


class _Session:
    """A ``jax.profiler`` session into ``path``; ``events`` afterwards:
    ``[(name, start_ns, end_ns, stats, line)]`` of the program's
    regions on the host planes."""

    def __init__(self, path):
        self.path = str(path)

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.path, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        pb = sorted(glob.glob(os.path.join(
            self.path, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        data = jax.profiler.ProfileData.from_file(pb)
        self.events = []
        for plane in data.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(P):
                        self.events.append((
                            ev.name[len(P):], ev.start_ns,
                            ev.start_ns + ev.duration_ns,
                            dict(ev.stats), line.name))
        self.events.sort(key=lambda e: e[1])

    def named(self, name):
        return [e for e in self.events if e[0] == name]

    def parent_of(self, ev):
        """The innermost region of the same thread that holds ``ev``."""
        holds = [e for e in self.events
                 if e is not ev and e[4] == ev[4]
                 and e[1] <= ev[1] and ev[2] <= e[2]]
        return max(holds, key=lambda e: e[1])[0] if holds else None


GEN_PARENTS = {
    "gen/admit": "gen/step", "gen/prefill": "gen/step",
    "gen/decode": "gen/step", "gen/fire": "gen/step",
    "gen/step/done": "gen/step", "gen/admit/done": "gen/admit",
    "gen/decode_rows": "gen/step", "gen/complete": "gen/step",
    "gen/prefill/rows": "gen/prefill",
    "gen/prefill/call": "gen/prefill",
    "gen/prefill/call/stage": "gen/prefill/call",
    "gen/prefill/call/dispatch": "gen/prefill/call",
    "gen/prefill/call/fetch": "gen/prefill/call",
    "gen/decode/stage": "gen/decode",
    "gen/decode/dispatch": "gen/decode",
    "gen/decode/fetch": "gen/decode",
}
GEN_COUNTS = {
    "gen/step": {"step", "max_lanes"},
    "gen/step/done": {"active", "admitted", "queued", "emitted",
                      "finished", "cpu_us", "gc_us", "gc_n"},
    "gen/admit/done": {"admitted", "evicted", "wait_us_sum"},
    "gen/prefill": {"rows", "rung", "bucket", "chunks"},
    "gen/prefill/rows": {"chunk"},
    "gen/prefill/call": {"rows", "bucket"},
    "gen/decode": {"slots"},
    "gen/sample": {"lanes"}, "gen/fire": {"tokens"},
    "gen/decode_rows": {"step"}, "gen/commit": {"step"},
    "gen/complete": {"requests"},
}


def test_generate_regions_land_in_the_xplane(runner, tmp_path):
    """Every serving region of the issue's table, with its counts, and
    nested as the table says."""
    with _Session(tmp_path) as s:
        streams, _ = _serve(runner)
    assert all(len(t) == 4 for t in streams)
    for name, parent in GEN_PARENTS.items():
        found = s.named(name)
        assert found, f"no region {name!r} in the xplane"
        for ev in found:
            assert s.parent_of(ev) == parent, (name, s.parent_of(ev))
    for name, keys in GEN_COUNTS.items():
        for ev in s.named(name):
            assert keys <= set(ev[3]), (name, ev[3])
    # first tokens are sampled and seated inside the prefill, later ones
    # in the step
    for name in ("gen/sample", "gen/commit"):
        assert {s.parent_of(e) for e in s.named(name)} == \
            {"gen/prefill", "gen/step"}, name
    # the counts are the step's own: both requests join in step 1, on
    # the rung of two rows and the bucket of four, after no wait on a
    # clock that stands still
    first, first_done = s.named("gen/step")[0], s.named("gen/step/done")[0]
    assert first[3]["step"] == 1 and first[3]["max_lanes"] == LANES
    assert first_done[3]["admitted"] == 2 and first_done[3]["active"] == 2
    assert first_done[3]["emitted"] == 4 and first_done[3]["queued"] == 0
    admit = s.named("gen/admit/done")[0][3]
    assert admit["admitted"] == 2 and admit["wait_us_sum"] == 0
    pre = s.named("gen/prefill")[0][3]
    assert (pre["rows"], pre["rung"], pre["bucket"], pre["chunks"]) == \
        (2, 2, 4, 1)
    # the calls' constant counts are gone: a bertgen-shaped graph (one
    # table, no counts of its own) writes no closing child of a call
    assert s.named("gen/prefill/call/done") == []
    assert s.named("gen/decode/done") == []
    for ev in s.events:
        assert not {"logits_bytes", "fetched_bytes"} & set(ev[3]), ev
    assert s.named("gen/decode")[0][3]["slots"] == LANES + 1
    steps = [e[3]["step"] for e in s.named("gen/step")]
    assert steps == list(range(1, len(steps) + 1))


def _leaves(s):
    """The session's leaf regions: no other region of its thread lies
    inside (closing children are not regions)."""
    regions = [e for e in s.events if not e[0].endswith("/done")]
    return [e for e in regions
            if not any(o is not e and o[4] == e[4] and e[1] <= o[1]
                       and o[2] <= e[2] for o in regions)]


def test_leaves_cover_every_step(runner, tmp_path, monkeypatch):
    """Every stretch of the step's host work lies in one leaf: on every
    step the leaves inside ``gen/step`` cover 98 % of its wall time or
    more, none of them overlaps another, and the new leaves are all
    there.  Each program takes 20 ms, as a decode step takes 11–30 on
    the chip: the tiny model's own microseconds would leave the regions'
    bookkeeping (a few microseconds each while a session runs) as the
    step's largest part.  A loaded test machine can take the thread off
    its CPU between two leaves for milliseconds, so the loop is served
    twice and one of the two has to hold on every step: a leaf that is
    missing leaves its gap in both."""
    import time
    for entry in runner._entries.values():
        run = entry["compiled"]
        monkeypatch.setitem(entry, "compiled",
                            lambda *a, _run=run: (time.sleep(0.02),
                                                  _run(*a))[1])
    short = []
    for attempt in range(2):
        with _Session(tmp_path / str(attempt)) as s:
            _serve(runner, prompts=((1, 2, 3), (4, 5, 6, 7)), max_tokens=6)
            _serve(runner, prompts=(tuple(range(1, 11)),), max_tokens=3)
        leaves = _leaves(s)
        names = {e[0] for e in leaves}
        assert {"gen/admit", "gen/prefill/rows", "gen/decode_rows",
                "gen/sample", "gen/commit", "gen/fire", "gen/complete",
                "gen/decode/fetch", "gen/prefill/call/fetch"} <= names, names
        steps = s.named("gen/step")
        assert len(steps) >= 6
        short = []
        for st in steps:
            inside = sorted((e[1], e[2]) for e in leaves
                            if e[4] == st[4] and st[1] <= e[1]
                            and e[2] <= st[2])
            for (_, end), (start, _) in zip(inside, inside[1:]):
                assert end <= start
            covered = sum(b - a for a, b in inside)
            if covered < 0.98 * (st[2] - st[1]):
                short.append((st[3]["step"], covered / (st[2] - st[1])))
        if not short:
            break
    assert not short, short


def test_fetch_is_one_leaf(runner, tmp_path):
    """``/fetch`` of either call is one leaf: the one blocking copy of
    the token ids, with nothing of its own inside (a split into a wait
    and a copy cost the untraced run a second sync point a call)."""
    with _Session(tmp_path) as s:
        _serve(runner)
    leaves = {e[0] for e in _leaves(s)}
    for call in ("gen/decode", "gen/prefill/call"):
        fetches = s.named(call + "/fetch")
        assert fetches
        for f in fetches:
            assert not [e for e in s.events if e[4] == f[4] and e is not f
                        and f[1] <= e[1] and e[2] <= f[2]]
        assert call + "/fetch" in leaves


def test_step_counts_its_cpu_time_and_its_collections(runner, tmp_path):
    """``gen/step`` closes with the serving thread's CPU time over the
    step, never more than the step's wall time, and the collector's
    pauses on that thread: an ``on_token`` that collects makes its
    step's ``gc_n`` one or more and ``gc_us`` positive."""
    import gc
    b = GenerateBatcher(runner, clock=FakeClock())
    quiet = b.submit([1, 2, 3], max_tokens=8)
    collecting = set()

    def collect(tok, i):
        collecting.add(b._step_no)
        gc.collect()

    with _Session(tmp_path) as s:
        for _ in range(3):
            b.step()
        loud = b.submit([4, 5], max_tokens=2, on_token=collect)
        while not (quiet.done() and loud.done()):
            b.step()
    steps, done = s.named("gen/step"), s.named("gen/step/done")
    assert len(done) == len(steps) >= 7
    for st, end in zip(steps, done):
        assert 0 <= end[3]["cpu_us"] * 1000 <= st[2] - st[1]
    assert collecting and collecting.isdisjoint({1, 2, 3})
    for st, end in zip(steps, done):
        if st[3]["step"] in collecting:
            assert end[3]["gc_n"] >= 1 and end[3]["gc_us"] > 0, end


def test_no_session_reads_no_thread_clock(runner, monkeypatch):
    """With no session the step reads neither the thread's CPU clock
    nor the collector's tallies: one boolean check a step."""
    import time as time_mod

    def boom():
        raise AssertionError("thread_time_ns read with no session")

    monkeypatch.setattr(time_mod, "thread_time_ns", boom)
    monkeypatch.setattr(obs, "gc_pauses", boom)
    streams, _ = _serve(runner)
    assert all(len(t) == 4 for t in streams)


def test_gc_pauses_are_the_operators_counter_too():
    """The same hook counts the process's pauses by generation for
    ``/metrics``; a collection on any thread moves its generation's
    series and that thread's own tally."""
    import gc
    import threading
    before = obs.gc_pauses()
    got = {}

    def work():
        got["start"] = obs.gc_pauses()
        gc.collect()
        got["end"] = obs.gc_pauses()

    t = threading.Thread(target=work)
    t.start()
    t.join()
    assert got["start"] == (0, 0)
    assert got["end"][1] == 1 and got["end"][0] > 0
    assert obs.gc_pauses() == before     # not this thread's
    text = obs.prometheus_text()
    samples = obs.parse_prometheus_text(text)
    gen2 = samples[("mxtpu_gc_pause_seconds_total",
                    (("generation", "2"),))]
    assert gen2 > 0
    assert "# TYPE mxtpu_gc_pause_seconds_total counter" in text
    gc.collect()
    again = obs.parse_prometheus_text(obs.prometheus_text())
    assert again[("mxtpu_gc_pause_seconds_total",
                  (("generation", "2"),))] > gen2


def test_server_loop_writes_its_between_leaf(export, tmp_path):
    """The serving thread's work between two steps (``drain``, the
    stats' log) is a leaf of its own, outside ``gen/step``; the fleet
    worker's stepping path writes the same leaf."""
    from mxtpu.serving.router import FleetWorker
    srv = InferenceServer()
    srv.register_generator("bert", _runner(export))
    try:
        with _Session(tmp_path / "server") as s:
            req = srv.submit_generate("bert", [1, 2, 3], max_tokens=5)
            assert len(req.result(timeout=60.0)) == 5
    finally:
        srv.close()
    between = s.named("gen/between")
    assert between and all(s.parent_of(e) is None for e in between)
    assert "gen/between" in {e[0] for e in _leaves(s)}
    w = FleetWorker(None, "w0", clock=FakeClock(),
                    gen_runner=_runner(export))
    with _Session(tmp_path / "fleet") as s:
        req = w.generator.submit([1, 2, 3], max_tokens=2)
        for _ in range(10):
            w.pump_generate()
            if req.done():
                break
    assert len(req.result(0)) == 2
    assert s.named("gen/between")
    assert all(s.parent_of(e) is None for e in s.named("gen/between"))


@pytest.fixture(scope="module")
def state_spec_runner():
    """A tiny hybrid decoder: a graph with a state spec, which is told
    each row's length and makes ``(b, 1, V)`` itself."""
    from mxtpu import symbol as sym_mod
    from mxtpu.models.hybrid import HybridDecoderModel
    net = HybridDecoderModel.from_config({
        "vocab_size": V + 3, "hidden_size": 32,
        "shared_intermediate_size": 64,
        "layer_types": ["mamba", "attention"],
        "num_attention_heads": 2, "num_key_value_heads": 1,
        "mamba_n_heads": 2, "mamba_d_head": 16, "mamba_d_state": 8,
        "mamba_d_conv": 4, "mamba_chunk_size": 4, "mamba_n_groups": 1,
        "rms_norm_eps": 1e-5, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "attention_multiplier": 0.0625,
        "logits_scaling": 8, "num_local_experts": 0,
        "position_embedding_type": "nope"})
    net.initialize()
    out = net(*[sym_mod.var(f"data{i}") for i in range(6)])
    params = {p.name: p.data() for p in net.collect_params().values()}
    r = GenerateRunner(sym_mod.Group(list(out)), params,
                       net.state_spec(LANES, L), prompt_buckets=(4, 8),
                       cache=None)
    r.warmup()
    return r


@pytest.mark.parametrize("graph", ["one_table", "state_spec"])
def test_a_prefill_call_fetches_four_bytes_a_row(graph, request, tmp_path):
    """Whichever kind of graph: every ``gen/prefill/call`` — a rung of
    two, a rung of one, the second chunk of a long prompt — makes one
    row of logits a row and brings over its first maximum, 4 bytes,
    in one fetch; no closing child says so any more."""
    r = request.getfixturevalue(
        "runner" if graph == "one_table" else "state_spec_runner")
    vocab = V if graph == "one_table" else V + 3
    with _Session(tmp_path) as s:
        streams, _ = _serve(r, prompts=((1, 2, 3), (4, 5, 6, 7)))
        more, _ = _serve(r, prompts=(tuple(range(1, 10)),))
    assert all(len(t) == 4 for t in streams + more)
    calls = s.named("gen/prefill/call")
    assert [c[3]["rows"] for c in calls] == [2, 1, 1]
    assert s.named("gen/prefill/call/done") == []
    assert len(s.named("gen/prefill/call/fetch")) == len(calls)
    # what a call hands back: (rows, 1, V) left on the device, and the
    # rows' first maxima, int32, on the host
    b, n = 2, 4
    logits, kv = r.prefill(np.ones((b, n), np.float32),
                           np.zeros(b, np.float32),
                           np.full(b, r.scratch_slot, np.float32),
                           r.new_cache())
    assert logits.shape == (b, 1, vocab)
    assert logits.first_maximum.nbytes == 4 * b


def test_gen_prefill_has_its_real_length(runner, tmp_path):
    """``gen/prefill`` used to be written with zero length: in the
    xplane it now holds its runner calls, and the chrome trace holds
    the same positive interval ONCE, under the ids of the group's
    requests (it used to be written again for each request)."""
    profiler.set_state("run")
    try:
        with _Session(tmp_path) as s:
            _serve(runner)
        events = profiler.events()
        found = [obs.trace_of(t) for t in ("t0", "t1")]
    finally:
        profiler.set_state("stop")
        profiler.dumps(reset=True)
    (pre,) = s.named("gen/prefill")
    (call,) = s.named("gen/prefill/call")
    assert pre[2] - pre[1] >= call[2] - call[1] > 0
    (mine,) = [e for e in events if e["name"] == obs.SPAN_PREFILL]
    assert mine["args"]["trace_ids"] == ["t0", "t1"]
    assert mine["args"]["rows"] == 2 and mine["dur"] > 0
    assert all(mine in timeline for timeline in found)


def test_trace_of_rebuilds_a_generation_from_the_chrome_events(export):
    """One request through the server: ``trace_of`` still finds its
    prefill and every token, all stamped by the profiler's clock (the
    batcher's scheduling clock is another clock)."""
    srv = InferenceServer()
    srv.register_generator("bert", _runner(export))
    profiler.set_state("run")
    t_lo = profiler._now_us()
    try:
        req = srv.submit_generate("bert", [1, 2, 3], max_tokens=5)
        assert len(req.result(timeout=60.0)) == 5
        t_hi = profiler._now_us()
        timeline = obs.trace_of(req.trace_id)
        steps = [e for e in profiler.events()
                 if e["name"] == obs.SPAN_GEN_STEP]
    finally:
        profiler.set_state("stop")
        profiler.dumps(reset=True)
        srv.close()
    names = [e["name"] for e in timeline]
    assert names.count(obs.SPAN_PREFILL) == 1
    assert names.count(obs.SPAN_TOKEN) == 5
    assert names.index(obs.SPAN_PREFILL) < names.index(obs.SPAN_TOKEN)
    assert sorted(e["args"]["index"] for e in timeline
                  if e["name"] == obs.SPAN_TOKEN) == list(range(5))
    origin = profiler._START_TS
    for e in timeline:
        assert t_lo - origin <= e["ts"] <= t_hi - origin, e
    # the step's own span replaced the server's serve/<name>:gen one
    assert steps and all(e["cat"] == "gen" for e in steps)
    assert {"step", "active", "admitted", "emitted"} <= set(
        steps[0]["args"])
    assert not [e for e in profiler.events() if "serve/" in e["name"]]


def test_no_session_and_obs_off_write_nothing_and_change_nothing(
        export, tmp_path, monkeypatch):
    """No session: no chrome event.  ``MXTPU_OBS=0`` inside both
    sessions: no region in either trace.  Same tokens every time."""
    want, _ = _serve(_runner(export))
    assert profiler.events() == []
    monkeypatch.setenv("MXTPU_OBS", "0")
    profiler.set_state("run")
    try:
        with _Session(tmp_path) as s:
            got, _ = _serve(_runner(export))
        chrome = profiler.events()
    finally:
        profiler.set_state("stop")
        profiler.dumps(reset=True)
    assert got == want
    assert s.events == []
    # what is left are the per-request instants of obs.span
    chrome = [e for e in chrome if e["cat"] == "gen"]
    assert {e["name"] for e in chrome} == {obs.SPAN_TOKEN}
    assert all("trace_id" in e["args"] for e in chrome)


def test_owners_read_the_switch_once_at_construction(export,
                                                     monkeypatch):
    """``region`` reads no knob: an owner binds ``obs.region`` or the
    null writer when it is built, as it binds its instruments, and a
    later flip of ``MXTPU_OBS`` does not reach it."""
    assert obs.region_writer(True) is obs.region
    off = obs.region_writer(False)
    assert off("gen/step", step=1) is obs.NULL_REGION
    assert off("gen/step", trace_id="t") is obs.NULL_REGION
    on = _runner(export)
    monkeypatch.setenv("MXTPU_OBS", "0")
    built_off = _runner(export)
    assert on._region is obs.region
    assert built_off._region is off
    batcher = GenerateBatcher(built_off, clock=FakeClock())
    assert batcher._region is off
    monkeypatch.setenv("MXTPU_OBS", "1")
    assert built_off._region is off and batcher._region is off
    assert GenerateBatcher(on, clock=FakeClock())._region is obs.region


def test_ttft_and_token_gaps_count_the_step_that_made_the_token(runner):
    """Under a fake clock that only the runner's calls advance, the
    operator's TTFT holds the prefill and each gap its decode: both
    used to be stamped with the step's start."""
    clk = FakeClock()
    stats = ServingStats(name="regions", clock=clk)
    real_prefill, real_decode = runner.prefill, runner.decode

    def prefill(*a):
        clk.t += 0.5
        return real_prefill(*a)

    def decode(*a):
        clk.t += 0.25
        return real_decode(*a)

    runner.prefill, runner.decode = prefill, decode
    try:
        _serve(runner, prompts=((1, 2, 3),), max_tokens=3, clock=clk,
               stats=stats)
    finally:
        del runner.prefill, runner.decode
    assert list(stats._ttft_us) == [0.5e6]
    assert list(stats._tok_us) == [0.25e6, 0.25e6]


@pytest.mark.parametrize("write", ["loop", "kernel"])
def test_compile_regions_count_new_buckets_only(export, tmp_path,
                                                request, write):
    """One ``compile`` region per entry built, none on a second call;
    each says how many of its program's one-token writes of the KV
    table the column-store kernel makes.  ``gen/decode`` no longer says
    it of every call: it is the program's, and its ``compile`` region
    and the runner's entry hold it."""
    if write == "kernel":
        request.getfixturevalue("column_store")
    r = _runner(export, prompt_buckets=(4,))
    with _Session(tmp_path / "first") as s:
        _serve(r, prompts=((1, 2, 3),), max_tokens=2)
    built = s.named("compile")
    assert sorted((e[3]["kind"], e[3]["bucket"]) for e in built) == \
        [("decode", f"({LANES + 1},)"), ("prefill", "(1, 4)")]
    assert all(e[3]["entry"].startswith("GenerateRunner") for e in built)
    done = s.named("compile/done")
    assert [e[3]["source"] for e in done] == ["cold", "cold"]
    # prefill is built first; its writes are four positions a lane
    sites = 2 * NL if write == "kernel" else 0
    assert [int(e[3]["kv_kernel_writes"]) for e in done] == [0, sites]
    assert s.named("gen/decode/done") == []
    assert r._entries[("decode", (LANES + 1,))]["kv_kernel_writes"] == \
        sites
    # each program's temporary bytes: the count a rebuilt KV table
    # shows in, and the operator's gauge of the same number
    temps = sorted(int(e[3]["temp_bytes"]) for e in done)
    assert all(t > 0 for t in temps)
    gauge = obs.snapshot()["mxtpu_gen_program_temp_bytes"]["series"]
    assert sorted(int(v["value"]) for v in gauge
                  if v["labels"]["bucket"] in
                  (f"({LANES + 1},)", "(1, 4)")) == temps
    with _Session(tmp_path / "second") as s:
        _serve(r, prompts=((1, 2, 3),), max_tokens=2)
    assert s.named("compile") == [] and s.named("gen/decode")


def test_gen_counters_are_the_operators_view_of_the_same_events(runner):
    obs.reset()
    _, b = _serve(runner)
    snap = obs.summary()
    assert snap["mxtpu_gen_admitted_total"] == 2
    assert snap["mxtpu_gen_evicted_total"] == 0
    assert snap['mxtpu_gen_prefill_rung_total{bucket="4",rows="2"}'] == 1
    # both streams end in the same step: the last decode ran two lanes
    assert snap["mxtpu_gen_lanes_active"] == 2
    assert b.joins == 2


# ---------------------------------------------------------------- train
def _train_step(**kw):
    net = BERTModel(V, U, HID, NL, NH, max_length=L, dropout=0.1)
    net.initialize()

    def mlm(pred, y):
        return gloss.SoftmaxCrossEntropyLoss()(
            pred.reshape((-1, V)), y.reshape((-1,)))

    step = parallel.build_train_step(net, mlm, "adam",
                                     {"learning_rate": 1e-3}, **kw)
    rng = np.random.default_rng(0)
    x = mx.nd.array(rng.integers(0, V, (4, 8)).astype(np.float32))
    y = mx.nd.array(rng.integers(0, V, (4, 8)).astype(np.float32))
    return step, x, y


def test_train_regions_land_in_the_xplane(tmp_path):
    step, x, y = _train_step()
    with _Session(tmp_path) as s:
        step(x, y)
        step(x, y).asnumpy()
        step.run_steps(x, y, 2, reuse_batch=True).asnumpy()
    calls = s.named("train/step")
    assert [e[3]["t"] for e in calls] == [1, 2, 4]
    assert calls[2][3]["steps"] == 2
    for name in ("train/prep", "train/dispatch", "train/writeback"):
        found = s.named(name)
        assert len(found) == 3, name
        assert all(s.parent_of(e) == "train/step" for e in found)
    assert all(e[3]["leaves"] > 0 for e in s.named("train/dispatch"))
    # the step is built once, inside the first call's prep; the scan
    # once more inside run_steps'
    built = s.named("compile")
    assert [(e[3]["kind"], s.parent_of(e)) for e in built] == \
        [("train", "train/prep"), ("train_scan", "train/prep")]
    assert s.named("compile/done")[0][3]["source"] == "cold"
    # what the partition did: one unstacked bucket a parameter
    assert int(built[0][3]["groups"]) == len(step._train_idx)
    assert int(built[0][3]["stacked_groups"]) == 0
    for a, b, c in zip(s.named("train/prep"), s.named("train/dispatch"),
                       s.named("train/writeback")):
        assert a[2] <= b[1] and b[2] <= c[1]


def test_train_step_help_says_what_it_times():
    obs.reset()
    _train_step()
    text = obs.prometheus_text()
    (line,) = [ln for ln in text.splitlines()
               if ln.startswith("# HELP mxtpu_train_step_seconds")]
    assert "enqueue" in line and "Wall time" not in line


# ------------------------------------------------- names on the device
def test_step_programs_carry_their_scopes_and_kernel_names(
        export, monkeypatch):
    """The lowered text of the tiny train step and of the generation
    programs names every phase and every kernel they hold (the Pallas
    interpreter keeps a kernel's ``name=`` in the op names)."""
    monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    step, x, y = _train_step()
    text = step.lowered_hlo_text(x, y)
    for name in ("train/forward_backward", "train/optimizer",
                 "flash_attention_fwd", "layer_norm_fwd",
                 "layer_norm_bwd", "fused_residual_layer_norm_fwd",
                 "fused_residual_layer_norm_bwd"):
        assert name in text, name
    r = _runner(export)
    decode = r.lowered_program_text()
    assert "gen/decode_program" in decode and "cached_attention" in decode
    prefill = r.lowered_program_text(r.default_bucket("prefill"))
    assert "gen/prefill_program" in prefill
    assert "cached_attention" in prefill


def _lowered_grad_text(fn, *args):
    from mxtpu import analysis
    return analysis.lowered_text(jax.grad(fn), *args)


def test_flash_backward_kernels_are_named(monkeypatch):
    import jax.numpy as jnp
    from mxtpu.kernels import flash_attention
    monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    monkeypatch.setenv("MXTPU_FLASH_BWD", "pallas")
    q = jnp.ones((1, 2, 128, 64), jnp.float32)
    text = _lowered_grad_text(
        lambda a: jnp.sum(flash_attention(a, a, a)), q)
    for name in ("flash_attention_fwd", "flash_attention_dq",
                 "flash_attention_dkv"):
        assert name in text, name


@pytest.mark.parametrize("layout,names", [
    ("major", ("batch_norm_fwd", "batch_norm_bwd")),
    ("cm", ("batch_norm_fwd_cm", "batch_norm_bwd_cm"))])
def test_batch_norm_kernels_are_named(monkeypatch, layout, names):
    import jax.numpy as jnp
    from mxtpu.kernels.batch_norm import fused_bn_act
    monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    monkeypatch.setenv("MXTPU_FUSED_BN", "1")
    monkeypatch.setenv("MXTPU_BN_LAYOUT", layout)
    x = jnp.ones((2, 128, 8, 8), jnp.float32)
    g, b = jnp.ones(128), jnp.zeros(128)
    text = _lowered_grad_text(
        lambda a: jnp.sum(fused_bn_act(a, g, b, act="relu")[0]), x)
    for name in names:
        assert name in text, name
