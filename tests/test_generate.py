"""mxtpu.serving.generate — KV-cache incremental decode, continuous
batching, token streaming, and replay-on-steal (ISSUE 19).

Layered like the subsystem: incremental-model parity first (the
hybrid-forward (step, cache) signature IS the substrate), then the
seeded sampler, the GenerateRunner executable ladder + persistent
cache, fake-clock GenerateBatcher units (join at step boundaries,
lane reuse after EOS, deadline eviction mid-decode, partial state on
close), and finally the fleet: a scripted kill mid-generation must
yield ZERO wrong or duplicated tokens and an exactly resumed stream,
reconstructable from the request's one trace id.
"""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxtpu as mx
from mxtpu import obs, profiler
from mxtpu.base import MXNetError
from mxtpu.cache import ExecutableCache
from mxtpu.gluon.block import HybridBlock
from mxtpu.models.transformer import BERTModel, TransformerModel
from mxtpu.ops.registry import get_op
from mxtpu.serving import (DeviceLogits, FleetGenerateRequest,
                           FleetRouter, FleetWorker, GenerateBatcher,
                           GenerateRunner, InferenceServer,
                           RequestTimeout, ServerBusy, WorkerLost,
                           sample_token)

V, U, HID, NL, NH, L = 32, 16, 32, 2, 2, 16
LANES = 2


class FakeClock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _bert():
    return BERTModel(V, U, HID, NL, NH, max_length=L, dropout=0.0,
                     use_token_type=False, causal=True)


@pytest.fixture(scope="module")
def net():
    n = _bert()
    n.initialize()
    n.hybridize()
    # trace the incremental signature once so export carries the
    # (tokens, step, cache) triple
    n(mx.nd.array(np.ones((1, 3))), mx.nd.array(np.zeros(1)),
      mx.nd.array(np.zeros(n.kv_cache_spec(1), np.float32)))
    return n


@pytest.fixture(scope="module")
def export(net, tmp_path_factory):
    d = tmp_path_factory.mktemp("genbert")
    # re-trace the incremental signature: an earlier test may have run
    # the plain forward last, and export serializes the latest trace
    net(mx.nd.array(np.ones((1, 3))), mx.nd.array(np.zeros(1)),
        mx.nd.array(np.zeros(net.kv_cache_spec(1), np.float32)))
    sym_file, param_file = net.export(str(d / "genbert"))
    return sym_file, param_file


def _runner(export, **kw):
    sym_file, param_file = export
    net_spec = _bert().kv_cache_spec(LANES, L)
    kw.setdefault("prompt_buckets", (4, 8))
    kw.setdefault("cache", None)
    return GenerateRunner.from_export(sym_file, param_file, net_spec,
                                      **kw)


@pytest.fixture(scope="module")
def runner(export):
    return _runner(export)


def _ref_greedy(net, prompt, n):
    """Reference decode: full forward re-run per token (the naive
    baseline the KV path must match token-for-token)."""
    toks = list(prompt)
    for _ in range(n):
        x = mx.nd.array(np.array(toks, np.float32)[None, :])
        logits = net(x).asnumpy()[0]
        toks.append(int(np.argmax(logits[len(toks) - 1])))
    return toks[len(prompt):]


def _batcher(runner, clk, **kw):
    kw.setdefault("clock", clk)
    return GenerateBatcher(runner, **kw)


def _drive(b, clk, *reqs, n=30, dt=0.01):
    for _ in range(n):
        clk.advance(dt)
        b.step()
        if all(r.done() for r in reqs):
            return
    raise AssertionError(f"requests not done after {n} steps")


# ----------------------------------- incremental forward parity (sat 1)

def test_incremental_forward_matches_full(net):
    """The hybrid-forward (step, cache) path must pin the full
    forward's logits bit-close at every position: prefill a prompt,
    then extend one token at a time through the cache and compare
    each step's last-position logits against a from-scratch run."""
    prompt = [3, 7, 1, 4]
    cache = mx.nd.array(np.zeros(net.kv_cache_spec(1), np.float32))
    x = mx.nd.array(np.array(prompt, np.float32)[None, :])
    inc, cache = net(x, mx.nd.array(np.zeros(1)), cache)
    full = net(x)
    np.testing.assert_allclose(inc.asnumpy(), full.asnumpy(),
                               rtol=1e-5, atol=1e-5)
    toks = list(prompt)
    for step in range(4):
        nxt = int(np.argmax(inc.asnumpy()[0, len(toks) - 1 if step == 0
                                          else 0]))
        toks.append(nxt)
        inc, cache = net(
            mx.nd.array(np.array([[nxt]], np.float32)),
            mx.nd.array(np.array([len(toks) - 1], np.float32)), cache)
        ref = net(mx.nd.array(np.array(toks, np.float32)[None, :]))
        np.testing.assert_allclose(
            inc.asnumpy()[0, 0], ref.asnumpy()[0, len(toks) - 1],
            rtol=1e-5, atol=1e-5)


def test_kv_cache_spec_shape(net):
    assert net.kv_cache_spec(LANES, L) == (NL, 2, LANES, NH, L, U // NH)


# ------------------------------------------------------- sample_token

def test_sample_token_greedy_is_argmax():
    logits = np.array([0.1, 2.0, -1.0, 0.5], np.float32)
    assert sample_token(logits, position=5) == 1


def test_sample_token_seeded_by_absolute_position():
    """The draw is keyed by (seed, absolute position) ONLY — the same
    position yields the same token no matter which attempt or process
    samples it.  This is what makes a replayed stream identical."""
    rng = np.random.RandomState(0)
    logits = rng.randn(64).astype(np.float32)
    a = [sample_token(logits, position=p, seed=9, top_k=8)
         for p in range(12)]
    b = [sample_token(logits, position=p, seed=9, top_k=8)
         for p in range(12)]
    assert a == b
    assert len(set(a)) > 1          # top-k actually varies by position
    c = [sample_token(logits, position=p, seed=10, top_k=8)
         for p in range(12)]
    assert a != c                   # seed matters


# --------------------------------------------------- runner executables

def test_runner_bucket_ladder(runner):
    bk = runner.buckets()
    assert ("decode", (LANES + 1,)) in bk
    assert ("prefill", (1, 4)) in bk and ("prefill", (2, 8)) in bk
    assert runner.prompt_bucket_for(3) == 4
    assert runner.prompt_bucket_for(9) == 8   # capped: chunked prefill


def test_runner_rejects_bad_kv_spec(export):
    sym_file, param_file = export
    with pytest.raises(MXNetError):
        GenerateRunner.from_export(sym_file, param_file,
                                   (NL, 2, LANES, NH, L),
                                   prompt_buckets=(4,), cache=None)
    with pytest.raises(MXNetError):
        _runner(export, prompt_buckets=(64,))  # bucket > KV capacity


def test_decode_program_contains_kv_update_write(runner):
    """The decode-step program writes the KV cache IN PLACE at each
    lane's own step index — a loop over the lanes round one
    ``lax.dynamic_update_slice`` on the whole slot table, in the
    as-written HLO and in the compiled artifact alike (hlocheck pins
    the latter).  The cache must thread through as an updated operand,
    never be rebuilt from scratch."""
    text = runner.lowered_program_text(("decode", (LANES + 1,)))
    assert "dynamic-update-slice" in text or \
        "dynamic_update_slice" in text
    compiled, _ = runner.program_artifact(("decode", (LANES + 1,)))
    assert "dynamic-update-slice" in compiled


def test_greedy_decode_matches_full_forward(net, runner):
    clk = FakeClock()
    b = _batcher(runner, clk)
    r = b.submit([1, 2, 3], max_tokens=5)
    _drive(b, clk, r)
    assert r.result(0) == _ref_greedy(net, [1, 2, 3], 5)
    assert r.finish_reason == "length"


def test_chunked_prefill_beyond_largest_bucket(net, runner):
    clk = FakeClock()
    b = _batcher(runner, clk)
    r = b.submit([1] * 9, max_tokens=3)       # 9 > largest bucket 8
    _drive(b, clk, r)
    assert r.result(0) == _ref_greedy(net, [1] * 9, 3)


# ------------------------------------ persistent cache: warm decode path

def test_warmed_worker_has_zero_cold_compiles(export, tmp_path):
    """THE first-token-is-never-a-compile acceptance: warm the ladder
    through one runner, then a fresh runner (new process stand-in)
    over the same disk cache must build every entry from disk —
    zero cold compiles — and still decode correctly."""
    # one prompt bucket keeps the ladder at 3 programs — the cache
    # contract is per-entry, a taller ladder proves nothing more
    donor = _runner(export, cache=ExecutableCache(tmp_path),
                    prompt_buckets=(4,))
    donor.warmup()
    assert donor.cold_compiles() == len(donor.buckets())

    fresh = _runner(export, cache=ExecutableCache(tmp_path),
                    prompt_buckets=(4,))
    warmed = fresh.warm_from_disk()
    assert set(warmed) == set(fresh.buckets())
    assert fresh.cold_compiles() == 0
    assert set(fresh.compile_sources().values()) == {"disk"}

    clk = FakeClock()
    b = _batcher(fresh, clk)
    r = b.submit([1, 2, 3], max_tokens=3)
    _drive(b, clk, r)
    assert len(r.result(0)) == 3
    assert fresh.cold_compiles() == 0          # still nothing cold


def test_int8_decode_keys_separately(export, tmp_path):
    """int8-armed executables must key APART from the float path in
    the persistent cache — a float warmup can never satisfy (or be
    poisoned by) an int8 decode entry."""
    cache = ExecutableCache(tmp_path)
    f32 = _runner(export, cache=cache)
    i8 = _runner(export, cache=cache, quant=True,
                 quant_scales={"t": 1.0})
    bucket = ("decode", (LANES + 1,))
    assert f32._cache_key(bucket) != i8._cache_key(bucket)
    f32.warmup([bucket])
    assert f32.cached_buckets() == [bucket]
    assert i8.cached_buckets() == []           # float entry invisible


# ------------------------------------------ batcher: continuous batching

def test_join_at_step_boundary_with_lane_accounting(net, runner):
    """A request submitted mid-decode joins at the NEXT step boundary
    by claiming a freed-or-free lane; both streams stay exact."""
    clk = FakeClock()
    b = _batcher(runner, clk)
    r1 = b.submit([1, 2, 3], max_tokens=5)
    out = b.step()
    assert out["admitted"] == 1 and b.free_lanes() == LANES - 1
    r2 = b.submit([4, 5], max_tokens=4)        # late joiner
    assert b.depth == 1                        # queued, not in a lane
    clk.advance(0.01)
    out = b.step()                             # the join boundary
    assert out["admitted"] == 1 and b.free_lanes() == LANES - 2
    _drive(b, clk, r1, r2)
    assert r1.result(0) == _ref_greedy(net, [1, 2, 3], 5)
    assert r2.result(0) == _ref_greedy(net, [4, 5], 4)
    assert b.joins == 2
    assert b.free_lanes() == LANES             # both lanes reclaimed


def test_lane_reuse_after_eos(net, runner):
    """An EOS-finished lane frees at the step boundary and the next
    queued request claims it — lane recycling must not leak the dead
    stream's KV state into the new one (the attention mask caps at
    the new lane's own frontier)."""
    ref = _ref_greedy(net, [1, 2, 3], 5)
    eos = ref[2]
    clk = FakeClock()
    b = _batcher(runner, clk)
    # saturate both lanes (one step = prefill + first decode)
    ra = b.submit([1, 2, 3], max_tokens=10, eos_id=eos)
    rb = b.submit([1] * 4, max_tokens=6)
    b.step()
    assert b.free_lanes() == 0
    rc = b.submit([4, 5], max_tokens=3)        # waits for a lane
    _drive(b, clk, ra)
    assert ra.finish_reason == "eos" and ra.result(0) == ref[:3]
    _drive(b, clk, rb, rc)
    assert rc.result(0) == _ref_greedy(net, [4, 5], 3)
    assert rb.result(0) == _ref_greedy(net, [1] * 4, 6)
    assert b.free_lanes() == LANES


def test_deadline_eviction_mid_decode(runner):
    clk = FakeClock()
    b = _batcher(runner, clk, on_timeout=None)
    r = b.submit([1, 2, 3], max_tokens=50, timeout_s=0.05)
    b.step()                                   # prefill, 1 token out
    clk.advance(1.0)
    b.step()                                   # evicted at the boundary
    with pytest.raises(RequestTimeout):
        r.result(0)
    assert b.free_lanes() == LANES


def test_queue_full_raises_server_busy(runner):
    clk = FakeClock()
    b = _batcher(runner, clk, max_queue=1)
    b.submit([1, 2], max_tokens=2)
    with pytest.raises(ServerBusy):
        for _ in range(3):
            b.submit([1, 2], max_tokens=2)


def test_max_lanes_knob_caps_batching_width(net, runner):
    # MXTPU_GEN_MAX_LANES narrows continuous batching below the
    # exported KV table width without re-exporting: with a 1-lane cap
    # on a 2-lane runner the second request waits for the first to
    # finish, and the result is still the greedy reference.
    clk = FakeClock()
    b = _batcher(runner, clk, max_lanes=1)
    ra = b.submit([1, 2, 3], max_tokens=3)
    rb = b.submit([4, 5], max_tokens=3)
    clk.advance(0.01)
    b.step()          # ra holds the only lane (prefill + 1st decode)
    assert len(b.active()) == 1 and b.depth == 1
    _drive(b, clk, ra, rb)
    assert ra.result(0) == _ref_greedy(net, [1, 2, 3], 3)
    assert rb.result(0) == _ref_greedy(net, [4, 5], 3)
    assert b.joins == 2


def test_stream_callbacks_carry_indices(net, runner):
    clk = FakeClock()
    b = _batcher(runner, clk)
    got = []
    r = b.submit([1, 2, 3], max_tokens=4,
                 on_token=lambda t, i: got.append((i, t)))
    _drive(b, clk, r)
    exp = _ref_greedy(net, [1, 2, 3], 4)
    assert [t for _, t in got] == exp
    assert [i for i, _ in got] == [0, 1, 2, 3]


# ------------------------------- partial state + replay economics (sat 2)

def test_close_carries_partial_generation_state(runner):
    """WorkerLost from a dying batcher carries prompt + emitted tokens
    + the ORIGINAL t_submit/deadline, so a replay resumes without
    double-billing the clock."""
    clk = FakeClock(200.0)
    b = _batcher(runner, clk)
    r = b.submit([1, 2, 3], max_tokens=50, timeout_s=9.0)
    clk.advance(0.5)
    b.step()                            # prefill + first decode step
    clk.advance(0.5)
    b.step()                            # one more decode step
    b.close()
    with pytest.raises(WorkerLost) as ei:
        r.result(0)
    p = ei.value.partial
    assert p["prompt"] == [1, 2, 3]
    assert p["tokens"] == r.prefix + r.tokens and len(p["tokens"]) == 3
    assert p["t_submit"] == 200.0              # original admission time
    assert p["deadline"] == pytest.approx(209.0)


def test_replay_prefix_resumes_exact_stream(net, runner):
    """Resuming from a prefix (prompt + already-streamed tokens) must
    produce the IDENTICAL remaining stream, with indices continuing
    where the dead attempt stopped — seeded sampling is keyed by
    absolute position, so the steal is invisible in the tokens."""
    exp = _ref_greedy(net, [1, 2, 3], 5)
    clk = FakeClock()
    b = _batcher(runner, clk)
    got = []
    r = b.submit([1, 2, 3], max_tokens=5, prefix=exp[:2],
                 on_token=lambda t, i: got.append((i, t)))
    _drive(b, clk, r)
    assert r.result(0) == exp                  # full stream, replayed
    assert [i for i, _ in got] == [2, 3, 4]    # only NEW indices fired
    assert [t for _, t in got] == exp[2:]


def test_replay_never_double_bills_deadline(runner):
    """A replay submitted with the original deadline already expired
    fails fast as queued-deadline-expiry — it must NOT restart the
    clock from the new submit."""
    clk = FakeClock(300.0)
    b = _batcher(runner, clk)
    r = b.submit([1, 2, 3], max_tokens=5, prefix=[0],
                 timeout_s=0.05)               # original budget spent
    clk.advance(1.0)
    b.step()
    with pytest.raises(RequestTimeout):
        r.result(0)


# -------------------------------------------------- sampling determinism

def test_topk_sampling_identical_across_runs_and_steal(net, runner):
    """Seeded top-k: two full runs produce the same stream, and a
    steal (replay from any prefix point) continues it exactly."""
    def run(prefix=()):
        clk = FakeClock()
        b = _batcher(runner, clk)
        r = b.submit([5, 6, 7], max_tokens=6, top_k=4, seed=13,
                     prefix=list(prefix))
        _drive(b, clk, r)
        return r.result(0)

    full_a, full_b = run(), run()
    assert full_a == full_b                    # across runs
    for cut in (1, 3, 5):
        assert run(prefix=full_a[:cut]) == full_a   # across a steal


# -------------------------------------------------------- fleet: replay

def _gen_worker(export, clk, name):
    # one prompt bucket (8 covers every fleet prompt + replay prefix)
    # keeps each worker's ladder at 3 programs — fleet behavior, not
    # ladder breadth, is under test here
    return FleetWorker(None, name, clock=clk,
                       gen_runner=_runner(export, prompt_buckets=(8,)))


def _gen_router(clk, **kw):
    kw.setdefault("backoff_base_us", 10_000)
    kw.setdefault("backoff_cap_us", 50_000)
    kw.setdefault("jitter", 0.0)
    kw.setdefault("hedge_after_us", 0)
    return FleetRouter(clock=clk, threaded=False, canary=None, **kw)


def _crank(router, clk, n=40, dt=0.05, until=None):
    for _ in range(n):
        clk.advance(dt)
        router.tick()
        if until is not None and until():
            return


def test_fleet_kill_mid_generation_exact_resume(net, export):
    """THE acceptance scenario: kill the hosting worker mid-stream.
    The request replays from prompt + already-streamed tokens on the
    survivor, the caller sees every stream index exactly once, zero
    wrong and zero duplicated tokens, and the final stream equals the
    uninterrupted reference."""
    clk = FakeClock(100.0)
    profiler.set_state("run")
    try:
        router = _gen_router(clk)
        router.add_worker(_gen_worker(export, clk, "w0"))
        router.add_worker(_gen_worker(export, clk, "w1"))
        exp = _ref_greedy(net, [1, 2, 3], 6)

        streamed = []
        freq = router.submit_generate(
            [1, 2, 3], max_tokens=6, timeout_s=60.0,
            on_token=lambda t, i: streamed.append((i, t)))
        assert isinstance(freq, FleetGenerateRequest)
        assert freq.trace_id is not None
        _crank(router, clk, dt=0.01, until=lambda: len(streamed) >= 2)
        assert len(streamed) >= 2 and not freq.done()

        host = freq.tried[-1]
        router.kill(host)
        _crank(router, clk, until=freq.done)

        assert freq.result(0) == exp
        assert freq.requeues == 1
        assert freq.anomalies() == {"duplicate_tokens": 0,
                                    "wrong_tokens": 0}
        assert [t for _, t in streamed] == exp     # exactly once, in
        assert [i for i, _ in streamed] == list(range(6))  # order
        surv = [w for w in ("w0", "w1") if w != host][0]
        assert freq.tried == [host, surv]

        # the whole story reconstructs from the one trace id: prefill
        # on the first host, tokens, the replay marker on the survivor
        events = json.loads(profiler.dumps())["traceEvents"]
        timeline = obs.trace_of(freq.trace_id, events=events)
        names = [e["name"] for e in timeline]
        for span in (obs.SPAN_SUBMIT, obs.SPAN_PREFILL, obs.SPAN_TOKEN,
                     obs.SPAN_STEAL, obs.SPAN_REPLAY):
            assert span in names, f"missing {span} in {names}"
        replay = next(e for e in timeline
                      if e["name"] == obs.SPAN_REPLAY)
        assert replay["args"]["worker"] == surv
        assert 1 <= replay["args"]["resumed"] < 6  # mid-stream resume
        token_idx = sorted(e["args"]["index"] for e in timeline
                           if e["name"] == obs.SPAN_TOKEN)
        assert token_idx[-1] == 5 and token_idx[0] == 0
        router.close()
    finally:
        profiler.set_state("stop")
        profiler.dumps(reset=True)


def test_fleet_generate_continuous_batching_late_join(net, export):
    """Two streams on ONE worker: the second submits mid-decode of the
    first and joins at a step boundary (lane accounting asserted)."""
    clk = FakeClock(100.0)
    router = _gen_router(clk)
    w = _gen_worker(export, clk, "w0")
    router.add_worker(w)
    f1 = router.submit_generate([1, 2, 3], max_tokens=5,
                                timeout_s=60.0)
    clk.advance(0.01)
    router.tick()                              # f1 prefilled: 1 lane
    assert w.generator.free_lanes() == LANES - 1
    f2 = router.submit_generate([4, 5], max_tokens=4, timeout_s=60.0)
    _crank(router, clk, until=lambda: f1.done() and f2.done())
    assert f1.result(0) == _ref_greedy(net, [1, 2, 3], 5)
    assert f2.result(0) == _ref_greedy(net, [4, 5], 4)
    assert w.generator.joins == 2
    assert w.generator.free_lanes() == LANES
    router.close()


def test_fleet_generate_never_hedges(export):
    """Hedging a stream would double-emit tokens — generation requests
    are excluded from the hedging loop by contract."""
    clk = FakeClock(100.0)
    router = _gen_router(clk, hedge_after_us=1)  # hedge ASAP
    router.add_worker(_gen_worker(export, clk, "w0"))
    router.add_worker(_gen_worker(export, clk, "w1"))
    freq = router.submit_generate([1, 2, 3], max_tokens=4,
                                  timeout_s=60.0)
    _crank(router, clk, until=freq.done)
    assert freq.hedges == 0 and len(freq.tried) == 1
    assert freq.anomalies() == {"duplicate_tokens": 0,
                                "wrong_tokens": 0}
    router.close()


def test_fleet_generate_deadline_never_stale_stream(export):
    clk = FakeClock(100.0)
    router = _gen_router(clk)
    router.add_worker(_gen_worker(export, clk, "w0"))
    freq = router.submit_generate([1, 2, 3], max_tokens=500,
                                  timeout_s=0.2)
    clk.advance(0.01)
    router.tick()                              # starts decoding
    clk.advance(5.0)
    router.tick()
    with pytest.raises(RequestTimeout):
        freq.result(0)
    router.close()


# ----------------------------------------------------- server endpoint

def test_server_generate_roundtrip(net, export):
    """Streamed generation through InferenceServer's continuous
    endpoint (threaded, real clock): result + per-token callbacks."""
    srv = InferenceServer()
    srv.register_generator("bert", _runner(export))
    got = []
    out = srv.generate("bert", [1, 2, 3], max_tokens=5, timeout_s=60.0,
                       on_token=lambda t, i: got.append((i, t)))
    assert out == _ref_greedy(net, [1, 2, 3], 5)
    assert [t for _, t in sorted(got)] == out
    snap = srv.stats("bert")
    assert snap["lanes"] == LANES
    # first emission lands in the TTFT histogram, the rest per-token
    assert snap["generate"]["tokens_emitted"] >= 4
    assert "bert:v1:gen" in srv.stats()
    srv.close()


def test_server_generator_registry_guards(export):
    srv = InferenceServer()
    srv.register_generator("g", _runner(export))
    with pytest.raises(MXNetError):
        srv.register_generator("g", _runner(export))  # dup version
    with pytest.raises(MXNetError):
        srv.generate("nope", [1], max_tokens=1)
    srv.unregister("g")
    with pytest.raises(MXNetError):
        srv.generate("g", [1], max_tokens=1)
    srv.close()


# ------------------------------- the slot table, in place (ISSUE 26)

class _TargetOnly(HybridBlock):
    """``TransformerModel`` as the 3-input incremental graph a
    ``GenerateRunner`` takes: the source sentence is token 0 at the
    target's own shape, so only the decoder's cached self-attention
    tells one call from the next."""

    def __init__(self, model, **kwargs):
        super().__init__(**kwargs)
        self.model = model

    def kv_cache_spec(self, *args):
        return self.model.kv_cache_spec(*args)

    def hybrid_forward(self, F, tgt, step, cache):
        return self.model(F.zeros_like(tgt), tgt, step, cache)


def _seq2seq():
    return _TargetOnly(TransformerModel(V, U, HID, NL, NH, max_length=L,
                                        dropout=0.0))


@pytest.fixture(scope="module", params=[_bert, _seq2seq],
                ids=["BERTModel", "TransformerModel"])
def any_export(request, tmp_path_factory):
    """(symbol file, params file, kv spec) of either model family's
    incremental graph: they share ``MultiHeadAttention``'s branch."""
    n = request.param()
    n.initialize()
    n.hybridize()
    n(mx.nd.array(np.ones((1, 3))), mx.nd.array(np.zeros(1)),
      mx.nd.array(np.zeros(n.kv_cache_spec(1), np.float32)))
    d = tmp_path_factory.mktemp("inplace")
    return n.export(str(d / "gen")) + (n.kv_cache_spec(LANES, L),)


def _any_runner(any_export):
    sym_file, param_file, spec = any_export
    return GenerateRunner.from_export(sym_file, param_file, spec,
                                      prompt_buckets=(4, 8), cache=None)


@pytest.fixture(scope="module")
def any_runner(any_export):
    return _any_runner(any_export)


def _moves_of_a_plane(text, plane):
    """``[(opcode, elements)]`` of the instructions of a compiled
    program that move ``plane`` elements or more by ``copy``,
    ``concatenate`` or ``transpose``: what a table that is taken apart
    and re-stacked leaves behind."""
    out = []
    for line in text.splitlines():
        m = re.search(r"= \w+\[([\d,]+)\]\S* "
                      r"(copy|concatenate|transpose)\(", line)
        if m:
            n = int(np.prod([int(d) for d in m.group(1).split(",")]))
            if n >= plane:
                out.append((m.group(2), n))
    return out


@pytest.mark.parametrize("bucket", [("decode", (LANES + 1,)),
                                    ("prefill", (2, 4))],
                         ids=["decode", "prefill"])
def test_compiled_programs_never_move_a_cache_plane(any_runner, bucket):
    """The table that leaves a generation program is the table that
    entered it, written in place.  The compiled text holds no ``copy``,
    ``concatenate`` or ``transpose`` of a piece of the table from one
    cache plane ``(lanes, heads, L, head_dim)`` up — the cut and the
    re-stack of the layers.  Only the program's whole table may move:
    in decode once, at the entry, because the CPU backend does not
    donate (on the chip the table's parameter is aliased to the
    result); in prefill also where the admitted lanes are gathered and
    scattered back, which is the runner's and stays."""
    r = any_runner
    text, _ = r.program_artifact(bucket)
    lanes = r._slots if bucket[0] == "decode" else bucket[1][0]
    plane = lanes * int(np.prod(r._kv_shape[3:]))
    moves = _moves_of_a_plane(text, plane)
    assert [m for m in moves if m[1] < NL * 2 * plane] == []
    assert not [m for m in moves if m[0] == "concatenate"]
    if bucket[0] == "decode":
        assert len(moves) <= 1, moves


def _write_by_slice_and_stack(table, new, step, layer=0, plane=0,
                              ring=False):
    """The plain formulation the programs had before ISSUE 26, kept as
    the reference: cut the plane out, write each lane's rows at its own
    frontier, stack the planes into a new table."""
    idx = jnp.asarray(step).astype(jnp.int32)
    cut = jax.vmap(
        lambda c, n, s: jax.lax.dynamic_update_slice(
            c, n.astype(c.dtype), (0, s, 0)))(table[layer, plane], new,
                                              idx)
    return jnp.stack([
        jnp.stack([cut if (i, w) == (layer, plane) else table[i, w]
                   for w in range(table.shape[1])], axis=0)
        for i in range(table.shape[0])], axis=0)


def _serve_by_hand(r):
    """Every logits array (a prefill's: each row's last position) and
    the final table of: two lanes prefilled
    together, lane 0's prompt continued by a second chunk, two decode
    steps at frontiers (8, 3), lane 1 handed to a new prompt (its stale
    rows stay), three more decode steps at (10, 4)."""
    f32 = np.float32
    out = []
    kv = r.new_cache()

    def prefill(tokens, step, lanes):
        nonlocal kv
        logits, kv = r.prefill(np.array(tokens, f32), np.array(step, f32),
                               np.array(lanes, f32), kv)
        assert logits.shape == (len(tokens), 1, V)
        out.append(np.asarray(logits))

    def decode(tokens, step):
        nonlocal kv
        logits, kv = r.decode(np.array(tokens, f32)[:, None],
                              np.array(step, f32), kv)
        out.append(np.asarray(logits))

    prefill([[3, 7, 1, 4], [5, 2, 6, 0]], [0, 0], [0, 1])
    prefill([[9, 8, 2, 2]], [4], [0])
    decode([11, 12, 0], [8, 3, 0])
    decode([13, 14, 0], [9, 4, 0])
    prefill([[21, 22, 23, 24]], [0], [1])
    for t in range(3):
        decode([15 + t, 25 + t, 0], [10 + t, 4 + t, 0])
    return out, np.asarray(kv)


@pytest.mark.parametrize("write", ["loop", "kernel"])
def test_in_place_table_equals_slice_and_stack(any_export, any_runner,
                                               monkeypatch, request,
                                               write):
    """Lanes at different frontiers, a chunked prefill and a reused
    lane: the programs that write the table in place — every write by
    the lanes' loop, or the decode step's by the column-store kernel —
    give the logits and the table of programs traced from the same
    graph with the slice/write/stack reference in the write's place."""
    if write == "kernel":
        request.getfixturevalue("column_store")
        r = _any_runner(any_export)
        r.warmup()
        assert r._entries[("decode", (LANES + 1,))][
            "kv_kernel_writes"] == 2 * NL
        assert r._entries[("prefill", (2, 4))]["kv_kernel_writes"] == 0
    else:
        r = any_runner
    got, got_kv = _serve_by_hand(r)
    ref = _any_runner(any_export)
    with monkeypatch.context() as m:
        m.setattr(get_op("kv_cache_write"), "fn",
                  _write_by_slice_and_stack)
        ref.warmup([("prefill", (2, 4)), ("prefill", (1, 4)),
                    ("decode", (LANES + 1,))])
    # the reference is what it says: its table is stacked anew
    assert "concatenate" in ref.program_artifact()[0]
    want, want_kv = _serve_by_hand(ref)
    assert len(got) == len(want) == 8
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_kv, want_kv, rtol=1e-6, atol=1e-6)
    # the scratch slot aside, the two lanes did get rows
    assert np.abs(got_kv[:, :, :LANES, :, :10]).sum() > 0


# ------------------- a prefill hands back one row a prompt (ISSUE 33)

def _whole_logits_prefill(r):
    """The one-table prefill program as it was before ISSUE 33, kept as
    the reference: the graph's logits over every position, ``(b, s,
    V)``, brought to the host whole, beside the new table."""
    def fn(tokens, step, lanes, state, params):
        idx = lanes.astype(jnp.int32)
        logits, (new,) = r._eval_incremental(
            (tokens, step), (state[:, :, idx],), params)
        return logits, state.at[:, :, idx].set(new.astype(state.dtype))

    jitted = jax.jit(fn)

    def call(tokens, step, lanes, kv):
        logits, kv = jitted(*(jnp.asarray(a, jnp.float32)
                              for a in (tokens, step, lanes)),
                            kv, r._param_vals)
        return np.asarray(logits), kv

    return call


# (tokens, step, length, lanes) of each call; 2 is the scratch slot
_LAST_ROW_CASES = {
    "full_rows": [([[3, 7, 1, 4], [5, 2, 6, 9]], [0, 0], [4, 4], [0, 1])],
    "short_rows": [([[3, 7, 0, 0], [5, 2, 6, 0]], [0, 0], [2, 3], [1, 0])],
    "padding_row": [([[3, 7, 1, 0], [0, 0, 0, 0]], [0, 0], [3, 0],
                     [1, LANES])],
    "two_chunks": [([[3, 7, 1, 4], [5, 2, 0, 0]], [0, 0], [4, 2], [0, 1]),
                   ([[9, 8, 2, 0], [0, 0, 0, 0]], [4, 0], [3, 0],
                    [0, LANES])],
}


@pytest.mark.parametrize("case", sorted(_LAST_ROW_CASES))
def test_prefill_returns_each_rows_last_valid_position(any_runner, case):
    """``prefill`` hands back what ``decode`` does: a ``DeviceLogits``
    over ``(b, 1, V)`` whose row is, bit for bit, the row of the
    graph's whole logits at the prompt's last valid position — for a
    full row, a short one, a prompt continued by a second call, and
    beside a padding row on the scratch slot (any finite row) — with
    each row's first maximum found on the device, and the table the
    whole-logits program leaves."""
    r = any_runner
    whole = _whole_logits_prefill(r)
    kv, ref_kv = r.new_cache(), r.new_cache()
    f32 = np.float32
    for tokens, step, length, lanes in _LAST_ROW_CASES[case]:
        got, kv = r.prefill(np.array(tokens, f32), np.array(step, f32),
                            np.array(lanes, f32), kv,
                            np.array(length, f32))
        want, ref_kv = whole(tokens, step, lanes, ref_kv)
        assert isinstance(got, DeviceLogits) and got._host is None
        assert got.shape == (len(tokens), 1, V)
        assert want.shape == (len(tokens), 4, V)
        assert got.first_maximum.dtype == np.int32
        host = np.asarray(got)
        assert np.isfinite(host).all()
        for row, n in enumerate(length):
            if n == 0:
                continue            # a row nobody reads
            assert np.array_equal(host[row, 0], want[row, n - 1]), row
            assert got[row, 0].first_maximum == \
                int(np.argmax(want[row, n - 1]))
        np.testing.assert_array_equal(np.asarray(kv), np.asarray(ref_kv))


def test_prefill_without_a_length_keeps_the_last_position(any_runner):
    """A caller that gives no ``length`` (the benchmark's warm-up) has
    rows that are valid to their end."""
    r = any_runner
    tokens = np.array([[3, 7, 1, 4]], np.float32)
    got, _ = r.prefill(tokens, np.zeros(1, np.float32),
                       np.zeros(1, np.float32), r.new_cache())
    want, _ = _whole_logits_prefill(r)(tokens, [0], [0], r.new_cache())
    assert np.array_equal(np.asarray(got)[0, 0], want[0, 3])


def _serve_from_whole_logits(r, prompt, n, *, top_k, seed, prefix=()):
    """One request's stream by the path the batcher took before ISSUE
    33: the prompt (and a replay's prefix) prefilled in chunks by the
    whole-logits program, the first token drawn on the host from
    ``logits[row, last]``, every later one from a decode step's
    numbers."""
    whole = _whole_logits_prefill(r)
    kv, full = r.new_cache(), list(prompt) + list(prefix)
    s = r.prompt_bucket_for(len(full))
    for base in range(0, len(full), s):
        tokens = np.zeros((1, s), np.float32)
        valid = min(s, len(full) - base)
        tokens[0, :valid] = full[base:base + valid]
        logits, kv = whole(tokens, [base], [0], kv)
    stream = [sample_token(logits[0, valid - 1], position=len(full),
                           seed=seed, top_k=top_k)]
    slots = r.max_lanes + 1
    while len(prefix) + len(stream) < n:
        tokens = np.zeros((slots, 1), np.float32)
        step = np.zeros(slots, np.float32)
        tokens[0, 0], step[0] = stream[-1], len(full) + len(stream) - 1
        logits, kv = r.decode(tokens, step, kv)
        stream.append(sample_token(
            np.asarray(logits)[0, 0], position=len(full) + len(stream),
            seed=seed, top_k=top_k))
    return list(prefix) + stream


@pytest.mark.parametrize("top_k", [1, 4], ids=["greedy", "top_k4"])
def test_served_tokens_are_those_drawn_from_whole_logits(runner, top_k):
    """The replay contract across a prefill: what a batcher serves for
    seeded requests — a short prompt, one chunked over two calls, a
    replay that resumes behind a prefix — is what drawing on the host
    from the whole ``(b, s, V)`` logits gave, greedy and top-k."""
    asks = [dict(prompt=[1, 2, 3], seed=7),
            dict(prompt=[4, 5, 6, 7, 8, 9, 10, 11, 12], seed=11),
            dict(prompt=[5, 6, 7], seed=13, prefix=[9, 3])]
    want = [_serve_from_whole_logits(runner, a["prompt"], 6, top_k=top_k,
                                     seed=a["seed"],
                                     prefix=a.get("prefix", ()))
            for a in asks]
    clk = FakeClock()
    b = _batcher(runner, clk)
    reqs = [b.submit(a["prompt"], max_tokens=6, top_k=top_k,
                     seed=a["seed"], prefix=a.get("prefix", ()))
            for a in asks]
    _drive(b, clk, *reqs)
    assert [q.result(0) for q in reqs] == want


@pytest.mark.parametrize("T,write", [(3, "loop"), (1, "loop"),
                                     (1, "kernel"), (3, "kernel")])
def test_kv_cache_write_rows_frontiers_cast_and_clamp(T, write, request):
    """The write alone against a loop over lanes: T rows at each
    lane's own frontier of the named plane and nowhere else, cast to
    the table's dtype (a bf16 table stays bf16 whatever the compute
    dtype), a write past the end of ``L`` clamped to end there.  By
    the lanes' loop, and where the column-store kernel is on offer: it
    takes the one-token write and leaves the others to the loop."""
    if write == "kernel":
        request.getfixturevalue("column_store")
    rng = np.random.RandomState(0)
    layers, B, H, cap, D = 3, 4, 2, 8, 4
    table = rng.randn(layers, 2, B, H, cap, D).astype(np.float32)
    new = rng.randn(B, H, T, D).astype(np.float32)
    step = np.array([0, 2, 5, 9], np.float32)     # past the end: clamps
    for dtype in (jnp.float32, jnp.bfloat16):
        t0 = jnp.asarray(table).astype(dtype)
        out = mx.nd.kv_cache_write(
            mx.nd.NDArray(t0, None, _placed=True), mx.nd.array(new),
            mx.nd.array(step), layer=1, plane=1)
        assert out.data.dtype == dtype
        want = np.array(t0.astype(jnp.float32))
        for b, s in enumerate([0, 2, 5, cap - T]):
            want[1, 1, b, :, s:s + T] = np.asarray(
                jnp.asarray(new[b]).astype(dtype).astype(jnp.float32))
        np.testing.assert_array_equal(
            np.asarray(out.data.astype(jnp.float32)), want)
        plane = mx.nd.kv_cache_read(out, layer=1, plane=1)
        np.testing.assert_array_equal(
            np.asarray(plane.data.astype(jnp.float32)), want[1, 1])
    from mxtpu import analysis
    from mxtpu.ndarray import rnn_impl
    text = analysis.lowered_text(
        lambda t, n, s: rnn_impl._kv_cache_write_op(t, n, s, 1, 1),
        table, new, step)
    # the Pallas interpreter keeps the kernel's ``name=`` in op names
    by_kernel = T == 1 and write == "kernel"
    assert ("kv_cache_write" in text) == by_kernel
    assert by_kernel or "dynamic-update-slice" in text


def _bits(x):
    return np.asarray(jax.lax.bitcast_convert_type(
        x, {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]))


@pytest.mark.parametrize("dtype,new_dtype,heads,cap", [
    (jnp.float32, jnp.float32, 16, 256),
    (jnp.bfloat16, jnp.bfloat16, 8, 384),
    (jnp.bfloat16, jnp.float32, 16, 256),      # the cast on the way in
    (jnp.float32, jnp.float32, 4, 200),        # a last block cut short
    (jnp.float32, jnp.float32, 32, 128),       # heads over two blocks
], ids=["f32-h16", "bf16-h8", "f32-into-bf16", "cap200", "h32"])
def test_column_store_stores_the_bits_of_the_lanes_loop(
        dtype, new_dtype, heads, cap, column_store, monkeypatch):
    """The kernel against ``_write_lanes`` bit for bit, through the
    op: frontiers at 0, either side of a block's edge, the last
    position and past it (clamped), two lanes at one frontier, the
    scratch slot, a negative zero; every plane but the named one
    untouched."""
    from mxtpu.kernels import kv_write
    from mxtpu.ndarray import rnn_impl
    if heads == 32:
        monkeypatch.setattr(kv_write, "_BLOCK_BYTES", 16 * 64 * 128 * 4)
    rng = np.random.RandomState(1)
    layers, B, D = 2, 7, 64
    table = jnp.asarray(rng.randn(layers, 2, B, heads, cap, D), dtype)
    new = jnp.asarray(rng.randn(B, heads, 1, D), new_dtype)
    new = new.at[0, 0, 0, 0].set(-0.0)
    step = jnp.asarray([0, 127, 128, cap - 1, cap + 5, 127, 3],
                       jnp.float32)
    for layer, plane in ((0, 1), (1, 0)):
        want = rnn_impl._write_lanes(
            table, new.astype(dtype), step.astype(jnp.int32),
            jnp.int32(layer), jnp.int32(plane))
        with kv_write.call_sites() as traced:
            got = rnn_impl._kv_cache_write_op(table, new, step,
                                              layer=layer, plane=plane)
        assert traced == [1]
        assert got.dtype == want.dtype == dtype
        np.testing.assert_array_equal(_bits(got), _bits(want))
        assert (_bits(got) != _bits(table)).any()


def test_column_store_is_taken_by_what_is_observed(monkeypatch):
    """One token a lane, Pallas kernels on, and a device that keeps
    the table with ``L`` minor and ``head_dim`` next: the three
    together, and nothing a caller sets."""
    from jax.experimental.layout import Layout
    from mxtpu import kernels
    from mxtpu.ndarray import rnn_impl
    table = jnp.zeros((1, 2, 2, 2, 8, 4))
    assert not rnn_impl._capacity_is_minor(table)   # the CPU, no Pallas
    monkeypatch.setattr(kernels, "pallas_enabled", lambda: True)
    assert not rnn_impl._capacity_is_minor(table)   # row-major here
    for order, minor in (((0, 1, 2, 3, 5, 4), True),
                         ((0, 1, 2, 3, 4, 5), False),
                         ((0, 1, 2, 5, 3, 4), False)):
        monkeypatch.setattr(
            rnn_impl, "_resident_layout",
            lambda x, order=order: Layout(major_to_minor=order,
                                          tiling=((8, 128),)))
        assert rnn_impl._capacity_is_minor(table) is minor
    monkeypatch.setattr(kernels, "pallas_enabled", lambda: False)
    assert not rnn_impl._capacity_is_minor(table)


def test_write_casts_to_a_bf16_table_under_amp(net):
    """Under ``amp`` the cache may be bfloat16: the incremental forward
    hands back a bfloat16 table with the new rows in it, and logits
    near the float32 run's."""
    from mxtpu import amp
    tokens = mx.nd.array(np.array([[3, 7, 1, 4]], np.float32))
    step = mx.nd.array(np.zeros(1))
    spec = net.kv_cache_spec(1)
    ref, _ = net(tokens, step, mx.nd.array(np.zeros(spec, np.float32)))
    with amp.autocast():
        out, cache = net(tokens, step, mx.nd.NDArray(
            jnp.zeros(spec, jnp.bfloat16), None, _placed=True))
    assert cache.data.dtype == jnp.bfloat16
    rows = np.asarray(cache.data.astype(jnp.float32))
    assert np.abs(rows[:, :, 0, :, :4]).min(axis=(2, 3, 4)).all()
    assert not rows[:, :, 0, :, 4:].any()
    np.testing.assert_allclose(out.asnumpy(), ref.asnumpy(), atol=0.1)


def test_cache_attributes_survive_the_symbol_json(any_export):
    """``layer`` and ``plane`` are static attributes of the write and
    of the read: the graph a runner loads from ``-symbol.json`` names
    every (layer, k|v) plane once on either."""
    sym_file = any_export[0]
    loaded = mx.sym.load(sym_file)
    again = mx.sym.load_json(loaded.tojson())
    planes = sorted((i, w) for i in range(NL) for w in (0, 1))
    for sym in (loaded, again):
        for op in ("kv_cache_write", "kv_cache_read"):
            seen = sorted(
                (int(n.attrs["layer"]), int(n.attrs["plane"]))
                for n in sym._topo() if n.op == op)
            assert seen == planes, (op, seen)
