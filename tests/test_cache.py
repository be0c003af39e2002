"""mxtpu.cache — persistent AOT executable cache (ISSUE 13).

Three layers of coverage:

* the cache core — key composition (flip ANY component and the entry
  misses; identical keys hit across processes), crash-safe concurrent
  writes, and the verify-or-quarantine loader against every scripted
  poisoning (corrupt byte, truncation, stale key, read-only root) —
  a wrong executable is NEVER returned;
* the serving integration — a fresh ``ModelRunner`` warms its full
  ladder from disk with zero XLA compiles, and the fleet's
  replacement path (``add_worker`` with no donor handoff) serves its
  first request with ``num_compiled`` == the warmed ladder in both
  the deterministic and the threaded router modes, recompiling (not
  executing!) poisoned entries;
* the training integration — a second ``TrainStep`` build loads from
  disk and steps bit-identically to the cold build.

Everything is deterministic: scripted cache faults keyed on the
cache's own store counter, hand-stepped clocks for the fleet, no
sleeps.
"""
import os
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from mxtpu import obs
from mxtpu import symbol as sym
from mxtpu.cache import (CacheKey, ExecutableCache, default_cache,
                         poison_corrupt, poison_stale, poison_truncate,
                         self_check)
from mxtpu.serving import (Autoscaler, CorruptEntry, FaultPlan,
                           FleetRouter, FleetWorker, ModelRunner,
                           ReadOnlyDir, StaleKey, TruncateEntry)


class FakeClock:
    """Hand-stepped monotonic clock (same pattern as test_fleet)."""

    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _mul_runner(**kwargs):
    data = sym.var("data")
    w = sym.var("w")
    return ModelRunner(data * w, {"w": np.array([1.0, 2.0, 3.0],
                                                np.float32)},
                       {"data": (3,)}, max_batch_size=4, **kwargs)


def _router(clk, **kw):
    return FleetRouter(clock=clk, threaded=False, canary=None, **kw)


def _payload(v):
    return {"data": np.full(3, float(v), np.float32)}


def _crank(router, clk, n=8, dt=0.05):
    for _ in range(n):
        clk.advance(dt)
        router.tick(clk())


def _tiny_compiled():
    import jax
    import jax.numpy as jnp
    x = jnp.arange(8, dtype=jnp.float32)
    return jax.jit(lambda v: v * 2 + 1).lower(x).compile(), x  # mxlint: disable=hlo-raw-assert (building a Compiled to cache, not inspecting HLO)


# ----------------------------------------------------- key composition

def test_cache_key_digest_is_order_independent_and_flip_sensitive():
    a = CacheKey({"model": "m", "shape": "(4,)", "mesh": "1dev"})
    b = CacheKey({"mesh": "1dev", "shape": "(4,)", "model": "m"})
    assert a.digest == b.digest and a.filename() == b.filename()
    for comp, val in (("model", "m2"), ("shape", "(8,)"),
                      ("mesh", "2dev")):
        assert a.replace(**{comp: val}).digest != a.digest


def test_flipping_any_key_component_misses_on_disk(tmp_path):
    cache = ExecutableCache(tmp_path)
    compiled, x = _tiny_compiled()
    key = cache.key(model="fp0", shape="(8,)f32", mesh="1dev")
    assert cache.store(key, compiled)
    assert cache.load(key) is not None
    # contract hash, mesh shape, jax version, bucket shape, model —
    # each flip must miss (and must NOT quarantine the good entry)
    for comp, val in (("contract", "feedfeedfeedfeed"),
                      ("mesh", "4dev"), ("jax", "0.0.0"),
                      ("shape", "(16,)f32"), ("model", "fp1"),
                      ("salt", "rolled")):
        assert cache.load(key.replace(**{comp: val})) is None
    st = cache.stats()
    assert st["quarantined"] == 0 and st["miss"] == 6
    assert cache.load(key) is not None       # original still intact


def test_round_trip_executes_identically(tmp_path):
    cache = ExecutableCache(tmp_path)
    compiled, x = _tiny_compiled()
    want = np.asarray(compiled(x))
    key = cache.key(model="rt", shape="(8,)f32")
    exe, source = cache.load_or_compile(key, lambda: compiled)
    assert source == "cold" and cache.entries() == 1
    exe2, source2 = cache.load_or_compile(
        key, lambda: pytest.fail("hit path must not compile"))
    assert source2 == "disk"
    np.testing.assert_array_equal(np.asarray(exe2(x)), want)


def test_store_meta_round_trips(tmp_path):
    """The entry-header meta sidecar (writer audit stamp): stored at
    store(), returned by load(with_meta=True), NOT part of the key —
    and a miss hands back an empty dict, never None."""
    cache = ExecutableCache(tmp_path)
    compiled, x = _tiny_compiled()
    key = cache.key(model="meta", shape="(8,)f32")
    assert cache.store(key, compiled,
                       meta={"hlo_audit": 2, "prec_audit": 0})
    loaded, meta = cache.load(key, with_meta=True)
    assert loaded is not None
    assert meta == {"hlo_audit": 2, "prec_audit": 0}
    missed, meta2 = cache.load(key.replace(model="nope"),
                               with_meta=True)
    assert missed is None and meta2 == {}
    # meta is a sidecar, not a key component: rewriting the entry
    # under different meta still hits the same key
    assert cache.store(key, compiled, meta={"hlo_audit": 0})
    _, meta3 = cache.load(key, with_meta=True)
    assert meta3 == {"hlo_audit": 0}


def test_identical_keys_across_two_processes_hit(tmp_path):
    """A second process composes the same key (same model fp, shape,
    mesh, jax, backend, contracts) and its entry hits here — the
    rollout/restart story in one assertion."""
    child = f"""
import sys
sys.path.insert(0, {str(Path(__file__).resolve().parents[1])!r})
from mxtpu.cache import ExecutableCache
import jax, jax.numpy as jnp
cache = ExecutableCache({str(tmp_path)!r})
x = jnp.arange(8, dtype=jnp.float32)
compiled = jax.jit(lambda v: v * 2 + 1).lower(x).compile()
key = cache.key(model="xproc", shape="(8,)f32", mesh="1dev")
assert cache.store(key, compiled), "child store failed"
print(key.digest)
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", child], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    child_digest = out.stdout.strip().splitlines()[-1]
    cache = ExecutableCache(tmp_path)
    key = cache.key(model="xproc", shape="(8,)f32", mesh="1dev")
    assert key.digest == child_digest        # same key composition
    loaded = cache.load(key)
    assert loaded is not None                # verified cross-process hit
    _, x = _tiny_compiled()
    np.testing.assert_array_equal(np.asarray(loaded(x)),
                                  np.arange(8, dtype=np.float32) * 2 + 1)


def test_concurrent_writers_race_cleanly(tmp_path):
    """N writers hammer the SAME key (separate cache instances — the
    multi-process shape, minus the fork) while a reader polls: the
    reader only ever sees nothing or a valid entry, never a torn one,
    and the survivor loads clean."""
    compiled, x = _tiny_compiled()
    want = np.asarray(compiled(x))
    caches = [ExecutableCache(tmp_path) for _ in range(4)]
    key = caches[0].key(model="race", shape="(8,)f32")
    start = threading.Barrier(5)
    failures = []

    def writer(c):
        start.wait()
        for _ in range(5):
            if not c.store(key, compiled):
                failures.append("store refused")

    def reader():
        rc = ExecutableCache(tmp_path)
        start.wait()
        for _ in range(20):
            got = rc.load(key)
            if got is not None:
                if not np.array_equal(np.asarray(got(x)), want):
                    failures.append("torn/wrong entry served")
        if rc.stats()["quarantined"]:
            failures.append("reader quarantined a mid-write entry")

    threads = [threading.Thread(target=writer, args=(c,), daemon=True)
               for c in caches] + [threading.Thread(target=reader,
                                                    daemon=True)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not failures, failures
    assert not any(t.is_alive() for t in threads)
    final = ExecutableCache(tmp_path).load(key)
    assert final is not None
    np.testing.assert_array_equal(np.asarray(final(x)), want)
    # temp files all consumed by the atomic renames
    assert not list(Path(tmp_path).glob("*.tmp"))


# ----------------------------------------------- scripted cache faults

@pytest.mark.parametrize("fault_cls,reason", [
    (CorruptEntry, "checksum"),
    (TruncateEntry, "truncated"),
    (StaleKey, "stale_key"),
])
def test_poisoned_entry_quarantines_never_executes(tmp_path, fault_cls,
                                                   reason):
    plan = FaultPlan(fault_cls(at_store=0))
    cache = ExecutableCache(tmp_path, faults=plan)
    compiled, x = _tiny_compiled()
    want = np.asarray(compiled(x))
    key = cache.key(model="poison", shape="(8,)f32")
    assert cache.store(key, compiled)        # fault poisons post-commit
    assert plan.fired == [f"{fault_cls.__name__.lower()}@0"]
    assert cache.load(key) is None           # never a wrong executable
    st = cache.stats()
    assert st["quarantined"] == 1 and st["hit"] == 0
    qfiles = list((Path(tmp_path) / "quarantine").iterdir())
    assert len(qfiles) == 1 and f".{reason}." in qfiles[0].name
    # the recovery: load_or_compile recompiles and the NEXT load hits
    exe, source = cache.load_or_compile(key, lambda: compiled)
    assert source == "cold"
    exe2, source2 = cache.load_or_compile(
        key, lambda: pytest.fail("recovered entry must hit"))
    assert source2 == "disk"
    np.testing.assert_array_equal(np.asarray(exe2(x)), want)


def test_read_only_dir_falls_back_without_error(tmp_path):
    plan = FaultPlan(ReadOnlyDir(from_store=0))
    cache = ExecutableCache(tmp_path, faults=plan)
    compiled, x = _tiny_compiled()
    key = cache.key(model="ro", shape="(8,)f32")
    exe, source = cache.load_or_compile(key, lambda: compiled)
    assert source == "cold" and exe is compiled   # plain compile, no raise
    assert plan.fired == ["readonlydir@0"]
    assert not cache.writable()              # latched off, no respam
    st = cache.stats()
    assert st["fallback"] == 1 and cache.entries() == 0
    if obs.enabled():
        kinds = [e["kind"] for e in cache.recorder.events()]
        assert "fallback" in kinds           # flight-recorder evidence
    # latched: the next store is refused silently (no second fire)
    assert not cache.store(key, compiled)
    assert plan.fired == ["readonlydir@0"]


def test_cache_self_check_passes(tmp_path):
    info = self_check(root=str(tmp_path / "sc"))
    assert info["serialize_supported"] and info["round_trip"]
    assert info["poisons"] == 3 and info["read_only_fallback"]


def test_default_cache_is_knob_driven(tmp_path, monkeypatch):
    monkeypatch.delenv("MXTPU_CACHE_DIR", raising=False)
    assert default_cache() is None           # no root, no persistence
    monkeypatch.setenv("MXTPU_CACHE_DIR", str(tmp_path))
    c1 = default_cache()
    assert c1 is not None and c1.root == Path(tmp_path)
    assert default_cache() is c1             # per-root singleton
    monkeypatch.setenv("MXTPU_CACHE_SALT", "v2")
    c2 = default_cache()
    assert c2 is not c1 and c2.salt == "v2"  # salt roll = new cache
    monkeypatch.setenv("MXTPU_CACHE", "0")
    assert default_cache() is None           # master kill switch


# ------------------------------------------------- serving integration

def test_runner_warms_full_ladder_from_disk_zero_compiles(tmp_path):
    cache = ExecutableCache(tmp_path)
    donor = _mul_runner(cache=cache)
    donor.warmup()
    nbuckets = donor.num_compiled()
    assert nbuckets == len(donor.buckets()) >= 2
    assert cache.stats()["store"] == nbuckets
    x = _payload(3)
    bucket = donor.bucket_for(1)
    want = np.asarray(donor.run_raw(donor._pad_stack([x], bucket),
                                    bucket)[0])

    fresh = ExecutableCache(tmp_path)        # "new process" instance
    runner = _mul_runner(cache=fresh)
    assert sorted(runner.cached_buckets()) == sorted(runner.buckets())
    runner.warm_from_disk()
    st = fresh.stats()
    assert st["hit"] == nbuckets and st["store"] == 0  # zero compiles
    assert runner.num_compiled() == nbuckets
    got = np.asarray(runner.run_raw(runner._pad_stack([x], bucket),
                                    bucket)[0])
    np.testing.assert_array_equal(got, want)
    assert runner.num_compiled() == nbuckets  # serving added nothing


@pytest.mark.parametrize("dev", [1, 5])
def test_replica_off_device_zero_warms_from_its_own_entries(tmp_path,
                                                            dev):
    """A serialized executable names its device by id: a replica on
    device N loads what a replica on device N stored (onto exactly
    that device — not across all local devices, where a one-device
    program then wants a shard per device), and a replica elsewhere
    MISSES those entries instead of quarantining them."""
    import jax
    device = jax.devices()[dev]
    donor = _mul_runner(cache=ExecutableCache(tmp_path), device=device)
    donor.warmup()
    nbuckets = donor.num_compiled()
    x = _payload(3)
    bucket = donor.bucket_for(1)
    want = np.asarray(donor.run_raw(donor._pad_stack([x], bucket),
                                    bucket)[0])

    fresh = ExecutableCache(tmp_path)
    twin = _mul_runner(cache=fresh, device=device)
    assert sorted(twin.cached_buckets()) == sorted(twin.buckets())
    twin.warm_from_disk()
    assert fresh.stats()["hit"] == nbuckets
    out = twin.run_raw(twin._pad_stack([x], bucket), bucket)[0]
    assert out.devices() == {device}
    np.testing.assert_array_equal(np.asarray(out), want)

    other = ExecutableCache(tmp_path)
    elsewhere = _mul_runner(cache=other, device=jax.devices()[0])
    assert elsewhere.cached_buckets() == []
    elsewhere.warmup()
    st = other.stats()
    assert st["hit"] == 0 and st["quarantined"] == 0
    assert st["store"] == nbuckets


def test_key_carries_the_devices_it_loads_onto():
    import jax
    cache = ExecutableCache("/nonexistent")
    devs = tuple(jax.devices()[2:4])
    key = cache.key(model="m", shape="s", mesh="dp2", devices=devs)
    assert key.devices == devs
    assert key.components["device_ids"] == "2,3"
    assert key.replace(model="other").devices == devs
    assert key.digest != cache.key(model="m", shape="s", mesh="dp2",
                                   devices=devs[::-1]).digest
    # no devices named: the first device, where an unplaced jit runs
    assert cache.key(model="m", shape="s").devices == \
        (jax.devices()[0],)


def _fc_quant_runner(cache, quant=False):
    """One FullyConnected — the smallest graph the INT8 calibration
    pass accepts (test_quant.py owns the numerics; here it only has
    to key the cache)."""
    data = sym.var("data")
    out = sym.FullyConnected(data, sym.var("w"), sym.var("b"),
                             num_hidden=4)
    rng = np.random.RandomState(7)
    r = ModelRunner(out, {"w": rng.randn(4, 6).astype(np.float32),
                          "b": np.zeros(4, np.float32)},
                    {"data": (6,)}, max_batch_size=2, cache=cache,
                    quant=quant or None)
    if quant:
        r.calibrate([{"data": np.linspace(-1.0, 1.0, 12,
                                          dtype=np.float32)
                      .reshape(2, 6)}], mode="minmax")
    return r


def test_quantized_entries_isolated_from_float_twin(tmp_path):
    """INT8 serving (ISSUE 18) never cross-loads: the calibrated
    fingerprint plus the explicit `quant` key component keep a
    quantized runner's disk entries disjoint from its float twin's,
    while a second identically-calibrated quantized process warms
    fully from disk — and a recalibration on different data misses."""
    seed = ExecutableCache(tmp_path)
    fl = _fc_quant_runner(seed)
    fl.warmup()
    n = fl.num_compiled()
    assert n == len(fl.buckets()) >= 2
    assert seed.stats()["store"] == n

    q1 = _fc_quant_runner(ExecutableCache(tmp_path), quant=True)
    bucket = fl.buckets()[0]
    # key level: same model/bucket, the quant component alone splits
    assert q1._cache_key(bucket).digest != fl._cache_key(bucket).digest
    # the float ladder on disk is invisible to the quantized runner
    assert q1.cached_buckets() == []
    q1.warmup()
    st = q1._cache.stats()
    assert st["hit"] == 0 and st["store"] == n
    # ... and vice versa: a fresh float twin still sees only its own
    fresh = _fc_quant_runner(ExecutableCache(tmp_path))
    assert sorted(fresh.cached_buckets()) == sorted(fresh.buckets())

    # same calibration in a "new process" -> full disk warm
    q2 = _fc_quant_runner(ExecutableCache(tmp_path), quant=True)
    assert sorted(q2.cached_buckets()) == sorted(q2.buckets())
    q2.warm_from_disk()
    st2 = q2._cache.stats()
    assert st2["hit"] == n and st2["store"] == 0

    # different calibration data -> different thresholds baked into
    # the trace -> the fingerprint must miss every entry
    q3 = ModelRunner(fl._symbol, {"w": fl._param_vals[0],
                                  "b": fl._param_vals[1]},
                     {"data": (6,)}, max_batch_size=2,
                     cache=ExecutableCache(tmp_path), quant=True)
    q3.calibrate([{"data": 5.0 * np.linspace(-1.0, 1.0, 12,
                                             dtype=np.float32)
                   .reshape(2, 6)}], mode="minmax")
    assert q3.cached_buckets() == []


def test_quantized_poisoned_entry_quarantines_and_recompiles(tmp_path):
    """Quarantine-on-mismatch holds on the int8 tier too: a corrupted
    quantized entry is caught by the verify-or-quarantine loader and
    recompiled off the data path, never executed."""
    plan = FaultPlan(CorruptEntry(at_store=0))
    q1 = _fc_quant_runner(ExecutableCache(tmp_path, faults=plan),
                          quant=True)
    q1.warmup()
    n = q1.num_compiled()
    assert n >= 2 and plan.fired == ["corruptentry@0"]

    fresh = ExecutableCache(tmp_path)
    q2 = _fc_quant_runner(fresh, quant=True)
    # the existence probe still lists the poisoned bucket ...
    assert sorted(q2.cached_buckets()) == sorted(q2.buckets())
    q2.warm_from_disk()
    st = fresh.stats()
    # ... but the verified load quarantines it and recompiles
    assert st["quarantined"] == 1 and st["hit"] == n - 1
    assert st["store"] == 1
    assert q2.num_compiled() == n


def test_fleet_kill_then_disk_warmed_replacement(tmp_path):
    """The acceptance scenario: a worker dies (preemption), no donor
    handoff exists, yet the replacement serves its FIRST request with
    zero data-path compiles — its whole ladder came off disk via
    ``add_worker``'s donor-less warm path."""
    clk = FakeClock()
    seed = ExecutableCache(tmp_path)
    with _router(clk) as router:
        w0 = FleetWorker(_mul_runner(cache=seed), "w0", clock=clk,
                         max_queue_delay_us=0.0)
        router.add_worker(w0)
        w0.runner.warmup()                   # populates the disk cache
        nbuckets = w0.runner.num_compiled()
        router.kill("w0")                    # hard preemption, no drain

        fresh = ExecutableCache(tmp_path)
        w1 = FleetWorker(_mul_runner(cache=fresh), "w1", clock=clk,
                         max_queue_delay_us=0.0)
        # NO warm_from metadata — add_worker reports the disk path
        assert router.add_worker(w1) == "disk_cache"
        # the ladder is compiled BEFORE the first request, all off disk
        assert w1.runner.num_compiled() == nbuckets
        assert fresh.stats()["hit"] == nbuckets
        assert fresh.stats()["store"] == 0   # zero data-path compiles
        reqs = [router.submit(_payload(i), timeout_s=10.0)
                for i in range(6)]
        _crank(router, clk, n=4)
        for i, r in enumerate(reqs):
            np.testing.assert_allclose(r.result(timeout=0)[0],
                                       [i, 2.0 * i, 3.0 * i])
        assert w1.runner.num_compiled() == nbuckets  # still zero


def test_fleet_replacement_with_poisoned_cache_recompiles(tmp_path):
    """Kill → replace where every disk entry was corrupted in the
    meantime: the replacement quarantines each entry and recompiles —
    the poisoned executables are NEVER executed, results stay exact."""
    clk = FakeClock()
    seed = ExecutableCache(tmp_path)
    with _router(clk) as router:
        w0 = FleetWorker(_mul_runner(cache=seed), "w0", clock=clk,
                         max_queue_delay_us=0.0)
        router.add_worker(w0)
        w0.runner.warmup()
        nbuckets = w0.runner.num_compiled()
        router.kill("w0")
        for entry in Path(tmp_path).glob("*.mxc"):
            poison_corrupt(entry)            # bit-rot while it was down

        fresh = ExecutableCache(tmp_path)
        w1 = FleetWorker(_mul_runner(cache=fresh), "w1", clock=clk,
                         max_queue_delay_us=0.0)
        router.add_worker(w1)
        st = fresh.stats()
        assert st["quarantined"] == nbuckets  # every entry caught
        assert st["hit"] == 0                 # nothing poisoned served
        assert st["store"] == nbuckets        # recompiled + re-stored
        assert w1.runner.num_compiled() == nbuckets
        qdir = Path(tmp_path) / "quarantine"
        assert len(list(qdir.iterdir())) == nbuckets
        req = router.submit(_payload(5), timeout_s=10.0)
        _crank(router, clk, n=2)
        np.testing.assert_allclose(req.result(timeout=0)[0],
                                   [5.0, 10.0, 15.0])


def test_fleet_threaded_disk_warmed_replacement(tmp_path):
    """Same replacement story through the threaded router (real
    threads, real clock): outcome-asserted, not latency-asserted."""
    seed = ExecutableCache(tmp_path)
    donor = _mul_runner(cache=seed)
    donor.warmup()
    nbuckets = donor.num_compiled()
    router = FleetRouter(threaded=True, tick_s=0.002, canary=None)
    with router:
        fresh = ExecutableCache(tmp_path)
        w = FleetWorker(_mul_runner(cache=fresh), "w0",
                        max_queue_delay_us=500.0)
        router.add_worker(w)
        assert w.runner.num_compiled() == nbuckets
        assert fresh.stats() == {"hit": nbuckets, "miss": 0,
                                 "store": 0, "fallback": 0,
                                 "quarantined": 0}
        reqs = [router.submit(_payload(i % 5), timeout_s=10.0)
                for i in range(8)]
        for i, r in enumerate(reqs):
            v = i % 5
            np.testing.assert_allclose(r.result(timeout=10.0)[0],
                                       [v, 2.0 * v, 3.0 * v])
        assert w.runner.num_compiled() == nbuckets


def test_disk_hit_reaudits_when_writer_audited_less(tmp_path,
                                                    monkeypatch):
    """Regression (review): ``MXTPU_HLO_AUDIT`` is a per-process
    knob.  Entries written by a process with auditing OFF carry that
    fact in their header stamp, and a process with auditing ON that
    warms from disk re-audits each reloaded program; a reader whose
    modes are no stricter than the writer's trusts the cold-birth
    audit and skips the pass."""
    from mxtpu import analysis

    calls = []
    real = analysis.maybe_audit

    def spy(compiled, label="", mem=None):
        calls.append(label)
        return real(compiled, label=label, mem=mem)

    monkeypatch.setattr(analysis, "maybe_audit", spy)

    monkeypatch.delenv("MXTPU_HLO_AUDIT", raising=False)
    monkeypatch.delenv("MXTPU_PREC_AUDIT", raising=False)
    writer = _mul_runner(cache=ExecutableCache(tmp_path))
    writer.warmup()                          # stamped hlo_audit=0
    calls.clear()

    monkeypatch.setenv("MXTPU_HLO_AUDIT", "1")
    reader = _mul_runner(cache=ExecutableCache(tmp_path))
    warmed = reader.warm_from_disk()
    assert len(warmed) == len(reader.buckets())
    # every disk hit was re-audited (writer never audited them)
    assert len(calls) == len(reader.buckets())

    # a writer that audits at the reader's level satisfies the stamp:
    # its entries are trusted, no re-audit fires on the hit
    for f in Path(tmp_path).glob("*.mxc"):
        f.unlink()
    w2 = _mul_runner(cache=ExecutableCache(tmp_path))
    w2.warmup()                              # stamped hlo_audit=1
    calls.clear()
    r2 = _mul_runner(cache=ExecutableCache(tmp_path))
    assert len(r2.warm_from_disk()) == len(r2.buckets())
    assert calls == []                       # cold-birth audit trusted


def test_autoscaler_scale_up_warms_from_disk_cache(tmp_path):
    """No live donor, no cached handoff — the scale-up replica warms
    from the persistent cache and the ``scale_up`` flight event says
    so (``donor="disk_cache"``)."""
    obs.reset()
    clk = FakeClock()
    seed = ExecutableCache(tmp_path)
    r = _router(clk)
    w0 = FleetWorker(_mul_runner(cache=seed), "w0", clock=clk,
                     max_queue_delay_us=0.0)
    r.add_worker(w0)
    w0.runner.warmup()                       # disk holds the ladder
    nbuckets = w0.runner.num_compiled()
    made = []

    def make_worker(name):
        w = FleetWorker(_mul_runner(cache=ExecutableCache(tmp_path)),
                        name, clock=clk, max_queue_delay_us=0.0)
        made.append(w)
        return w

    scaler = Autoscaler(r, make_worker, min_workers=1, max_workers=2,
                        up_depth=3.0, down_depth=0.5, breach_ticks=2,
                        cooldown_s=0.1)
    r.add_controller(scaler.tick)
    r.kill("w0")                             # preempted; NO handoff
    reqs = [r.submit(_payload(i), timeout_s=30.0) for i in range(6)]
    for _ in range(20):
        clk.advance(0.05)
        r.tick(clk())
        if made:
            break
    assert made, "floor repair never fired"
    assert made[0].runner.num_compiled() == nbuckets  # warm, off disk
    ups = [e for e in scaler.recorder.events()
           if e["kind"] == "scale_up"]
    assert ups and ups[0]["donor"] == "disk_cache"
    _crank(r, clk, n=6)
    for i, req in enumerate(reqs):
        np.testing.assert_allclose(req.result(timeout=0)[0],
                                   [i, 2.0 * i, 3.0 * i])
    assert made[0].runner.num_compiled() == nbuckets
    r.close()


# ------------------------------------------------ training integration

def test_train_step_same_signature_different_program_misses(tmp_path):
    """Regression (review): two nets with the same container class
    and IDENTICAL param shapes/dtypes but different computations
    (relu vs tanh activations) must never share a TrainStep cache
    entry — the key fingerprints the lowered program itself, so the
    second build is a clean miss (own store), never a silent
    wrong-gradient hit; rebuilding the same program still hits."""
    import mxtpu as mx
    from mxtpu import nd, parallel
    from mxtpu.gluon import loss as gloss, nn

    cache = ExecutableCache(tmp_path)

    def build(act):
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation=act), nn.Dense(2))
        net.initialize(init="xavier")
        return parallel.build_train_step(
            net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.2}, cache=cache)

    rng = np.random.RandomState(11)
    X = nd.array(rng.randn(8, 2).astype("float32"))
    y = nd.array((rng.rand(8) > 0.5).astype("int64"))
    build("relu")(X, y)
    assert cache.stats() == {"hit": 0, "miss": 1, "store": 1,
                             "fallback": 0, "quarantined": 0}
    build("tanh")(X, y)                      # same shapes, same classes
    st = cache.stats()
    assert st["store"] == 2 and st["hit"] == 0   # program differs: miss
    build("tanh")(X, y)                      # identical program: hit
    st = cache.stats()
    assert st["hit"] == 1 and st["store"] == 2


def test_train_step_second_build_hits_disk_bit_identical(tmp_path):
    import mxtpu as mx
    from mxtpu import nd, parallel
    from mxtpu.gluon import loss as gloss, nn

    cache = ExecutableCache(tmp_path)

    def build():
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu"), nn.Dense(2))
        net.initialize(init="xavier")
        return parallel.build_train_step(
            net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.2, "momentum": 0.9}, cache=cache)

    rng = np.random.RandomState(7)
    X = rng.randn(32, 2).astype("float32")
    y = (rng.rand(32) > 0.5).astype("int64")
    losses_cold = build().run_steps(nd.array(X), nd.array(y),
                                    steps=4).asnumpy()
    assert cache.stats()["store"] == 1
    losses_warm = build().run_steps(nd.array(X), nd.array(y),
                                    steps=4).asnumpy()
    st = cache.stats()
    assert st["hit"] == 1 and st["store"] == 1  # second build off disk
    np.testing.assert_array_equal(losses_cold, losses_warm)
