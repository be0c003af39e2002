"""tools/mxlint — framework-aware static analysis (ISSUE 5).

Tier-1 gate: the repo itself must lint clean against the committed
baseline (currently empty), plus unit coverage for every rule family,
the suppression machinery, the baseline fingerprinting, and the CLI
exit-code contract.
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from tools.mxlint.core import (DEFAULT_BASELINE, DEFAULT_PATHS,
                               REPO_ROOT, FileCtx, lint_repo,
                               load_baseline, load_knobs_module,
                               split_by_baseline, write_baseline)
from tools.mxlint import rules as R


def _ctx(src: str, rel: str = "mxtpu/fake.py") -> FileCtx:
    return FileCtx(Path("/nonexistent/fake.py"), rel,
                   textwrap.dedent(src))


def _names(findings):
    return [f.rule for f in findings]


# ------------------------------------------------------------- the gate

def test_repo_lints_clean_against_baseline():
    """THE acceptance check: mxtpu/, tools/ and bench.py produce no
    findings outside tools/mxlint/baseline.json."""
    findings = lint_repo(DEFAULT_PATHS)
    new, _ = split_by_baseline(findings, load_baseline())
    assert not new, "new lint findings:\n" + "\n".join(
        f.format() for f in new)


def test_cli_exit_code_contract(tmp_path):
    """`python -m tools.mxlint --check` exits 0 on a clean tree and 1
    when a new violation appears."""
    env_ok = subprocess.run(
        [sys.executable, "-m", "tools.mxlint", "--check"],
        cwd=REPO_ROOT, capture_output=True, text=True)
    assert env_ok.returncode == 0, env_ok.stdout + env_ok.stderr

    bad = tmp_path / "violating.py"
    bad.write_text('import os\n'
                   'V = os.environ.get("MXTPU_BOGUS", "1")\n')
    env_bad = subprocess.run(
        [sys.executable, "-m", "tools.mxlint", "--check", str(bad)],
        cwd=REPO_ROOT, capture_output=True, text=True)
    assert env_bad.returncode == 1, env_bad.stdout + env_bad.stderr
    assert "knob-raw-env" in env_bad.stdout


# ------------------------------------------------------- retrace rules

def test_impure_call_in_jit_body():
    ctx = _ctx("""
        import jax, time

        @jax.jit
        def step(x):
            t0 = time.time()
            return x + t0
    """)
    found = R.RetraceImpureCall().check(ctx)
    assert _names(found) == ["retrace-impure-call"]
    assert "time.time" in found[0].message


def test_jax_random_is_not_impure():
    ctx = _ctx("""
        import jax

        @jax.jit
        def step(key, x):
            k1, k2 = jax.random.split(key)
            return x + jax.random.normal(k1, x.shape)
    """)
    assert R.RetraceImpureCall().check(ctx) == []


def test_np_random_in_jitted_name():
    ctx = _ctx("""
        import jax
        import numpy as np

        def fn(x):
            return x + np.random.randn(4)

        step = jax.jit(fn)
    """)
    assert _names(R.RetraceImpureCall().check(ctx)) == \
        ["retrace-impure-call"]


def test_traced_branch_flagged_but_static_branches_allowed():
    ctx = _ctx("""
        import jax

        @jax.jit
        def step(x, y=None):
            if y is None:          # None-ness: static, fine
                y = x
            if x.shape[0] > 2:     # shape: static, fine
                y = y * 2
            if x > 0:              # VALUE: retrace hazard
                y = y + 1
            return y
    """)
    found = R.RetraceTracedBranch().check(ctx)
    assert _names(found) == ["retrace-traced-branch"]
    assert "`x`" in found[0].message


def test_inline_jit_flagged():
    ctx = _ctx("""
        import jax

        def f(x):
            return jax.jit(lambda a: a * 2)(x)
    """)
    assert _names(R.RetraceInlineJit().check(ctx)) == \
        ["retrace-inline-jit"]


def test_concretize_in_jit_body():
    ctx = _ctx("""
        import jax

        @jax.jit
        def step(x):
            return float(x) + x.item()
    """)
    names = _names(R.RetraceConcretize().check(ctx))
    assert names == ["retrace-concretize", "retrace-concretize"]


# ----------------------------------------------------------- host-sync

_HOT_SRC = """
    # mxlint: hot-path
    import numpy as np

    def dispatch(out):
        return np.asarray(out)
"""


def test_host_sync_needs_hot_path_pragma():
    cold = _ctx(_HOT_SRC.replace("# mxlint: hot-path", "# plain"))
    assert R.HostSync().check(cold) == []
    hot = _ctx(_HOT_SRC)
    assert _names(R.HostSync().check(hot)) == ["host-sync"]


def test_host_sync_sync_point_whitelists():
    ctx = _ctx("""
        # mxlint: hot-path
        import numpy as np

        def dispatch(out):
            # mxlint: sync-point — deliberate materialization
            return np.asarray(out)
    """)
    assert R.HostSync().check(ctx) == []


def test_suppression_comment_filters_finding():
    src = """
        # mxlint: hot-path
        import numpy as np

        def dispatch(out):
            return np.asarray(out)  # mxlint: disable=host-sync
    """
    ctx = _ctx(src)
    findings = [f for f in R.HostSync().check(ctx)
                if not ctx.suppressed(f.rule, f.line)]
    assert findings == []


# ------------------------------------------------------ lock discipline

_LOCK_SRC = """
    import threading

    class Counter:
        def __init__(self):
            self._lock = threading.Lock()
            self.total = 0  # guarded-by: _lock

        def bump(self):
            with self._lock:
                self.total += 1

        def peek(self):
            return self.total          # VIOLATION: no lock

        def _sum_locked(self):
            return self.total          # convention: lock held
"""


def test_lock_discipline_flags_unlocked_access():
    found = R.LockDiscipline().check(_ctx(_LOCK_SRC))
    assert _names(found) == ["lock-discipline"]
    assert "self.total" in found[0].message and \
        "_lock" in found[0].message


def test_lock_discipline_nested_function_does_not_inherit():
    ctx = _ctx("""
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0  # guarded-by: _lock

            def go(self):
                with self._lock:
                    def cb():
                        return self.n   # runs later, unlocked
                    return cb
    """)
    assert _names(R.LockDiscipline().check(ctx)) == ["lock-discipline"]


# -------------------------------------------------------- knob registry

def test_knob_raw_env_read_flagged_but_write_allowed():
    ctx = _ctx("""
        import os
        A = os.environ.get("MXTPU_FOO", "1")
        os.environ["MXTPU_FOO"] = "0"     # write: launch/probe pattern
        B = os.environ["MXNET_BAR"]
        C = os.environ.get(dynamic_name)  # non-literal: out of scope
    """)
    found = R.KnobRawEnv().check(ctx)
    assert _names(found) == ["knob-raw-env", "knob-raw-env"]


def test_knob_raw_env_exempts_knobs_py():
    ctx = _ctx('import os\nA = os.environ.get("MXTPU_FOO")\n',
               rel="mxtpu/knobs.py")
    assert R.KnobRawEnv().check(ctx) == []


def test_knob_unregistered():
    ctx = _ctx("""
        from mxtpu import knobs
        a = knobs.get("MXTPU_GUARDS")          # registered
        b = knobs.get("MXTPU_NOT_A_KNOB")      # not
    """)
    found = R.KnobUnregistered().check(ctx)
    assert _names(found) == ["knob-unregistered"]
    assert "MXTPU_NOT_A_KNOB" in found[0].message


def test_knobs_module_standalone_load_and_types():
    mod = load_knobs_module()
    reg = mod.registered()
    assert "MXTPU_GUARDS" in reg and "MXTPU_BENCH_MODEL" in reg
    # typed defaults straight from the registry
    assert mod.get("MXTPU_SERVING_MAX_BATCH") == 32
    assert mod.get("MXTPU_FUSED_LN_EPILOGUE") is True
    with pytest.raises(Exception, match="unregistered"):
        mod.get("MXTPU_NOT_A_KNOB")


def test_knobs_env_and_mxnet_fallback(monkeypatch):
    from mxtpu import knobs
    monkeypatch.setenv("MXTPU_SERVING_MAX_BATCH", "8")
    assert knobs.get("MXTPU_SERVING_MAX_BATCH") == 8
    monkeypatch.delenv("MXTPU_SERVING_MAX_BATCH")
    monkeypatch.setenv("MXNET_SERVING_MAX_BATCH", "16")
    assert knobs.get("MXTPU_SERVING_MAX_BATCH") == 16


def test_readme_drift_detection_and_fix(tmp_path):
    root = tmp_path
    (root / "mxtpu").mkdir()
    (root / "mxtpu" / "knobs.py").write_text(
        (REPO_ROOT / "mxtpu" / "knobs.py").read_text())
    knobs = load_knobs_module(root)
    (root / "README.md").write_text(
        f"# fake\n\n{knobs.TABLE_BEGIN}\nstale\n{knobs.TABLE_END}\n")
    assert _names(R.readme_drift(root)) == ["knob-readme-drift"]
    assert R.fix_readme(root) is True
    assert R.readme_drift(root) == []
    assert R.fix_readme(root) is False  # idempotent


def test_real_readme_table_is_current():
    assert R.readme_drift(REPO_ROOT) == []


# --------------------------------------------------------- obs registry

def test_obs_registry_naming_convention():
    src = """
        from mxtpu import obs
        ok1 = obs.counter("mxtpu_req_total", "fine")
        ok2 = obs.histogram("mxtpu_wait_seconds", "fine")
        ok3 = obs.gauge("mxtpu_depth", "fine")
        bad1 = obs.counter("requests_total", "no prefix")
        bad2 = obs.counter("mxtpu_requests", "no _total")
        bad3 = obs.histogram("mxtpu_wait", "no unit suffix")
        bad4 = obs.gauge("mxtpu_BadName", "not snake_case")
    """
    found = R.ObsRegistry().check(_ctx(src))
    assert _names(found) == ["obs-registry"] * 4
    assert {f.line for f in found} == {6, 7, 8, 9}


def test_obs_registry_hot_path_counters():
    src = """
        from mxtpu import profiler
        _N_CALLS = 0
        _RETRY_COUNT = 0
        PAD = 1
        c = profiler.Counter("batches", 0)
    """
    # flagged inside the serving/parallel hot paths...
    found = R.ObsRegistry().check(
        _ctx(src, rel="mxtpu/serving/fake.py"))
    msgs = " ".join(f.message for f in found)
    assert len(found) == 3
    assert "_N_CALLS" in msgs and "_RETRY_COUNT" in msgs
    assert "profiler.Counter" in msgs
    # ... but not elsewhere (profiler.py itself, examples, ...)
    assert R.ObsRegistry().check(
        _ctx(src, rel="mxtpu/other.py")) == []


def test_obs_registry_suppression():
    src = """
        from mxtpu import profiler
        _N_CALLS = 0  # mxlint: disable=obs-registry
    """
    ctx = _ctx(src, rel="mxtpu/parallel/fake.py")
    found = [f for f in R.ObsRegistry().check(ctx)
             if not ctx.suppressed(f.rule, f.line)]
    assert found == []


# -------------------------------------------------------- thread-hygiene

def test_thread_hygiene_flags_sleep_polling_loop():
    src = """
        import time
        import threading

        def worker(stop):
            while not stop.is_set():
                time.sleep(0.1)

        def fine(stop):
            while not stop.is_set():
                stop.wait(0.1)
        time.sleep(1.0)   # outside a loop: startup delay, allowed
    """
    found = R.ThreadHygiene().check(
        _ctx(src, rel="mxtpu/serving/fake.py"))
    assert _names(found) == ["thread-hygiene"]
    assert found[0].line == 7 and "time.sleep" in found[0].message


def test_thread_hygiene_flags_non_daemon_thread():
    src = """
        import threading
        t_bad = threading.Thread(target=print)
        t_also_bad = threading.Thread(target=print, daemon=False)
        t_ok = threading.Thread(target=print, daemon=True)
    """
    found = R.ThreadHygiene().check(
        _ctx(src, rel="mxtpu/obs/fake.py"))
    assert _names(found) == ["thread-hygiene"] * 2
    assert {f.line for f in found} == {3, 4}


def test_thread_hygiene_scoped_to_serving_and_obs():
    src = """
        import time
        import threading
        t = threading.Thread(target=print)
        def spin():
            while True:
                time.sleep(1)
    """
    # outside serving/obs the rule does not apply at all
    assert R.ThreadHygiene().applies(
        _ctx(src, rel="mxtpu/parallel/fake.py")) is False
    assert R.ThreadHygiene().applies(
        _ctx(src, rel="mxtpu/serving/fake.py")) is True


# ------------------------------------------------------- dtype hygiene

def test_dtype_hygiene_flags_f64_forms():
    ctx = _ctx("""
        import numpy as np
        import jax

        def widen(x):
            jax.config.update("jax_enable_x64", True)
            y = x.astype(np.float64)
            return np.float64(y.sum())
    """)
    found = R.DtypeHygiene().check(ctx)
    assert _names(found) == ["dtype-hygiene"] * 3
    msgs = " ".join(f.message for f in found)
    assert "jax_enable_x64" in msgs
    assert ".astype(float64)" in msgs
    assert "float64 literal" in msgs


def test_dtype_hygiene_astype_string_and_pragma():
    ctx = _ctx("""
        def narrow(x):
            a = x.astype("float64")
            b = x.astype("float64")  # mxlint: disable=dtype-hygiene
            return a + b
    """)
    found = [f for f in R.DtypeHygiene().check(ctx)
             if not ctx.suppressed(f.rule, f.line)]
    assert len(found) == 1
    assert found[0].line == 3


def test_dtype_hygiene_scoped_to_library_code():
    src = """
        import numpy as np
        SEED = np.float64(0.5)
    """
    # tests/ and tools/ seed f64 on purpose (the f64-creep rule's
    # fixtures live there) — only mxtpu/ is held to the policy
    assert R.DtypeHygiene().applies(
        _ctx(src, rel="tests/test_fake.py")) is False
    assert R.DtypeHygiene().applies(
        _ctx(src, rel="tools/fake.py")) is False
    assert R.DtypeHygiene().applies(
        _ctx(src, rel="mxtpu/fake.py")) is True


# ------------------------------------------------------- no-adhoc-bf16

def test_no_adhoc_bf16_flags_cast_forms():
    ctx = _ctx("""
        import jax.numpy as jnp

        def forward(self, F, x, net):
            a = x.astype("bfloat16")
            b = x.astype(jnp.bfloat16)
            c = F.cast(x, dtype="bf16")
            net.cast("bfloat16")
            w = F.zeros((4, 4), dtype="bfloat16")
            return a, b, c, w
    """, rel="mxtpu/models/fake.py")
    found = R.NoAdhocBf16().check(ctx)
    assert _names(found) == ["no-adhoc-bf16"] * 5
    assert {f.line for f in found} == {5, 6, 7, 8, 9}
    msgs = " ".join(f.message for f in found)
    assert "amp_policy.json" in msgs


def test_no_adhoc_bf16_pragma_waives():
    ctx = _ctx("""
        def forward(x):
            a = x.astype("bfloat16")
            b = x.astype("bfloat16")  # mxlint: disable=no-adhoc-bf16
            return a + b
    """, rel="mxtpu/gluon/fake.py")
    found = [f for f in R.NoAdhocBf16().check(ctx)
             if not ctx.suppressed(f.rule, f.line)]
    assert len(found) == 1
    assert found[0].line == 3


def test_no_adhoc_bf16_scoped_to_hot_paths():
    src = """
        def forward(x):
            return x.astype("bfloat16")
    """
    # the amp module, parallel's entry upcasts and tests cast bf16 on
    # purpose — only the model/layer hot paths are held to the policy
    rule = R.NoAdhocBf16()
    assert rule.applies(_ctx(src, rel="mxtpu/models/fake.py")) is True
    assert rule.applies(_ctx(src, rel="mxtpu/gluon/fake.py")) is True
    assert rule.applies(_ctx(src, rel="mxtpu/amp/fake.py")) is False
    assert rule.applies(_ctx(src, rel="mxtpu/parallel/fake.py")) \
        is False
    assert rule.applies(_ctx(src, rel="tests/test_fake.py")) is False


# ----------------------------------------------------- raw-deserialize

def test_raw_deserialize_flags_pickle_and_executable_load():
    ctx = _ctx("""
        import pickle, marshal
        from jax.experimental import serialize_executable

        def load(path):
            with open(path, "rb") as f:
                a = pickle.load(f)
            b = pickle.loads(open(path, "rb").read())
            c = marshal.loads(open(path, "rb").read())
            d = serialize_executable.deserialize_and_load(a, b, c)
            return d
    """)
    found = R.RawDeserialize().check(ctx)
    assert _names(found) == ["raw-deserialize"] * 4
    msgs = " ".join(f.message for f in found)
    assert "pickle.load" in msgs
    assert "deserialize_and_load" in msgs
    assert "WRONG program" in msgs


def test_raw_deserialize_pragma_waives():
    ctx = _ctx("""
        import pickle

        def load(blob):
            return pickle.loads(blob)  # mxlint: disable=raw-deserialize (in-process bytes)
    """)
    found = [f for f in R.RawDeserialize().check(ctx)
             if not ctx.suppressed(f.rule, f.line)]
    assert found == []


def test_raw_deserialize_cache_module_is_the_sanctioned_door():
    src = """
        import pickle
        def load(blob):
            return pickle.loads(blob)
    """
    # the checksum-verified loader in mxtpu/cache.py is THE one place
    # allowed to revive disk bytes; tests stay exempt like the other
    # source-hygiene rules
    assert R.RawDeserialize().applies(
        _ctx(src, rel="mxtpu/cache.py")) is False
    assert R.RawDeserialize().applies(
        _ctx(src, rel="tests/test_fake.py")) is False
    assert R.RawDeserialize().applies(
        _ctx(src, rel="mxtpu/serving/runner.py")) is True
    assert R.RawDeserialize().applies(
        _ctx(src, rel="tools/fake.py")) is True


# ------------------------------------------------------------- baseline

def test_baseline_fingerprint_survives_line_moves(tmp_path):
    src = """
        import os
        PAD = 1
        A = os.environ.get("MXTPU_FOO", "1")
    """
    f1 = R.KnobRawEnv().check(_ctx(src))[0]
    # same line text, shifted three lines down
    f2 = R.KnobRawEnv().check(_ctx("\n\n\n" + textwrap.dedent(src)))[0]
    for f in (f1, f2):
        f.snippet = 'A = os.environ.get("MXTPU_FOO", "1")'
    assert f1.fingerprint == f2.fingerprint

    path = tmp_path / "baseline.json"
    write_baseline([f1], path)
    new, old = split_by_baseline([f2], load_baseline(path))
    assert new == [] and old == [f2]


def test_committed_baseline_is_empty():
    """ISSUE 5 acceptance: the tree lints clean — every real finding
    was fixed or judged and annotated in place, none baselined."""
    data = json.loads(DEFAULT_BASELINE.read_text())
    assert data["fingerprints"] == []
