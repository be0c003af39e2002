"""bench.py must not lie about where or whether it ran (ISSUE 21):
no CPU default, no CPU run under device metric names, no ``mfu: null``
for a device it does not know, no exit 0 after a row failed."""
import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import bench  # noqa: E402


def test_no_platform_default_in_code():
    with open(bench.__file__) as f:
        src = f.read()
    assert 'setdefault("JAX_PLATFORMS"' not in src
    assert "os._exit(0)" not in src


def test_refuses_to_bench_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXTPU_BENCH_MODEL="lenet")
    r = subprocess.run([sys.executable, os.path.join(_ROOT, "bench.py")],
                       capture_output=True, text=True, env=env,
                       timeout=300)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr and "'cpu'" in r.stderr
    assert r.stdout.strip() == ""            # no metric line at all


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no peak bf16"):
        bench._peak_flops()                  # device_kind here: "cpu"


def _run_main(monkeypatch, capsys, row):
    monkeypatch.setenv("MXTPU_BENCH_MODEL", "lenet")
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.setattr(bench, "_device", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(bench, "_peak_flops", lambda: 197e12)
    monkeypatch.setattr(bench, "bench_lenet", row)
    code = 0
    try:
        bench.main()
    except SystemExit as e:
        code = e.code
    return code, json.loads(capsys.readouterr().out.strip()
                            .splitlines()[-1])


def test_failed_row_is_on_record_and_exit_is_nonzero(monkeypatch,
                                                     capsys):
    def boom():
        raise RuntimeError("row blew up")

    code, out = _run_main(monkeypatch, capsys, boom)
    assert code not in (0, None)
    assert "RuntimeError: row blew up" in out["error"]
    assert out["value"] is None
    assert out["device"]["platform"] == "tpu"


def test_good_row_names_its_device_and_exits_zero(monkeypatch, capsys):
    stats = {"best": 10.0, "median": 9.0, "n": 3, "spread": 0.1,
             "info": {"hbm_peak": 1}}
    code, out = _run_main(
        monkeypatch, capsys,
        lambda: (stats, "lenet_mnist_train_throughput", "samples/sec"))
    assert code in (0, None)
    assert out["value"] == 10.0
    assert out["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                             "count": 1}
    assert "vs_baseline" not in out and "within_noise" not in out
