"""tools/launch.py --launcher local on a host with TPU chips: one
process per chip.  The chip count and the children are faked — the
launcher itself must never import JAX, which would take the chips."""
import os
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

from tools import launch  # noqa: E402


class _FakeProc:
    def wait(self):
        return 0


@pytest.fixture
def spawned(monkeypatch):
    envs = []

    def popen(command, env):
        envs.append(env)
        return _FakeProc()

    monkeypatch.setattr(launch.subprocess, "Popen", popen)
    return envs


def _run(monkeypatch, n, chips, platforms=None):
    monkeypatch.setattr(launch, "local_tpu_chips", lambda: chips)
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    monkeypatch.setattr(sys, "argv", [
        "launch.py", "-n", str(n), "--launcher", "local", "true"])
    with pytest.raises(SystemExit) as e:
        launch.main()
    return e.value.code


@pytest.mark.parametrize("n,chips", [(2, 1), (3, 4), (5, 4), (2, 4)])
def test_local_refuses_children_that_would_share_chips(
        monkeypatch, spawned, n, chips):
    assert _run(monkeypatch, n, chips) == 2      # argparse error
    assert spawned == []


def test_local_gives_each_child_its_own_chip(monkeypatch, spawned):
    assert _run(monkeypatch, 4, 4) == 0
    assert [e["TPU_VISIBLE_CHIPS"] for e in spawned] == list("0123")
    assert len({e["TPU_PROCESS_PORT"] for e in spawned}) == 4
    assert {e["TPU_PROCESS_BOUNDS"] for e in spawned} == {"2,2,1"}
    assert [e["JAX_PROCESS_ID"] for e in spawned] == list("0123")


@pytest.mark.parametrize("n,chips,platforms", [
    (3, 4, "cpu"),      # children pinned to the CPU: no chip needed
    (3, 0, None),       # no chips on the host
    (1, 4, None),       # one child may have every chip
])
def test_local_leaves_libtpu_alone_when_no_chip_is_shared(
        monkeypatch, spawned, n, chips, platforms):
    assert _run(monkeypatch, n, chips, platforms) == 0
    assert len(spawned) == n
    inherited = {k: os.environ[k] for k in launch.chip_env(0, 1)
                 if k in os.environ}
    for env in spawned:
        assert {k: env[k] for k in launch.chip_env(0, 1)
                if k in env} == inherited


def test_launcher_source_never_imports_jax():
    with open(launch.__file__) as f:
        src = f.read()
    assert "import jax" not in src
