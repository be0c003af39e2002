"""Rehearse ``chip_smoke.py`` at a toy size on the CPU.

The chip run itself (``python chip_smoke.py``, BERT-Large, one TPU) is
made through the builder's chip tool; what can rot without a chip is
the script's own control flow — the phases, their checks, the refusal
to run off the chip.  These tests drive the very phase functions
``main()`` calls, with a toy ``Sizes``, on the virtual CPU devices.
Pallas runs nowhere here, so the programs hold no ``tpu_custom_call``
— the presence check is ``main()``'s, on the chip.
"""
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import chip_smoke  # noqa: E402

TOY = chip_smoke.Sizes(
    vocab=97, units=32, hidden=64, layers=2, heads=2, max_length=32,
    batch=4, seq=16, steps=3, scan_steps=2, compare_batch=8,
    lanes=3, prompt_buckets=(4, 16), prompt_lens=(2, 3, 5, 6),
    max_tokens=6, fleet_seq=8, fleet_batch=2, fleet_requests=12)


def test_train_phase_toy():
    import jax
    facts = chip_smoke.train_phase(TOY, 0, jax.devices()[0])
    assert facts["batch"] == TOY.batch
    assert len(facts["losses"]) == TOY.steps + 2 * TOY.scan_steps
    assert facts["losses"][-1] < facts["losses"][0]
    assert facts["param_platforms"] == ["cpu"]
    assert facts["pallas_calls"] == {}       # the lax reference, on CPU


def test_serve_phase_toy(tmp_path):
    import jax
    facts = chip_smoke.serve_phase(TOY, 0, jax.devices()[0],
                                   str(tmp_path))
    assert [len(s) for s in facts["streams"]] == \
        [TOY.max_tokens] * len(TOY.prompt_lens)
    assert facts["weight_platforms"] == ["cpu"]


def test_zero_phase_toy_on_four_virtual_devices():
    import jax
    facts = chip_smoke.zero_phase(TOY, 0, jax.devices()[:4])
    assert facts["full"]["collectives"]["reduce-scatter"] >= 1
    assert facts["full"]["collectives"]["all-gather"] >= 1
    assert facts["one_device"]["collectives"] == {}
    assert 0.25 <= facts["opt_state_ratio"] <= 0.2625


def test_fleet_phase_toy_one_replica_per_device(tmp_path):
    import jax
    facts = chip_smoke.fleet_phase(TOY, 0, jax.devices()[:4],
                                   str(tmp_path))
    assert sorted(facts["served"]) == ["w0", "w1", "w2", "w3"]
    assert sum(facts["served"].values()) == TOY.fleet_requests


def test_failed_check_raises():
    with pytest.raises(chip_smoke.SmokeFailure, match="boom"):
        chip_smoke.check(False, "boom")


def test_pallas_calls_counts_by_kernel_file():
    text = """HloModule m

FileNames
1 "/x/mxtpu/kernels/flash_attention.py"
2 "/x/mxtpu/kernels/layer_norm.py"

FileLocations
1 {file_name_id=1 function_name_id=1 line=207 end_line=207 column=1 end_column=2}
2 {file_name_id=2 function_name_id=1 line=364 end_line=364 column=1 end_column=2}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=2}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %c.1 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="pallas_call" stack_frame_id=1}
  %c.2 = f32[8]{0} custom-call(%c.1), custom_call_target="tpu_custom_call", metadata={op_name="pallas_call" stack_frame_id=2}
  %c.3 = f32[8]{0} custom-call(%c.2), custom_call_target="tpu_custom_call", metadata={op_name="pallas_call" stack_frame_id=2}
  ROOT %t = f32[8]{0} custom-call(%c.3), custom_call_target="Sharding"
}
"""
    assert chip_smoke.pallas_calls(text) == {
        "flash_attention.py": 1, "layer_norm.py": 2}


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_refuses_to_run_without_a_tpu(argv):
    """Off the chip the script exits non-zero, names the platform it
    found and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "chip_smoke.py")] + argv,
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout
