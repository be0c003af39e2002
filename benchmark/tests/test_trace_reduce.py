"""trace_reduce.py on a small recorded trace (a CPU run of three spans
``bench:step`` round a jitted matrix product, ``data/small.xplane.pb``),
and the program-text reader on a cut of a compiled program."""
import os

from benchmark import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def small():
    return trace_reduce.load(os.path.join(DATA, "small.xplane.pb"))


def test_spans_and_device_ops_are_found():
    t = small()
    spans = trace_reduce.spans_named(t, "step")
    assert len(spans) == 3 and all(d > 0 for _, d in spans)
    assert list(t.devices) == ["host-as-device"]
    names = {n for n, _, _ in t.devices["host-as-device"]}
    assert "dot_general.1" in names
    assert t.extent[0] < t.extent[1]


def test_busy_idle_and_breakdown():
    t = small()
    window = trace_reduce.window_seconds(t)
    busy = trace_reduce.busy_seconds(t)
    assert 0 < busy < window
    assert trace_reduce.idle_pct(t) == 100.0 * (1.0 - busy / window)
    top = trace_reduce.top_ops(t, 3)
    assert top[0][0] == "dot_general.1" and top[0][1] > 0
    assert sum(sec for _, sec in trace_reduce.top_ops(t, 100)) >= busy
    gaps = dict(trace_reduce.idle_gaps(t))
    assert abs(sum(gaps.values()) - (window - busy)) < 1e-9
    inside, n = trace_reduce.device_seconds_within(t, "step")
    assert n == 3 and 0 < inside <= busy + 1e-12
    sec, events = trace_reduce.op_seconds(t, {"dot_general.1"})
    assert events == 3 and abs(sec - top[0][1]) < 1e-12


def test_stems():
    assert trace_reduce.stem("fusion.535") == "fusion"
    assert trace_reduce.stem("jvp__.113") == "jvp__"
    assert trace_reduce.stem("copy") == "copy"
    t = small()
    by_stem = dict(trace_reduce.top_ops(t, 10, key=trace_reduce.stem))
    assert by_stem["dot_general"] == dict(
        trace_reduce.top_ops(t, 10))["dot_general.1"]


def test_intervals():
    assert trace_reduce.merged([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    cover = trace_reduce.Cover([(0, 2), (3, 4)])
    assert cover.within(1, 3.5) == 1.5 and cover.until(10) == 3
    assert list(cover.gaps(-1, 5)) == [(-1, 0), (2, 3), (4, 5)]


def test_self_seconds():
    t = trace_reduce.Trace(
        {}, [("outer", 0.0, 1.0), ("a", 0.1, 0.2), ("b", 0.5, 0.1),
             ("outer", 2.0, 1.0)], (0.0, 3.0), {})
    own = trace_reduce.self_seconds(t, "outer", ("a", "b"))
    assert [round(x, 9) for x in own] == [0.7, 1.0]
    index = trace_reduce.SpanIndex(t.spans)
    assert index.at(0.15) == "a" and index.at(0.45) == "outer"
    assert index.at(1.5).startswith("outside")


HLO = '''HloModule jit_step
FileNames
1 "/x/mxtpu/kernels/flash_attention.py"
2 "/x/mxtpu/kernels/layer_norm.py"
FunctionNames
1 "fwd"
FileLocations
1 {file_name_id=1 function_name_id=1 line=5 end_line=5 column=1 end_column=2}
2 {file_name_id=2 function_name_id=1 line=9 end_line=9 column=1 end_column=2}
StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=2}

ENTRY %main {
  %custom-call.3 = bf16[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="a" stack_frame_id=1}
  %custom-call.4 = bf16[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="b" stack_frame_id=2}
  ROOT %custom-call.5 = bf16[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="c" stack_frame_id=1}
  %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, metadata={stack_frame_id=1}
}
'''


def test_custom_calls_by_file():
    got = trace_reduce.custom_calls_by_file(HLO)
    assert got == {"flash_attention.py": ["custom-call.3", "custom-call.5"],
                   "layer_norm.py": ["custom-call.4"]}


def test_tpu_event_names_are_cut_to_the_instruction():
    assert trace_reduce.op_name(
        '%jvp__.113 = (bf16[128,512,64]{2,1,0}) custom-call(bf16[8] %b), '
        'custom_call_target="tpu_custom_call"') == "jvp__.113"
    assert trace_reduce.op_name("dot_general.1") == "dot_general.1"


def _flash_reading(calls_per_layer, layers=2, steps=3, call_s=1e-3):
    """A step program with ``calls_per_layer`` flash-attention calls a
    layer and one LayerNorm call, and a trace of ``steps`` steps."""
    import types
    rows, events, t = [], [], 0.0
    for i in range(calls_per_layer * layers):
        rows.append(f'  %jvp__.{i} = bf16[8]{{0}} custom-call(%p), '
                    f'custom_call_target="tpu_custom_call", '
                    f'metadata={{op_name="a" stack_frame_id=1}}')
    rows.append('  %jvp__.99 = bf16[8]{0} custom-call(%p), '
                'custom_call_target="tpu_custom_call", '
                'metadata={op_name="b" stack_frame_id=2}')
    text = HLO.split("ENTRY")[0] + "ENTRY %main {\n" + "\n".join(rows) + "\n}\n"
    for _ in range(steps):
        for i in list(range(calls_per_layer * layers)) + [99]:
            events.append((f"jvp__.{i}", t, call_s))
            t += 2 * call_s
    trace = trace_reduce.Trace({"/device:TPU:0": events}, [], (0.0, t), {})
    cfg = {"num_hidden_layers": layers, "num_attention_heads": 16,
           "hidden_size": 1024, "causal": False}
    mix = {"batch": 8, "seq": 512, "compute_dtype": "bfloat16"}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return types.SimpleNamespace(
        facts={"hlo_text": text}, trace=trace, cfg=cfg, mix=mix, peaks=peaks,
        note=lambda *a, **k: None)


def test_flash_reader_counts_the_calls_the_program_holds():
    """Forward-only programs are held against the forward's least time,
    not the forward's and the backward's: 8 x 16 heads x 512 x 512 x 64
    is 8.59 GFLOP forward, 43.6 us at 197 TFLOP/s, and the backward 2.5
    times that."""
    from benchmark import run as run_mod
    fwd = 4.0 * 8 * 16 * 512 * 512 * 64 / 197e12
    got = run_mod.read_metric("flash_attention_roofline", _flash_reading(1))
    assert abs(got - 100.0 * fwd / 1e-3) < 1e-9
    got = run_mod.read_metric("flash_attention_roofline", _flash_reading(3))
    assert abs(got - 100.0 * 3.5 * fwd / 3e-3) < 1e-9
    # two calls a layer: not a program this reader knows
    assert run_mod.read_metric("flash_attention_roofline",
                               _flash_reading(2)) is None
