"""C predict ABI: ctypes driver + a real compiled C program, both
running an exported model through libmxtpu_predict.so.

Reference: ``include/mxnet/c_predict_api.h``† /
``src/c_api/c_predict_api.cc``† and the predict-cpp example†.
"""
import ctypes
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import nd
from mxtpu.gluon import nn

_CORE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "core")
_LIB = os.path.join(_CORE, "libmxtpu_predict.so")


def _build_lib():
    if shutil.which("g++") is None or shutil.which("make") is None:
        pytest.skip("g++/make not available")
    # link against THIS interpreter, not whatever python3 is on PATH
    r = subprocess.run(["make", "predict", f"PYTHON={sys.executable}"],
                       cwd=_CORE, capture_output=True, text=True)
    # toolchain present → a failing build is a real regression, not a
    # skip condition
    assert r.returncode == 0, \
        f"libmxtpu_predict build failed: {r.stderr[-1000:]}"


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    d = tmp_path_factory.mktemp("cpredict")
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize(init="xavier")
    x = nd.array(np.random.RandomState(0).randn(2, 8)
                 .astype(np.float32))
    y0 = net(x).asnumpy()
    sym_file, param_file = net.export(str(d / "model"))
    return sym_file, param_file, np.asarray(x.asnumpy()), y0


def _load():
    if not os.path.exists(_LIB):
        _build_lib()
    lib = ctypes.CDLL(_LIB)
    lib.MXGetLastError.restype = ctypes.c_char_p
    return lib


def test_ctypes_full_flow(model):
    sym_file, param_file, x, y0 = model
    lib = _load()
    with open(sym_file) as f:
        sym_json = f.read().encode()
    with open(param_file, "rb") as f:
        params = f.read()

    handle = ctypes.c_void_p()
    keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint * 2)(0, 2)
    shape = (ctypes.c_uint * 2)(*x.shape)
    rc = lib.MXPredCreate(sym_json, params, len(params), 1, 0, 1,
                          keys, indptr, shape, ctypes.byref(handle))
    assert rc == 0, lib.MXGetLastError().decode()

    data = x.astype(np.float32).ravel()
    rc = lib.MXPredSetInput(
        handle, b"data",
        data.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        data.size)
    assert rc == 0, lib.MXGetLastError().decode()
    assert lib.MXPredForward(handle) == 0, \
        lib.MXGetLastError().decode()

    sdata = ctypes.POINTER(ctypes.c_uint)()
    ndim = ctypes.c_uint()
    rc = lib.MXPredGetOutputShape(handle, 0, ctypes.byref(sdata),
                                  ctypes.byref(ndim))
    assert rc == 0, lib.MXGetLastError().decode()
    oshape = tuple(sdata[i] for i in range(ndim.value))
    assert oshape == y0.shape

    out = np.zeros(int(np.prod(oshape)), np.float32)
    rc = lib.MXPredGetOutput(
        handle, 0,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), out.size)
    assert rc == 0, lib.MXGetLastError().decode()
    np.testing.assert_allclose(out.reshape(oshape), y0, rtol=1e-5,
                               atol=1e-5)
    assert lib.MXPredFree(handle) == 0

    # error paths surface through MXGetLastError
    h2 = ctypes.c_void_p()
    rc = lib.MXPredCreate(b"not json", params, len(params), 1, 0, 1,
                          keys, indptr, shape, ctypes.byref(h2))
    assert rc == -1
    assert lib.MXGetLastError()


def test_predictor_semantics(model):
    """ABI-level contracts, tested at the Python half: output shapes
    available BEFORE forward (reference create→shape→alloc pattern),
    and only declared inputs are writable."""
    from mxtpu.base import MXNetError
    from mxtpu.c_predict import Predictor
    sym_file, param_file, x, y0 = model
    with open(sym_file) as f:
        sym_json = f.read()
    with open(param_file, "rb") as f:
        params = f.read()
    p = Predictor(sym_json, params, 1, 0, {"data": x.shape})
    assert p.get_output_shape(0) == y0.shape  # pre-forward
    assert p.num_outputs() == 1
    with pytest.raises(MXNetError, match="not a declared input"):
        p.set_input("dense36_weight",
                    np.zeros(4, np.float32).tobytes())
    with pytest.raises(MXNetError, match="forward"):
        p.get_output(0)
    p.set_input("data", x.astype(np.float32).tobytes())
    p.forward()
    got = np.frombuffer(p.get_output(0), np.float32) \
        .reshape(p.get_output_shape(0))
    np.testing.assert_allclose(got, y0, rtol=1e-5, atol=1e-5)


def test_reshape_shares_device_buffers(model):
    """MXPredReshape zero-copy contract: a reshape clone binds the SAME
    weight NDArrays (same underlying device buffers — no second
    upload), and its outputs match a fresh bind at the new shape."""
    from mxtpu.c_predict import Predictor
    sym_file, param_file, x, y0 = model
    with open(sym_file) as f:
        sym_json = f.read()
    with open(param_file, "rb") as f:
        params = f.read()
    p = Predictor(sym_json, params, 1, 0, {"data": x.shape})
    clone = p.reshape({"data": (5, x.shape[1])})

    weight_names = [k for k in p._executor.arg_dict
                    if k not in p._input_names]
    assert weight_names
    for k in weight_names:
        a, b = p._executor.arg_dict[k], clone._executor.arg_dict[k]
        assert a is b                 # same NDArray object...
        assert a.data is b.data       # ...wrapping the same jax buffer

    x5 = np.random.RandomState(1).randn(5, x.shape[1]) \
        .astype(np.float32)
    clone.set_input("data", x5.tobytes())
    clone.forward()
    got = np.frombuffer(clone.get_output(0), np.float32) \
        .reshape(clone.get_output_shape(0))
    fresh = Predictor(sym_json, params, 1, 0, {"data": x5.shape})
    fresh.set_input("data", x5.tobytes())
    fresh.forward()
    want = np.frombuffer(fresh.get_output(0), np.float32) \
        .reshape(fresh.get_output_shape(0))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # the original's binding is untouched by the clone
    p.set_input("data", x.astype(np.float32).tobytes())
    p.forward()
    np.testing.assert_allclose(
        np.frombuffer(p.get_output(0), np.float32).reshape(y0.shape),
        y0, rtol=1e-5, atol=1e-5)


def test_int32_inputs_cross_wire_exactly(tmp_path):
    """Integer bindings are honoured on the wire: an int32 token-id
    input reads its bytes as int32 (ids above 2^24 must survive —
    float32 wire silently corrupted them), and integer outputs declare
    their dtype via get_output_dtype."""
    from mxtpu import symbol as sym
    from mxtpu.c_predict import Predictor
    big = 2 ** 24 + 3   # not representable in float32
    data = sym.var("data", dtype="int32")
    graph = data + data   # stays int32; 2*big still needs > 24 bits
    ids = np.array([[1, 7, big]], np.int32)
    pfile = str(tmp_path / "int32.params")
    nd.save(pfile, {"arg:unused": nd.zeros((1,))})
    with open(pfile, "rb") as f:
        blob = f.read()

    p = Predictor(graph.tojson(), blob, 1, 0, {"data": ids.shape})
    # bound dtype resolved from the var's __dtype__ attr
    assert p._executor.arg_dict["data"].dtype == np.int32
    p.set_input("data", ids.tobytes())   # int32 bytes, verbatim
    p.forward()
    assert p.get_output_dtype(0) == "int32"
    got = np.frombuffer(p.get_output(0), np.int32) \
        .reshape(p.get_output_shape(0))
    np.testing.assert_array_equal(got, ids * 2)   # exact, no 2^24 loss
    # explicit input_dtypes wins too, and survives reshape clones
    p2 = Predictor(graph.tojson(), blob, 1, 0, {"data": ids.shape},
                   input_dtypes={"data": "int32"})
    clone = p2.reshape({"data": (1, 2)})
    assert clone._executor.arg_dict["data"].dtype == np.int32
    clone.set_input("data", ids[:, :2].tobytes())
    clone.forward()
    np.testing.assert_array_equal(
        np.frombuffer(clone.get_output(0), np.int32),
        ids.ravel()[:2] * 2)


def test_float_outputs_keep_float32_wire(model):
    """ABI back-compat: floating bindings still cross as float32."""
    from mxtpu.c_predict import Predictor
    sym_file, param_file, x, y0 = model
    with open(sym_file) as f:
        sym_json = f.read()
    with open(param_file, "rb") as f:
        params = f.read()
    p = Predictor(sym_json, params, 1, 0, {"data": x.shape})
    p.set_input("data", x.astype(np.float32).tobytes())
    p.forward()
    assert p.get_output_dtype(0) == "float32"
    assert len(p.get_output(0)) == int(np.prod(y0.shape)) * 4


def test_compiled_c_program(model, tmp_path):
    """Compile predict_example.c with gcc/g++ and run it as a true
    external C consumer (embedded interpreter boot path)."""
    if shutil.which("g++") is None:
        pytest.skip("g++ not available")
    sym_file, param_file, x, y0 = model
    if not os.path.exists(_LIB):
        _build_lib()
    exe = str(tmp_path / "predict")
    r = subprocess.run(
        ["g++", os.path.join(_CORE, "predict_example.c"),
         f"-L{_CORE}", "-lmxtpu_predict", f"-Wl,-rpath,{_CORE}",
         f"-I{_CORE}", "-o", exe],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    inp = str(tmp_path / "input.f32")
    x.astype(np.float32).tofile(inp)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(_CORE) + os.pathsep + \
        env.get("PYTHONPATH", "")
    r = subprocess.run(
        [exe, sym_file, param_file, f"{x.shape[0]},{x.shape[1]}", inp],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr + r.stdout
    assert f"output shape: {y0.shape[0]} {y0.shape[1]}" in r.stdout
    got = [float(v) for v in
           r.stdout.split("output:")[1].split()]
    # the embedded interpreter initialises its own JAX and may land
    # on a different backend than this process — compare at
    # cross-backend tolerance
    np.testing.assert_allclose(got, y0.ravel()[:len(got)], rtol=2e-2,
                               atol=5e-3)
