"""C inference API tests (native/pt_capi.cc, the capi_exp equivalent).

A real C program is compiled with g++ and linked against the C API library;
it loads a saved inference model, runs it, and prints the output, which
is compared against the in-process Python predictor.
"""

import json
import os
import subprocess
import sys
import sysconfig

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import native

CAPI_LIB = native.build_capi()

pytestmark = pytest.mark.skipif(CAPI_LIB is None,
                                reason="C toolchain unavailable")

_C_PROGRAM = r"""
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <stdint.h>

#include "pt_capi.h"

int main(int argc, char** argv) {
  PD_Config* cfg = PD_ConfigCreate();
  PD_ConfigSetModel(cfg, argv[1]);
  PD_ConfigDisableGpu(cfg);
  PD_Predictor* pred = PD_PredictorCreate(cfg);
  if (!pred) { fprintf(stderr, "create: %s\n", PD_GetLastError()); return 1; }

  int n_in = PD_PredictorGetInputNum(pred);
  char name[128];
  if (PD_PredictorGetInputName(pred, 0, name, sizeof(name)) != 0) return 2;

  int64_t shape[2] = {2, 8};
  float x[16];
  for (int i = 0; i < 16; ++i) x[i] = 0.125f * (float)i;
  if (PD_PredictorSetInput(pred, name, x, shape, 2, "float32") != 0) {
    fprintf(stderr, "set_input: %s\n", PD_GetLastError()); return 3;
  }
  int n_out = PD_PredictorRun(pred);
  if (n_out < 1) { fprintf(stderr, "run: %s\n", PD_GetLastError()); return 4; }

  char oname[128];
  if (PD_PredictorGetOutputName(pred, 0, oname, sizeof(oname)) != 0) return 5;
  int64_t oshape[8];
  int ndim = 8;
  char dtype[32];
  int64_t nbytes = PD_PredictorGetOutput(pred, oname, NULL, 0, oshape,
                                         &ndim, dtype, sizeof(dtype));
  if (nbytes <= 0) { fprintf(stderr, "shape: %s\n", PD_GetLastError()); return 6; }
  float* out = (float*)malloc((size_t)nbytes);
  PD_PredictorGetOutput(pred, oname, out, nbytes, oshape, &ndim, dtype,
                        sizeof(dtype));

  printf("{\"n_in\": %d, \"n_out\": %d, \"ndim\": %d, \"shape\": [", n_in,
         n_out, ndim);
  for (int i = 0; i < ndim; ++i)
    printf("%s%lld", i ? ", " : "", (long long)oshape[i]);
  printf("], \"dtype\": \"%s\", \"data\": [", dtype);
  int64_t n = nbytes / 4;
  for (int64_t i = 0; i < n; ++i)
    printf("%s%.6f", i ? ", " : "", (double)out[i]);
  printf("]}\n");
  free(out);
  PD_PredictorDestroy(pred);
  PD_ConfigDestroy(cfg);
  return 0;
}
"""


@pytest.fixture(scope="module")
def saved_model(tmp_path_factory):
    """Save a small MLP inference model and return (prefix, ref_out)."""
    import jax
    from paddle_tpu import nn, static

    pt.seed(0)

    class MLP(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(8, 16)
            self.fc2 = nn.Linear(16, 4)

        def forward(self, x):
            import paddle_tpu.nn.functional as F
            return F.softmax(self.fc2(F.relu(self.fc1(x))), axis=-1)

    model = MLP()
    model.eval()
    prefix = str(tmp_path_factory.mktemp("capi") / "mlp")
    static.save_inference_model(
        prefix, [static.InputSpec((2, 8), "float32", "x")], layer=model)

    x = (0.125 * np.arange(16, dtype=np.float32)).reshape(2, 8)
    from paddle_tpu.inference import Config, create_predictor
    cfg = Config(prefix)
    cfg.disable_gpu()
    pred = create_predictor(cfg)
    (ref,) = pred.run([x])
    return prefix, np.asarray(ref)


def test_c_program_matches_python_predictor(saved_model, tmp_path):
    prefix, ref = saved_model
    csrc = tmp_path / "consumer.c"
    csrc.write_text(_C_PROGRAM)
    exe = tmp_path / "consumer"
    libdir = sysconfig.get_config_var("LIBDIR")
    subprocess.run(
        ["gcc", str(csrc), CAPI_LIB, "-o", str(exe),
         f"-I{os.path.dirname(native._CAPI_SRC)}",
         f"-Wl,-rpath,{os.path.dirname(CAPI_LIB)}",
         f"-Wl,-rpath,{libdir}"],
        check=True, capture_output=True)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    # the embedded interpreter runs on the CPU like the suite
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([str(exe), prefix], capture_output=True, text=True,
                       timeout=300, env=env)
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["n_in"] == 1 and out["n_out"] >= 1
    assert out["shape"] == [2, 4] and out["dtype"] == "float32"
    np.testing.assert_allclose(
        np.asarray(out["data"], np.float32).reshape(2, 4), ref,
        rtol=1e-4, atol=1e-5)


def test_c_api_error_surface(tmp_path):
    """Invalid model path must yield a clean error, not a crash."""
    import ctypes
    lib = ctypes.CDLL(CAPI_LIB)
    lib.PD_ConfigCreate.restype = ctypes.c_void_p
    lib.PD_ConfigSetModel.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.PD_PredictorCreate.argtypes = [ctypes.c_void_p]
    lib.PD_PredictorCreate.restype = ctypes.c_void_p
    lib.PD_GetLastError.restype = ctypes.c_char_p
    cfg = lib.PD_ConfigCreate()
    lib.PD_ConfigSetModel(cfg, str(tmp_path / "nope").encode())
    pred = lib.PD_PredictorCreate(cfg)
    assert not pred
    assert lib.PD_GetLastError()


def test_go_wrapper_matches_c_abi():
    """Every C symbol the Go wrapper (go/*.go) calls must exist in
    pt_capi.h AND pt_capi.cc — the goapi parity contract validated
    without a Go toolchain (reference: inference/goapi over capi_exp)."""
    import glob
    import re

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    header = open(os.path.join(root, "native", "pt_capi.h")).read()
    impl = open(os.path.join(root, "native", "pt_capi.cc")).read()
    go_files = glob.glob(os.path.join(root, "go", "*.go"))
    assert go_files, "go wrapper missing"
    called = set()
    for gf in go_files:
        called |= set(re.findall(r"C\.(PD_[A-Za-z]+)\(", open(gf).read()))
    assert len(called) >= 12, called
    missing_h = [c for c in called if c + "(" not in header]
    missing_cc = [c for c in called if c + "(" not in impl]
    assert missing_h == [], missing_h
    assert missing_cc == [], missing_cc
    # and the header covers the full implemented surface
    # ("new PD_Config()" constructor calls are type uses, not functions)
    impl_syms = set(re.findall(r"\b(PD_[A-Za-z]+)\(", impl)) - \
        {"PD_Config", "PD_Predictor"}
    undeclared = [s2 for s2 in impl_syms if s2 + "(" not in header]
    assert undeclared == [], undeclared
