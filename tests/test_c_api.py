"""C inference API (native/c_api.cc — reference analog:
paddle/fluid/inference/capi_exp/pd_inference_api.h, the paddle_inference_c
library C/Go deployments link against).

Two integration levels:
- ctypes inside this process (attach-to-running-interpreter path),
- a standalone C program compiled at test time (embed-an-interpreter path).
"""

import ctypes
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_LIB = os.path.join(_REPO, "native", "libpaddle_tpu_c.so")


def _build_lib():
    # always through make: a library left in the tree by another checkout of
    # the sources must not be the one under test
    subprocess.run(["make", "-C", os.path.join(_REPO, "native"), "c_api"],
                   check=True, capture_output=True)
    return _LIB


def _save_tiny_model(tmp_path):
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=16, num_layers=1,
                    num_heads=2, max_position_embeddings=32)
    m = GPTForCausalLM(cfg)
    m.eval()
    ids = np.random.RandomState(0).randint(0, 64, (2, 8)).astype(np.int32)
    ref = m(paddle.to_tensor(ids)).numpy()
    prefix = os.path.join(str(tmp_path), "gpt")
    paddle.jit.save(m, prefix,
                    input_spec=[paddle.jit.InputSpec([2, 8], "int32")])
    return prefix + ".pdmodel", ids, ref


def test_c_api_ctypes_roundtrip(tmp_path):
    lib = ctypes.CDLL(_build_lib())
    lib.PD_ConfigCreate.restype = ctypes.c_void_p
    lib.PD_PredictorCreate.restype = ctypes.c_void_p
    lib.PD_PredictorCreate.argtypes = [ctypes.c_void_p]
    lib.PD_ConfigSetModel.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_char_p]
    lib.PD_PredictorGetInputNum.restype = ctypes.c_size_t
    lib.PD_PredictorGetInputNum.argtypes = [ctypes.c_void_p]
    lib.PD_PredictorGetInputNameByIndex.restype = ctypes.c_char_p
    lib.PD_PredictorGetInputNameByIndex.argtypes = [ctypes.c_void_p,
                                                    ctypes.c_int]
    lib.PD_PredictorGetInputHandle.restype = ctypes.c_void_p
    lib.PD_PredictorGetInputHandle.argtypes = [ctypes.c_void_p,
                                               ctypes.c_char_p]
    lib.PD_TensorReshape.argtypes = [ctypes.c_void_p, ctypes.c_size_t,
                                     ctypes.POINTER(ctypes.c_int32)]
    lib.PD_TensorCopyFromCpuInt32.argtypes = [ctypes.c_void_p,
                                              ctypes.c_void_p]
    lib.PD_PredictorRun.restype = ctypes.c_int32
    lib.PD_PredictorRun.argtypes = [ctypes.c_void_p]
    lib.PD_PredictorGetOutputNum.restype = ctypes.c_size_t
    lib.PD_PredictorGetOutputNum.argtypes = [ctypes.c_void_p]
    lib.PD_PredictorGetOutputNameByIndex.restype = ctypes.c_char_p
    lib.PD_PredictorGetOutputNameByIndex.argtypes = [ctypes.c_void_p,
                                                     ctypes.c_int]
    lib.PD_PredictorGetOutputHandle.restype = ctypes.c_void_p
    lib.PD_PredictorGetOutputHandle.argtypes = [ctypes.c_void_p,
                                                ctypes.c_char_p]
    lib.PD_TensorGetNumDims.restype = ctypes.c_size_t
    lib.PD_TensorGetNumDims.argtypes = [ctypes.c_void_p]
    lib.PD_TensorGetShape.argtypes = [ctypes.c_void_p,
                                      ctypes.POINTER(ctypes.c_int32)]
    lib.PD_TensorCopyToCpuFloat.argtypes = [ctypes.c_void_p,
                                            ctypes.c_void_p]
    lib.PD_TensorDestroy.argtypes = [ctypes.c_void_p]
    lib.PD_PredictorDestroy.argtypes = [ctypes.c_void_p]
    lib.PD_ConfigDestroy.argtypes = [ctypes.c_void_p]

    model_path, ids, ref = _save_tiny_model(tmp_path)

    cfg = lib.PD_ConfigCreate()
    lib.PD_ConfigSetModel(cfg, model_path.encode(), b"")
    pred = lib.PD_PredictorCreate(cfg)
    assert pred, "PD_PredictorCreate failed"

    n_in = lib.PD_PredictorGetInputNum(pred)
    assert n_in == 1
    name = lib.PD_PredictorGetInputNameByIndex(pred, 0)
    h = lib.PD_PredictorGetInputHandle(pred, name)
    shape = (ctypes.c_int32 * 2)(2, 8)
    lib.PD_TensorReshape(h, 2, shape)
    buf = np.ascontiguousarray(ids)
    lib.PD_TensorCopyFromCpuInt32(h, buf.ctypes.data_as(ctypes.c_void_p))

    assert lib.PD_PredictorRun(pred) == 1

    assert lib.PD_PredictorGetOutputNum(pred) == 1
    oname = lib.PD_PredictorGetOutputNameByIndex(pred, 0)
    oh = lib.PD_PredictorGetOutputHandle(pred, oname)
    nd = lib.PD_TensorGetNumDims(oh)
    oshape = (ctypes.c_int32 * nd)()
    lib.PD_TensorGetShape(oh, oshape)
    assert list(oshape) == list(ref.shape), (list(oshape), ref.shape)
    out = np.empty(ref.shape, np.float32)
    lib.PD_TensorCopyToCpuFloat(oh, out.ctypes.data_as(ctypes.c_void_p))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    lib.PD_TensorDestroy(h)
    lib.PD_TensorDestroy(oh)
    lib.PD_PredictorDestroy(pred)
    lib.PD_ConfigDestroy(cfg)


_C_MAIN = r"""
#include <stdio.h>
#include <stdint.h>
#include <stdlib.h>

typedef int32_t PD_Bool;
typedef struct PD_Config PD_Config;
typedef struct PD_Predictor PD_Predictor;
typedef struct PD_Tensor PD_Tensor;

extern PD_Config* PD_ConfigCreate(void);
extern void PD_ConfigSetModel(PD_Config*, const char*, const char*);
extern PD_Predictor* PD_PredictorCreate(PD_Config*);
extern const char* PD_PredictorGetInputNameByIndex(PD_Predictor*, int);
extern PD_Tensor* PD_PredictorGetInputHandle(PD_Predictor*, const char*);
extern void PD_TensorReshape(PD_Tensor*, size_t, const int32_t*);
extern void PD_TensorCopyFromCpuInt32(PD_Tensor*, const int32_t*);
extern PD_Bool PD_PredictorRun(PD_Predictor*);
extern const char* PD_PredictorGetOutputNameByIndex(PD_Predictor*, int);
extern PD_Tensor* PD_PredictorGetOutputHandle(PD_Predictor*, const char*);
extern size_t PD_TensorGetNumDims(PD_Tensor*);
extern void PD_TensorGetShape(PD_Tensor*, int32_t*);
extern void PD_TensorCopyToCpuFloat(PD_Tensor*, float*);

int main(int argc, char** argv) {
  PD_Config* cfg = PD_ConfigCreate();
  PD_ConfigSetModel(cfg, argv[1], "");
  PD_Predictor* pred = PD_PredictorCreate(cfg);
  if (!pred) { fprintf(stderr, "create failed\n"); return 1; }
  PD_Tensor* h = PD_PredictorGetInputHandle(
      pred, PD_PredictorGetInputNameByIndex(pred, 0));
  int32_t shape[2] = {2, 8};
  PD_TensorReshape(h, 2, shape);
  int32_t ids[16];
  for (int i = 0; i < 16; ++i) ids[i] = (i * 7) % 64;
  PD_TensorCopyFromCpuInt32(h, ids);
  if (!PD_PredictorRun(pred)) { fprintf(stderr, "run failed\n"); return 2; }
  PD_Tensor* oh = PD_PredictorGetOutputHandle(
      pred, PD_PredictorGetOutputNameByIndex(pred, 0));
  size_t nd = PD_TensorGetNumDims(oh);
  int32_t oshape[8];
  PD_TensorGetShape(oh, oshape);
  size_t n = 1;
  for (size_t i = 0; i < nd; ++i) n *= (size_t)oshape[i];
  float* out = (float*)malloc(n * sizeof(float));
  PD_TensorCopyToCpuFloat(oh, out);
  double s = 0;
  for (size_t i = 0; i < n; ++i) s += out[i];
  printf("C_API_OK ndims=%zu n=%zu checksum=%.4f\n", nd, n, s);
  return 0;
}
"""


def test_c_api_standalone_program(tmp_path):
    """Compile a real C program against the lib and run it — exercises the
    embed-an-interpreter path a C/Go deployment would take."""
    lib = _build_lib()
    model_path, ids, ref = _save_tiny_model(tmp_path)
    src = tmp_path / "main.c"
    src.write_text(_C_MAIN)
    exe = tmp_path / "capi_demo"
    subprocess.run(
        ["gcc", str(src), "-o", str(exe), f"-L{os.path.dirname(lib)}",
         "-lpaddle_tpu_c", f"-Wl,-rpath,{os.path.dirname(lib)}"],
        check=True, capture_output=True, text=True)
    extra = [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
             if p]
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([_REPO] + extra)}
    r = subprocess.run([str(exe), model_path], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "C_API_OK" in r.stdout, r.stdout
    assert f"n={ref.size}" in r.stdout, (r.stdout, ref.size)
