"""C inference API end-to-end (reference: paddle/fluid/inference/capi_exp
demo flow — config -> predictor -> tensor handles -> run -> fetch): build
libpd_inference.so, compile a pure-C driver against it, run the driver in
a subprocess on a jit.save'd model, and compare its output with the
Python predictor bit-for-bit."""
import os
import subprocess
import sys
import sysconfig

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import jit
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.static import InputSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CSRC = os.path.join(REPO, "paddle_tpu", "csrc", "inference_capi.cpp")

DRIVER = r"""
#include <stdint.h>
#include <stdio.h>
#include <stdlib.h>

extern void* PD_ConfigCreate(void);
extern void PD_ConfigSetModel(void*, const char*, const char*);
extern void PD_ConfigDestroy(void*);
extern void* PD_PredictorCreate(void*);
extern void PD_PredictorDestroy(void*);
extern const char* PD_PredictorGetInputName(void*, size_t);
extern const char* PD_PredictorGetOutputName(void*, size_t);
extern void* PD_PredictorGetInputHandle(void*, const char*);
extern void* PD_PredictorGetOutputHandle(void*, const char*);
extern int PD_PredictorRun(void*);
extern void PD_TensorReshape(void*, size_t, const int32_t*);
extern int PD_TensorCopyFromCpuInt64(void*, const int64_t*);
extern int PD_TensorGetShape(void*, int32_t*, int);
extern int PD_TensorCopyToCpuFloat(void*, float*);
extern void PD_TensorDestroy(void*);
extern const char* PD_GetLastError(void);

int main(int argc, char** argv) {
  if (argc < 2) return 64;
  void* cfg = PD_ConfigCreate();
  PD_ConfigSetModel(cfg, argv[1], "");
  void* pred = PD_PredictorCreate(cfg);
  PD_ConfigDestroy(cfg);
  if (!pred) { fprintf(stderr, "create: %s\n", PD_GetLastError()); return 1; }

  void* in = PD_PredictorGetInputHandle(pred,
                                        PD_PredictorGetInputName(pred, 0));
  int32_t shape[2] = {2, 16};
  PD_TensorReshape(in, 2, shape);
  int64_t ids[32];
  for (int i = 0; i < 32; i++) ids[i] = (int64_t)(i * 7 % 250);
  if (!PD_TensorCopyFromCpuInt64(in, ids)) {
    fprintf(stderr, "copy_from: %s\n", PD_GetLastError());
    return 2;
  }
  if (!PD_PredictorRun(pred)) {
    fprintf(stderr, "run: %s\n", PD_GetLastError());
    return 3;
  }
  void* out = PD_PredictorGetOutputHandle(
      pred, PD_PredictorGetOutputName(pred, 0));
  int32_t os[8];
  int nd = PD_TensorGetShape(out, os, 8);
  long total = 1;
  for (int i = 0; i < nd; i++) total *= os[i];
  float* buf = (float*)malloc(total * sizeof(float));
  if (!PD_TensorCopyToCpuFloat(out, buf)) {
    fprintf(stderr, "copy_to: %s\n", PD_GetLastError());
    return 4;
  }
  double sum = 0;
  for (long i = 0; i < total; i++) sum += buf[i];
  printf("nd=%d d0=%d d1=%d d2=%d sum=%.6f f0=%.6f\n", nd, os[0], os[1],
         nd > 2 ? os[2] : -1, sum, buf[0]);
  free(buf);
  PD_TensorDestroy(in);
  PD_TensorDestroy(out);
  PD_PredictorDestroy(pred);
  return 0;
}
"""


@pytest.fixture(scope="module")
def capi_lib(tmp_path_factory):
    d = tmp_path_factory.mktemp("capi")
    so = d / "libpd_inference.so"
    inc = sysconfig.get_paths()["include"]
    libdir = sysconfig.get_config_var("LIBDIR")
    ver = sysconfig.get_config_var("LDVERSION")
    r = subprocess.run(
        ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", f"-I{inc}",
         "-o", str(so), CSRC, f"-L{libdir}", f"-lpython{ver}"],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    drv_c = d / "driver.c"
    drv_c.write_text(DRIVER)
    drv = d / "driver"
    r = subprocess.run(
        ["gcc", "-O2", "-o", str(drv), str(drv_c), str(so),
         f"-L{libdir}", f"-lpython{ver}", f"-Wl,-rpath,{d}",
         f"-Wl,-rpath,{libdir}"],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    return drv


def test_c_driver_matches_python_predictor(capi_lib, tmp_path):
    paddle.seed(0)
    cfg = llama_tiny()
    m = LlamaForCausalLM(cfg)
    m.eval()
    prefix = str(tmp_path / "m")
    jit.save(m, prefix, input_spec=[InputSpec([2, 16], "int64")])

    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_PLATFORMS")}
    # the embedded interpreter runs pure-CPU on a path built here:
    # stdlib comes from the base prefix, packages from the venv
    site = sysconfig.get_paths()["purelib"]
    env["PYTHONPATH"] = os.pathsep.join([REPO, site])
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONHOME"] = sys.base_prefix
    r = subprocess.run([str(capi_lib), prefix], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    fields = dict(p.split("=") for p in r.stdout.split())
    assert int(fields["nd"]) == 3
    assert (int(fields["d0"]), int(fields["d1"]),
            int(fields["d2"])) == (2, 16, cfg.vocab_size)

    ids = (np.arange(32, dtype=np.int64) * 7 % 250).reshape(2, 16)
    ref = create_predictor(Config(prefix)).run([ids])[0]
    np.testing.assert_allclose(float(fields["sum"]), float(ref.sum()),
                               rtol=1e-4)
    np.testing.assert_allclose(float(fields["f0"]), float(ref.ravel()[0]),
                               rtol=1e-4, atol=1e-6)
