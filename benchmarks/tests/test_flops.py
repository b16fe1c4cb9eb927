"""The FLOPs and bytes functions against hand-worked values."""
import os

import pytest

from benchmarks.harness import flops
from conftest import ROOT


def _dims(name):
    from benchmarks.harness import spec
    bench = spec.load_benchmark(os.path.join(ROOT, "BENCHMARK.json"))
    values = bench.configs[name].values
    return bench.module("reference", values["family"]).dims(values)


def test_gpt2_small_flops_per_token():
    v = _dims("gpt2-small")
    # a layer: q, k, v, o 4 x 768^2 = 2,359,296; MLP 2 x 768 x 3072 = 4,718,592
    layer = 2_359_296 + 4_718_592
    head = 50_304 * 768                       # 38,633,472
    matmul = 12 * layer + head                # 123,568,128
    attn = 12 * 6 * 1024 * 12 * 64            # 56,623,104 a token
    assert flops.train_flops_per_token(v, 1024) == 6 * matmul + attn
    assert flops.train_flops_per_token(v, 1024) == pytest.approx(798.03e6,
                                                                 rel=1e-4)


def test_mistral_l2_flops_per_token():
    v = _dims("mistral-7b-l2")
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808
    matmul = 2 * layer + 32000 * 4096         # 567,279,616
    attn = 2 * 6 * 4096 * 32 * 128            # 201,326,592 a token
    assert flops.train_flops_per_token(v, 4096) == 6 * matmul + attn
    assert flops.train_flops_per_token(v, 4096) == pytest.approx(3.605e9,
                                                                 rel=1e-3)


def test_attention_flops_and_bytes():
    # b1, 2 heads, s4, d8: one full matmul 2*1*2*16*8 = 512; 6 of them, half
    assert flops.attention_flops(1, 2, 4, 8) == 6 * 512 / 2
    # GPT-2 cell, one layer: 6 x 32 x 12 x 1024^2 x 64 = 154.6 GFLOP
    assert flops.attention_flops(32, 12, 1024, 64) == pytest.approx(
        154.62e9, rel=1e-4)
    # q = 1*2*4*8*2 = 128 bytes, kv head 1: 64 bytes
    # fwd q + 2kv + q = 384; bwd (3q + 2kv) + (q + 2kv) = 768
    assert flops.attention_bytes(1, 2, 1, 4, 8) == 384 + 768
