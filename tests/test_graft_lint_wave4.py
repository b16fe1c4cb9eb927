"""graft_lint wave 4 (ISSUE 16 tentpole): Pallas/Mosaic kernel hygiene.
Fixture-driven good/bad snippets for the kernel-hygiene pass
(GL901-GL906): block-tiling legality, grid/index_map coverage,
padded-tail reduction masks, fp32 accumulation (+ --fix idempotence for
GL904), VMEM budget estimates, and interpret-mode drift."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.graft_lint import lint_file, registered_passes  # noqa: E402

_PRELUDE = """
    import functools
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def pad_rows(a, br):
        return a

    def pad_seq(a, b):
        return a

    def copy_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]
"""


def _lint_src(tmp_path, src, name="mod.py", **kw):
    p = tmp_path / name
    p.write_text(textwrap.dedent(_PRELUDE) + textwrap.dedent(src))
    passes = [cls() for cls in registered_passes().values()]
    findings, suppressed, err = lint_file(str(p), passes, **kw)
    assert err is None, err
    return findings, suppressed


def _gl9(findings, rule=None):
    return [f for f in findings
            if f.rule.startswith(rule or "GL9")]


def test_wave4_pass_registered():
    assert "kernel-hygiene" in registered_passes()


# -- GL901: block tiling legality --------------------------------------------

def test_gl901_rank1_vmem_block_flagged(tmp_path):
    findings, _ = _lint_src(tmp_path, """
        def f(x):
            return pl.pallas_call(
                copy_kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((8,), lambda i: (i,))],
                out_specs=pl.BlockSpec((8,), lambda i: (i,)),
                out_shape=jax.ShapeDtypeStruct((32,), jnp.float32),
            )(x)
    """)
    assert len(_gl9(findings, "GL901")) == 2   # in spec + out spec
    assert all("rank-1" in f.message for f in _gl9(findings, "GL901"))


def test_gl901_rank1_smem_scalar_is_exempt(tmp_path):
    # the flash-attention seed spec shape: scalars ride SMEM legally
    findings, _ = _lint_src(tmp_path, """
        def f(x, seed):
            return pl.pallas_call(
                copy_kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec(
                    (1,), lambda i: (0,), memory_space=pltpu.SMEM)],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
            )(seed)
    """)
    assert _gl9(findings) == []


def test_gl901_rank1_lane_multiple_is_clean(tmp_path):
    findings, _ = _lint_src(tmp_path, """
        def f(x):
            return pl.pallas_call(
                copy_kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((256,), lambda i: (i,))],
                out_specs=pl.BlockSpec((256,), lambda i: (i,)),
                out_shape=jax.ShapeDtypeStruct((1024,), jnp.float32),
            )(x)
    """)
    assert _gl9(findings) == []


def test_gl901_trailing_non_multiple_flagged(tmp_path):
    findings, _ = _lint_src(tmp_path, """
        def f(x):
            return pl.pallas_call(
                copy_kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 96), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 96), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((32, 192), jnp.float32),
            )(x)
    """)
    assert len(_gl9(findings, "GL901")) == 2
    assert all("trailing" in f.message for f in _gl9(findings, "GL901"))


def test_gl901_trailing_full_array_dim_is_clean(tmp_path):
    # 100 is no 128-multiple but IS the whole array dim: legal block
    findings, _ = _lint_src(tmp_path, """
        def f():
            x = jnp.zeros((32, 100), jnp.float32)
            return pl.pallas_call(
                copy_kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 100), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 100), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((32, 100), jnp.float32),
            )(x)
    """)
    assert _gl9(findings) == []


def test_gl901_trailing_unit_scalar_idiom_is_clean(tmp_path):
    # the repo's (rows, 1) per-row-scalar idiom: array dims unknown, so
    # the trailing-unit block is trusted
    findings, _ = _lint_src(tmp_path, """
        def f(lse):
            br = 8
            return pl.pallas_call(
                copy_kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((br, 1), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((br, 1), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((32, 1), jnp.float32),
            )(lse)
    """)
    assert _gl9(findings) == []


def test_gl901_trailing_unit_over_wide_array_flagged(tmp_path):
    # a (8, 1) block over a provably (32, 128) array is a 1-lane slice
    findings, _ = _lint_src(tmp_path, """
        def f():
            x = jnp.zeros((32, 128), jnp.float32)
            return pl.pallas_call(
                copy_kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 1), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
            )(x)
    """)
    flagged = _gl9(findings, "GL901")
    assert len(flagged) == 1
    assert "in_specs[0]" in flagged[0].symbol


def test_gl901_bf16_sublane_flagged(tmp_path):
    # 8 rows is a legal f32 block but bf16 tiles are (16, 128)
    findings, _ = _lint_src(tmp_path, """
        def f():
            x = jnp.zeros((64, 128), jnp.bfloat16)
            return pl.pallas_call(
                copy_kernel,
                grid=(8,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((64, 128), jnp.bfloat16),
            )(x)
    """)
    flagged = _gl9(findings, "GL901")
    assert len(flagged) == 2
    assert all("sublane" in f.message for f in flagged)


def test_gl901_bf16_sublane_multiple_is_clean(tmp_path):
    findings, _ = _lint_src(tmp_path, """
        def f():
            x = jnp.zeros((64, 128), jnp.bfloat16)
            return pl.pallas_call(
                copy_kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((16, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((16, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((64, 128), jnp.bfloat16),
            )(x)
    """)
    assert _gl9(findings) == []


def test_gl901_broadcast_row_block_is_clean(tmp_path):
    # the norms (1, n) weight block: second-minor 1 IS the array dim
    findings, _ = _lint_src(tmp_path, """
        def f(w, n):
            w2 = w.reshape(1, n)
            return pl.pallas_call(
                copy_kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((1, n), lambda i: (0, 0))],
                out_specs=pl.BlockSpec((1, n), lambda i: (0, 0)),
                out_shape=jax.ShapeDtypeStruct((1, 128), jnp.float32),
            )(w2)
    """)
    assert _gl9(findings) == []


# -- GL902: grid/index_map coverage ------------------------------------------

def test_gl902_index_map_grid_arity_mismatch(tmp_path):
    findings, _ = _lint_src(tmp_path, """
        def f(x):
            return pl.pallas_call(
                copy_kernel,
                grid=(4, 2),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128),
                                       lambda i, j: (i, j)),
                out_shape=jax.ShapeDtypeStruct((32, 256), jnp.float32),
            )(x)
    """)
    flagged = _gl9(findings, "GL902")
    assert len(flagged) == 1
    assert "grid indices" in flagged[0].message


def test_gl902_index_map_block_rank_mismatch(tmp_path):
    findings, _ = _lint_src(tmp_path, """
        def f(x):
            return pl.pallas_call(
                copy_kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i,))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
            )(x)
    """)
    flagged = _gl9(findings, "GL902")
    assert len(flagged) == 1
    assert "rank-2 block" in flagged[0].message


def test_gl902_under_coverage_flagged(tmp_path):
    # 12 blocks of 8 over 100 rows: rows 96..99 silently never computed
    findings, _ = _lint_src(tmp_path, """
        def f():
            x = jnp.zeros((100, 128), jnp.float32)
            return pl.pallas_call(
                copy_kernel,
                grid=(x.shape[0] // 8,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((100, 128), jnp.float32),
            )(x)
    """)
    flagged = _gl9(findings, "GL902")
    assert len(flagged) == 2
    assert all("silently never computed" in f.message for f in flagged)


def test_gl902_over_coverage_flagged(tmp_path):
    findings, _ = _lint_src(tmp_path, """
        def f():
            x = jnp.zeros((32, 128), jnp.float32)
            return pl.pallas_call(
                copy_kernel,
                grid=(5,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
            )(x)
    """)
    flagged = _gl9(findings, "GL902")
    assert len(flagged) == 2
    assert all("past array axis" in f.message for f in flagged)


def test_gl902_exact_coverage_is_clean(tmp_path):
    findings, _ = _lint_src(tmp_path, """
        def f():
            x = jnp.zeros((32, 128), jnp.float32)
            return pl.pallas_call(
                copy_kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
            )(x)
    """)
    assert _gl9(findings) == []


def test_gl902_padded_ceildiv_grid_is_clean(tmp_path):
    # the repo idiom: pad_rows + rp // br covers exactly; the model
    # cannot prove a mismatch, so it must stay silent
    findings, _ = _lint_src(tmp_path, """
        def f(x, br):
            xp = pad_rows(x, br)
            rp = xp.shape[0]
            return pl.pallas_call(
                copy_kernel,
                grid=(rp // br,),
                in_specs=[pl.BlockSpec((br, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((br, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((128, 128), jnp.float32),
            )(xp)
    """)
    assert _gl9(findings) == []


# -- GL903: padded-tail reduction without a mask -----------------------------

def test_gl903_padded_axis_reduction_flagged(tmp_path):
    findings, _ = _lint_src(tmp_path, """
        def sum_kernel(x_ref, o_ref):
            x = x_ref[...].astype(jnp.float32)
            o_ref[...] = jnp.sum(x, axis=0, keepdims=True)

        def f(x, br):
            xp = pad_rows(x, br)
            return pl.pallas_call(
                sum_kernel,
                grid=(1,),
                in_specs=[pl.BlockSpec((br, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((1, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((1, 128), jnp.float32),
            )(xp)
    """)
    flagged = _gl9(findings, "GL903")
    assert len(flagged) == 1
    assert "axis 0" in flagged[0].message
    assert "broadcasted_iota" in flagged[0].message


def test_gl903_full_reduction_flagged(tmp_path):
    findings, _ = _lint_src(tmp_path, """
        def sum_kernel(x_ref, o_ref):
            x = x_ref[...]
            o_ref[0, 0] = jnp.sum(x)

        def f(x, br):
            xp = pad_rows(x, br)
            return pl.pallas_call(
                sum_kernel,
                grid=(1,),
                in_specs=[pl.BlockSpec((br, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
                out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
            )(xp)
    """)
    assert len(_gl9(findings, "GL903")) == 1


def test_gl903_iota_mask_is_clean(tmp_path):
    findings, _ = _lint_src(tmp_path, """
        def sum_kernel(x_ref, o_ref, *, rows):
            x = x_ref[...].astype(jnp.float32)
            ridx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
            x = jnp.where(ridx < rows, x, 0.0)
            o_ref[...] = jnp.sum(x, axis=0, keepdims=True)

        def f(x, br, rows):
            xp = pad_rows(x, br)
            return pl.pallas_call(
                functools.partial(sum_kernel, rows=rows),
                grid=(1,),
                in_specs=[pl.BlockSpec((br, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((1, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((1, 128), jnp.float32),
            )(xp)
    """)
    assert _gl9(findings) == []


def test_gl903_reduction_over_unpadded_axis_is_clean(tmp_path):
    # the norms/cross-entropy shape: rows padded, reduce over columns
    findings, _ = _lint_src(tmp_path, """
        def mean_kernel(x_ref, o_ref):
            x = x_ref[...].astype(jnp.float32)
            o_ref[...] = jnp.mean(x, axis=1, keepdims=True)

        def f(x, br):
            xp = pad_rows(x, br)
            return pl.pallas_call(
                mean_kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((br, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((br, 1), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((32, 1), jnp.float32),
            )(xp)
    """)
    assert _gl9(findings) == []


def test_gl903_pad_seq_axis1_reduction_flagged(tmp_path):
    findings, _ = _lint_src(tmp_path, """
        def sum_kernel(x_ref, o_ref):
            x = x_ref[...]
            o_ref[...] = jnp.sum(x, axis=1, keepdims=True)

        def f(x, bk):
            xp = pad_seq(x, bk)
            return pl.pallas_call(
                sum_kernel,
                grid=(1,),
                in_specs=[pl.BlockSpec((8, bk), lambda i: (0, i))],
                out_specs=pl.BlockSpec((8, 1), lambda i: (0, 0)),
                out_shape=jax.ShapeDtypeStruct((8, 1), jnp.float32),
            )(xp)
    """)
    flagged = _gl9(findings, "GL903")
    assert len(flagged) == 1
    assert "axis 1" in flagged[0].message


# -- GL904: low-precision accumulation ---------------------------------------

def test_gl904_dot_without_pet_flagged_with_fix(tmp_path):
    findings, _ = _lint_src(tmp_path, """
        def dot_kernel(q_ref, k_ref, o_ref):
            q = q_ref[...]
            k = k_ref[...]
            o_ref[...] = jax.lax.dot(q, k)

        def f(q, k):
            return pl.pallas_call(
                dot_kernel,
                out_shape=jax.ShapeDtypeStruct((128, 128), jnp.float32),
            )(q, k)
    """)
    flagged = _gl9(findings, "GL904")
    assert len(flagged) == 1
    assert flagged[0].fix is not None, "GL904 dots must be autofixable"


def test_gl904_dot_with_pet_is_clean(tmp_path):
    findings, _ = _lint_src(tmp_path, """
        def dot_kernel(q_ref, k_ref, o_ref):
            q = q_ref[...]
            k = k_ref[...]
            o_ref[...] = jax.lax.dot(
                q, k, preferred_element_type=jnp.float32)

        def f(q, k):
            return pl.pallas_call(
                dot_kernel,
                out_shape=jax.ShapeDtypeStruct((128, 128), jnp.float32),
            )(q, k)
    """)
    assert _gl9(findings) == []


def test_gl904_f32_astype_before_dot_is_clean(tmp_path):
    findings, _ = _lint_src(tmp_path, """
        def dot_kernel(q_ref, k_ref, o_ref):
            q = q_ref[...].astype(jnp.float32)
            k = k_ref[...].astype(jnp.float32)
            o_ref[...] = jnp.dot(q, k)

        def f(q, k):
            return pl.pallas_call(
                dot_kernel,
                out_shape=jax.ShapeDtypeStruct((128, 128), jnp.float32),
            )(q, k)
    """)
    assert _gl9(findings) == []


def test_gl904_dot_general_without_pet_flagged(tmp_path):
    findings, _ = _lint_src(tmp_path, """
        def dot_kernel(q_ref, k_ref, o_ref):
            o_ref[...] = jax.lax.dot_general(
                q_ref[...], k_ref[...], (((1,), (1,)), ((), ())))

        def f(q, k):
            return pl.pallas_call(
                dot_kernel,
                out_shape=jax.ShapeDtypeStruct((128, 128), jnp.float32),
            )(q, k)
    """)
    assert len(_gl9(findings, "GL904")) == 1


def test_gl904_bf16_sum_reported_without_fix(tmp_path):
    findings, _ = _lint_src(tmp_path, """
        def sum_kernel(x_ref, o_ref):
            x = x_ref[...].astype(jnp.bfloat16)
            o_ref[...] = jnp.sum(x, axis=1, keepdims=True)

        def f(x):
            return pl.pallas_call(
                sum_kernel,
                out_shape=jax.ShapeDtypeStruct((8, 1), jnp.bfloat16),
            )(x)
    """)
    flagged = _gl9(findings, "GL904")
    assert len(flagged) == 1
    assert flagged[0].fix is None      # judgment call: report-only
    assert "bfloat16" in flagged[0].message


def test_gl904_each_kernel_flagged_once_across_calls(tmp_path):
    # the same kernel def launched from two pallas_call sites must not
    # produce duplicate kernel-body findings
    findings, _ = _lint_src(tmp_path, """
        def dot_kernel(q_ref, k_ref, o_ref):
            o_ref[...] = jnp.dot(q_ref[...], k_ref[...])

        def f(q, k):
            return pl.pallas_call(
                dot_kernel,
                out_shape=jax.ShapeDtypeStruct((128, 128), jnp.float32),
            )(q, k)

        def g(q, k):
            return pl.pallas_call(
                dot_kernel,
                out_shape=jax.ShapeDtypeStruct((128, 128), jnp.float32),
            )(q, k)
    """)
    assert len(_gl9(findings, "GL904")) == 1


# -- GL905: VMEM footprint ---------------------------------------------------

def test_gl905_oversized_blocks_flagged(tmp_path):
    # 1024x2048 f32 in + out, double-buffered: 32 MiB > 12 MiB budget
    findings, _ = _lint_src(tmp_path, """
        def f(x):
            return pl.pallas_call(
                copy_kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((1024, 2048), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((1024, 2048), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((4096, 2048),
                                               jnp.float32),
            )(x)
    """)
    flagged = _gl9(findings, "GL905")
    assert len(flagged) == 1
    assert "32.0 MiB" in flagged[0].message


def test_gl905_scratch_counts_toward_the_budget(tmp_path):
    findings, _ = _lint_src(tmp_path, """
        def f(x):
            return pl.pallas_call(
                copy_kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
                scratch_shapes=[pltpu.VMEM((2048, 2048), jnp.float32)],
            )(x)
    """)
    assert len(_gl9(findings, "GL905")) == 1


def test_gl905_modest_blocks_are_clean(tmp_path):
    findings, _ = _lint_src(tmp_path, """
        def f(x):
            return pl.pallas_call(
                copy_kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((256, 512), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((256, 512), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((1024, 512), jnp.float32),
                scratch_shapes=[pltpu.VMEM((256, 128), jnp.float32)],
            )(x)
    """)
    assert _gl9(findings) == []


_GL905_RAISED = """
        def params(limit):
            return pltpu.CompilerParams(
                dimension_semantics=("parallel",), vmem_limit_bytes=limit)

        def f(x):
            return pl.pallas_call(
                copy_kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((1024, 2048), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((1024, 2048), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((4096, 2048),
                                               jnp.float32),
                compiler_params=%s,
            )(x)
    """


@pytest.mark.parametrize("params,flagged", [
    # 32 MiB of blocks: inside 75% of a 64 MiB limit, over 75% of 40 MiB
    ("pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)", 0),
    ("pltpu.CompilerParams(vmem_limit_bytes=40 * 1024 * 1024)", 1),
    # no limit set: Mosaic's 16 MiB default
    ("pltpu.CompilerParams(dimension_semantics=('parallel',))", 1),
    ("pltpu.CompilerParams(vmem_limit_bytes=None)", 1),
    # a limit the model cannot evaluate is no proof: silent
    ("pltpu.CompilerParams(vmem_limit_bytes=estimate(x))", 0),
    ("params(estimate(x))", 0),
    ("params(64 * 1024 * 1024)", 0),
])
def test_gl905_reads_a_raised_vmem_limit(tmp_path, params, flagged):
    findings, _ = _lint_src(tmp_path, _GL905_RAISED % params)
    got = _gl9(findings, "GL905")
    assert len(got) == flagged
    if flagged and "40" in params:
        assert "40 MiB" in got[0].message


# -- GL906: interpret-mode drift ---------------------------------------------

def test_gl906_local_backend_check_flagged(tmp_path):
    findings, _ = _lint_src(tmp_path, """
        def f(x):
            interpret = jax.default_backend() != "tpu"
            return pl.pallas_call(
                copy_kernel,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                interpret=interpret,
            )(x)
    """)
    flagged = _gl9(findings, "GL906")
    assert len(flagged) == 1
    assert "common.py" in flagged[0].message


def test_gl906_shared_helper_is_clean(tmp_path):
    findings, _ = _lint_src(tmp_path, """
        def pallas_interpret():
            return False

        def f(x):
            return pl.pallas_call(
                copy_kernel,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                interpret=pallas_interpret(),
            )(x)
    """)
    assert _gl9(findings) == []


def test_gl906_scoped_to_pallas_modules(tmp_path):
    # backend dispatch OUTSIDE kernel modules is someone else's business
    findings, _ = _lint_src(tmp_path, """
        def pick():
            return "x" if jax.default_backend() == "tpu" else "y"
    """)
    assert _gl9(findings) == []


# -- resolution robustness ---------------------------------------------------

def test_dynamically_built_spec_lists_stay_silent(tmp_path):
    # flash-attention style: in_specs built with .append is beyond the
    # model — no guessing, no findings
    findings, _ = _lint_src(tmp_path, """
        def f(x, y, extra):
            in_specs = [pl.BlockSpec((8, 96), lambda i: (i, 0))]
            if extra is not None:
                in_specs.append(
                    pl.BlockSpec((8, 96), lambda i: (i, 0)))
            return pl.pallas_call(
                copy_kernel,
                grid=(4,),
                in_specs=in_specs,
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
            )(x, y)
    """)
    assert _gl9(findings) == []


def test_grid_spec_form_is_resolved(tmp_path):
    findings, _ = _lint_src(tmp_path, """
        def f(x):
            return pl.pallas_call(
                copy_kernel,
                grid_spec=pl.GridSpec(
                    grid=(4,),
                    in_specs=[pl.BlockSpec((8,), lambda i: (i,))],
                    out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                ),
                out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
            )(x)
    """)
    assert len(_gl9(findings, "GL901")) == 1   # rank-1 block inside GridSpec


def test_gl9_suppression_with_reason_works(tmp_path):
    findings, suppressed = _lint_src(tmp_path, """
        def f(x):
            return pl.pallas_call(
                copy_kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec(  # graft-lint: disable=GL901 -- proven on hw
                    (8,),
                    lambda i: (i,))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
            )(x)
    """)
    assert _gl9(findings) == []
    assert len(_gl9(suppressed, "GL901")) == 1


# -- CLI integration ---------------------------------------------------------

def _run_cli(*args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "tools.graft_lint", *args],
        capture_output=True, text=True, cwd=cwd)


def test_cli_gl9_family_select(tmp_path):
    p = tmp_path / "bad_kernel.py"
    p.write_text(textwrap.dedent(_PRELUDE) + textwrap.dedent("""
        def f(x):
            interpret = jax.default_backend() != "tpu"
            return pl.pallas_call(
                copy_kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((8,), lambda i: (i,))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
                interpret=interpret,
            )(x)
    """))
    proc = _run_cli(str(p), "--select", "GL9", "--no-baseline",
                    "--json")
    assert proc.returncode == 1
    data = json.loads(proc.stdout)
    rules = {f["rule"] for f in data["findings"]}
    assert rules == {"GL901", "GL906"}
    # a non-GL9 select must drop them
    proc2 = _run_cli(str(p), "--select", "GL5", "--no-baseline")
    assert proc2.returncode == 0


def test_cli_list_rules_includes_wave4_group():
    proc = _run_cli("--list-rules", "--json")
    assert proc.returncode == 0
    data = json.loads(proc.stdout)
    assert "kernel-hygiene" in data["passes"]
    assert {"GL901", "GL902", "GL903", "GL904", "GL905",
            "GL906"} <= set(data["groups"]["kernel-hygiene"])


def test_cli_fix_gl904_idempotent(tmp_path):
    p = tmp_path / "fixme.py"
    src = textwrap.dedent(_PRELUDE) + textwrap.dedent("""
        def dot_kernel(q_ref, k_ref, o_ref):
            o_ref[...] = jax.lax.dot(q_ref[...], k_ref[...])

        def f(q, k):
            return pl.pallas_call(
                dot_kernel,
                out_shape=jax.ShapeDtypeStruct((128, 128), jnp.float32),
            )(q, k)
    """)
    p.write_text(src)
    proc = _run_cli(str(p), "--select", "GL904", "--no-baseline",
                    "--fix")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    fixed = p.read_text()
    assert "preferred_element_type=jnp.float32" in fixed
    # idempotent: a second --fix run changes nothing
    proc2 = _run_cli(str(p), "--select", "GL904", "--no-baseline",
                     "--fix")
    assert proc2.returncode == 0
    assert p.read_text() == fixed
    assert "applied 0 fix(es)" in proc2.stdout
