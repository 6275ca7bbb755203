"""Ahead-of-time compiles of the main path's Pallas kernels for one TPU
v5e chip, at the paper's geometry and rwkv6-3b's widths.

Nothing runs: the TPU compiler, installed with JAX, compiles for a chip
that is described (`get_topology_desc`) and not attached.  So these tests
catch what interpret mode cannot — block shapes off the (8, 128) tile,
slices Mosaic refuses, VMEM overruns — on any machine.  The topology is
described inside a module fixture, never at import time: only one process
may load the TPU library, and it keeps it until it exits.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs.p2m_vww import CONFIG, STREAM_MAX_SLOTS
from repro.core.pixel_model import default_pixel_model
from repro.kernels.p2m_conv import (
    p2m_bwd_dw_pallas,
    p2m_bwd_dx_pallas,
    p2m_conv,
    p2m_conv_pallas,
    p2m_conv_pallas_gated,
)
from repro.kernels.p2m_conv.conv import ceil_to, default_conv_blocks
from repro.kernels.p2m_conv.kernel import p2m_matmul_pallas
from repro.kernels.p2m_conv.ops import _coeff_tuple
from repro.kernels.rwkv_wkv.kernel import wkv_pallas

COEFFS = _coeff_tuple(default_pixel_model())
P2M = CONFIG.p2m
K, S, CO = P2M.kernel, P2M.stride, P2M.out_channels
IMG = CONFIG.image_size  # 560
HO = P2M.out_spatial(IMG)  # 112
TRAIN_BATCH = 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("mode,want_raw", [("quant", False), ("relu", True),
                                          ("raw", True)])
def test_grid_conv_compiles_at_paper_geometry(spec, mode, want_raw):
    """Serving stem (quant) and training forward (raw accumulation kept
    for the backward mask) at (8, 560, 560, 3), k = s = 5, c_o = 8."""

    def fn(x, w, sh):
        return p2m_conv_pallas(x, w, sh, kernel=K, stride=S, coeffs=COEFFS,
                               mode=mode, want_raw=want_raw, interpret=False)

    text = _compiled_text(fn, spec(8, IMG, IMG, 3), spec(K * K * 3, CO),
                          spec(CO))
    assert "tpu_custom_call" in text


def test_gated_stem_compiles_at_paper_geometry(spec):
    def fn(x, w, sh, cached, rerun):
        return p2m_conv_pallas_gated(x, w, sh, cached, rerun, kernel=K,
                                     stride=S, coeffs=COEFFS, mode="quant",
                                     interpret=False)

    b = STREAM_MAX_SLOTS
    text = _compiled_text(fn, spec(b, IMG, IMG, 3), spec(K * K * 3, CO),
                          spec(CO), spec(b, HO, HO, CO),
                          spec(b, dtype=jnp.bool_))
    assert "tpu_custom_call" in text


def _view_rows() -> int:
    """Rows of the forward's image view at train size: B·Ho padded to the
    forward kernel's default block_h."""
    bh, _ = default_conv_blocks(TRAIN_BATCH, HO, HO, CO, 3 * K * 3)
    return ceil_to(TRAIN_BATCH * HO, bh)


@pytest.mark.parametrize("kernel_fn,x_form",
                         [(p2m_bwd_dx_pallas, "patches"),
                          (p2m_bwd_dw_pallas, "patches"),
                          (p2m_bwd_dw_pallas, "view")],
                         ids=["dX", "dW", "dW-view"])
def test_backward_kernels_compile_at_train_size(spec, kernel_fn, x_form):
    """M = 32·112² patch rows, K = 75, N = 8: one paper-geometry train
    batch, X as the (M, K) patch matrix or, for dW, as the forward's
    (mh_pad, k, Wo, k·C) image view."""
    m = TRAIN_BATCH * HO * HO

    def fn(g, w, x):
        return kernel_fn(g, w, x, coeffs=COEFFS, interpret=False)

    x = (spec(m, K * K * 3) if x_form == "patches"
         else spec(_view_rows(), K, HO, K * 3))
    text = _compiled_text(fn, spec(m, CO), spec(K * K * 3, CO), x)
    assert "tpu_custom_call" in text


def test_weight_gradient_reads_the_image_view(spec):
    """The compiled weight-only gradient of p2m_conv at stride == kernel,
    train size: no instruction of the backward's im2col, one dW kernel,
    and the dW kernel's image operand is the forward's padded view."""
    from repro.core.adc import ADCConfig

    def loss(w, x, sh):
        return p2m_conv(x, w, sh, default_pixel_model(), ADCConfig(), "raw",
                        K, S, False, "pallas").sum()

    text = _compiled_text(jax.grad(loss), spec(K * K * 3, CO),
                          spec(TRAIN_BATCH, IMG, IMG, 3), spec(CO))
    instr = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
    names = [m.group(1) for m in map(instr.match, text.splitlines()) if m]
    assert not [line for line in text.splitlines()
                if "p2m_conv_bwd/im2col" in line and instr.match(line)]
    dw = [n for n in names if re.fullmatch(r"p2m_bwd_dw_pallas(\.\d+)?", n)]
    assert len(dw) == 1, dw
    (call,) = [line for line in text.splitlines()
               if re.match(rf"^\s*(?:ROOT\s+)?%?{re.escape(dw[0])} =", line)]
    assert f"f32[{_view_rows()},{K},{HO},{K * 3}]" in call


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_patch_matmul_compiles(spec, dtype):
    """The patch-level kernel: a v5e's VPU has no bf16 compare, so bf16
    operands are widened before the CDS sign split."""

    def fn(x, w, sh):
        return p2m_matmul_pallas(x, w, sh, coeffs=COEFFS, mode="relu",
                                 interpret=False)

    text = _compiled_text(fn, spec(128, K * K * 3, dtype=dtype),
                          spec(K * K * 3, CO, dtype=dtype), spec(CO))
    assert "tpu_custom_call" in text


def test_wkv_compiles_at_rwkv6_3b_widths(spec):
    """40 heads of dim 64 (`configs/rwkv6_3b.py`)."""
    b, t, h, d = 2, 256, 40, 64

    def fn(r, k, v, lw, u, s0):
        return wkv_pallas(r, k, v, lw, u, s0, interpret=False)

    seq = spec(b, t, h, d)
    text = _compiled_text(fn, seq, seq, seq, seq, spec(h, d),
                          spec(b, h, d, d))
    assert "tpu_custom_call" in text


def test_dma_ring_rejected_where_it_cannot_tile_and_compiles_where_it_can(
        spec):
    """The ring's HBM tile slices need k·C on the 128-lane quantum: at
    the paper's k·C = 15 it raises before lowering; at k·C = 128 it
    compiles."""

    def ring(kernel, x, w, sh):
        return p2m_conv_pallas(x, w, sh, kernel=kernel, stride=kernel,
                               coeffs=COEFFS, mode="quant", pipeline_depth=2,
                               interpret=False)

    with pytest.raises(ValueError, match="multiple of 128"):
        _compiled_text(lambda x, w, sh: ring(K, x, w, sh),
                       spec(8, IMG, IMG, 3), spec(K * K * 3, CO), spec(CO))
    text = _compiled_text(lambda x, w, sh: ring(4, x, w, sh),
                          spec(2, 64, 64, 32), spec(4 * 4 * 32, CO),
                          spec(CO))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("gated", [False, True], ids=["dense", "gated"])
def test_strided_general_path_raises_for_tpu(spec, gated):
    """stride != kernel does not lower with Mosaic: a clear ValueError,
    never a silent reroute.  The stride-5 path beside it compiles."""
    b, img, k = 4, 224, 5

    def conv(stride, x, w, sh, cached, rerun):
        if gated:
            return p2m_conv_pallas_gated(x, w, sh, cached, rerun, kernel=k,
                                         stride=stride, coeffs=COEFFS,
                                         mode="quant", interpret=False)
        return p2m_conv_pallas(x, w, sh, kernel=k, stride=stride,
                               coeffs=COEFFS, mode="quant", interpret=False)

    def args(stride):
        ho = (img - k) // stride + 1
        return (spec(b, img, img, 3), spec(k * k * 3, CO), spec(CO),
                spec(b, ho, ho, CO), spec(b, dtype=jnp.bool_))

    with pytest.raises(ValueError, match="stride 2 != kernel 5"):
        _compiled_text(lambda *a: conv(2, *a), *args(2))
    text = _compiled_text(lambda *a: conv(5, *a), *args(5))
    assert "tpu_custom_call" in text


def test_stem_compiles_batch_sharded_over_four_chips(topo):
    """XLA cannot partition a Mosaic kernel; under the data-parallel
    vision plan the stem runs per batch shard and compiles for a 2x2
    v5e mesh."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    from repro.parallel import batch_shard_map, under_plan, vision_plan_for

    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(4, 1),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    plan = vision_plan_for(mesh)
    rep = NamedSharding(mesh, P())

    def conv(x, w, sh):
        return p2m_conv_pallas(x, w, sh, kernel=K, stride=S, coeffs=COEFFS,
                               mode="quant", interpret=False)

    def fn(x, w, sh):
        return batch_shard_map(conv, x, w, sh)

    text = _compiled_text(
        under_plan(fn, plan),
        jax.ShapeDtypeStruct((8, IMG, IMG, 3), jnp.float32,
                             sharding=NamedSharding(mesh, P("data"))),
        jax.ShapeDtypeStruct((K * K * 3, CO), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((CO,), jnp.float32, sharding=rep))
    assert "tpu_custom_call" in text
