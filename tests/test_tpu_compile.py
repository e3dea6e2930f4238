"""Compile the chip's programs for a described TPU v5e, with no chip.

The TPU compiler is installed with JAX and compiles for a topology that
is described, not attached.  Each case lowers one program at the shapes
``chip_smoke.py`` runs and asserts that the compiler accepts it and that
``memory_analysis()`` fits one v5e chip: the four main-path programs of
the pricing service (fused chunk, chunk_mc, gen_step, the raw
``engine.total`` lane) and each Pallas kernel at one model width.

Only one process may load the TPU library, so the topology is described
inside a fixture of this one file, never at import time.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from repro.core.batch import SystemBatch, pad_batch
from repro.core.engine import _TOTAL_JIT
from repro.core.system import spec
from repro.dse.evaluate import _CHUNK_JIT, _CHUNK_MC_JIT
from repro.dse.search import _GEN_STEP_JIT
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.flash_decode import flash_decode
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.moe_gmm import gmm
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.slstm_cell import slstm_seq
from repro.service import PricingService, ServiceConfig

V5E_HBM_BYTES = 16 * 2**30          # one TPU v5e chip
SIZES = chip_smoke.Sizes()


@pytest.fixture(scope="module")
def one_chip():
    """Device 0 of a described v5e:2x2, with JAX's persistent compile
    cache off: what is compiled for a described chip cannot be read back
    without one."""
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                   # noqa: BLE001
        jax.config.update("jax_enable_compilation_cache", before)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def svc():
    """The smoke's service, for its encoder tables and raw-lane padding
    (built on the host; nothing is compiled)."""
    return PricingService(chip_smoke.SPACE,
                          ServiceConfig(chunk=SIZES.chunk))


def _shaped(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype,
                                       sharding=sharding), tree)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used <= V5E_HBM_BYTES, mem
    return mem


def _main_path_lowering(name, svc, chip):
    tables = _shaped(svc.enc.tables, chip)
    meta, flow = svc.enc.meta, "chip-last"
    idx = _sds((SIZES.chunk,), jnp.int32, chip)
    qty = _sds((len(chip_smoke.SPACE.skus),), jnp.float32, chip)
    key = _sds((2,), jnp.uint32, chip)
    sig = _sds((4,), jnp.float32, chip)
    if name == "chunk":
        return _CHUNK_JIT.fn.lower(tables, idx, qty, meta=meta, flow=flow)
    if name == "chunk_mc":
        return _CHUNK_MC_JIT.fn.lower(
            tables, idx, qty, key, sig, meta=meta, flow=flow,
            n_draws=SIZES.draws, quantiles=SIZES.quantiles)
    if name == "gen_step":
        pop = _sds((SIZES.population,), jnp.int32, chip)
        return _GEN_STEP_JIT.fn.lower(
            tables, key, pop, qty, key, sig, meta=meta, flow=flow,
            population=SIZES.population, elite=SIZES.elite,
            jump_prob=0.15, n_draws=0, quantile=0.5)
    assert name == "raw_total"
    group = [spec(dict(d)) for d in chip_smoke.RAW_GROUPS[0]]
    batch = pad_batch(SystemBatch.pack(
        group, share_nre=[0] * len(group), max_chips=svc.raw_max_chips),
        **svc.raw_pad)
    return _TOTAL_JIT.fn.lower(_shaped(batch, chip), flow)


@pytest.mark.parametrize("name", ["chunk", "chunk_mc", "gen_step",
                                  "raw_total"])
def test_main_path_program_compiles_for_v5e(name, svc, one_chip):
    compiled = _main_path_lowering(name, svc, one_chip).compile()
    _fits(compiled)
    if name == "gen_step":
        # the population buffer is donated: its output aliases it
        assert compiled.memory_analysis().alias_size_in_bytes > 0


# One model width per Pallas kernel: (kernel, argument shapes).
_BF16, _F32 = jnp.bfloat16, jnp.float32
KERNELS = {
    # deepseek-7b attention: 32 heads of 128, 2k prefill
    "flash_attention": (
        lambda q, k, v: flash_attention_fwd(q, k, v, causal=True),
        [((1, 32, 2048, 128), _BF16)] * 3),
    # deepseek-7b decode: 8 sequences against a 4k cache
    "flash_decode": (
        lambda q, k, v, n: flash_decode(q, k, v, n),
        [((8, 32, 128), _BF16), ((8, 32, 4096, 128), _BF16),
         ((8, 32, 4096, 128), _BF16), ((8,), jnp.int32)]),
    # zamba2-7b Mamba2 mixer: d_inner 7168 = 112 heads of 64, state 64
    "mamba_scan": (
        lambda x, dt, a, b, c: mamba_scan(x, dt, a, b, c, chunk=64)[0],
        [((1, 2048, 112, 64), _F32), ((1, 2048, 112), _F32),
         ((112,), _F32), ((1, 2048, 64), _F32), ((1, 2048, 64), _F32)]),
    # deepseek-moe-16b routed experts: 64 x (2048 -> 1408)
    "moe_gmm": (
        lambda x, w: gmm(x, w),
        [((64, 256, 2048), _BF16), ((64, 2048, 1408), _BF16)]),
    # deepseek-7b residual stream: 4096 tokens x 4096
    "rmsnorm": (
        lambda x, s: rmsnorm(x, s),
        [((4096, 4096), _BF16), ((4096,), _F32)]),
    # xlstm-125m sLSTM: 4 heads of 192
    "slstm_cell": (
        lambda xg, r, b: slstm_seq(xg, r, b),
        [((1, 2048, 4, 4, 192), _F32), ((4, 4, 192, 192), _F32),
         ((4, 4, 192), _F32)]),
}


# Kernels the v5e compiler refuses at these widths, with its message.
REFUSED = {
    "mamba_scan": "Pallas TPU lowering: the last two dimensions of a block "
                  "shape must be divisible by 8 and 128, or equal the "
                  "array's; the x block is (1, 64, 1, 64) of "
                  "(1, 2048, 112, 64)",
    "slstm_cell": "Mosaic failed to compile TPU kernel: infer-vector-layout:"
                  " unsupported shape cast, tpu.reshape vector<4x768xf32> ->"
                  " vector<4x4x192xf32> (the recurrent einsum)",
}


@pytest.mark.parametrize("name", [
    pytest.param(k, marks=pytest.mark.xfail(strict=True, reason=REFUSED[k]))
    if k in REFUSED else k for k in sorted(KERNELS)])
def test_pallas_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    args = [_sds(s, d, one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    _fits(compiled)
    assert "tpu_custom_call" in compiled.as_text()
