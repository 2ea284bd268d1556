"""Compile the device paths for a TPU v5e that is described, not attached.

The TPU compiler refuses things the CPU backend and the Pallas
interpreter accept (block tiling, VMEM budget, int64 lowering),
so these tests compile, with no chip, what the device paths run on one:

* the jaxsim stage pass (single machine and a vmapped fleet) at a
  4,096-row decode bucket.  resnet18@224's real bucket (262,144 rows)
  takes about half a minute per compile on an 8-core host, too slow for
  tier-1; ``python chip_smoke.py`` compiles and runs it on the chip;
* the bit-serial Pallas kernel, natively (``interpret=False``), at two
  resnet18@224 layer shapes, which must lower to a Mosaic
  ``tpu_custom_call``.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and the
test workers all import this file.  The persistent compile cache is off
around these compiles: an executable compiled for a described chip is
written to it but cannot be read back without one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import jaxsim
from repro.core.arch import default_chip
from repro.core.codegen import _ensure_vec_flag_operand
from repro.core.isa import default_isa
from repro.core.machine import machine_for
from repro.core.vectorsim import StageDecoder
from repro.kernels.bitserial_mvm import bitserial_mvm_pallas

pytestmark = pytest.mark.filterwarnings(
    "ignore:Some donated buffers were not usable")

BUCKET = 4096
N_REGS = 16                 # resnet18@224's register bucket
FLEET = 8
# im2col matmuls of resnet18@224 convolutions: (M x K) @ (K x N)
KERNEL_SHAPES = [(128, 4608, 512),       # layer4 3x3 conv, 512 ch
                 (12544, 1152, 128)]     # layer2 3x3 conv, batch 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:               # noqa: BLE001 — skip reason
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _stage_exec():
    isa = default_isa()
    _ensure_vec_flag_operand(isa)
    return jaxsim._exec_for(StageDecoder(isa, machine_for(default_chip())),
                            N_REGS)


def _stage_args(sharding, n_machines=None):
    shp = () if n_machines is None else (n_machines,)
    sc = tuple(jax.ShapeDtypeStruct(shp, jnp.int64, sharding=sharding)
               for _ in jaxsim._INT_KEYS)
    cols = {k: jax.ShapeDtypeStruct((BUCKET,), jnp.int64, sharding=sharding)
            for k in ("op", "starts") + jaxsim._COL_NAMES}
    return sc, cols


@pytest.mark.parametrize("n_machines", [None, FLEET],
                         ids=["single", "fleet"])
def test_stage_pass_compiles_for_v5e(one_chip, n_machines):
    single, fleet = _stage_exec()
    fn = single if n_machines is None else fleet
    with jax.enable_x64(True):
        compiled = fn.lower(*_stage_args(one_chip, n_machines)).compile()
    lat = compiled.out_info[0]
    want = (BUCKET,) if n_machines is None else (n_machines, BUCKET)
    assert lat.shape == want and lat.dtype == np.int64
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (1 << 30)


@pytest.mark.parametrize("m,k,n", KERNEL_SHAPES,
                         ids=[f"{m}x{k}x{n}" for m, k, n in KERNEL_SHAPES])
def test_bitserial_kernel_compiles_native(one_chip, m, k, n):
    x = jax.ShapeDtypeStruct((m, k), jnp.int8, sharding=one_chip)
    w = jax.ShapeDtypeStruct((k, n), jnp.int8, sharding=one_chip)
    compiled = jax.jit(
        lambda a, b: bitserial_mvm_pallas(a, b, interpret=False)
    ).lower(x, w).compile()
    assert "tpu_custom_call" in compiled.as_text()
    out = compiled.out_info
    assert out.shape == (m, n) and out.dtype == np.int32
