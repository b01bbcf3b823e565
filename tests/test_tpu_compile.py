"""Main-path kernels compile for a TPU v5e, at the paper deployment's width.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology.  This is what the Pallas interpreter cannot show — Mosaic refuses
ops it has no lowering for (a 1-D matvec inside the screening loop was one),
blocks not aligned to the tiling, and kernels that need more VMEM than a
core has.  Each case compiles one kernel wrapper of ``repro.kernels.ops`` at
K=100 clients and D=535,818 (the packed paper DNN 784x512x256x10), checks
that the compiled program holds the kernel (``tpu_custom_call``) and that
it fits one chip's HBM.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.afa import AFAConfig, afa_aggregate
from repro.kernels import ops

K = 100
D = 784 * 512 + 512 + 512 * 256 + 256 + 256 * 10 + 10  # 535,818
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    # the TPU compiler ships in libtpu (the ``tpu`` extra); with it
    # installed, a topology that cannot be described is a failure
    pytest.importorskip("libtpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compilation cache off:
    a program compiled for a described chip is written to the cache but
    cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def tpu_geometry(monkeypatch):
    """The wrappers pick their launch geometry from the backend they run
    on; steer them to the TPU's (this process's backend is the CPU)."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _spec(chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _compile_for_chip(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, used
    return compiled


_KERNELS = {
    "afa_screen": lambda c: (
        lambda u, pn, m: ops.afa_screen(
            u, pn, m, xi0=2.0, delta_xi=0.5, max_rounds=8, interpret=False),
        (_spec(c, (K, D)), _spec(c, (K,)), _spec(c, (K,), jnp.bool_)),
    ),
    "gram": lambda c: (
        lambda u: ops.gram(u, interpret=False), (_spec(c, (K, D)),),
    ),
    # the (BK, BK) output tile is a lane dimension too: BK a multiple of 128
    "gram_k_tiled": lambda c: (
        lambda u: ops.gram(u, block_k=128, interpret=False), (_spec(c, (K, D)),),
    ),
    "cosine_sim": lambda c: (
        lambda u, w: ops.cosine_sim(u, w, interpret=False),
        (_spec(c, (K, D)), _spec(c, (D,))),
    ),
    "weighted_sum": lambda c: (
        lambda w, u: ops.weighted_sum(w, u, interpret=False),
        (_spec(c, (K,)), _spec(c, (K, D))),
    ),
    "coord_median_masked": lambda c: (
        lambda u, m: ops.coord_median(u, m, interpret=False),
        (_spec(c, (K, D)), _spec(c, (K,), jnp.bool_)),
    ),
    "trimmed_mean": lambda c: (
        lambda u, m: ops.trimmed_mean(u, m, trim=30, interpret=False),
        (_spec(c, (K, D)), _spec(c, (K,), jnp.bool_)),
    ),
}


@pytest.mark.parametrize("kernel", sorted(_KERNELS))
def test_kernel_compiles_for_v5e(kernel, one_chip, tpu_geometry):
    fn, args = _KERNELS[kernel](one_chip)
    _compile_for_chip(fn, *args)


@pytest.mark.parametrize("variant", ["gram", "iterative"])
def test_afa_aggregation_compiles_for_v5e(variant, one_chip, tpu_geometry):
    """AFA as the fused round calls it with the kernel plan pinned to
    pallas: the gram variant is one afa_screen launch, the iterative one
    chains weighted_sum / cosine_sim inside the screening while-loop and a
    last weighted_sum after it."""
    cfg = AFAConfig(variant=variant, use_kernels="pallas")
    compiled = _compile_for_chip(
        lambda u, n, p, m: afa_aggregate(u, n, p, mask0=m, config=cfg),
        _spec(one_chip, (K, D)), _spec(one_chip, (K,)), _spec(one_chip, (K,)),
        _spec(one_chip, (K,), jnp.bool_),
    )
    launches = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    assert launches == {"gram": 1, "iterative": 3}[variant], launches
