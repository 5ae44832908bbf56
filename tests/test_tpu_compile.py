"""Compile the tick kernel for a described TPU v5e at the sizes users run.

Nothing here runs on a chip.  The TPU compiler compiles the batched tick
kernel (``_get_batch_kernel``) for one chip of a described ``v5e:2x2``
topology at the phase-1 shapes of ``chip_smoke.py``: 512 candidates in the
512-instance / 128-container bucket, 800 ticks (the evaluator's 8 s
horizon), dense and sparse (32,768-edge bucket), full and summary payloads.
Each case must fit one chip's 16 GiB, and every one-hot contraction must
ask for HIGHEST precision, so that no TPU pass rounds its f32 operands to
bf16.  The topology is described inside a fixture (never at import), and
every case skips where it cannot be described.
"""
from __future__ import annotations

import os
import re

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ContainerDim, round_robin_configuration
from repro.streams import SimParams, wordcount
from repro.streams import simulator as sim

HBM_BYTES = 16 * 2**30
BATCH, N_INST, N_CONT, N_EDGES, DEGREE = 512, 512, 128, 32768, 64
N_TICKS = 800


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def isolated_compiles():
    """No persistent compilation cache (a described-chip compile cannot be
    read back without the chip), and the module kernel cache left as found."""
    from jax.experimental.compilation_cache import compilation_cache

    kernels, stats = dict(sim._KERNEL_CACHE), dict(sim._CACHE_STATS)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()
        sim._KERNEL_CACHE.clear()
        sim._KERNEL_CACHE.update(kernels)
        sim._CACHE_STATS.update(stats)


def _batch_args(backend: str, sharding) -> tuple:
    """Shapes of one kernel call, laid out by ``pad_structure`` itself."""
    cfg = round_robin_configuration(wordcount(), {"W": 2, "C": 2}, 2, ContainerDim())
    padded = sim.pad_structure(
        sim.structure_for(cfg, SimParams()), N_INST, N_CONT,
        N_EDGES if backend == "sparse" else None, DEGREE, DEGREE,
    )

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct((BATCH, *shape), dtype, sharding=sharding)

    arrays = {k: spec(v.shape, v.dtype) for k, v in padded.items()}
    return arrays, spec((N_TICKS,), np.float32), spec((), np.int32)


@pytest.mark.parametrize("samples", ["full", "summary"])
@pytest.mark.parametrize("backend", ["dense", "sparse"])
def test_tick_kernel_compiles_for_v5e(backend, samples, one_chip, isolated_compiles):
    p = SimParams()
    kernel = sim._get_batch_kernel(
        BATCH, N_INST, N_CONT, N_TICKS, p.sample_every, 1, backend,
        N_EDGES if backend == "sparse" else 0, DEGREE, DEGREE,
        samples_mode=samples,
    )
    lowered = kernel.lower(
        *_batch_args(backend, one_chip), p.dt, p.noise_std,
        p.queue_high_ktuples, p.queue_low_ktuples, p.gc_heap_mb,
        p.gc_cost_frac, p.mem_alloc_mb_per_ktuple,
    )
    # all five one-hot contractions of a tick (CPU demand, SM origins,
    # SM arrivals, two traversal sums) ask for f32-exact passes ...
    dots = re.findall(r"stablehlo\.dot_general.*", lowered.as_text())
    assert len(dots) == 5
    assert all("precision = [HIGHEST, HIGHEST]" in d for d in dots), dots
    compiled = lowered.compile()
    # ... and any contraction the chip's compiler keeps on the MXU carries it
    for line in compiled.as_text().splitlines():
        if re.search(r"= \S+ (dot|convolution)\(", line):
            assert "operand_precision={highest,highest}" in line, line
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes + m.generated_code_size_in_bytes
             - m.alias_size_in_bytes)
    assert 0 < total < HBM_BYTES
