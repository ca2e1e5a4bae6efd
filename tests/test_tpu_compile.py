"""Compile the serving path's Pallas kernels for a described TPU v5e.

Nothing runs: each program is compiled for the first chip of a described
``v5e:2x2`` topology at the size the serving engine meets in use, so a
refusal of the chip's compiler (a slice off the tiling, more scoped VMEM
than a kernel may use, a program that does not fit) fails here instead of
on the chip. Interpret-mode tests cannot see those refusals. Each program
must hold a Pallas kernel (``tpu_custom_call``).

The process's backend is still the CPU, so ``ops.pallas_supported`` is
steered to True inside each test: the code then builds the chip's
programs. The topology is described inside a fixture, never at import:
only one process at a time may load the TPU compiler library.
"""

from __future__ import annotations

import functools
import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.matmul import (DEFAULT_BLOCK, SQUARE_PANEL_LIMIT,
                                  SQUARE_VMEM_LIMIT, matmul_pallas,
                                  panel_vmem_footprint, square_pallas,
                                  square_tier)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def chip(one_chip, no_persistent_cache, monkeypatch):
    """Build the chip's programs: Pallas routes on, shapes on chip 0."""
    monkeypatch.setattr(ops, "pallas_supported", lambda: True)

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return spec


def _kernels(fn, *specs) -> int:
    return jax.jit(fn).lower(*specs).compile().as_text().count(
        "tpu_custom_call")


@pytest.mark.parametrize("p,dtype,limits,tier", [
    (1024, jnp.float32, {}, "whole"),
    (2048, jnp.bfloat16, {"vmem_limit": 4 << 20}, "panel"),
    (4096, jnp.float32, {}, "two_operand"),
], ids=["whole-f32-1024", "panel-bf16-2048", "two_operand-f32-4096"])
def test_square_pallas_tiers_compile(chip, p, dtype, limits, tier):
    vmem = limits.get("vmem_limit", SQUARE_VMEM_LIMIT)
    panel = limits.get("panel_limit", SQUARE_PANEL_LIMIT)
    itemsize = jnp.dtype(dtype).itemsize
    chosen = square_tier(p * p * itemsize, vmem, panel)
    # square_pallas demotes a panel whose blocks bust VMEM, as below.
    if chosen == "panel" and panel_vmem_footprint(
            p, *DEFAULT_BLOCK[:2], itemsize) > 2 * SQUARE_VMEM_LIMIT:
        chosen = "two_operand"
    assert chosen == tier
    fn = functools.partial(square_pallas, **limits)
    assert _kernels(fn, chip((p, p), dtype)) == 1


def test_matmul_pallas_4096_compiles(chip):
    spec = chip((4096, 4096))
    assert _kernels(matmul_pallas, spec, spec) == 1


def _engine_executable(op, n, power=1, batch=1):
    """The jitted executable the serving engine builds for one bucket."""
    from repro.serve import MatFnEngine
    engine = MatFnEngine()
    route = engine.route_for(n, batch, "float32", power)
    _key, exe, _fresh = engine._executable(op, route, batch, n, "float32",
                                           power)
    return route, exe


@pytest.mark.parametrize("op,n,power,route", [
    ("matpow", 1024, 64, "chain"),
    ("matpow", 4096, 64, "fastmm"),
    ("markov", 2048, -1, "fastmm"),
], ids=["chain-matpow-1024", "fastmm-matpow-4096", "steady_state-2048"])
def test_engine_chain_executables_compile(chip, op, n, power, route):
    taken, exe = _engine_executable(op, n, power)
    assert taken == route
    assert _kernels(exe, chip((1, n, n))) >= 1


def test_evolve_distributions_2048_compiles(chip):
    from repro.core.markov import evolve_distributions
    fn = functools.partial(evolve_distributions, steps=1000,
                           backend="pallas_chain", validate=False)
    assert _kernels(lambda d, p: fn(d, p), chip((64, 2048)),
                    chip((2048, 2048))) >= 1
