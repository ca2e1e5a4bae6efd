"""The PRISM tandem queueing network's transient probabilities on the
fastmm route, against the plain f64 reference.

The chain and fastmm routes compute every product at
``matpow.CHAIN_PRECISION`` (three bf16 passes on a TPU); the ``xla``
route keeps JAX's default (one bf16 pass). Here, on the CPU:

  * the engine's fastmm route (Pallas kernel bodies interpreted, the
    Strassen crossover lowered so one level engages) answers e^{Qt} for
    the tandem generator within a tenth of the benchmark cell's limit of
    scipy's f64 expm;
  * an emulation of each product's precision (operands rounded as the
    chip rounds them) puts one bf16 pass above the benchmark cell's
    ``p_err`` limit and three passes ten times below it;
  * the lowered programs carry the precision of their route.
"""

import functools
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.linalg
from jax import lax

from repro.core import expm, matpow
from repro.core.expm import _THETA13, _pade13
from repro.kernels import autotune
from repro.kernels.fastmm import CHIP_PRODUCT_RTOL
from repro.kernels.matmul import tile_dot
from repro.serve import MatFnEngine

BENCH = Path(__file__).resolve().parents[1] / "bench"
TIMES = (0.1, 1.0, 10.0)


def _tandem(c: int) -> np.ndarray:
    """Q of ``tandem.sm`` at capacity c, with the benchmark's rates."""
    from importlib.util import module_from_spec, spec_from_file_location
    spec = spec_from_file_location("tandem", BENCH / "configs" /
                                   "ctmc_tandem31.py")
    module = module_from_spec(spec)
    spec.loader.exec_module(module)
    config = json.loads((BENCH / "configs" /
                         "ctmc_tandem31_f32.json").read_text())
    config["c"] = c
    return module.generator(module.draw(None, config), config)


def _limit() -> float:
    cell = json.loads((BENCH / "cells" /
                       "ctmc_tandem31_f32.transient.json").read_text())
    return cell["checks"]["p_err"]["limit"]


def _p_err(got, ref) -> float:
    """The cell's number: the largest row-L1 gap."""
    return float(np.abs(np.asarray(got, np.float64) - ref).sum(1).max())


@pytest.fixture
def fastmm_engine(tmp_path, monkeypatch):
    """An interpreting engine whose dispatch sends n >= 9 to the fastmm
    route with one Strassen level (the tuning cache steers it)."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    autotune.record_dispatch_thresholds(8, 1 << 30)
    autotune.record_fastmm(16, 1)
    return MatFnEngine(interpret=True)


@pytest.mark.parametrize("c", [3, 7])
def test_tandem_transient_on_the_fastmm_route_matches_f64(fastmm_engine, c):
    q = _tandem(c)
    n = q.shape[0]
    assert fastmm_engine.route_for(n, len(TIMES)) == "fastmm"
    for t in TIMES:
        fastmm_engine.submit("expm", jnp.asarray(q * t, jnp.float32))
    got = jax.block_until_ready(fastmm_engine.flush())
    assert fastmm_engine.stats["routes"]["fastmm"] == 1
    for t, p in zip(TIMES, got):
        # What the chip must meet in the benchmark cell: a tenth of its
        # limit. Three-pass products lose about 2**-16 each, and one
        # Strassen level makes that error normwise, so a row's mass
        # drifts; up to 8 squarings then double the drift each. The CPU
        # reads 2e-5 (t = 0.1) to 1.2e-3 (c = 7, t = 10) here.
        assert _p_err(p, scipy.linalg.expm(q * t)) <= _limit() / 10, (c, t)


def _one_pass(x, y):
    """One bf16 pass, as a TPU v5e runs an f32 product at JAX's default:
    each operand rounded to bf16, products accumulated in f32."""
    return tile_dot(x.astype(jnp.bfloat16), y.astype(jnp.bfloat16))


_three_pass = functools.partial(tile_dot, precision=lax.Precision.HIGH)


@functools.partial(jax.jit, static_argnames=("mm", "squarings"))
def _emulated_expm(a, *, mm, squarings):
    """Pade-13 scaling and squaring with every product through ``mm``."""
    x = a / 2.0 ** squarings
    u, v = _pade13(x, jnp.eye(a.shape[-1], dtype=a.dtype), mm)
    r = jnp.linalg.solve(v - u, v + u)
    return lax.fori_loop(0, squarings, lambda _, y: mm(y, y), r)


def _emulated_p_err(c: int, mm) -> float:
    q = _tandem(c) * 10.0
    norm = np.abs(q).sum(0).max()
    s = max(0, int(np.ceil(np.log2(norm / _THETA13))))
    got = _emulated_expm(jnp.asarray(q, jnp.float32), mm=mm, squarings=s)
    return _p_err(got, scipy.linalg.expm(q))


#: The smallest tandem capacity at which one bf16 pass per product fails
#: the cell's limit at t = 10 (7 squarings; c = 1 to 3 stay below it).
ONE_PASS_FAILS_AT_C = 4


def test_one_bf16_pass_fails_the_cell_limit_and_three_passes_do_not():
    limit = _limit()
    for c in range(1, ONE_PASS_FAILS_AT_C):
        assert _emulated_p_err(c, _one_pass) <= limit, c
    one = _emulated_p_err(ONE_PASS_FAILS_AT_C, _one_pass)
    three = _emulated_p_err(ONE_PASS_FAILS_AT_C, _three_pass)
    assert one > limit
    assert three * 10 <= limit, three


def test_product_emulations_match_the_chips_per_product_error():
    """The split x = x_hi + x_lo (both bf16) loses only x_lo y_lo and the
    rounding of x_lo, below 2**-16 of the product; one pass is off by
    about 2**-9. Each emulation stays within what a TPU v5e read for its
    route (``fastmm.CHIP_PRODUCT_RTOL``), measured the same way: a
    1024 x 1024 product of uniform [0, 1) operands."""
    rng = np.random.default_rng(5)
    x, y = (rng.random((1024, 1024)).astype(np.float32) for _ in range(2))
    ref = x.astype(np.float64) @ y.astype(np.float64)
    err = lambda got: float(np.abs(np.asarray(got) - ref).max()
                            / np.abs(ref).max())
    three = err(_three_pass(jnp.asarray(x), jnp.asarray(y)))
    one = err(_one_pass(jnp.asarray(x), jnp.asarray(y)))
    assert three < 2 ** -16 < one
    assert one > 100 * three
    assert one <= CHIP_PRODUCT_RTOL["xla"]
    assert three <= CHIP_PRODUCT_RTOL["fastmm"]
    assert CHIP_PRODUCT_RTOL["chain"] == CHIP_PRODUCT_RTOL["fastmm"]


def _lowered(backend: str) -> str:
    a = jax.ShapeDtypeStruct((96, 96), jnp.float32)
    return jax.jit(functools.partial(expm, backend=backend)).lower(a).as_text()


def _precisions(text: str) -> list:
    return re.findall(r"precision = \[(\w+), (\w+)\]", text)


def test_xla_route_keeps_the_default_precision():
    assert matpow.precision_for("xla") is None
    found = _precisions(_lowered("xla"))
    assert len(found) >= 7                      # Pade's six and a squaring
    assert set(found) == {("DEFAULT", "DEFAULT")}


@pytest.mark.parametrize("backend", ["pallas_chain", "pallas_fastmm"])
def test_chain_routes_carry_the_chain_precision(backend):
    assert matpow.precision_for(backend) == matpow.CHAIN_PRECISION
    want = matpow.CHAIN_PRECISION.name
    # Off the chip the chain degrades to the XLA dot: Pade's six products
    # and every squaring carry the precision, and no product runs at the
    # default.
    found = _precisions(_lowered(backend))
    assert len(found) >= 7
    assert set(found) == {(want, want)}


def test_engine_reports_each_routes_precision(fastmm_engine):
    want = matpow.CHAIN_PRECISION.name.lower()
    assert fastmm_engine.stats()["precision"] == {
        "xla": "default", "chain": want, "sharded": "default",
        "fastmm": want, "evolve": want}


def test_tracing_engine_samples_every_long_fastmm_bucket(tmp_path,
                                                         monkeypatch):
    """A fastmm bucket runs for longer than the device watcher's spacing
    (20 ms), so a tracing engine times each one (``stage=device``), and
    its ``bucket.execute`` span names the route's precision."""
    import time
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    autotune.record_dispatch_thresholds(8, 1 << 30)
    autotune.record_fastmm(16, 1)
    engine = MatFnEngine(interpret=True, trace=True)
    q = jnp.asarray(_tandem(3), jnp.float32)
    for _ in range(3):
        engine.submit("expm", q)
        jax.block_until_ready(engine.flush())
        time.sleep(1.5 * engine._device_watch.SPACING_S)
    engine._close_device_watch()
    stats = engine.stats()
    assert stats["routes"]["fastmm"] == 3
    assert stats["stages"]["device"]["count"] == 3
    spans = [s for s in engine.tracer.spans()
             if s["name"] == "bucket.execute"]
    assert [s["args"]["precision"] for s in spans] == \
        [matpow.CHAIN_PRECISION.name.lower()] * 3
