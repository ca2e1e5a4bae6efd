"""Fused chain-execution path (backend="pallas_chain_interpret").

Covers the acceptance criteria of the fused-pipeline change:
  * numerics vs jnp.linalg/np.linalg matrix_power for NON-block-divisible
    sizes (96, 200, 1000) in interpret mode, across all matpow entry points
    and expm;
  * the single-pad invariant — a counter on ops.pad_to_blocks and a
    trace-inspection over the jaxpr both show ONE pad per chain (the seed
    per-multiply path pads every operand of every multiply);
  * the single-ref squaring kernel vs the ref oracle, including its
    large-operand fallback;
  * eager HBM buffer donation in the squaring step.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (expm, matpow_binary, matpow_binary_traced,
                        matpow_naive)
from repro.core import matpow as core_matpow
from repro.kernels import ops, ref
from repro.kernels.matmul import square_pallas

CHAIN = "pallas_chain_interpret"
SEED_PATH = "pallas_interpret"  # the per-multiply ops.matmul route


def _mat(n, seed, scale=None):
    rng = np.random.default_rng(seed)
    scale = scale if scale is not None else 0.5 / np.sqrt(n)
    return jnp.asarray(rng.standard_normal((n, n)) * scale, jnp.float32)


def _ref_pow(a, n):
    return np.linalg.matrix_power(np.asarray(a, np.float64), n)


def _count_prims(jaxpr, names, count=0):
    """Recursively count primitives (jnp.pad hides inside an inner pjit)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in names:
            count += 1
        for v in eqn.params.values():
            sub = v if isinstance(v, (list, tuple)) else [v]
            for x in sub:
                if isinstance(x, jax.extend.core.ClosedJaxpr):
                    count = _count_prims(x.jaxpr, names, count)
                elif isinstance(x, jax.extend.core.Jaxpr):
                    count = _count_prims(x, names, count)
    return count


class TestChainNumerics:
    @pytest.mark.parametrize("size", [96, 200, 1000])
    def test_binary_matches_matrix_power(self, size):
        a = _mat(size, seed=size)
        got = np.asarray(matpow_binary(a, 7, backend=CHAIN))
        np.testing.assert_allclose(got, _ref_pow(a, 7), rtol=2e-3, atol=1e-5)

    @pytest.mark.parametrize("size", [96, 200])
    def test_naive_matches_matrix_power(self, size):
        a = _mat(size, seed=10 + size)
        got = np.asarray(matpow_naive(a, 5, backend=CHAIN))
        np.testing.assert_allclose(got, _ref_pow(a, 5), rtol=2e-3, atol=1e-5)

    @pytest.mark.parametrize("n", [0, 1, 2, 3, 12, 64])
    def test_traced_matches_static(self, n):
        a = _mat(96, seed=20 + n)
        got = np.asarray(matpow_binary_traced(a, jnp.int32(n), backend=CHAIN))
        want = np.asarray(matpow_binary(a, n))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)

    @pytest.mark.parametrize("n", [1, 2, 4, 9, 16])
    def test_powers_including_powers_of_two(self, n):
        """Power-of-two n exercises the copy-free result seeding."""
        a = _mat(96, seed=30 + n)
        got = np.asarray(matpow_binary(a, n, backend=CHAIN))
        np.testing.assert_allclose(got, _ref_pow(a, n), rtol=1e-3, atol=1e-5)

    def test_batched_chain(self):
        a = jnp.stack([_mat(96, 1), _mat(96, 2)])
        got = np.asarray(matpow_binary(a, 5, backend=CHAIN))
        for i in range(2):
            np.testing.assert_allclose(got[i], _ref_pow(a[i], 5),
                                       rtol=1e-3, atol=1e-5)

    def test_chain_under_jit(self):
        a = _mat(96, seed=3)
        got = jax.jit(lambda x: matpow_binary(x, 9, backend=CHAIN))(a)
        np.testing.assert_allclose(np.asarray(got), _ref_pow(a, 9),
                                   rtol=1e-3, atol=1e-5)

    def test_expm_chain_matches_xla(self, monkeypatch):
        # At equal precision: the chain's products otherwise run at
        # matpow.CHAIN_PRECISION (three bf16 passes, which the interpreter
        # carries out exactly), the xla route's at JAX's default (exact f32
        # on a CPU). tests/test_tandem_fastmm.py holds the chain at its own
        # precision against f64.
        monkeypatch.setattr(core_matpow, "CHAIN_PRECISION", None)
        rng = np.random.default_rng(0)
        a = rng.standard_normal((96, 96)) * 0.2
        want = np.asarray(expm(jnp.asarray(a, jnp.float32)), np.float64)
        got = np.asarray(expm(jnp.asarray(a, jnp.float32), backend=CHAIN),
                         np.float64)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)

    def test_expm_chain_at_its_precision_matches_f64(self):
        """The chain as it runs: every squaring at matpow.CHAIN_PRECISION
        (three bf16 passes; the interpreter carries them out exactly),
        against scipy's f64 expm of the same operand. A three-pass product
        is off by about 2**-16 of its largest entry (x_lo y_lo dropped, the
        remainders rounded), and each of the s squarings doubles what the
        result already carries: budget 2 * 2**s * 2**-16 of the largest
        entry. One bf16 pass (2**-9 a product, 128 times as much) cannot
        meet it."""
        import scipy.linalg
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.standard_normal((96, 96)) * 0.2, jnp.float32)
        a64 = np.asarray(a, np.float64)
        want = scipy.linalg.expm(a64)
        s = int(np.ceil(np.log2(np.abs(a64).sum(axis=0).max()
                                / 5.371920351148152)))
        assert s == 2                       # the chain squares here
        got = np.asarray(expm(a, backend=CHAIN), np.float64)
        budget = 2 * 2.0 ** s * 2.0 ** -16 * np.abs(want).max()
        assert np.abs(got - want).max() <= budget


class TestSinglePadInvariant:
    def test_chain_pads_exactly_once_counter(self, monkeypatch):
        """Counter-based: ONE ops.pad_to_blocks call per chain vs two per
        multiply (both operands) on the seed per-multiply path."""
        calls = []
        real = ops.pad_to_blocks

        def counting(a, bm, bn):
            calls.append(a.shape)
            return real(a, bm, bn)

        monkeypatch.setattr(ops, "pad_to_blocks", counting)
        a = _mat(96, seed=4)
        matpow_binary(a, 9, backend=CHAIN)          # 4 multiplies
        assert len(calls) == 1
        calls.clear()
        matpow_binary(a, 9, backend=SEED_PATH)
        assert len(calls) == 8                       # 2 operands x 4 multiplies

    def test_chain_jaxpr_one_pad_one_unpad(self):
        """Trace inspection: the chain jaxpr contains exactly one pad and one
        un-pad; the seed path one pad per padded operand."""
        a = _mat(96, seed=5)
        chain_jx = jax.make_jaxpr(
            lambda x: matpow_binary(x, 9, backend=CHAIN))(a)
        seed_jx = jax.make_jaxpr(
            lambda x: matpow_binary(x, 9, backend=SEED_PATH))(a)
        chain_pads = _count_prims(chain_jx.jaxpr, {"pad"})
        seed_pads = _count_prims(seed_jx.jaxpr, {"pad"})
        assert chain_pads == 1
        assert seed_pads == 8
        # un-pad lowers to slice or gather depending on the indexing route
        assert _count_prims(chain_jx.jaxpr, {"slice", "gather"}) == 1

    def test_divisible_size_pads_nothing(self):
        a = _mat(128, seed=6)
        jx = jax.make_jaxpr(lambda x: matpow_binary(x, 9, backend=CHAIN))(a)
        assert _count_prims(jx.jaxpr, {"pad"}) == 0


class TestSquareKernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("p", [128, 256, 512])
    def test_single_ref_kernel_vs_ref(self, p, dtype):
        rng = np.random.default_rng(p)
        a = jnp.asarray(rng.standard_normal((p, p)), dtype)
        got = square_pallas(a, block_m=128, block_n=128, block_k=128,
                            interpret=True)
        want = ref.matmul_ref(a, a)
        np.testing.assert_allclose(np.float32(got), np.float32(want),
                                   rtol=2e-2 if dtype == jnp.bfloat16
                                   else 2e-5, atol=1e-2)

    def test_large_operand_falls_back_to_tiled(self):
        """Above the VMEM limit the squaring delegates to matmul_pallas."""
        rng = np.random.default_rng(7)
        a = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
        got = square_pallas(a, block_m=128, block_n=128, block_k=128,
                            interpret=True, vmem_limit=1024)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(ref.matmul_ref(a, a)),
                                   rtol=1e-4, atol=1e-4)

    def test_ops_square_arbitrary_shape(self):
        a = _mat(200, seed=8, scale=1.0)
        got = ops.square(a, interpret=True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(ref.matmul_ref(a, a)),
                                   rtol=1e-4, atol=1e-4)

    def test_rejects_nonsquare(self):
        with pytest.raises(ValueError):
            square_pallas(jnp.ones((128, 256)), interpret=True)


class TestDonation:
    def test_eager_square_donates_operand(self):
        """Eager chain squarings hand their HBM buffer to the output."""
        chain = ops.MatmulChain(128, jnp.float32, interpret=True)
        x = chain.pad(_mat(128, seed=9, scale=1.0))
        y = chain.square(x)
        assert x.is_deleted()
        assert not y.is_deleted()

    def test_donation_inert_under_trace(self):
        """Inside jit the donated step is just the kernel (no error)."""
        chain = ops.MatmulChain(128, jnp.float32, interpret=True)
        a = _mat(128, seed=11, scale=1.0)
        got = jax.jit(chain.square)(a)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(ref.matmul_ref(a, a)),
                                   rtol=1e-4, atol=1e-4)
        assert not a.is_deleted()

    def test_no_donate_chain_keeps_operand(self):
        chain = ops.MatmulChain(128, jnp.float32, interpret=True,
                                donate=False)
        x = _mat(128, seed=12, scale=1.0)
        chain.square(x)
        assert not x.is_deleted()

    def test_matpow_never_consumes_caller_input(self):
        """Even when padding is a no-op (block-divisible size), the eager
        chain must square a copy — the caller's buffer survives."""
        a = _mat(128, seed=13)
        out = matpow_binary(a, 4, backend=CHAIN)
        assert not a.is_deleted()
        np.testing.assert_allclose(np.asarray(out), _ref_pow(a, 4),
                                   rtol=1e-3, atol=1e-5)
        # and the non-divisible (padded) path as well
        b = _mat(96, seed=14)
        matpow_binary(b, 4, backend=CHAIN)
        assert not b.is_deleted()
