"""Batched (stacked) squaring chains — the paper's "different sizes and
different powers" regime.

The 2012 paper's heterogeneous pipeline keeps the device saturated across a
*mix* of matrices; our chains (``ops.MatmulChain``, ``ShardedMatmulChain``)
run one matrix at a time, so small-n traffic leaves the hardware idle —
exactly the regime where Tomov et al.'s probability-based GPU simulations
and D'Alberto's heterogeneous matmul get their wins from batching.

``BatchedMatmulChain`` is the stacked (B, n, n) twin of ``ops.MatmulChain``:

  * the whole stack is padded to block multiples ONCE at chain entry
    (zero-padding is closed under multiplication, per matrix);
  * every squaring runs as ONE donated dispatch over the stack — the Pallas
    route maps ``square_pallas`` over B (vmap of the pallas_call adds a
    leading grid dimension, so the B squarings share one kernel launch),
    and off-TPU the stack goes through the batched XLA dot
    (``jnp.matmul``-equivalent fp32-accumulating fallback);
  * the stack is un-padded once at exit.

``batched_matpow`` drives the binary exponentiation loop over it; the
serving engine (``repro.serve.matfn``) builds its bucket executables from
these entry points.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import matpow as _matpow
from repro.kernels import ops as _kops
from repro.kernels import ref as _ref
from repro.kernels.matmul import square_pallas

__all__ = ["BatchedMatmulChain", "batched_matpow", "batched_expm"]


# Donated batched squaring step — the stacked analogue of ops._square_step:
# called eagerly (one dispatch per squaring of a python-level chain), XLA
# reuses the whole stack's HBM buffer for the output. The vmap over the
# leading dim turns into an extra (parallel) grid dimension of the
# pallas_call, so all B matrices square in one kernel launch.
@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret", "out_dtype",
                     "vmem_limit", "panel_limit", "precision"),
    donate_argnums=(0,),
)
def _batched_square_step(a, *, block_m, block_n, block_k, interpret, out_dtype,
                         vmem_limit, panel_limit, precision):
    return jax.vmap(lambda x: square_pallas(
        x, block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=interpret, out_dtype=out_dtype,
        vmem_limit=vmem_limit, panel_limit=panel_limit,
        precision=precision))(a)


@functools.partial(jax.jit, static_argnames=("precision",),
                   donate_argnums=(0,))
def _batched_square_step_ref(a, *, precision):
    return _ref.matmul_ref(a, a, precision=precision)


class BatchedMatmulChain(_kops.MatmulChain):
    """Fused executor for a chain of same-shape squarings over a (B, n, n)
    stack: pad the stack once, donated batched squarings, unpad once.

    Everything (block selection, VMEM tier policy, off-TPU degradation to
    the XLA dot) is inherited from :class:`~repro.kernels.ops.MatmulChain`;
    this class only (a) pins the leading batch dimension so shape mistakes
    fail at the chain boundary, and (b) routes eager donated squarings
    through ONE batched dispatch instead of B per-matrix dispatches — the
    per-matrix chain's ``vmap(self.square)`` traces its way around the
    donated jit, so a stacked workload would never reuse its HBM buffer.

    ``square(x)`` CONSUMES ``x`` when called eagerly (the whole stack's
    buffer is donated); ``pad`` protects the caller's array exactly like the
    per-matrix chain does.
    """

    def __init__(self, batch: int, n: int, dtype, *, interpret: bool = False,
                 blocks=None, donate: bool = True, fast=False,
                 precision=None):
        if not isinstance(batch, int) or batch < 1:
            raise ValueError(f"batched chains need a static batch >= 1, "
                             f"got {batch!r}")
        super().__init__(n, dtype, interpret=interpret, blocks=blocks,
                         donate=donate, fast=fast, precision=precision)
        self.batch = batch

    # -- chain boundary ----------------------------------------------------
    def pad(self, a: jax.Array) -> jax.Array:
        """Zero-pad (B, n, n) -> (B, P, P). Called once per chain."""
        if a.ndim != 3 or a.shape[0] != self.batch:
            raise ValueError(
                f"batched chain expects a ({self.batch}, {self.n}, {self.n}) "
                f"stack, got shape {a.shape}")
        return super().pad(a)

    # -- chain body (stack already padded) ---------------------------------
    def square(self, x: jax.Array) -> jax.Array:
        """x @ x for the whole stack in ONE dispatch; CONSUMES x when eager."""
        if self.donate and not isinstance(x, jax.core.Tracer):
            if self.fast:
                # The donated Strassen step slices the stack's trailing dims
                # and batches its leaves natively — already ONE dispatch.
                return super().square(x)
            if not self.active:
                return _batched_square_step_ref(x, precision=self.precision)
            bm, bn, bk = self.blocks
            vmem_limit, panel_limit = self.tiers
            return _batched_square_step(
                x, block_m=bm, block_n=bn, block_k=bk,
                interpret=self.interpret, out_dtype=self.dtype,
                vmem_limit=vmem_limit, panel_limit=panel_limit,
                precision=self.precision)
        # Traced (outer jit / lax loop): donation is inert, the base class
        # vmaps the kernel per matrix and XLA fuses the batch itself.
        return super().square(x)


def batched_matpow(a: jax.Array, p: int, *, backend: str = "xla") -> jax.Array:
    """A_i^p for every matrix of a stacked (B, n, n) operand.

    The binary-exponentiation chain of :func:`repro.core.matpow.matpow_binary`
    executed stack-at-once: floor(log2 p) batched squarings plus
    popcount(p)-1 batched combines, each ONE dispatch for all B matrices.
    ``backend`` follows :func:`repro.core.matpow.matmul_backend` names; the
    ``"pallas_chain[_interpret]"`` routes run through
    :class:`BatchedMatmulChain` (pad the stack once, donated batched
    squarings, unpad once), the ``"pallas_fastmm[_interpret]"`` routes run
    the same chain with Strassen recursion per squaring
    (tolerance-bounded — see ``kernels.fastmm.error_budget``), and
    everything else falls through to the already batch-capable
    :func:`matpow_binary`.

    ``p`` must be a static python int >= 0; ``p == 0`` returns a stack of
    identities (the same contract as every other matpow entry point).
    """
    if a.ndim != 3 or a.shape[-1] != a.shape[-2]:
        raise ValueError(f"batched_matpow needs a stacked (B, n, n) operand, "
                         f"got shape {a.shape}")
    if not isinstance(p, int):
        raise TypeError("batched_matpow requires a static python int p")
    if p < 0:
        raise ValueError("negative powers not supported")
    interpret = _matpow._CHAIN_BACKENDS.get(backend)
    if interpret is None:
        return _matpow.matpow_binary(a, p, backend=backend)
    # Shared n >= 1 / p == 0 handling lives in matpow_binary; the chain
    # route re-checks n via the chain constructor.
    if a.shape[-1] < 1:
        raise ValueError(f"batched_matpow needs matrices with n >= 1, "
                         f"got shape {a.shape}")
    if p == 0:
        return jnp.broadcast_to(jnp.eye(a.shape[-1], dtype=a.dtype), a.shape)
    chain = BatchedMatmulChain(a.shape[0], a.shape[-1], a.dtype,
                               interpret=interpret,
                               fast=backend in _matpow._FAST_BACKENDS,
                               precision=_matpow.precision_for(backend))
    return chain.unpad(_matpow._binary_chain_body(chain.pad(a), p, chain))


def batched_expm(a: jax.Array, *, backend: str = "xla",
                 max_squarings: int = 32) -> jax.Array:
    """e^{A_i} for every matrix of a stacked (B, n, n) operand.

    :func:`repro.core.expm.expm` is already stack-capable (per-matrix
    scaling, batched Pade solve, masked squarings to the stack's max s);
    this wrapper only pins the 3-D contract so the serving engine's expm
    buckets fail loudly on shape mistakes instead of silently broadcasting.
    """
    if a.ndim != 3 or a.shape[-1] != a.shape[-2]:
        raise ValueError(f"batched_expm needs a stacked (B, n, n) operand, "
                         f"got shape {a.shape}")
    from repro.core.expm import expm
    return expm(a, backend=backend, max_squarings=max_squarings)
