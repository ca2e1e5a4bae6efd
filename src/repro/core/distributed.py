"""Mesh-sharded matrix exponentiation — the paper's algorithm at pod scale.

The 2012 paper stops at one Tesla C2050. The log-depth structure distributes
naturally: each squaring is ONE collective matmul over the device mesh, so
A^n at 512 chips costs ceil(log2 n) collective matmuls with A resident and
2-D sharded the whole time (zero host traffic, the pod-scale version of the
paper's "less data transfer between host and GPU").

Two collective-matmul schedules over a (rows x cols) mesh:

  * ``matmul_2d_gather`` — all-gather A along the col axis and B along the
    row axis, one local matmul. Simple, works on any mesh shape; comm volume
    per device = |A_panel| * (cols-1)/cols + |B_panel| * (rows-1)/rows.
  * ``matmul_cannon``    — Cannon's algorithm on square meshes: skew, then
    ``rows`` steps of (local matmul + neighbor collective_permute shifts).
    Same total volume moved but in ring steps that XLA can overlap with the
    local matmuls — the TPU analogue of SUMMA's pipelined panel broadcasts.

Both are exact (fp32 accumulation) and validated against jnp.matmul in
``tests/test_distributed.py`` on a forced multi-device CPU.

``ShardedMatmulChain`` fuses a whole squaring chain over either schedule the
way ``ops.MatmulChain`` does on one device: the operand is padded to
mesh-and-block multiples and committed to its 2-D sharding ONCE, every
squaring is a donated jitted collective step (each device reuses its HBM
shard for the output — the operand stays resident across the chain), and the
result is un-padded once at exit. ``matpow_sharded`` and ``expm_sharded``
route through it. See ``docs/distributed.md`` for the full story.
"""

from __future__ import annotations

import functools
import math
import operator
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels import ops as _kops
from repro.kernels.ops import PaddedChain

__all__ = [
    "matmul_2d_gather",
    "matmul_cannon",
    "sharded_matmul",
    "ShardedMatmulChain",
    "matpow_sharded",
    "expm_sharded",
]


def _mesh_axis_sizes(mesh: Mesh, row_axis: str, col_axis: str):
    return mesh.shape[row_axis], mesh.shape[col_axis]


def matmul_2d_gather(a: jax.Array, b: jax.Array, mesh: Mesh, *,
                     row_axis: str = "data", col_axis: str = "model") -> jax.Array:
    """C = A @ B with A, B, C all 2-D sharded P(row_axis, col_axis)."""
    spec = P(row_axis, col_axis)

    def local(a_blk, b_blk):
        # a_blk: (m/r, k/c) -> gather row panel (m/r, k) along cols
        a_row = lax.all_gather(a_blk, col_axis, axis=1, tiled=True)
        # b_blk: (k/r, n/c) -> gather col panel (k, n/c) along rows
        b_col = lax.all_gather(b_blk, row_axis, axis=0, tiled=True)
        return jnp.matmul(a_row, b_col, preferred_element_type=jnp.float32
                          ).astype(a_blk.dtype)

    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec),
                         out_specs=spec, check_vma=False)(a, b)


def matmul_cannon(a: jax.Array, b: jax.Array, mesh: Mesh, *,
                  row_axis: str = "data", col_axis: str = "model") -> jax.Array:
    """Cannon's algorithm: square-mesh collective matmul with ring shifts.

    Requires mesh.shape[row_axis] == mesh.shape[col_axis] and block-divisible
    operands. Per step the working set is one A block + one B block per
    device, and the two collective_permutes are independent of the local
    matmul of the *previous* step — XLA overlaps them (verified in the HLO:
    collective-permute-start/-done straddle the dot).
    """
    r, c = _mesh_axis_sizes(mesh, row_axis, col_axis)
    if r != c:
        raise ValueError(f"Cannon needs a square mesh, got {r}x{c}; "
                         "use matmul_2d_gather instead")
    spec = P(row_axis, col_axis)
    n_steps = r

    def lin(i, j):
        # linearized device index over the (row_axis, col_axis) axis tuple
        return i * c + j

    def local(a_blk, b_blk):
        my_row = lax.axis_index(row_axis)
        my_col = lax.axis_index(col_axis)

        # Initial skew: A row i shifted left by i; B col j shifted up by j.
        # A shift by a *traced* amount is not expressible as one static perm,
        # so skew by doubling: shift by 2^t iff bit t of the row/col index is
        # set — log2(size) masked ppermutes (every device participates in the
        # collective; non-shifting devices select their old block afterward).
        a_cur, b_cur = a_blk, b_blk
        t, s = 0, 1
        while s < n_steps:
            perm_a = [(lin(i, j), lin(i, (j - s) % c))
                      for i in range(r) for j in range(c)]
            perm_b = [(lin(i, j), lin((i - s) % r, j))
                      for i in range(r) for j in range(c)]
            bit_a = ((my_row >> t) & 1).astype(bool)
            bit_b = ((my_col >> t) & 1).astype(bool)
            a_shift = lax.ppermute(a_cur, axis_name=(row_axis, col_axis), perm=perm_a)
            b_shift = lax.ppermute(b_cur, axis_name=(row_axis, col_axis), perm=perm_b)
            # every device must participate in the collective; select after.
            a_cur = jnp.where(bit_a, a_shift, a_cur)
            b_cur = jnp.where(bit_b, b_shift, b_cur)
            s <<= 1
            t += 1

        perm_a1 = [(lin(i, j), lin(i, (j - 1) % c))
                   for i in range(r) for j in range(c)]
        perm_b1 = [(lin(i, j), lin((i - 1) % r, j))
                   for i in range(r) for j in range(c)]

        acc = jnp.zeros((a_blk.shape[0], b_blk.shape[1]), dtype=jnp.float32)

        def body(step, state):
            acc, a_cur, b_cur = state
            acc = acc + jnp.matmul(a_cur, b_cur,
                                   preferred_element_type=jnp.float32)
            a_cur = lax.ppermute(a_cur, axis_name=(row_axis, col_axis), perm=perm_a1)
            b_cur = lax.ppermute(b_cur, axis_name=(row_axis, col_axis), perm=perm_b1)
            return acc, a_cur, b_cur

        acc, _, _ = lax.fori_loop(0, n_steps, body, (acc, a_cur, b_cur))
        return acc.astype(a_blk.dtype)

    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec),
                         out_specs=spec, check_vma=False)(a, b)


def _pick_algorithm(algorithm: str, rows: int, cols: int) -> str:
    """Resolve ``"auto"`` to a concrete schedule for an (rows x cols) mesh.

    Cannon wants a square multi-device mesh (its ring shifts assume one A
    block and one B block per device per step); anything else — rectangular
    meshes, degenerate 1 x c / r x 1 meshes, a single device — runs the
    all-gather schedule, which is shape-agnostic.
    """
    if algorithm == "auto":
        return "cannon" if rows == cols and rows > 1 else "gather"
    if algorithm not in ("cannon", "gather"):
        raise ValueError(f"unknown algorithm {algorithm!r}")
    return algorithm


def sharded_matmul(a, b, mesh: Mesh, *, algorithm: str = "auto",
                   row_axis: str = "data", col_axis: str = "model"):
    """C = A @ B with A, B, C all 2-D sharded ``P(row_axis, col_axis)``.

    Dispatches to the best collective-matmul schedule for this mesh shape
    (see :func:`_pick_algorithm`): ``"cannon"`` on square multi-device
    meshes, ``"gather"`` otherwise; pass either name explicitly to force a
    schedule. Operand dims must divide the mesh axis sizes (``shard_map``
    needs even shards) — :class:`ShardedMatmulChain` handles arbitrary sizes
    by padding once at the chain boundary.

    Args:
      a, b: (n, n) operands, ideally already placed with a
        ``NamedSharding(mesh, P(row_axis, col_axis))``; anything else is
        resharded on entry by GSPMD.
      mesh: the device mesh holding both operands.
      algorithm: ``"auto"`` | ``"cannon"`` | ``"gather"``.
      row_axis, col_axis: mesh axis names for the operands' two dims.

    Returns:
      The (n, n) product, 2-D sharded exactly like the inputs (fp32
      accumulation, cast back to the input dtype).
    """
    r, c = _mesh_axis_sizes(mesh, row_axis, col_axis)
    algorithm = _pick_algorithm(algorithm, r, c)
    if algorithm == "cannon":
        return matmul_cannon(a, b, mesh, row_axis=row_axis, col_axis=col_axis)
    return matmul_2d_gather(a, b, mesh, row_axis=row_axis, col_axis=col_axis)


# Donated per-squaring collective step — the distributed analogue of
# ops._square_step. Called EAGERLY (one dispatch per squaring in a
# python-level chain) with the operand committed to the chain's 2-D
# sharding, ``donate_argnums`` lets XLA alias each device's input shard to
# its output shard: A^2 lands in the HBM that held A, so the operand stays
# resident across the whole chain (the paper's "operand never leaves the
# accelerator", per device). ``mesh``/``algorithm``/axis names are static,
# so every chain on the same mesh shares one compiled step per operand
# shape/dtype.
@functools.partial(
    jax.jit,
    static_argnames=("mesh", "algorithm", "row_axis", "col_axis"),
    donate_argnums=(0,),
)
def _sharded_square_step(x, *, mesh, algorithm, row_axis, col_axis):
    return sharded_matmul(x, x, mesh, algorithm=algorithm,
                          row_axis=row_axis, col_axis=col_axis)


# Un-donated combine step for eager chains (matpow's popcount combines).
# The ``result`` accumulator is NOT donated: ``mm`` is public chain API and
# silently consuming either operand would surprise callers holding a ref.
@functools.partial(
    jax.jit,
    static_argnames=("mesh", "algorithm", "row_axis", "col_axis"),
)
def _sharded_mm_step(x, y, *, mesh, algorithm, row_axis, col_axis):
    return sharded_matmul(x, y, mesh, algorithm=algorithm,
                          row_axis=row_axis, col_axis=col_axis)


class ShardedMatmulChain(PaddedChain):
    """Distributed analogue of ``ops.MatmulChain``: pad once, donated
    collective squarings on the resident 2-D-sharded operand, unpad once.

    Before this class the distributed path re-materialized the sharded
    operand every squaring: each ``sharded_matmul`` call resharded its
    inputs, allocated a fresh output, and (for non-divisible sizes) could
    not run at all, while the single-device chain already had pad-once /
    donate / unpad-once semantics. This object gives the mesh path the same
    contract (shared via :class:`~repro.kernels.ops.PaddedChain`):

        chain = ShardedMatmulChain(a.shape[-1], a.dtype, mesh)
        x = chain.pad(a)           # ONE pad to mesh multiples + placement
        x = chain.square(x)        # k times: donated collective squarings,
        ...                        #   each device reuses its HBM shard
        out = chain.unpad(result)  # ONE slice back to (n, n)

    * ``pad`` zero-pads (n, n) up to the chain's ``padded_n`` — the smallest
      multiple of ``lcm(rows, cols) * shard_multiple`` >= n, so every shard
      is even (a ``shard_map`` requirement) and, on TPU, 128-aligned — and
      commits the operand to ``NamedSharding(mesh, P(row_axis, col_axis))``.
      Zero-padding is closed under multiplication, so the whole chain runs
      on the padded buffer.
    * ``square`` CONSUMES its argument when called eagerly (buffer
      donation): each device's output shard reuses the HBM of its input
      shard. Under an outer trace (jit / fori_loop bodies) donation is inert
      and the step inlines into the surrounding program as a plain
      collective matmul.
    * ``algorithm="auto"`` resolves per mesh shape at construction
      (Cannon on square multi-device meshes, all-gather otherwise), so every
      step of one chain runs the same schedule.

    Used by :func:`matpow_sharded` and :func:`expm_sharded`.
    """

    def __init__(self, n: int, dtype, mesh: Mesh, *, algorithm: str = "auto",
                 row_axis: str = "data", col_axis: str = "model",
                 shard_multiple: Optional[int] = None, donate: bool = True):
        super().__init__(n, dtype, donate=donate)
        self.mesh = mesh
        self.row_axis = row_axis
        self.col_axis = col_axis
        rows, cols = _mesh_axis_sizes(mesh, row_axis, col_axis)
        self.algorithm = _pick_algorithm(algorithm, rows, cols)
        if shard_multiple is None:
            # Per-shard dims should stay MXU-aligned on TPU; on CPU meshes
            # (tests, local development) any even shard works.
            shard_multiple = 128 if jax.default_backend() == "tpu" else 1
        step = math.lcm(rows, cols) * int(shard_multiple)
        self.padded_n = (self.n + step - 1) // step * step
        self.sharding = NamedSharding(mesh, P(row_axis, col_axis))
        self._static = dict(mesh=mesh, algorithm=self.algorithm,
                            row_axis=row_axis, col_axis=col_axis)

    # -- chain boundary ----------------------------------------------------
    def pad(self, a: jax.Array) -> jax.Array:
        """Pad (n, n) -> (P, P) and commit the chain's 2-D sharding. ONCE.

        The committed ``NamedSharding(mesh, P(row, col))`` is what makes the
        donated squaring steps alias in place: input and output shards have
        identical layouts, so XLA reuses each device's buffer. The base-class
        contract (never hand the caller's own buffer into the chain) is
        honored with a defensive copy only when ``device_put`` could return
        that buffer — an operand whose placement is already *equivalent* to
        the chain's sharding (``Sharding.is_equivalent_to``: same devices
        and partitioning, e.g. a single-device array entering a 1x1-mesh
        chain, or one already committed to the chain's NamedSharding).
        Every other case (padding, real resharding) allocates fresh buffers
        anyway, and the copy would be a pure O(n^2) waste on exactly the
        huge single matrices the serving engine routes here.
        """
        if a.ndim != 2:
            raise ValueError(
                f"sharded chains are 2-D only, got shape {a.shape}")
        if isinstance(a, jax.core.Tracer):
            return lax.with_sharding_constraint(super().pad(a), self.sharding)
        if self.padded_n != self.n:
            # Through the module attr (not a direct name) so the pad-count
            # instrumentation in tests — and any future wrapping of
            # ops.pad_to_blocks — observes the chain boundary.
            a = _kops.pad_to_blocks(a, self.padded_n, self.padded_n)
        elif self.donate and getattr(a, "sharding", None) is not None \
                and a.sharding.is_equivalent_to(self.sharding, a.ndim):
            a = jnp.copy(a)
        return jax.device_put(a, self.sharding)

    # -- chain body (operand already padded + placed) ----------------------
    def mm(self, x: jax.Array, y: jax.Array) -> jax.Array:
        """x @ y on the padded sharded buffers (combine step; no donation)."""
        if isinstance(x, jax.core.Tracer) or isinstance(y, jax.core.Tracer):
            return sharded_matmul(x, y, self.mesh, algorithm=self.algorithm,
                                  row_axis=self.row_axis,
                                  col_axis=self.col_axis)
        return _sharded_mm_step(x, y, **self._static)

    def square(self, x: jax.Array) -> jax.Array:
        """x @ x as one collective step; CONSUMES x when eager (donation).

        Eager calls go through the donated jitted step — each device's
        output shard reuses its input shard's HBM. Traced calls (inside an
        outer jit / lax loop) go straight to the collective matmul: donation
        is inert there and the extra pjit boundary would only block fusion.
        """
        if self.donate and not isinstance(x, jax.core.Tracer):
            return _sharded_square_step(x, **self._static)
        return sharded_matmul(x, x, self.mesh, algorithm=self.algorithm,
                              row_axis=self.row_axis, col_axis=self.col_axis)


def matpow_sharded(a: jax.Array, n: int, mesh: Mesh, *, algorithm: str = "auto",
                   row_axis: str = "data", col_axis: str = "model") -> jax.Array:
    """A^n with A 2-D resident-sharded; ceil(log2 n) collective matmuls.

    The paper's squaring chain at mesh scale, routed through
    :class:`ShardedMatmulChain`: the operand is padded to mesh multiples and
    committed to its ``P(row_axis, col_axis)`` sharding exactly ONCE, every
    squaring is one donated collective step (each device reuses its HBM
    shard — A never leaves the devices), the popcount(n)-1 combines run
    un-donated, and the result is sliced back to (n, n) once at exit.
    Arbitrary n x n sizes are supported (the chain pads non-divisible sizes;
    the bare :func:`sharded_matmul` requires even shards).

    Args:
      a: (n, n) operand. Called eagerly, ``a`` is never consumed (the chain
        squares a padded buffer or a defensive copy, not the caller's).
      n: static python int >= 0 (``n == 0`` returns the sharded identity).
      mesh: the device mesh to keep A resident on.
      algorithm: ``"auto"`` | ``"cannon"`` | ``"gather"`` — the collective
        schedule for every step (auto-picked per mesh shape).
      row_axis, col_axis: mesh axis names for A's two dims.

    Returns:
      A^n, 2-D sharded over the mesh like the input.
    """
    if not isinstance(n, int) or n < 0:
        raise ValueError("matpow_sharded requires a static python int n >= 0")
    chain = ShardedMatmulChain(a.shape[-1], a.dtype, mesh,
                               algorithm=algorithm, row_axis=row_axis,
                               col_axis=col_axis)
    if n == 0:
        # Build the identity at the chain's padded size so the even-shard
        # placement always succeeds, then slice back — non-divisible n would
        # otherwise crash the device_put.
        eye = jnp.eye(chain.padded_n, dtype=a.dtype)
        return chain.unpad(jax.device_put(eye, chain.sharding))
    # Deferred for the same reason as expm_sharded's expm import: keeps
    # this module importable on its own. The squaring/combine loop —
    # including the donation-aware result seeding — is shared with the
    # single-device and batched chains, so a fix lands in every executor.
    from repro.core.matpow import _binary_chain_body
    return chain.unpad(_binary_chain_body(chain.pad(a), n, chain))


def expm_sharded(a: jax.Array, mesh: Mesh, *, max_squarings: int = 32,
                 algorithm: str = "auto", row_axis: str = "data",
                 col_axis: str = "model") -> jax.Array:
    """Matrix exponential e^A with A 2-D-sharded — the scientific workload
    at mesh scale.

    The scaling-and-squaring body of :func:`repro.core.expm.expm`
    (Pade-13 + data-dependent squarings), with the squaring chain routed
    through :class:`ShardedMatmulChain`: the Pade result is padded and
    committed to its 2-D sharding ONCE, then squared ``s`` times inside a
    ``lax.fori_loop`` as collective matmuls over the mesh (donation is inert
    under the loop trace; XLA's own buffer reuse applies). The small fixed
    Pade polynomial (6 matmuls + one solve) is not a chain — it stays on
    GSPMD-partitioned XLA ops, and the solve gathers: it is O(1) in the
    squaring count, which is where the mesh residency matters.

    Args:
      a: (n, n) operand (2-D only — the sharded chain has no batch path).
      mesh / algorithm / row_axis / col_axis: as :func:`matpow_sharded`.
      max_squarings: clip on the data-dependent squaring count.

    Returns:
      e^A in ``a.dtype``, 2-D sharded over the mesh.
    """
    # Deferred: repro.core.expm imports repro.core.matpow at module load;
    # importing it lazily keeps distributed importable on its own.
    from repro.core.expm import _scaling_and_squaring

    if a.ndim != 2 or a.shape[-1] != a.shape[-2] or a.shape[-1] < 1:
        raise ValueError(f"expm_sharded needs one square matrix with n >= 1, "
                         f"got {a.shape}")
    # Squarings always run inside the fori_loop (traced) — donation never
    # fires, so skip the donate-enabled chain's defensive pad-time copy.
    return _scaling_and_squaring(
        a, max_squarings, operator.matmul,
        lambda r: ShardedMatmulChain(r.shape[-1], r.dtype, mesh,
                                     algorithm=algorithm, row_axis=row_axis,
                                     col_axis=col_axis, donate=False))
