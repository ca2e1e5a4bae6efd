"""Persistent tuning subsystem for every Pallas kernel in the package.

The 2012 paper sweeps tile sizes per problem ("an appropriate TILE size is
used based on the problem and local memory available"); D'Alberto's
heterogeneous matmul work and the QCD-on-GPUs methodology both show a
*measured* sweep is worth 2-4x over a static heuristic. PR 1 built that
sweep for the matmul kernel; this module generalizes it into a
kernel-registry: every cache key is namespaced by the kernel it tunes and
every kernel variant consults the same persistent artifact.

Namespaces (the ``kernel`` key segment):

  * ``matmul``       — ``(block_m, block_n, block_k)`` tilings for the tiled
                       matmul / squaring-chain kernels; consulted by
                       ``ops.pick_blocks`` (and therefore ``ops.matmul``,
                       ``ops.MatmulChain``, and ``models.layers.dense``).
  * ``attention``    — ``(block_q, block_k)`` tilings for the flash-attention
                       kernel, keyed on ``(sq, skv, d)``; consulted by
                       ``ops.pick_attn_blocks`` / ``flash_attention``.
  * ``square_panel`` — the VMEM tier thresholds of ``square_pallas``
                       (whole-operand-resident limit, panel-resident limit);
                       consulted by ``square_tiers``.
  * ``fastmm``       — the Strassen fast-matmul route's knobs: the crossover
                       size above which a squaring/multiply recurses one
                       Strassen level instead of running dense, the recursion
                       depth cap, and (optionally) the leaf tile shapes; all
                       per dtype/backend. Consulted by ``fastmm_config`` (the
                       ``kernels.fastmm`` recursion, ``ops.MatmulChain``'s
                       ``fast`` path, and the serving engine's ``"fastmm"``
                       dispatch route).
  * ``dispatch``     — the serving engine's scheduling knobs: the matrix-size
                       thresholds of heterogeneous dispatch (largest n kept on
                       the CPU/XLA route, smallest single-matrix n promoted to
                       the sharded chain; ``dispatch_thresholds``) AND the
                       continuous-batching daemon's per-traffic-class flush
                       deadlines (``bucket_deadline_ms`` — how long a
                       partially-filled (op, n, dtype) bucket may wait for
                       more requests before it executes). Both are consulted
                       by ``repro.serve.matfn``, so hardware sweeps retune
                       where each bucket runs and how long it batches.

Every mutation of the cache (a ``record_*`` call, a persist, a memo clear
picking up an external file edit) bumps a process-wide generation counter
(``cache_generation``); long-lived consumers that memoize resolved entries
— the serving engine memoizes its dispatch thresholds and deadlines — key
their memo on the generation so a mid-process retune reroutes them instead
of being silently ignored.

Shared machinery:

  * ``sweep`` / ``sweep_attention``
                   — score candidates for a problem: wall-clock on real TPU
                     hardware, an analytic VMEM/arithmetic-intensity model
                     everywhere else (interpret-mode wall clock is python
                     overhead, never timed).
  * on-disk cache  — ``~/.cache/repro/autotune.json`` (override with
                     ``REPRO_AUTOTUNE_CACHE``), atomic writes, corrupted or
                     partially-valid files degrade to an empty/filtered cache
                     instead of raising.
  * ``lookup``     — consulted by the ``pick_*`` helpers before their VMEM
                     heuristics, so every kernel call picks tuned tiles for
                     free. Pre-namespace (PR 1) matmul keys keep working.

``benchmarks/kernel_sweep.py`` populates all three namespaces as part of the
paper's tile sweep; ``benchmarks/run.py --quick`` seeds the benched sizes.
See ``docs/autotuning.md`` for the JSON schema and regeneration workflow.
"""

from __future__ import annotations

import json
import math
import os
import time
import warnings
from pathlib import Path
from typing import Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.matmul import (matmul_pallas, DEFAULT_BLOCK,
                                  SQUARE_VMEM_LIMIT, SQUARE_PANEL_LIMIT)

__all__ = [
    "cache_path", "load_cache", "save_cache", "clear_memory_cache",
    "lookup", "record", "sweep", "DEFAULT_CANDIDATES",
    "VMEM_BUDGET", "vmem_footprint",
    "KERNELS", "DEFAULT_ATTN_CANDIDATES", "attn_vmem_footprint",
    "modeled_attn_score", "sweep_attention",
    "DEFAULT_SQUARE_TIERS", "square_tiers", "record_square_tiers",
    "sweep_square_tiers",
    "DEFAULT_DISPATCH_THRESHOLDS", "dispatch_thresholds",
    "record_dispatch_thresholds",
    "DEFAULT_FASTMM_CROSSOVER", "DEFAULT_FASTMM_LEVELS", "fastmm_config",
    "record_fastmm", "sweep_fastmm",
    "DEFAULT_MAX_DELAY_MS", "bucket_deadline_ms", "record_bucket_deadline",
    "DEFAULT_MARKOV_EVOLVE_THRESHOLD", "markov_evolve_threshold",
    "record_markov_evolve_threshold",
    "cache_generation",
]

_ENV_VAR = "REPRO_AUTOTUNE_CACHE"

#: Kernel namespaces the cache knows about (the first segment of every key).
KERNELS = ("matmul", "attention", "square_panel", "dispatch", "fastmm",
           "markov")

#: Default VMEM working-set budget shared by ops.pick_blocks and the sweep
#: scorer — ONE definition so the heuristic and the cache never disagree.
VMEM_BUDGET = 8 * 1024 * 1024


def vmem_footprint(blocks: Sequence[int], itemsize: int = 2) -> int:
    """Working-set bytes of one matmul grid step: two double-buffered input
    tiles plus the fp32 accumulator tile (the paper's local-memory
    constraint)."""
    bm, bn, bk = blocks
    return 2 * (bm * bk + bk * bn) * itemsize + bm * bn * 4


def attn_vmem_footprint(block_q: int, block_k: int, d: int,
                        itemsize: int = 2) -> int:
    """Working-set bytes of one flash-attention grid step.

    Double-buffered q/k/v input tiles, the fp32 (block_q, block_k) score
    tile, and the fp32 running (max, denom, acc) scratch — the attention
    analogue of ``vmem_footprint``.
    """
    inputs = 2 * (block_q * d + 2 * block_k * d) * itemsize
    scores = block_q * block_k * 4
    scratch = block_q * (d + 2) * 4
    return inputs + scores + scratch

# MXU-aligned candidates; power-of-two multiples of 128 so any mix has a
# small lcm (chain execution needs one padded size divisible by all three).
DEFAULT_CANDIDATES: tuple = (
    (128, 128, 128), (256, 256, 256), (512, 512, 512),
    (512, 512, 256), (256, 512, 512), (128, 512, 512),
    (512, 128, 512), (256, 256, 512), (512, 256, 512),
)

#: (block_q, block_k) candidates for the flash-attention sweep — MXU-aligned
#: powers of two; the q/kv tile shapes the TPU pipeline can double-buffer.
DEFAULT_ATTN_CANDIDATES: tuple = (
    (128, 128), (128, 256), (256, 128), (256, 256),
    (256, 512), (512, 256), (512, 512), (512, 1024), (1024, 512),
)

#: Default ``square_pallas`` memory-tier thresholds (operand bytes):
#: whole-operand-resident below the first, panel-resident up to the second,
#: generic two-operand streaming kernel above. Overridable per backend/dtype
#: through the ``square_panel`` cache namespace (``square_tiers``).
DEFAULT_SQUARE_TIERS: tuple = (SQUARE_VMEM_LIMIT, SQUARE_PANEL_LIMIT)

#: Default heterogeneous-dispatch thresholds ``(cpu_max_n, sharded_min_n)``
#: for the matrix-function serving engine: buckets with n <= cpu_max_n run
#: the plain XLA route (kernel-launch overhead dominates tiny matmuls —
#: the paper's "CPU side" of the heterogeneous split), single matrices with
#: n >= sharded_min_n are promoted to ``ShardedMatmulChain`` when a mesh is
#: available, everything between runs the fused Pallas chain. Overridable
#: per backend/dtype through the ``dispatch`` cache namespace.
DEFAULT_DISPATCH_THRESHOLDS: tuple = (64, 4096)

#: Default continuous-batching flush deadline (milliseconds): how long a
#: partially-filled serving bucket may wait for more requests before it
#: executes anyway. Small enough that a lone request never waits
#: perceptibly; per-(op, n, dtype) entries in the ``dispatch`` namespace
#: override it (``bucket_deadline_ms``) — big slow buckets can afford to
#: wait longer than their own execution time, tiny ones cannot.
DEFAULT_MAX_DELAY_MS: float = 2.0

#: Default Strassen fast-matmul crossover (matrix size n): multiplies with
#: n above this recurse one Strassen level (7 half-size sub-products, ~1 bit
#: of accuracy per level) until the sub-problem reaches the crossover or the
#: level cap. Modeled default from a CPU measurement: one XLA-dot core only
#: loses to depth-1 Strassen above ~1k (1.1-1.2x at n=1536), so the default
#: stays conservative; ``sweep_fastmm`` retunes per backend/dtype.
DEFAULT_FASTMM_CROSSOVER: int = 1024

#: Default Strassen recursion-depth cap. Every level multiplies the error
#: constant (~1 bit lost) and the sub-product bookkeeping, so depth is
#: capped independently of the crossover.
DEFAULT_FASTMM_LEVELS: int = 2

# In-memory image of each cache file, keyed by resolved path.
_MEM: dict = {}

# Process-wide mutation counter for the cache (see ``cache_generation``).
_GENERATION = 0


def cache_generation() -> int:
    """Monotone counter bumped on every cache mutation in this process.

    Covers ``record*`` calls, ``save_cache``, ``clear_memory_cache`` (the
    documented way to pick up an external file edit), and fresh disk reads.
    Consumers that memoize resolved entries (e.g. the serving engine's
    dispatch thresholds and deadlines) compare generations instead of
    re-reading the cache on every call — and re-resolve the moment a
    retune lands, instead of routing on stale values until restart.
    """
    return _GENERATION


def _bump_generation() -> None:
    global _GENERATION
    _GENERATION += 1


def cache_path() -> Path:
    """Resolve the on-disk cache location (env override wins)."""
    override = os.environ.get(_ENV_VAR)
    if override:
        return Path(override).expanduser()
    return Path.home() / ".cache" / "repro" / "autotune.json"


def _key(m: int, n: int, k: int, dtype=None, backend: Optional[str] = None,
         kernel: str = "matmul") -> str:
    d = jnp.dtype(dtype).name if dtype is not None else "any"
    b = backend or jax.default_backend()
    return f"{kernel}/{m}x{n}x{k}/{d}/{b}"


def _legacy_key(m: int, n: int, k: int, dtype=None,
                backend: Optional[str] = None) -> str:
    """Pre-namespace (PR 1) matmul key — still honored on lookup."""
    d = jnp.dtype(dtype).name if dtype is not None else "any"
    b = backend or jax.default_backend()
    return f"{m}x{n}x{k}/{d}/{b}"


def _tiers_key(dtype=None, backend: Optional[str] = None) -> str:
    d = jnp.dtype(dtype).name if dtype is not None else "any"
    b = backend or jax.default_backend()
    return f"square_panel/tiers/{d}/{b}"


def _dispatch_key(dtype=None, backend: Optional[str] = None) -> str:
    d = jnp.dtype(dtype).name if dtype is not None else "any"
    b = backend or jax.default_backend()
    return f"dispatch/thresholds/{d}/{b}"


def _fastmm_key(dtype=None, backend: Optional[str] = None) -> str:
    d = jnp.dtype(dtype).name if dtype is not None else "any"
    b = backend or jax.default_backend()
    return f"fastmm/config/{d}/{b}"


def _deadline_key(op: str, n: int, dtype=None,
                  backend: Optional[str] = None) -> str:
    d = jnp.dtype(dtype).name if dtype is not None else "any"
    b = backend or jax.default_backend()
    return f"dispatch/deadline/{op}/{n}/{d}/{b}"


def _markov_key(dtype=None, backend: Optional[str] = None) -> str:
    d = jnp.dtype(dtype).name if dtype is not None else "any"
    b = backend or jax.default_backend()
    return f"markov/evolve/{d}/{b}"


def _ascending_pair(vals) -> bool:
    return (len(vals) == 2
            and all(isinstance(x, int) and x > 0 for x in vals)
            and vals[0] <= vals[1])


def _valid_entry(entry) -> bool:
    """A usable cache entry: a block tiling (len 2 for attention, len 3 for
    matmul), a ``square_panel`` tier pair or ``dispatch`` threshold pair
    (both: two ascending positive ints), or a ``dispatch`` deadline entry
    (one positive finite ``max_delay_ms``), or a ``fastmm`` config entry
    (``[crossover_n, max_levels]`` — positive int and non-negative int —
    with optional 3-int positive ``leaf_blocks``), or a ``markov`` evolve
    dispatch entry (one positive finite ``evolve_threshold`` B/n ratio)."""
    try:
        if "tiers" in entry:
            return _ascending_pair(entry["tiers"])
        if "thresholds" in entry:
            return _ascending_pair(entry["thresholds"])
        if "evolve_threshold" in entry:
            v = entry["evolve_threshold"]
            return (isinstance(v, (int, float)) and not isinstance(v, bool)
                    and math.isfinite(v) and v > 0)
        if "fastmm" in entry:
            cfg = entry["fastmm"]
            leaf = entry.get("leaf_blocks")
            return (len(cfg) == 2
                    and isinstance(cfg[0], int) and not isinstance(cfg[0], bool)
                    and cfg[0] > 0
                    and isinstance(cfg[1], int) and not isinstance(cfg[1], bool)
                    and cfg[1] >= 0
                    and (leaf is None
                         or (len(leaf) == 3
                             and all(isinstance(x, int) and x > 0
                                     for x in leaf))))
        if "max_delay_ms" in entry:
            v = entry["max_delay_ms"]
            return (isinstance(v, (int, float)) and not isinstance(v, bool)
                    and math.isfinite(v) and v > 0)
        blocks = entry["blocks"]
        return (len(blocks) in (2, 3)
                and all(isinstance(x, int) and x > 0 for x in blocks))
    except (TypeError, KeyError):
        return False


def load_cache(path: Optional[os.PathLike] = None) -> dict:
    """Read (and memoize) the cache file; corrupted files degrade to {}."""
    path = Path(path) if path is not None else cache_path()
    memo_key = str(path)
    if memo_key in _MEM:
        return _MEM[memo_key]
    data: dict = {}
    if path.exists():
        try:
            raw = json.loads(path.read_text())
            if not isinstance(raw, dict):
                raise ValueError("cache root must be a JSON object")
            data = {k: v for k, v in raw.items() if _valid_entry(v)}
        except (ValueError, OSError) as exc:
            warnings.warn(f"ignoring corrupted autotune cache {path}: {exc}")
            data = {}
    _MEM[memo_key] = data
    _bump_generation()  # fresh disk read: memoized resolutions are stale
    return data


def save_cache(cache: Optional[dict] = None,
               path: Optional[os.PathLike] = None) -> Path:
    """Atomically persist the cache (tmp file + rename).

    An unwritable location degrades to a warning — tuning results stay
    usable in-process; a cache must never take down the workload.
    """
    path = Path(path) if path is not None else cache_path()
    if cache is None:
        cache = _MEM.get(str(path), {})
    _MEM[str(path)] = cache
    _bump_generation()
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_name(path.name + ".tmp")
        tmp.write_text(json.dumps(cache, indent=2, sort_keys=True))
        os.replace(tmp, path)
    except OSError as exc:
        warnings.warn(f"could not persist autotune cache to {path}: {exc}")
    return path


def clear_memory_cache() -> None:
    """Drop the in-process memo (tests; picks up external file edits)."""
    _MEM.clear()
    _bump_generation()


def lookup(m: int, n: int, k: int, dtype=None,
           backend: Optional[str] = None,
           kernel: str = "matmul") -> Optional[tuple]:
    """Tuned blocks for the ``kernel``-namespace problem key, or ``None``.

    The key is ``{kernel}/{m}x{n}x{k}/{dtype}/{backend}``; for attention the
    three dims are ``(sq, skv, d)`` and the entry is ``(block_q, block_k)``.
    A dtype-specific entry wins over a dtype-agnostic (``any``) one; matmul
    lookups additionally fall back to the pre-namespace PR 1 key format so
    existing caches keep working. Callers must re-validate the returned
    blocks against current kernel invariants (see ``ops.pick_blocks``) —
    the cache is advisory, never trusted blindly. Entries whose block count
    doesn't match the namespace (3 for matmul, 2 for attention — e.g. a
    hand-edited file) are skipped, never returned.
    """
    cache = load_cache()
    keys = [_key(m, n, k, dtype, backend, kernel),
            _key(m, n, k, None, backend, kernel)]
    if kernel == "matmul":
        keys += [_legacy_key(m, n, k, dtype, backend),
                 _legacy_key(m, n, k, None, backend)]
    want_len = 2 if kernel == "attention" else 3
    for key in keys:
        entry = cache.get(key)
        if (entry is not None and _valid_entry(entry)
                and "blocks" in entry and len(entry["blocks"]) == want_len):
            return tuple(entry["blocks"])
    return None


def record(m: int, n: int, k: int, blocks: Sequence[int], dtype=None,
           backend: Optional[str] = None, score: Optional[float] = None,
           measured: bool = False, save: bool = True,
           kernel: str = "matmul") -> None:
    """Store the winning blocks for a problem key (and persist by default).

    ``measured`` records provenance: ``True`` for wall-clock winners timed on
    real hardware, ``False`` for the analytic model — so modeled entries can
    be invalidated wholesale once hardware numbers exist. ``score`` is the
    winning metric (µs when measured, the unitless model score otherwise).
    """
    cache = load_cache()
    cache[_key(m, n, k, dtype, backend, kernel)] = {
        "blocks": [int(x) for x in blocks],
        "score": None if score is None else float(score),
        "measured": bool(measured),
    }
    _bump_generation()
    if save:
        save_cache(cache)


def square_tiers(dtype=None, backend: Optional[str] = None) -> tuple:
    """(whole_limit, panel_limit) operand-byte thresholds for ``square_pallas``.

    Consults the ``square_panel`` cache namespace (dtype-specific entry
    first, then dtype-agnostic) and falls back to ``DEFAULT_SQUARE_TIERS``.
    """
    cache = load_cache()
    for key in (_tiers_key(dtype, backend), _tiers_key(None, backend)):
        entry = cache.get(key)
        if entry is not None and _valid_entry(entry) and "tiers" in entry:
            return tuple(entry["tiers"])
    return DEFAULT_SQUARE_TIERS


def record_square_tiers(whole_limit: int, panel_limit: int, dtype=None,
                        backend: Optional[str] = None, measured: bool = False,
                        save: bool = True) -> None:
    """Store tuned ``square_pallas`` tier thresholds (operand bytes)."""
    if not (0 < whole_limit <= panel_limit):
        raise ValueError(f"tiers must be ascending positive ints, got "
                         f"({whole_limit}, {panel_limit})")
    cache = load_cache()
    cache[_tiers_key(dtype, backend)] = {
        "tiers": [int(whole_limit), int(panel_limit)],
        "measured": bool(measured),
    }
    _bump_generation()
    if save:
        save_cache(cache)


def dispatch_thresholds(dtype=None, backend: Optional[str] = None) -> tuple:
    """(cpu_max_n, sharded_min_n) for the serving engine's heterogeneous
    dispatch (``repro.serve.matfn``).

    Consults the ``dispatch`` cache namespace (dtype-specific entry first,
    then dtype-agnostic) and falls back to ``DEFAULT_DISPATCH_THRESHOLDS``.
    Resolution happens outside any jit, so a retuned entry takes effect on
    the engine's next bucket instead of being baked into a stale executable.
    """
    cache = load_cache()
    for key in (_dispatch_key(dtype, backend), _dispatch_key(None, backend)):
        entry = cache.get(key)
        if entry is not None and _valid_entry(entry) and "thresholds" in entry:
            return tuple(entry["thresholds"])
    return DEFAULT_DISPATCH_THRESHOLDS


def record_dispatch_thresholds(cpu_max_n: int, sharded_min_n: int, dtype=None,
                               backend: Optional[str] = None,
                               measured: bool = False,
                               save: bool = True) -> None:
    """Store tuned heterogeneous-dispatch thresholds (matrix sizes).

    ``measured`` records provenance exactly like the block namespaces:
    hardware sweeps that timed real crossovers record ``True`` so the
    modeled defaults can be invalidated wholesale.
    """
    if not (0 < cpu_max_n <= sharded_min_n):
        raise ValueError(f"dispatch thresholds must be ascending positive "
                         f"ints, got ({cpu_max_n}, {sharded_min_n})")
    cache = load_cache()
    cache[_dispatch_key(dtype, backend)] = {
        "thresholds": [int(cpu_max_n), int(sharded_min_n)],
        "measured": bool(measured),
    }
    _bump_generation()
    if save:
        save_cache(cache)


def fastmm_config(dtype=None, backend: Optional[str] = None) -> tuple:
    """(crossover_n, max_levels, leaf_blocks) for the Strassen route.

    ``leaf_blocks`` is ``None`` unless a sweep recorded explicit leaf tile
    shapes — ``None`` means the dense leaves pick their own tiles through
    ``ops.pick_blocks`` (the ``matmul`` namespace). Consults the ``fastmm``
    cache namespace (dtype-specific entry first, then dtype-agnostic) and
    falls back to the modeled defaults. Resolution happens outside any jit
    and is re-memoized by consumers per cache generation, so a retuned
    crossover reroutes a live engine instead of being silently ignored.
    """
    cache = load_cache()
    for key in (_fastmm_key(dtype, backend), _fastmm_key(None, backend)):
        entry = cache.get(key)
        if entry is not None and _valid_entry(entry) and "fastmm" in entry:
            leaf = entry.get("leaf_blocks")
            return (int(entry["fastmm"][0]), int(entry["fastmm"][1]),
                    None if leaf is None else tuple(int(x) for x in leaf))
    return DEFAULT_FASTMM_CROSSOVER, DEFAULT_FASTMM_LEVELS, None


def record_fastmm(crossover_n: int, max_levels: int, leaf_blocks=None,
                  dtype=None, backend: Optional[str] = None,
                  measured: bool = False, save: bool = True) -> None:
    """Store a tuned Strassen config for one dtype/backend.

    ``measured`` records provenance exactly like the block namespaces:
    hardware sweeps that timed the real dense-vs-Strassen crossover record
    ``True`` so the modeled defaults can be invalidated wholesale.
    """
    if not isinstance(crossover_n, int) or isinstance(crossover_n, bool) \
            or crossover_n < 1:
        raise ValueError(f"fastmm crossover must be a positive int, "
                         f"got {crossover_n!r}")
    if not isinstance(max_levels, int) or isinstance(max_levels, bool) \
            or max_levels < 0:
        raise ValueError(f"fastmm max_levels must be a non-negative int, "
                         f"got {max_levels!r}")
    if leaf_blocks is not None:
        leaf_blocks = [int(x) for x in leaf_blocks]
        if len(leaf_blocks) != 3 or any(x < 1 for x in leaf_blocks):
            raise ValueError(f"fastmm leaf_blocks must be three positive "
                             f"ints, got {leaf_blocks!r}")
    cache = load_cache()
    cache[_fastmm_key(dtype, backend)] = {
        "fastmm": [int(crossover_n), int(max_levels)],
        "leaf_blocks": leaf_blocks,
        "measured": bool(measured),
    }
    _bump_generation()
    if save:
        save_cache(cache)


def sweep_fastmm(dtype=jnp.float32, *, backend: Optional[str] = None,
                 measure: Optional[bool] = None,
                 candidates: Sequence[int] = (256, 512, 1024),
                 reps: int = 3, save: bool = True) -> tuple:
    """Record the Strassen crossover for this backend; returns
    ``(crossover_n, max_levels)``.

    When measuring (auto on a real TPU backend, forceable anywhere with
    ``measure=True``), each candidate crossover c is probed at n = 2c —
    the smallest problem that recurses exactly one level — and the smallest
    candidate where depth-1 Strassen beats the dense squaring wins.
    Everywhere else the modeled defaults are recorded as a ``measured:
    false`` entry so the cache documents the active policy and hardware
    campaigns know what to invalidate.
    """
    if measure is None:
        measure = jax.default_backend() == "tpu"
    crossover, levels = DEFAULT_FASTMM_CROSSOVER, DEFAULT_FASTMM_LEVELS
    if measure:
        from repro.kernels import fastmm as _fastmm
        from repro.kernels import ops as kops

        def _best_us(fn, a):
            jax.block_until_ready(fn(a))
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(a))
                best = min(best, time.perf_counter() - t0)
            return best * 1e6

        for cand in sorted(int(c) for c in candidates):
            n = 2 * cand
            rng = np.random.default_rng(0)
            a = jnp.asarray(rng.standard_normal((n, n)), dtype)
            dense_us = _best_us(jax.jit(lambda x: kops.square(x)), a)
            fast_us = _best_us(
                jax.jit(lambda x, c=cand: _fastmm.strassen_square(
                    x, levels=1, crossover=c)), a)
            if fast_us < dense_us:
                crossover = cand
                break
    if save:
        record_fastmm(crossover, levels, dtype=dtype, backend=backend,
                      measured=bool(measure))
    return crossover, levels


def bucket_deadline_ms(op: str, n: int, dtype=None,
                       backend: Optional[str] = None) -> float:
    """Tuned continuous-batching flush deadline for one traffic class.

    How long the serving daemon lets a partially-filled ``(op, n, dtype)``
    bucket wait for more requests before executing anyway. Consults the
    ``dispatch`` namespace's deadline entries (dtype-specific first, then
    dtype-agnostic) and falls back to ``DEFAULT_MAX_DELAY_MS``. Resolution
    happens outside any jit and is re-memoized by the engine per cache
    generation, so a retuned entry takes effect on the next bucket.
    """
    cache = load_cache()
    for key in (_deadline_key(op, n, dtype, backend),
                _deadline_key(op, n, None, backend)):
        entry = cache.get(key)
        if (entry is not None and _valid_entry(entry)
                and "max_delay_ms" in entry):
            return float(entry["max_delay_ms"])
    return DEFAULT_MAX_DELAY_MS


def record_bucket_deadline(op: str, n: int, max_delay_ms: float, dtype=None,
                           backend: Optional[str] = None,
                           measured: bool = False, save: bool = True) -> None:
    """Store a tuned flush deadline for one serving traffic class.

    ``measured`` records provenance exactly like the block namespaces:
    an open-loop latency sweep on real hardware records ``True`` so
    modeled/default entries can be invalidated wholesale.
    """
    if not isinstance(op, str) or not op:
        raise ValueError(f"op must be a non-empty string, got {op!r}")
    if not isinstance(n, int) or n < 1:
        raise ValueError(f"n must be a positive int, got {n!r}")
    if not (isinstance(max_delay_ms, (int, float))
            and math.isfinite(max_delay_ms) and max_delay_ms > 0):
        raise ValueError(f"max_delay_ms must be a positive finite number, "
                         f"got {max_delay_ms!r}")
    cache = load_cache()
    cache[_deadline_key(op, n, dtype, backend)] = {
        "max_delay_ms": float(max_delay_ms),
        "measured": bool(measured),
    }
    _bump_generation()
    if save:
        save_cache(cache)


#: Modeled evolve-vs-dense dispatch ratio: the evolve route's extra
#: per-set-bit O(B n^2) vecmats beat the dense route's saved O(n^3)
#: combines roughly while B <= n, so the default threshold is B/n = 1.
DEFAULT_MARKOV_EVOLVE_THRESHOLD: float = 1.0


def markov_evolve_threshold(dtype=None, backend: Optional[str] = None) -> float:
    """Max B/n ratio for the markov `evolve` route (``core.markov``).

    ``evolve_distributions`` (and the engine's evolve dispatch) routes a
    B-distribution batch through per-bit vector–matrix products while
    ``B <= threshold * n``, and falls back to dense matpow + one apply
    above it. Consults the ``markov`` cache namespace (dtype-specific
    entry first, then dtype-agnostic) and falls back to the modeled
    default. Resolution happens outside any jit, so a retuned entry takes
    effect on the next dispatch instead of being baked into a stale
    executable.
    """
    cache = load_cache()
    for key in (_markov_key(dtype, backend), _markov_key(None, backend)):
        entry = cache.get(key)
        if (entry is not None and _valid_entry(entry)
                and "evolve_threshold" in entry):
            return float(entry["evolve_threshold"])
    return DEFAULT_MARKOV_EVOLVE_THRESHOLD


def record_markov_evolve_threshold(threshold: float, dtype=None,
                                   backend: Optional[str] = None,
                                   measured: bool = False,
                                   save: bool = True) -> None:
    """Store a tuned evolve-vs-dense B/n dispatch ratio.

    ``measured`` records provenance exactly like the block namespaces:
    hardware sweeps that timed the real evolve/dense crossover record
    ``True`` so the modeled default can be invalidated wholesale.
    """
    if not (isinstance(threshold, (int, float))
            and not isinstance(threshold, bool)
            and math.isfinite(threshold) and threshold > 0):
        raise ValueError(f"markov evolve threshold must be a positive "
                         f"finite number, got {threshold!r}")
    cache = load_cache()
    cache[_markov_key(dtype, backend)] = {
        "evolve_threshold": float(threshold),
        "measured": bool(measured),
    }
    _bump_generation()
    if save:
        save_cache(cache)


def _round_up(x: int, mult: int) -> int:
    return (x + mult - 1) // mult * mult


def modeled_score(m: int, n: int, k: int, blocks: Sequence[int], dtype,
                  vmem_budget_bytes: int = VMEM_BUDGET) -> float:
    """Analytic cost proxy (lower is better) when we cannot time real runs.

    Penalizes tilings whose working set busts VMEM, then ranks by padding
    waste over arithmetic intensity — the two quantities the paper's local-
    memory sweep was implicitly optimizing.
    """
    bm, bn, bk = blocks
    itemsize = jnp.dtype(dtype).itemsize
    if vmem_footprint(blocks, itemsize) > vmem_budget_bytes:
        return float("inf")
    flops = 2 * bm * bn * bk
    move = (bm * bk + bk * bn) * itemsize + bm * bn * 4
    intensity = flops / move
    waste = (_round_up(m, bm) * _round_up(n, bn) * _round_up(k, bk)) / (m * n * k)
    return waste / intensity


def modeled_attn_score(sq: int, skv: int, d: int, blocks: Sequence[int],
                       dtype,
                       vmem_budget_bytes: int = VMEM_BUDGET) -> float:
    """Analytic cost proxy for a flash-attention ``(block_q, block_k)`` pair.

    Same shape as ``modeled_score``: infinite when the working set busts
    VMEM or the tile cannot divide the (clamped) sequence lengths — the
    kernel's hard divisibility invariant (attention.py) — otherwise padding
    waste over the arithmetic intensity of one grid step.
    """
    bq, bk = blocks
    itemsize = jnp.dtype(dtype).itemsize
    if attn_vmem_footprint(bq, bk, d, itemsize) > vmem_budget_bytes:
        return float("inf")
    if sq % min(bq, sq) or skv % min(bk, skv):
        return float("inf")
    flops = 4 * bq * bk * d            # scores + p@v per grid step
    move = (bq * d + 2 * bk * d) * itemsize
    intensity = flops / move
    waste = (_round_up(sq, bq) * _round_up(skv, bk)) / (sq * skv)
    return waste / intensity


def measure_us(m: int, n: int, k: int, blocks: Sequence[int], dtype,
               reps: int = 3, warmup: int = 1) -> float:
    """Wall-clock min-of-reps for one tiling (real compiled kernel only)."""
    bm, bn, bk = blocks
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((mp, kp)), dtype)
    b = jnp.asarray(rng.standard_normal((kp, np_)), dtype)
    fn = lambda: matmul_pallas(a, b, block_m=bm, block_n=bn, block_k=bk)
    for _ in range(warmup):
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def measure_attn_us(sq: int, skv: int, d: int, blocks: Sequence[int], dtype,
                    reps: int = 3, warmup: int = 1) -> float:
    """Wall-clock min-of-reps for one attention tiling (real TPU only)."""
    from repro.kernels.attention import flash_attention
    bq, bk = blocks
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((sq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((skv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((skv, d)), dtype)
    fn = lambda: flash_attention(q, k, v, block_q=bq, block_k=bk)
    for _ in range(warmup):
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _run_sweep(candidates, score_fn, fallback_fn, *, measure, record_fn,
               save: bool):
    """Shared sweep loop: score all candidates, pick/record the winner."""
    results = []
    for blocks in candidates:
        results.append({"blocks": blocks, "score": score_fn(blocks),
                        "measured": measure})
    results.sort(key=lambda r: r["score"])
    best = results[0]
    if not math.isfinite(best["score"]):
        best = {"blocks": fallback_fn(), "score": None, "measured": False}
    if save:
        record_fn(best)
    return tuple(best["blocks"]), results


def sweep(m: int, n: int, k: int, dtype=jnp.float32,
          candidates: Optional[Iterable[Sequence[int]]] = None, *,
          backend: Optional[str] = None, measure: Optional[bool] = None,
          reps: int = 3, save: bool = True):
    """Score every candidate matmul tiling, record the winner under the
    ``matmul`` namespace, return ``(best, results)``.

    ``measure=None`` auto-selects: wall-clock on a real TPU backend, the
    analytic model otherwise. ``results`` is a list of dicts (blocks, score,
    measured) sorted best-first.
    """
    candidates = [tuple(int(x) for x in c)
                  for c in (candidates or DEFAULT_CANDIDATES)]
    if measure is None:
        measure = jax.default_backend() == "tpu"
    itemsize = jnp.dtype(dtype).itemsize
    return _run_sweep(
        candidates,
        (lambda b: measure_us(m, n, k, b, dtype, reps=reps)) if measure
        else (lambda b: modeled_score(m, n, k, b, dtype)),
        # Every candidate busts VMEM — fall back to the smallest-footprint
        # tiling (NOT lexicographic min, which could pick a huge tile).
        lambda: min(candidates, key=lambda c: vmem_footprint(c, itemsize)),
        measure=measure,
        record_fn=lambda best: record(
            m, n, k, best["blocks"], dtype=dtype, backend=backend,
            score=best["score"], measured=bool(measure and best["score"])),
        save=save)


def sweep_attention(sq: int, skv: int, d: int, dtype=jnp.float32,
                    candidates: Optional[Iterable[Sequence[int]]] = None, *,
                    backend: Optional[str] = None,
                    measure: Optional[bool] = None,
                    reps: int = 3, save: bool = True):
    """Score every candidate ``(block_q, block_k)`` pair for an attention
    problem, record the winner under the ``attention`` namespace, return
    ``(best, results)`` — the flash-attention face of ``sweep``.
    """
    candidates = [tuple(int(x) for x in c)
                  for c in (candidates or DEFAULT_ATTN_CANDIDATES)]
    if measure is None:
        measure = jax.default_backend() == "tpu"
    itemsize = jnp.dtype(dtype).itemsize

    def _measured(b):
        # A candidate the kernel rejects (divisibility ValueError) scores
        # inf instead of aborting the sweep — parity with the modeled path.
        try:
            return measure_attn_us(sq, skv, d, b, dtype, reps=reps)
        except ValueError:
            return float("inf")

    return _run_sweep(
        candidates,
        _measured if measure
        else (lambda b: modeled_attn_score(sq, skv, d, b, dtype)),
        lambda: min(candidates,
                    key=lambda c: attn_vmem_footprint(c[0], c[1], d,
                                                      itemsize)),
        measure=measure,
        record_fn=lambda best: record(
            sq, skv, d, best["blocks"], dtype=dtype, backend=backend,
            score=best["score"], measured=bool(measure and best["score"]),
            kernel="attention"),
        save=save)


def sweep_square_tiers(dtype=jnp.float32, *, backend: Optional[str] = None,
                       measure: Optional[bool] = None,
                       save: bool = True) -> tuple:
    """Record the ``square_pallas`` tier thresholds for this backend.

    On real TPU hardware the crossover between the whole-operand, panel-
    resident, and two-operand kernels would be timed at probe sizes around
    each default boundary; everywhere else the defaults are recorded as a
    modeled (``measured: false``) entry so the cache documents the active
    policy and hardware sweeps know what to invalidate.
    """
    if measure is None:
        measure = jax.default_backend() == "tpu"
    whole, panel = DEFAULT_SQUARE_TIERS
    if measure:
        # Probe one size per boundary: largest power-of-two operand that
        # stays under the default threshold; promote/demote the threshold if
        # the neighboring kernel wins there.
        itemsize = jnp.dtype(dtype).itemsize
        from repro.kernels.matmul import square_pallas

        def _time(p, vmem_limit, panel_limit):
            rng = np.random.default_rng(0)
            a = jnp.asarray(rng.standard_normal((p, p)), dtype)
            fn = lambda: square_pallas(a, vmem_limit=vmem_limit,
                                       panel_limit=panel_limit)
            jax.block_until_ready(fn())
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            return time.perf_counter() - t0

        p0 = 1 << int(math.log2(math.isqrt(whole // itemsize)))
        if _time(p0, whole, panel) > _time(p0, 1, panel):
            whole = p0 * p0 * itemsize - 1          # panel wins: shrink tier
        p1 = 1 << int(math.log2(math.isqrt(panel // itemsize)))
        if _time(p1, whole, panel) > _time(p1, 1, 1):
            panel = p1 * p1 * itemsize - 1          # two-op wins: shrink tier
    if save:
        record_square_tiers(whole, panel, dtype=dtype, backend=backend,
                            measured=bool(measure))
    return whole, panel
