"""Tile-size sweep across every kernel namespace of the tuning subsystem —
the paper's Section 4.3.7 ("different kernels having different TILES of size
4x4 ... 16x16") mapped to MXU block shapes, for matmul, flash attention, and
the tiered squaring kernel.

Wall-clock timing in interpret mode is meaningless (the kernel body runs as
python on CPU), so each block config reports MODELED metrics derived from
the BlockSpec structure — exactly the quantities that decide tile choice on
TPU:
    vmem_kib            working set (double-buffered in tiles + acc/scratch)
    intensity_flops_b   arithmetic intensity of one grid step
    mxu_aligned         all dims multiples of 128?
plus a correctness check against the ref.py oracle at every config.

The sweep feeds the kernel namespaces of the persistent autotuner
(repro.kernels.autotune): winning tilings are recorded under their problem
keys so ops.pick_blocks / ops.pick_attn_blocks — and therefore every
ops.matmul, MatmulChain, flash_attention, and models.layers.dense on these
problem sizes — reuse them, the square_pallas tier thresholds are
published as the ``square_panel`` entry, and the Strassen crossover as the
``fastmm`` entry.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import autotune, ref
from repro.kernels.matmul import matmul_pallas, square_pallas

M = K = N = 1024
# One candidate list and one VMEM model for the whole system: the sweep
# displays, scores, and records exactly what ops.pick_blocks will consume.
BLOCKS = autotune.DEFAULT_CANDIDATES

# Attention problem swept: a 2k-context prefill slice at d_head 128; the
# correctness probe below runs each candidate at a small clamped shape.
ATTN_SQ = ATTN_SKV = 2048
ATTN_D = 128
ATTN_BLOCKS = autotune.DEFAULT_ATTN_CANDIDATES


def _matmul_section(rows):
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((M, K)), jnp.bfloat16)
    b = jnp.asarray(rng.standard_normal((K, N)), jnp.bfloat16)
    want = np.float32(ref.matmul_ref(a, b))

    for bm, bn, bk in BLOCKS:
        got = matmul_pallas(a, b, block_m=bm, block_n=bn, block_k=bk,
                            interpret=True)
        err = float(np.abs(np.float32(got) - want).max())
        rel = err / float(np.abs(want).max())
        vmem = autotune.vmem_footprint((bm, bn, bk), itemsize=2) / 1024
        flops = 2 * bm * bn * bk
        byts = (bm * bk + bk * bn) * 2 + bm * bn * 4
        rows.append({
            "name": f"matmul_block_{bm}x{bn}x{bk}",
            "us_per_call": 0.0,   # interpret mode: structural metrics only
            "derived": (f"vmem_kib={vmem:.0f};intensity={flops/byts:.0f};"
                        f"mxu_aligned={all(x % 128 == 0 for x in (bm, bn, bk))};"
                        f"rel_err={rel:.1e}"),
        })

    # Record the winner in the persistent autotune cache (measured wall-clock
    # on TPU, the analytic model here) so pick_blocks reuses this sweep.
    best, results = autotune.sweep(M, N, K, dtype=jnp.bfloat16,
                                   candidates=BLOCKS)
    # Also publish under the dtype-agnostic key so float32 matmul/chain
    # lookups on this problem size hit too (pick_blocks re-validates the
    # footprint per-dtype before trusting any cache entry). Thread the
    # winner's score/measured provenance through rather than re-defaulting.
    win = next((r for r in results if tuple(r["blocks"]) == tuple(best)), None)
    autotune.record(M, N, K, best, dtype=None,
                    score=None if win is None else win["score"],
                    measured=bool(win and win["measured"]))
    rows.append({
        "name": f"autotune_sweep_{M}x{N}x{K}",
        "us_per_call": 0.0,
        "derived": (f"best_blocks={'x'.join(map(str, best))};"
                    f"cache={autotune.cache_path()}"),
    })


def _attention_section(rows):
    """Sweep (block_q, block_k): modeled metrics at the 2k-prefill problem,
    correctness probe per candidate at a small shape (blocks clamped)."""
    rng = np.random.default_rng(1)
    sq = skv = 256
    d = 64
    q = jnp.asarray(rng.standard_normal((sq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((skv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((skv, d)), jnp.float32)
    want = np.float32(ref.flash_attention_ref(q, k, v, causal=True))
    from repro.kernels.attention import flash_attention

    for bq, bk in ATTN_BLOCKS:
        got = flash_attention(q, k, v, causal=True, interpret=True,
                              block_q=min(bq, sq), block_k=min(bk, skv))
        rel = (float(np.abs(np.float32(got) - want).max())
               / float(np.abs(want).max()))
        vmem = autotune.attn_vmem_footprint(bq, bk, ATTN_D, itemsize=2) / 1024
        flops = 4 * bq * bk * ATTN_D
        byts = (bq * ATTN_D + 2 * bk * ATTN_D) * 2
        rows.append({
            "name": f"attention_block_{bq}x{bk}",
            "us_per_call": 0.0,
            "derived": (f"vmem_kib={vmem:.0f};intensity={flops/byts:.0f};"
                        f"mxu_aligned={all(x % 128 == 0 for x in (bq, bk))};"
                        f"rel_err={rel:.1e}"),
        })

    best, _ = autotune.sweep_attention(ATTN_SQ, ATTN_SKV, ATTN_D,
                                       dtype=jnp.bfloat16,
                                       candidates=ATTN_BLOCKS)
    rows.append({
        "name": f"autotune_attn_sweep_{ATTN_SQ}x{ATTN_SKV}x{ATTN_D}",
        "us_per_call": 0.0,
        "derived": (f"best_blocks={'x'.join(map(str, best))};"
                    f"cache={autotune.cache_path()}"),
    })


def _square_tier_section(rows):
    """Publish the square_pallas tier thresholds (timed crossover on TPU,
    the defaults as a modeled entry elsewhere) and probe each tier's kernel
    for correctness at a small size."""
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.standard_normal((256, 256)) * 0.1, jnp.float32)
    want = np.float32(ref.matmul_ref(a, a))
    # Force each tier at the same operand by moving the thresholds.
    forced = {"whole": (1 << 30, 1 << 31), "panel": (1, 1 << 30),
              "two_operand": (1, 1)}
    for tier, (lo, hi) in forced.items():
        got = square_pallas(a, block_m=128, block_n=128, block_k=128,
                            interpret=True, vmem_limit=lo, panel_limit=hi)
        rel = (float(np.abs(np.float32(got) - want).max())
               / float(np.abs(want).max()))
        rows.append({
            "name": f"square_tier_{tier}",
            "us_per_call": 0.0,
            "derived": f"rel_err={rel:.1e}",
        })

    whole, panel = autotune.sweep_square_tiers(dtype=jnp.float32)
    rows.append({
        "name": "autotune_square_tiers",
        "us_per_call": 0.0,
        "derived": (f"whole_limit={whole};panel_limit={panel};"
                    f"cache={autotune.cache_path()}"),
    })


def _fastmm_section(rows):
    """Publish the Strassen crossover (timed dense-vs-depth-1 probing on
    TPU, the modeled defaults elsewhere) and probe the recursion against
    the oracle at a deliberately odd size — every level pads."""
    rng = np.random.default_rng(3)
    from repro.kernels import fastmm
    a = jnp.asarray(rng.standard_normal((101, 101)) * 0.1, jnp.float32)
    want = np.float32(ref.matmul_ref(a, a))
    got = fastmm.strassen_square(a, levels=2, crossover=16)
    rel = (float(np.abs(np.float32(got) - want).max())
           / float(np.abs(want).max()))
    rows.append({
        "name": "fastmm_strassen_101_d2",
        "us_per_call": 0.0,
        "derived": f"rel_err={rel:.1e}",
    })

    crossover, levels = autotune.sweep_fastmm(dtype=jnp.float32)
    rows.append({
        "name": "autotune_fastmm",
        "us_per_call": 0.0,
        "derived": (f"crossover={crossover};levels={levels};"
                    f"cache={autotune.cache_path()}"),
    })


def main(rows=None):
    own = rows is None
    rows = [] if own else rows
    _matmul_section(rows)
    _attention_section(rows)
    _square_tier_section(rows)
    _fastmm_section(rows)
    if own:
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    return rows


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
