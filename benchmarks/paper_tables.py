"""Paper-table reproduction: Tables 2-5 (matrix size x power grid).

The 2012 paper compares, per (size, power):
    Sequential CPU | Naive GPU (N-1 kernel launches) | Our Approach (log N)

Measured here on the CPU XLA backend (the only hardware present):
    * naive    — matpow_naive:  N-1 on-device multiplies in one program
    * binary   — matpow_binary: exponentiation by squaring (the paper's
                 contribution), <= 2 log2 N multiplies
    * numpy    — np.linalg.matrix_power (host BLAS reference = the paper's
                 "Sequential CPU" column, though modern BLAS also uses
                 binary powering, so it is fast)

plus the analytic TPU-v5e projection for both algorithms from the matmul
roofline (197 TF bf16 / 819 GB/s): per-multiply time =
max(2n^3/peak, 3*n^2*bytes/bw), x multiply count. The paper's headline is
the RATIO naive/ours; that ratio is hardware-independent at large N
(-> (N-1)/(#multiplies in the chain)) and is what we validate.
"""

from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import matpow_binary, matpow_naive

PEAK = 197e12
BW = 819e9


def _time(fn, *args, reps=3, warmup=1):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return min(ts)


def _mult_count(n: int) -> int:
    # squarings + combines in matpow_binary
    return max(0, n.bit_length() - 1) + max(0, bin(n).count("1") - 1)


def tpu_projection_s(size: int, n_mults: int, dtype_bytes: int = 4) -> float:
    compute = 2 * size ** 3 / PEAK
    memory = 3 * size ** 2 * dtype_bytes / BW
    return n_mults * max(compute, memory)


def run_table(size: int, powers, rows):
    key = jax.random.PRNGKey(size)
    a = jax.random.normal(key, (size, size), jnp.float32)
    # normalize spectral radius so high powers stay finite (the paper's
    # precision check would otherwise overflow fp32 at N=1024)
    a = a / (jnp.linalg.norm(a, 2) * 1.02)

    for p in powers:
        nv = jax.jit(lambda x, pp=p: matpow_naive(x, pp))
        bv = jax.jit(lambda x, pp=p: matpow_binary(x, pp))
        t_naive = _time(nv, a)
        t_bin = _time(bv, a)
        t_np = _time(lambda x: np.linalg.matrix_power(np.asarray(x), p), a,
                     reps=1)
        # precision check (the paper: "strictly compared with sequential")
        err = float(jnp.max(jnp.abs(bv(a) - nv(a))))
        mults = _mult_count(p)
        proj_naive = tpu_projection_s(size, p - 1)
        proj_bin = tpu_projection_s(size, mults)
        rows.append({
            "name": f"matpow_{size}x{size}_p{p}",
            "us_per_call": t_bin * 1e6,
            "derived": (f"naive_us={t_naive*1e6:.0f};speedup={t_naive/t_bin:.1f};"
                        f"numpy_us={t_np*1e6:.0f};mults={mults}_vs_{p-1};"
                        f"tpu_proj_speedup={proj_naive/proj_bin:.1f};"
                        f"maxerr_vs_naive={err:.1e}"),
        })


def main(rows=None, quick=False):
    own = rows is None
    rows = [] if own else rows
    if quick:
        run_table(64, (64, 256), rows)                # paper Table 2 (subset)
        run_table(128, (64, 256), rows)               # paper Table 3 (subset)
    else:
        run_table(64, (64, 128, 256, 512, 1024), rows)    # paper Table 2
        run_table(128, (64, 128, 256, 512), rows)         # paper Table 3
        run_table(256, (64, 128, 256, 512), rows)         # paper Table 4
        run_table(512, (64, 128, 256), rows)              # paper Table 5
    if own:
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    return rows


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
