"""Multi-device correctness via subprocess (forced 4-device CPU).

The main pytest process must keep ONE device (assignment), so every
multi-device check runs in a child python with
XLA_FLAGS=--xla_force_host_platform_device_count=4. Each child asserts
internally and exits nonzero on failure.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parent.parent / "src")

# jax.make_mesh defaults to Explicit axes; the code under test is written
# for GSPMD-propagated (Auto) axes. Prepended to every child.
_MESH_PRELUDE = """
import jax
def _make_mesh(shape, names):
    return jax.make_mesh(shape, names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names))
"""


def _run(body: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    code = _MESH_PRELUDE + textwrap.dedent(body)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"child failed:\n{proc.stdout}\n{proc.stderr}"
    return proc.stdout


def test_cannon_and_gather_match_matmul():
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import matmul_2d_gather, matmul_cannon
        mesh = _make_mesh((2,2), ("data","model"))
        sh = NamedSharding(mesh, P("data","model"))
        a = jax.device_put(jax.random.normal(jax.random.PRNGKey(0), (64,64))*0.2, sh)
        b = jax.device_put(jax.random.normal(jax.random.PRNGKey(1), (64,64))*0.2, sh)
        ref = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
        for fn in (matmul_2d_gather, matmul_cannon):
            got = np.asarray(fn(a, b, mesh))
            assert np.abs(got - ref).max() < 1e-4, fn.__name__
        print("ok")
    """)


def test_matpow_sharded_matches_numpy():
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import matpow_sharded
        mesh = _make_mesh((2,2), ("data","model"))
        sh = NamedSharding(mesh, P("data","model"))
        a = jax.device_put(jax.random.normal(jax.random.PRNGKey(0), (64,64))*0.2, sh)
        got = np.asarray(jax.jit(lambda x: matpow_sharded(x, 13, mesh))(a))
        ref = np.linalg.matrix_power(np.asarray(a, np.float64), 13)
        rel = np.abs(got - ref).max() / np.abs(ref).max()
        assert rel < 1e-4, rel
        print("ok")
    """)


def test_sharded_forward_matches_single_device():
    """DP=2 x TP=2 sharded forward == unsharded forward (same params)."""
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models import init_params, forward, unembed
        from repro.models.layers import ShardCtx
        from repro.parallel import sharding

        cfg = get_config("qwen3-1.7b", smoke=True)
        params = init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                  cfg.vocab_size)
        want = unembed(cfg, params, forward(cfg, params, toks)["x"])

        mesh = _make_mesh((2,2), ("data","model"))
        spec = sharding.param_specs(params, cfg, mesh, "train")
        p_sh = jax.device_put(params, sharding.named(mesh, spec))
        sctx = ShardCtx(mesh=mesh, dp=("data",))
        with mesh:
            got = jax.jit(lambda p, t: unembed(
                cfg, p, forward(cfg, p, t, sctx=sctx)["x"]))(p_sh, toks)
        err = np.abs(np.asarray(got) - np.asarray(want)).max()
        assert err < 2e-2, err   # fp reassociation across shards
        print("ok", err)
    """)


def test_compressed_psum_and_error_feedback():
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.parallel.collectives import compressed_psum, ef_compress
        mesh = _make_mesh((4,), ("data",))
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 1024))

        def f(xs):
            return compressed_psum(xs, "data")
        got = jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                            out_specs=P("data"), check_vma=False)(x)
        want = jnp.sum(x, axis=0, keepdims=True)
        rel = float(jnp.abs(got[0] - want[0]).max() / jnp.abs(want).max())
        assert rel < 2e-2, rel

        # error feedback: mean of quantized reductions converges to truth
        err = jnp.zeros((4, 1024))
        acc = jnp.zeros((1024,))
        def g(xs, es):
            r, ne = ef_compress(xs, es, "data")
            return r, ne
        gg = jax.shard_map(g, mesh=mesh, in_specs=(P("data"), P("data")),
                           out_specs=(P("data"), P("data")), check_vma=False)
        total = jnp.zeros((1024,))
        for i in range(16):
            r, err = gg(x, err)
            total = total + r[0]
        truth = 16 * want[0]
        rel2 = float(jnp.abs(total - truth).max() / jnp.abs(truth).max())
        assert rel2 < 5e-3, rel2   # EF beats one-shot quantization error
        print("ok", rel, rel2)
    """)


def test_elastic_restore_across_meshes(tmp_path):
    """Save params sharded on a 4-dev (2x2) mesh, restore onto 2-dev (1x2) —
    the elastic-restart path (DESIGN.md §10)."""
    _run(f"""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.checkpointer import Checkpointer
        from repro.configs import get_config
        from repro.models import init_params
        from repro.parallel import sharding

        cfg = get_config("qwen3-1.7b", smoke=True)
        params = init_params(cfg, jax.random.PRNGKey(0))
        mesh4 = _make_mesh((2,2), ("data","model"))
        spec = sharding.param_specs(params, cfg, mesh4, "train")
        p4 = jax.device_put(params, sharding.named(mesh4, spec))
        ck = Checkpointer(r"{tmp_path}")
        ck.save(1, p4)

        # "restart" on a smaller mesh
        mesh2 = _make_mesh((1,2), ("data","model"))
        spec2 = sharding.param_specs(params, cfg, mesh2, "train")
        template = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        step, restored = ck.restore(None, template,
                                    shardings=sharding.named(mesh2, spec2))
        assert step == 1
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("ok")
    """)


# ---------------------------------------------------------------------------
# ShardedMatmulChain — the distributed squaring chain
# ---------------------------------------------------------------------------

def test_sharded_chain_numerics_across_meshes():
    """matpow_sharded (routed through ShardedMatmulChain) vs numpy for
    powers {1, 2, 7, 96} on 1x1, 1x4, and 2x2 meshes, at a prime size the
    bare collective matmul cannot shard (the chain pads once)."""
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import matpow_sharded
        for shape in ((1, 1), (1, 4), (2, 2)):
            mesh = _make_mesh(shape, ("data", "model"))
            a = jax.random.normal(jax.random.PRNGKey(0), (67, 67)) * 0.15
            ref_a = np.asarray(a, np.float64)
            for p in (1, 2, 7, 96):
                got = np.asarray(matpow_sharded(a, p, mesh))
                ref = np.linalg.matrix_power(ref_a, p)
                rel = np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30)
                assert rel < 1e-4, (shape, p, rel)
            assert not a.is_deleted()   # caller's buffer never consumed
            # p=0: sharded identity even at the non-divisible size
            assert np.array_equal(np.asarray(matpow_sharded(a, 0, mesh)),
                                  np.eye(67, dtype=np.float32)), shape
        # the traced route (chain under jit) and a forced schedule
        mesh = _make_mesh((2, 2), ("data", "model"))
        a = jax.random.normal(jax.random.PRNGKey(1), (67, 67)) * 0.15
        got = np.asarray(jax.jit(
            lambda x: matpow_sharded(x, 7, mesh, algorithm="gather"))(a))
        ref = np.linalg.matrix_power(np.asarray(a, np.float64), 7)
        assert np.abs(got - ref).max() / np.abs(ref).max() < 1e-4
        print("ok")
    """)


def test_sharded_chain_pads_exactly_once():
    """The single-pad invariant at mesh scale: ONE ops.pad_to_blocks call
    per matpow_sharded call, however many squarings/combines run."""
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import matpow_sharded
        from repro.kernels import ops
        calls = []
        real = ops.pad_to_blocks
        def counting(a, bm, bn):
            calls.append(a.shape)
            return real(a, bm, bn)
        ops.pad_to_blocks = counting
        mesh = _make_mesh((2, 2), ("data", "model"))
        a = jax.random.normal(jax.random.PRNGKey(0), (67, 67)) * 0.15
        out = matpow_sharded(a, 96, mesh)   # 6 squarings + 2 combines
        assert len(calls) == 1, calls
        assert out.shape == (67, 67)
        # divisible size: no pad at all (identity-pad is a defensive copy)
        calls.clear()
        b = jax.random.normal(jax.random.PRNGKey(1), (64, 64)) * 0.15
        matpow_sharded(b, 96, mesh)
        assert len(calls) == 0, calls
        print("ok")
    """)


def test_sharded_chain_donation_smoke():
    """The jitted collective square step accepts donated buffers cleanly:
    the operand's per-device shards are consumed (reused for the output)
    and XLA emits NO donation/copy fallback warnings."""
    _run("""
        import warnings
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import ShardedMatmulChain
        mesh = _make_mesh((2, 2), ("data", "model"))
        chain = ShardedMatmulChain(64, jnp.float32, mesh)
        a = jax.random.normal(jax.random.PRNGKey(0), (64, 64)) * 0.2
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            x = chain.pad(a)
            y = chain.square(x)
            z = chain.square(y)
            jax.block_until_ready(z)
        bad = [str(m.message) for m in w
               if "donat" in str(m.message).lower()]
        assert not bad, bad
        assert x.is_deleted() and y.is_deleted()   # HBM handed forward
        assert not z.is_deleted()
        assert not a.is_deleted()                  # caller's buffer survives
        want = np.linalg.matrix_power(np.asarray(a, np.float64), 4)
        got = np.asarray(chain.unpad(z))
        assert np.abs(got - want).max() / np.abs(want).max() < 1e-4
        # donation is inert under a trace: no error, operand kept
        chain2 = ShardedMatmulChain(64, jnp.float32, mesh)
        b = chain2.pad(a)
        jax.block_until_ready(jax.jit(chain2.square)(b))
        assert not b.is_deleted()
        print("ok")
    """)


def test_expm_sharded_matches_single_device():
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import expm, expm_sharded
        mesh = _make_mesh((2, 2), ("data", "model"))
        a = (jax.random.normal(jax.random.PRNGKey(5), (67, 67)) * 0.3
             ).astype(jnp.float32)
        want = np.asarray(expm(a), np.float64)
        got = np.asarray(expm_sharded(a, mesh), np.float64)
        assert np.abs(got - want).max() / np.abs(want).max() < 1e-4
        gotj = np.asarray(jax.jit(lambda x: expm_sharded(x, mesh))(a),
                          np.float64)
        assert np.abs(gotj - want).max() / np.abs(want).max() < 1e-4
        print("ok")
    """)


def test_expm_sharded_mask_no_nan_near_overflow():
    # Companion to TestExpm.test_batched_mask_no_nan_near_overflow: the
    # sharded squaring loop carries the same per-step mask, so it gets the
    # same near-overflow guard — e^{60 I} pushes every squaring to within
    # one step of fp32 overflow and must come out exact and NaN-free.
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import expm_sharded
        mesh = _make_mesh((2, 2), ("data", "model"))
        a = jnp.asarray(60.0 * np.eye(64, dtype=np.float32))
        got = np.asarray(expm_sharded(a, mesh))
        assert np.isfinite(got).all()
        np.testing.assert_allclose(np.diag(got),
                                   np.full(64, np.exp(np.float32(60.0))),
                                   rtol=1e-5)
        print("ok")
    """)
