"""Matrix-function serving subsystem: batched-chain numerics, request
bucketing, executable-cache reuse, and heterogeneous dispatch.

Covers the acceptance criteria of the serving-engine change:
  * stacked matpow at p in {1, 2, 7, 96} vs a per-matrix loop, mixed
    dtypes (f32/bf16), non-divisible n, through the batched Pallas chain
    (interpret mode);
  * the single-pad invariant on the batched chain (one ops.pad_to_blocks
    call for the whole stacked chain);
  * engine answers bit-identical to per-matrix jitted calls, in submission
    order, across mixed (op, n, dtype, power) traffic;
  * bucket policy (power-of-two batch padding, max_batch chunking) and the
    executable cache (compile once per bucket shape, hit afterwards);
  * dispatch thresholds resolved from the tuning cache's ``dispatch``
    namespace (tiny -> xla, mid -> chain, huge singles -> sharded).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (BatchedMatmulChain, batched_expm, batched_matpow,
                        expm, matpow_binary)
from repro.kernels import autotune, ops
from repro.serve import matfn, streams
from repro.serve.matfn import MatFnEngine, MatFnRequest, bucket_batch
from repro.serve.streams import ExecutionStreams
from repro.serve.scheduler import ManualClock

CHAIN = "pallas_chain_interpret"


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    path = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    autotune.clear_memory_cache()
    yield path
    autotune.clear_memory_cache()


def _stack(b, n, seed=0, dtype=jnp.float32, scale=None):
    rng = np.random.default_rng(seed)
    scale = scale if scale is not None else 0.5 / np.sqrt(n)
    return jnp.asarray(rng.standard_normal((b, n, n)) * scale, dtype)


def _ref_pow(a, p):
    return np.linalg.matrix_power(np.asarray(a, np.float64), p)


class TestBatchedChainNumerics:
    @pytest.mark.parametrize("p", [1, 2, 7, 96])
    def test_stacked_matpow_vs_per_matrix_loop(self, p):
        """The batched chain must match a loop of per-matrix chains."""
        a = _stack(3, 96, seed=p)
        got = np.asarray(batched_matpow(a, p, backend=CHAIN))
        for i in range(a.shape[0]):
            want = np.asarray(matpow_binary(a[i], p, backend=CHAIN))
            np.testing.assert_array_equal(got[i], want)
            np.testing.assert_allclose(got[i], _ref_pow(a[i], p),
                                       rtol=5e-3, atol=1e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_mixed_dtypes(self, dtype):
        a = _stack(2, 64, seed=5, dtype=dtype)
        got = np.float32(batched_matpow(a, 7, backend=CHAIN))
        for i in range(2):
            np.testing.assert_allclose(
                got[i], _ref_pow(np.float32(a[i]), 7),
                rtol=5e-2 if dtype == jnp.bfloat16 else 2e-3, atol=1e-2)

    @pytest.mark.parametrize("n", [67, 200])
    def test_non_divisible_n(self, n):
        """Sizes that force real padding (not multiples of any block)."""
        a = _stack(2, n, seed=n)
        got = np.asarray(batched_matpow(a, 7, backend=CHAIN))
        for i in range(2):
            np.testing.assert_allclose(got[i], _ref_pow(a[i], 7),
                                       rtol=5e-3, atol=1e-5)

    def test_xla_backend_matches_per_matrix(self):
        a = _stack(4, 24, seed=9)
        got = np.asarray(batched_matpow(a, 12))
        for i in range(4):
            np.testing.assert_array_equal(
                got[i], np.asarray(matpow_binary(a[i], 12)))

    def test_p0_identity_contract(self):
        a = _stack(3, 20, seed=1)
        for backend in ("xla", CHAIN):
            got = np.asarray(batched_matpow(a, 0, backend=backend))
            np.testing.assert_array_equal(
                got, np.broadcast_to(np.eye(20, dtype=np.float32), a.shape))

    def test_batched_expm_matches_per_matrix(self):
        a = _stack(3, 16, seed=2, scale=0.4)
        got = np.asarray(batched_expm(a))
        for i in range(3):
            np.testing.assert_allclose(got[i], np.asarray(expm(a[i])),
                                       rtol=1e-5, atol=1e-6)

    def test_rejections(self):
        with pytest.raises(ValueError):
            batched_matpow(jnp.ones((4, 4)), 2)         # not a stack
        with pytest.raises(ValueError):
            batched_matpow(jnp.ones((2, 3, 4)), 2)      # not square
        with pytest.raises(TypeError):
            batched_matpow(_stack(2, 8), jnp.int32(3))  # traced power
        with pytest.raises(ValueError):
            batched_matpow(_stack(2, 8), -1)            # negative power
        with pytest.raises(ValueError):
            batched_expm(jnp.ones((4, 4)))              # not a stack


class TestBatchedChainStructure:
    def test_single_pad_invariant(self, monkeypatch):
        """ONE ops.pad_to_blocks call for the whole stacked chain."""
        calls = []
        real = ops.pad_to_blocks

        def counting(a, bm, bn):
            calls.append(a.shape)
            return real(a, bm, bn)

        monkeypatch.setattr(ops, "pad_to_blocks", counting)
        batched_matpow(_stack(3, 96, seed=4), 9, backend=CHAIN)
        assert len(calls) == 1
        assert calls[0][0] == 3                      # padded as ONE stack

    def test_eager_square_donates_stack(self):
        """ONE donated dispatch squares the whole stack in place."""
        chain = BatchedMatmulChain(2, 128, jnp.float32, interpret=True)
        a = _stack(2, 128, seed=6, scale=1.0)
        want = np.asarray(a) @ np.asarray(a)         # before consumption
        x = chain.pad(a)
        y = chain.square(x)
        assert x.is_deleted()
        assert not y.is_deleted()
        np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-3)

    def test_square_matches_ref_per_matrix(self):
        chain = BatchedMatmulChain(2, 128, jnp.float32, interpret=True,
                                   donate=False)
        x = _stack(2, 128, seed=7, scale=1.0)
        y = chain.square(x)
        for i in range(2):
            np.testing.assert_allclose(
                np.asarray(y[i]), np.asarray(x[i]) @ np.asarray(x[i]),
                rtol=1e-4, atol=1e-3)
        assert not x.is_deleted()

    def test_caller_buffer_never_consumed(self):
        a = _stack(2, 128, seed=8)                   # block-divisible: no pad
        out = batched_matpow(a, 4, backend=CHAIN)
        assert not a.is_deleted()
        np.testing.assert_allclose(np.asarray(out[0]), _ref_pow(a[0], 4),
                                   rtol=2e-3, atol=1e-5)

    def test_constructor_rejections(self):
        with pytest.raises(ValueError):
            BatchedMatmulChain(0, 16, jnp.float32)
        with pytest.raises(ValueError):
            BatchedMatmulChain(2, 0, jnp.float32)
        chain = BatchedMatmulChain(2, 16, jnp.float32, interpret=True)
        with pytest.raises(ValueError):
            chain.pad(jnp.ones((3, 16, 16)))         # wrong batch
        with pytest.raises(ValueError):
            chain.pad(jnp.ones((16, 16)))            # not a stack


class TestBucketPolicy:
    def test_bucket_batch_powers_of_two(self):
        assert [bucket_batch(b) for b in (1, 2, 3, 5, 8, 9, 33)] == \
            [1, 2, 4, 8, 8, 16, 64]
        assert bucket_batch(100, max_batch=64) == 64
        with pytest.raises(ValueError):
            bucket_batch(0)

    def test_request_validation(self):
        with pytest.raises(ValueError):
            MatFnRequest("cholesky", jnp.eye(4))
        with pytest.raises(ValueError):
            MatFnRequest("matpow", jnp.ones((3, 4)), 2)
        with pytest.raises(ValueError):
            MatFnRequest("matpow", jnp.ones((0, 0)), 2)
        with pytest.raises(TypeError):
            MatFnRequest("matpow", jnp.eye(4), jnp.int32(2))
        with pytest.raises(ValueError):
            MatFnRequest("matpow", jnp.eye(4), -1)

    def test_bucket_key_groups_by_op_n_dtype_power(self):
        k1 = MatFnRequest("matpow", jnp.eye(8), 3).bucket_key()
        k2 = MatFnRequest("matpow", jnp.eye(8), 3).bucket_key()
        k3 = MatFnRequest("matpow", jnp.eye(8), 4).bucket_key()
        k4 = MatFnRequest("matpow", jnp.eye(8, dtype=jnp.bfloat16), 3).bucket_key()
        k5 = MatFnRequest("expm", jnp.eye(8)).bucket_key()
        assert k1 == k2
        assert len({k1, k3, k4, k5}) == 4


class TestEngine:
    def test_results_bit_identical_and_in_order(self):
        """Mixed traffic: answers match jitted per-matrix calls exactly."""
        rng = np.random.default_rng(0)
        eng = MatFnEngine()
        work = []
        for i in range(12):
            n = int(rng.choice((8, 12, 16)))
            a = jnp.asarray(rng.standard_normal((n, n)) * 0.3, jnp.float32)
            if i % 4 == 3:
                work.append(("expm", a, 1))
            else:
                work.append(("matpow", a, int(rng.choice((2, 7)))))
        tickets = [eng.submit(op, a, power=p) for op, a, p in work]
        results = eng.flush()
        assert tickets == list(range(12))
        for (op, a, p), t in zip(work, tickets):
            want = (jax.jit(expm)(a) if op == "expm"
                    else jax.jit(lambda x, pp=p: matpow_binary(x, pp))(a))
            np.testing.assert_array_equal(np.asarray(results[t]),
                                          np.asarray(want))

    def test_bucketing_counts(self):
        eng = MatFnEngine()
        a8 = _stack(5, 8, seed=1)
        for i in range(5):
            eng.submit("matpow", a8[i], power=7)
        eng.submit("matpow", _stack(1, 12, seed=2)[0], power=7)
        eng.flush()
        # two buckets: (matpow, 8, f32, 7) x5 padded to 8, and one n=12
        assert eng.stats["buckets"] == 2
        assert eng.stats["padded_slots"] == 3
        assert eng.stats["requests"] == 6

    def test_numpy_f64_operand_canonicalized_into_f32_bucket(self):
        """A default-dtype numpy operand (f64 under disabled x64) must share
        a bucket — and an executable — with the identical f32 request."""
        rng = np.random.default_rng(11)
        host = rng.standard_normal((8, 8))             # np.float64
        eng = MatFnEngine()
        eng.submit("matpow", host, power=3)
        eng.submit("matpow", jnp.asarray(host, jnp.float32), power=3)
        res = eng.flush()
        assert eng.stats["buckets"] == 1
        np.testing.assert_array_equal(np.asarray(res[0]), np.asarray(res[1]))

    def test_mixed_dtypes_split_buckets(self):
        eng = MatFnEngine()
        eng.submit("matpow", _stack(1, 8, dtype=jnp.float32)[0], power=3)
        eng.submit("matpow", _stack(1, 8, dtype=jnp.bfloat16)[0], power=3)
        res = eng.flush()
        assert eng.stats["buckets"] == 2
        assert res[0].dtype == jnp.float32
        assert res[1].dtype == jnp.bfloat16

    def test_executable_cache_reused_across_flushes(self):
        eng = MatFnEngine()
        a = _stack(3, 8, seed=3)
        for i in range(3):
            eng.submit("matpow", a[i], power=5)
        eng.flush()
        compiles = eng.stats["compiles"]
        for i in range(3):
            eng.submit("matpow", a[i], power=5)
        eng.flush()
        assert eng.stats["compiles"] == compiles     # no new executable
        assert eng.stats["cache_hits"] >= 1

    def test_max_batch_chunking(self):
        eng = MatFnEngine(max_batch=4)
        a = _stack(10, 8, seed=4)
        for i in range(10):
            eng.submit("matpow", a[i], power=3)
        res = eng.flush()
        assert eng.stats["buckets"] == 3             # 4 + 4 + 2
        for i in range(10):
            np.testing.assert_array_equal(
                np.asarray(res[i]),
                np.asarray(jax.jit(lambda x: matpow_binary(x, 3))(a[i])))

    def test_chain_route_interpret_numerics(self, tmp_cache):
        """Force mid-size traffic onto the batched Pallas chain."""
        autotune.record_dispatch_thresholds(8, 1 << 30)
        eng = MatFnEngine(interpret=True)
        assert eng.thresholds == (8, 1 << 30)
        a = _stack(3, 40, seed=5)
        for i in range(3):
            eng.submit("matpow", a[i], power=7)
        res = eng.flush()
        assert eng.stats["routes"]["chain"] == 1
        for i in range(3):
            np.testing.assert_allclose(np.asarray(res[i]),
                                       _ref_pow(a[i], 7),
                                       rtol=2e-3, atol=1e-5)

    def test_p0_and_convenience_api(self):
        eng = MatFnEngine()
        a = _stack(1, 8, seed=6)[0]
        np.testing.assert_array_equal(np.asarray(eng.matpow(a, 0)),
                                      np.eye(8, dtype=np.float32))
        np.testing.assert_array_equal(np.asarray(eng.expm(a)),
                                      np.asarray(jax.jit(expm)(a)))

    def test_profile_mode_records_bucket_seconds(self):
        eng = MatFnEngine(profile=True)
        eng.submit("matpow", _stack(1, 8)[0], power=3)
        eng.flush()
        rows = eng.stats["last_flush"]
        assert len(rows) == 1 and rows[0]["seconds"] > 0


# The four bucket classes of the local routes: op, power slot, and whether
# each request carries start distributions (the evolve class).
_KINDS = {"expm": ("expm", 1, False), "matpow": ("matpow", 7, False),
          "steady_state": ("markov", 1, False), "evolve": ("markov", 9, True)}
_KIND_REFS = {}


def _kind_payload(kind, n, seed):
    """One request's (operand, dists) for a bucket class; dists is None
    outside evolve. Markov operands are row-stochastic."""
    rng = np.random.default_rng(seed)
    if _KINDS[kind][0] != "markov":
        return jnp.asarray(rng.standard_normal((n, n)) * 0.3,
                           jnp.float32), None
    p = rng.random((n, n)) + 0.05
    p = jnp.asarray(p / p.sum(1, keepdims=True), jnp.float32)
    if not _KINDS[kind][2]:
        return p, None
    d = rng.random((3, n))
    return p, jnp.asarray(d / d.sum(1, keepdims=True), jnp.float32)


def _kind_ref(kind, a, d):
    """The per-matrix jitted call a bucket member must equal bit for bit."""
    if kind not in _KIND_REFS:
        from repro.core.markov import evolve_distributions, steady_state
        _KIND_REFS[kind] = jax.jit({
            "expm": lambda x, _d: expm(x),
            "matpow": lambda x, _d: matpow_binary(x, 7),
            "steady_state": lambda x, _d: steady_state(x, validate=False),
            "evolve": lambda x, dd: evolve_distributions(dd, x, 9,
                                                         validate=False),
        }[kind])
    return _KIND_REFS[kind](a, d)


def _submit_kind(eng, kind, a, d):
    op, power, _ = _KINDS[kind]
    return eng.submit(op, a, power=power, dists=d)


def _assert_same(got, want):
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


class TestOneProgramPerBucket:
    """Each bucket runs as ONE device program: the members and filler
    references go in as separate arguments, the stack and the per-slot
    split happen inside it, and the resolve stage keeps the members' rows
    on the host."""

    @pytest.mark.parametrize("b", [1, 5, 8, 63, 64])
    @pytest.mark.parametrize("kind", list(_KINDS))
    def test_bucket_answers_bit_identical_per_member(self, kind, b):
        eng = MatFnEngine(max_batch=64, clock=ManualClock(),
                          max_delay_ms=10.0)
        delivered = []
        real = eng._resolve

        def counting(fut, **kw):
            delivered.append(fut)
            return real(fut, **kw)

        eng._resolve = counting
        work = [_kind_payload(kind, 8, seed=100 + i) for i in range(b)]
        with eng:
            futs = [_submit_kind(eng, kind, a, d) for a, d in work]
            eng.kick()
            got = [f.result(timeout=30.0) for f in futs]
            snap = eng.stats()
        assert snap["buckets"] == 1
        assert snap["padded_slots"] == bucket_batch(b) - b
        assert sorted(map(id, delivered)) == sorted(map(id, futs))
        for (a, d), g in zip(work, got):
            _assert_same(g, _kind_ref(kind, a, d))

    @pytest.mark.parametrize("mode", ["flush", "daemon"])
    def test_one_dispatch_per_bucket(self, mode):
        eng = MatFnEngine(max_batch=4, clock=ManualClock(),
                          max_delay_ms=10.0)
        work = [("matpow", _stack(1, n, seed=i)[0], p)
                for i, (n, p) in enumerate([(8, 3)] * 6 + [(12, 3)] * 3
                                           + [(8, 5)] * 2)]
        work += [("expm", _stack(1, 8, seed=20 + i)[0], 1) for i in range(3)]
        if mode == "daemon":
            with eng:
                futs = [eng.submit(op, a, power=p) for op, a, p in work]
                eng.kick()
                for f in futs:
                    f.result(timeout=30.0)
                snap = eng.stats()
        else:
            for op, a, p in work:
                eng.submit(op, a, power=p)
            eng.flush()
            snap = eng.stats()
        # (8, 3) x6 -> 4 + 2; (12, 3) x3; (8, 5) x2; expm x3
        assert snap["buckets"] == 5
        assert snap["routes"]["xla"] == snap["buckets"]
        assert snap["dispatches"] == snap["buckets"]
        assert eng.stats["dispatches"] == eng.stats["buckets"]

    @pytest.mark.parametrize("kind", list(_KINDS))
    def test_caller_operands_stay_usable(self, kind):
        eng = MatFnEngine()
        work = [_kind_payload(kind, 8, seed=200 + i) for i in range(3)]
        # The values from equal-seed twins: a host view of the operands
        # themselves would pin their buffers against donation on the CPU.
        before = [jax.tree_util.tree_map(
            np.asarray, _kind_payload(kind, 8, seed=200 + i))
            for i in range(3)]
        for a, d in work:
            _submit_kind(eng, kind, a, d)
        eng.flush()                        # one bucket of 3, padded to 4
        assert eng.stats["padded_slots"] == 1
        for w, host in zip(work, before):
            for arr, want in zip(jax.tree_util.tree_leaves(w),
                                 jax.tree_util.tree_leaves(host),
                                 strict=True):
                assert not arr.is_deleted()
                np.testing.assert_array_equal(np.asarray(arr), want)
        for a, d in work:                  # and usable in another bucket
            _submit_kind(eng, kind, a, d)
        for (a, d), g in zip(work, eng.flush()):
            _assert_same(g, _kind_ref(kind, a, d))

    @pytest.mark.parametrize("max_batch, sizes", [
        (64, 7), (5, 4), (1, 1)])
    def test_warm_compiles_each_padded_size_once(self, max_batch, sizes):
        eng = MatFnEngine(max_batch=max_batch)
        assert eng.warm("expm", 8) == sizes
        assert eng.stats["compiles"] == sizes
        assert eng.stats["dispatches"] == sizes
        # A bucket size warm did not run reuses its padded size's program,
        # which was traced for that padded size alone.
        b = max(1, max_batch - 1)
        for i in range(b):
            eng.submit("expm", _stack(1, 8, seed=300 + i)[0])
        eng.flush()
        assert eng.stats["compiles"] == sizes
        assert all(exe._cache_size() == 1
                   for exe in eng._executables.values())


# The test kind that exercises each op-table entry.
_ENTRY_KINDS = {"matpow": "matpow", "expm": "expm", "markov": "steady_state",
                "evolve": "evolve"}


class TestOpAndRouteTables:
    """Each traffic class is one op-table entry and each route one
    route-table row; the engine's behaviour follows from them."""

    @pytest.mark.parametrize("entry", sorted(matfn._OP_TABLE))
    def test_every_entry_warms_and_answers_on_its_route(self, entry):
        kind = _ENTRY_KINDS[entry]
        op, power, _ = _KINDS[kind]
        eng = MatFnEngine(max_batch=4)
        assert eng.warm(op, 8, power=power) == 3      # padded 1, 2 and 4
        warmed = eng.stats["compiles"]
        for b in (1, 3):                              # one member; padded
            work = [_kind_payload(kind, 8, seed=400 + b + i)
                    for i in range(b)]
            for a, d in work:
                _submit_kind(eng, kind, a, d)
            got = eng.flush()
            (row,) = eng.stats["last_flush"]
            _op, route, bpad, n, dtype, slot = row["key"]
            assert route == row["route"] == eng.route_for(n, b, dtype, slot)
            assert bpad == row["padded_batch"] == bucket_batch(b)
            for (a, d), g in zip(work, got, strict=True):
                _assert_same(g, _kind_ref(kind, a, d))
        # warm reaches every class but evolve's, which it has no dists for
        fresh = 2 if matfn._OP_TABLE[entry].dists else 0
        assert eng.stats["compiles"] == warmed + fresh

    def test_route_names_agree(self):
        eng = MatFnEngine()
        snap = eng.stats()
        default = ExecutionStreams()
        assert matfn.ROUTES == streams.DEFAULT_ROUTES == default.routes
        assert default.streams == len(matfn.ROUTES)
        assert tuple(snap["routes"]) == matfn.ROUTES
        assert tuple(snap["precision"]) == matfn.ROUTES
        assert [eng.route_precision(r) for r in matfn.ROUTES] == \
            list(snap["precision"].values())


class TestHeterogeneousDispatch:
    def test_default_thresholds(self):
        assert autotune.DEFAULT_DISPATCH_THRESHOLDS == (64, 4096)

    def test_cache_round_trip(self, tmp_cache):
        autotune.record_dispatch_thresholds(32, 2048, dtype=jnp.float32)
        assert autotune.dispatch_thresholds(dtype=jnp.float32) == (32, 2048)
        # dtype-agnostic fallback
        assert autotune.dispatch_thresholds(dtype=jnp.bfloat16) == \
            autotune.DEFAULT_DISPATCH_THRESHOLDS
        autotune.clear_memory_cache()                # survives reload
        assert autotune.dispatch_thresholds(dtype=jnp.float32) == (32, 2048)

    def test_record_rejects_descending(self):
        with pytest.raises(ValueError):
            autotune.record_dispatch_thresholds(4096, 64)
        with pytest.raises(ValueError):
            autotune.record_dispatch_thresholds(0, 64)

    def test_thresholds_never_cross_namespaces(self, tmp_cache):
        """A dispatch entry must not answer square_panel tier lookups."""
        autotune.record_dispatch_thresholds(32, 2048)
        assert autotune.square_tiers() == autotune.DEFAULT_SQUARE_TIERS

    def test_routing_table(self, tmp_cache):
        autotune.record_dispatch_thresholds(16, 256)
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        eng = MatFnEngine(mesh=mesh)
        assert eng.route_for(8, 4) == "xla"          # tiny -> CPU/XLA
        assert eng.route_for(16, 1) == "xla"
        assert eng.route_for(64, 4) == "chain"       # mid -> pallas chain
        assert eng.route_for(256, 1) == "sharded"    # huge single -> mesh
        assert eng.route_for(256, 2) == "chain"      # huge BATCH stays local
        no_mesh = MatFnEngine()
        assert no_mesh.route_for(512, 1) == "chain"  # no mesh -> no sharding

    def test_sharded_route_end_to_end(self, tmp_cache):
        """A huge single matrix runs the sharded chain (1x1 mesh on CPU)."""
        autotune.record_dispatch_thresholds(8, 32)
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        eng = MatFnEngine(mesh=mesh)
        a = _stack(1, 48, seed=7)[0]
        got = eng.matpow(a, 7)
        assert eng.stats["routes"]["sharded"] == 1
        np.testing.assert_allclose(np.asarray(got), _ref_pow(a, 7),
                                   rtol=2e-3, atol=1e-5)

    def test_explicit_thresholds_override_cache(self, tmp_cache):
        autotune.record_dispatch_thresholds(16, 256)
        eng = MatFnEngine(thresholds=(4, 1 << 20))
        assert eng.route_for(8, 2) == "chain"

    def test_per_dtype_thresholds_respected(self, tmp_cache):
        """A dtype-specific dispatch entry must actually steer routing
        (bf16 crossovers legitimately differ from f32)."""
        autotune.record_dispatch_thresholds(16, 1 << 20, dtype=jnp.bfloat16)
        eng = MatFnEngine()
        assert eng.route_for(32, 2, dtype=jnp.bfloat16) == "chain"
        assert eng.route_for(32, 2, dtype=jnp.float32) == "xla"  # any/default
        assert eng.thresholds == autotune.DEFAULT_DISPATCH_THRESHOLDS
        # and end to end: the bucket dtype picks the entry
        a = _stack(2, 32, seed=9, dtype=jnp.bfloat16)
        eng2 = MatFnEngine(interpret=True)
        for i in range(2):
            eng2.submit("matpow", a[i], power=3)
        eng2.flush()
        assert eng2.stats["routes"]["chain"] == 1


class TestMatserveVerify:
    """``matserve --verify`` compares every answer with a host f64
    reference that shares no code with the engine."""

    def test_host_reference_matches_numpy_and_scipy(self):
        import scipy.linalg
        from repro.launch.matserve import host_reference

        rng = np.random.default_rng(0)
        a = rng.standard_normal((12, 12)) * 0.2
        np.testing.assert_allclose(host_reference("matpow", a, 5),
                                   np.linalg.matrix_power(a, 5))
        np.testing.assert_allclose(host_reference("expm", a, 1),
                                   scipy.linalg.expm(a))
        p = np.abs(a) + 0.1
        p /= p.sum(1, keepdims=True)
        pi = host_reference("markov", p, 1)
        np.testing.assert_allclose(pi @ p, pi, atol=1e-12)
        assert abs(pi.sum() - 1.0) < 1e-12
        d = np.full((3, 12), 1.0 / 12)
        np.testing.assert_allclose(host_reference("markov", p, 7, d),
                                   d @ np.linalg.matrix_power(p, 7))

    def test_verify_reports_f64_error_and_skips_underflow(self, capsys):
        from repro.launch.matserve import _verify, make_workload, run_workload

        work = make_workload(12, (8, 16), (3, 4000), expm_frac=0.25,
                             seed=1, markov_frac=0.25)
        results, _ = run_workload(MatFnEngine(), work)
        _verify(work, results)
        lines = capsys.readouterr().out.splitlines()
        assert lines[0].startswith("[matserve] verify: max |batched")
        f64 = lines[1]
        assert "rel err vs host f64 reference" in f64
        # A^4000 of a gaussian*0.4/sqrt(n) operand underflows f32: those
        # requests are counted, not compared; the rest are within f32.
        underflows = sum(1 for op, _a, p, *_ in work
                         if op == "matpow" and p == 4000)
        assert underflows > 0
        assert f"{len(work) - underflows} compared, {underflows} skipped" \
            in f64
        for part in f64.split(": ", 2)[2].split(" (")[0].split():
            assert float(part.split("=")[1]) < 1e-4
