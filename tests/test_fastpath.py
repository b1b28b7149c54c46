"""Estimation fast-path equivalence tests (ISSUE 1).

Three guarantees, each against the seed pipeline preserved verbatim as
``fastpath=False``:

* cached vs uncached estimates are byte-identical (the trace cache only
  memoizes; it never changes results);
* periodic composition + steady-state replay matches the fully
  materialized slow path for iterations in {2, 3, 8} across all three
  allocator policies and every grad-release mode;
* ``min_feasible_capacity`` agrees with a bisected ``would_oom`` sweep.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    BlockKind, BlockLifecycle, MemorySimulator, OrchestratorPolicy,
    PeriodicBlocks, Phase, TraceCache, XMemEstimator, peak_live_bytes,
    periodic_peak_live,
)
from repro.core.allocator import CUDA_CACHING, TPU_ARENA, XLA_BFC, round_up
from repro.core.cache import trace_key

# ---------------------------------------------------------------------------
D, H, B = 128, 256, 32


def _loss(params, batch):
    h = jnp.tanh(batch["x"] @ params["w1"])
    y = h @ params["w2"]
    return jnp.mean((y - batch["y"]) ** 2)


def _fwd_bwd(p, b):
    return jax.value_and_grad(_loss)(p, b)


def _adam_init(p):
    return jax.tree.map(lambda x: (jnp.zeros_like(x), jnp.zeros_like(x)), p)


def _adam(p, g, s):
    def upd(pp, gg, ss):
        m, v = ss
        m = 0.9 * m + 0.1 * gg
        v = 0.999 * v + 0.001 * gg * gg
        return pp - 1e-3 * m / (jnp.sqrt(v) + 1e-8), (m, v)
    out = jax.tree.map(upd, p, g, s, is_leaf=lambda x: isinstance(x, tuple))
    return {k: out[k][0] for k in out}, {k: out[k][1] for k in out}


@pytest.fixture
def shapes():
    params = {"w1": jax.ShapeDtypeStruct((D, H), jnp.float32),
              "w2": jax.ShapeDtypeStruct((H, D), jnp.float32)}
    batch = {"x": jax.ShapeDtypeStruct((B, D), jnp.float32),
             "y": jax.ShapeDtypeStruct((B, D), jnp.float32)}
    return params, batch


def _estimate(est, shapes):
    params, batch = shapes
    return est.estimate_training(_fwd_bwd, params, batch,
                                 update_fn=_adam, opt_init_fn=_adam_init)


def _assert_reports_equal(a, b):
    """Every estimate-bearing field identical (wall time and cache
    counters are the only legitimately differing fields)."""
    assert a.peak_bytes == b.peak_bytes
    assert a.peak_tensor_bytes == b.peak_tensor_bytes
    assert a.persistent_bytes == b.persistent_bytes
    assert a.oom == b.oom
    assert a.num_events == b.num_events
    assert a.breakdown == b.breakdown
    assert a.sim.peak_reserved == b.sim.peak_reserved
    assert a.sim.peak_allocated == b.sim.peak_allocated
    assert a.sim.oom == b.sim.oom


# ---------------------------------------------------------------------------
class TestTraceCache:
    def test_cached_vs_uncached_identical(self, shapes):
        est = XMemEstimator.for_tpu(trace_cache=TraceCache())
        r_cold = _estimate(est, shapes)
        r_warm = _estimate(est, shapes)
        assert r_cold.cache_stats["hits"] == 0
        assert r_warm.cache_stats["hits"] == 3       # fwd + init + upd
        assert r_warm.cache_stats["misses"] == 0
        _assert_reports_equal(r_cold, r_warm)

    def test_cache_shared_across_estimator_instances(self, shapes):
        cache = TraceCache()
        r1 = _estimate(XMemEstimator.for_tpu(trace_cache=cache), shapes)
        r2 = _estimate(XMemEstimator.for_tpu(trace_cache=cache), shapes)
        assert r1.cache_stats["misses"] == 3
        assert r2.cache_stats["hits"] == 3
        _assert_reports_equal(r1, r2)

    def test_key_distinguishes_avals_and_cap(self, shapes):
        params, batch = shapes
        flat = list(params.values())
        td = (jax.tree_util.tree_structure(params),)
        kinds = [BlockKind.PARAM] * len(flat)
        k1 = trace_key(_fwd_bwd, "t", flat, td, kinds, 3,
                       Phase.FORWARD_BACKWARD, fusion_folding=True)
        k2 = trace_key(_fwd_bwd, "t", flat, td, kinds, 5,
                       Phase.FORWARD_BACKWARD, fusion_folding=True)
        other = [jax.ShapeDtypeStruct((D, H + 1), jnp.float32)] * len(flat)
        k3 = trace_key(_fwd_bwd, "t", other, td, kinds, 3,
                       Phase.FORWARD_BACKWARD, fusion_folding=True)
        assert len({k1, k2, k3}) == 3

    def test_recreated_identical_fn_is_warm(self, shapes):
        # ISSUE 4: content-addressed keys — hillclimb/dryrun rebuild the
        # train step per policy, so structurally identical but re-created
        # closures must hit, not miss on function-identity churn
        cache = TraceCache()
        est = XMemEstimator.for_tpu(trace_cache=cache)

        def make_fn():
            return lambda p, b: jax.value_and_grad(_loss)(p, b)
        fn = make_fn()
        params, batch = shapes
        est.estimate_training(fn, params, batch, update_fn=_adam,
                              opt_init_fn=_adam_init)
        fn2 = make_fn()
        assert fn2 is not fn
        r = est.estimate_training(fn2, params, batch, update_fn=_adam,
                                  opt_init_fn=_adam_init)
        assert r.cache_stats["misses"] == 0
        assert r.cache_stats["hits"] == 3

    def test_stale_identity_is_a_miss_for_uncanonical_fns(self, shapes):
        # functions whose closures cannot be content-hashed fall back to
        # weak id() keys: a different function object with (possibly) a
        # recycled id must not hit the old entry
        import threading
        cache = TraceCache()
        est = XMemEstimator.for_tpu(trace_cache=cache)

        def make_fn():
            lock = threading.Lock()    # closure cell defeats hashing
            def fn(p, b):
                assert lock is not None
                return jax.value_and_grad(_loss)(p, b)
            return fn
        from repro.core.cache import fn_identity
        fn = make_fn()
        assert fn_identity(fn)[0] == "id"
        params, batch = shapes
        est.estimate_training(fn, params, batch, update_fn=_adam,
                              opt_init_fn=_adam_init)
        fn2 = make_fn()
        r = est.estimate_training(fn2, params, batch, update_fn=_adam,
                                  opt_init_fn=_adam_init)
        assert r.cache_stats["misses"] >= 1

    def test_lru_eviction(self):
        cache = TraceCache(maxsize=2)
        fns = [lambda i=i: i for i in range(3)]
        for i, f in enumerate(fns):
            key = trace_key(f, "t", [], (), [], 3, Phase.FORWARD_BACKWARD,
                            fusion_folding=True)
            cache.put(f, key, object())
        assert len(cache) == 2

    def test_batch_change_misses_but_opt_phases_hit(self, shapes):
        params, _ = shapes
        cache = TraceCache()
        est = XMemEstimator.for_tpu(trace_cache=cache)
        for bsz in (8, 16):
            batch = {"x": jax.ShapeDtypeStruct((bsz, D), jnp.float32),
                     "y": jax.ShapeDtypeStruct((bsz, D), jnp.float32)}
            r = est.estimate_training(_fwd_bwd, params, batch,
                                      update_fn=_adam,
                                      opt_init_fn=_adam_init)
        # second batch size: fwd re-traced, init+upd (batch-independent)
        # served from cache — the hillclimb access pattern
        assert r.cache_stats["hits"] == 2
        assert r.cache_stats["misses"] == 1


# ---------------------------------------------------------------------------
class TestSteadyStateEquivalence:
    @pytest.mark.parametrize("policy", [CUDA_CACHING, XLA_BFC, TPU_ARENA],
                             ids=lambda p: p.name)
    @pytest.mark.parametrize("iterations", [2, 3, 8])
    def test_matches_full_replay(self, shapes, policy, iterations):
        kw = dict(allocator_policy=policy, iterations=iterations)
        fast = XMemEstimator(trace_cache=TraceCache(), **kw)
        slow = XMemEstimator(fastpath=False, **kw)
        _assert_reports_equal(_estimate(fast, shapes),
                              _estimate(slow, shapes))

    @pytest.mark.parametrize("mode", ["at_update", "at_next_iter",
                                      "eager_fused", "auto"])
    def test_matches_across_grad_release(self, shapes, mode):
        op = OrchestratorPolicy(grad_release=mode)
        kw = dict(orchestrator_policy=op, iterations=8)
        fast = XMemEstimator(trace_cache=TraceCache(), **kw)
        slow = XMemEstimator(fastpath=False,
                             orchestrator_policy=op, iterations=8)
        _assert_reports_equal(_estimate(fast, shapes),
                              _estimate(slow, shapes))

    def test_steady_state_actually_skips(self, shapes):
        # steady-state extrapolation is an object-engine feature; the
        # columnar engine replays the tiled expansion instead (and must
        # agree — asserted below and in tests/test_columnar.py)
        est = XMemEstimator.for_tpu(iterations=32,
                                    trace_cache=TraceCache(),
                                    engine="object")
        rep = _estimate(est, shapes)
        ss = rep.sim.stats["steady_state"]
        assert ss["cycles_total"] == 30
        assert ss["cycles_skipped"] >= 25      # paper §3.1: stabilizes fast
        # replay cost independent of N: compare against N=8
        rep8 = _estimate(XMemEstimator.for_tpu(
            iterations=8, trace_cache=TraceCache(), engine="object"), shapes)
        extra = (rep.sim.stats["events_replayed"]
                 - rep8.sim.stats["events_replayed"])
        assert extra == 0
        rep_col = _estimate(XMemEstimator.for_tpu(
            iterations=32, trace_cache=TraceCache()), shapes)
        assert rep_col.sim.stats["engine"] == "columnar"
        assert rep_col.peak_bytes == rep.peak_bytes

    def test_oom_verdict_matches(self, shapes):
        for fastpath in (True, False):
            est = XMemEstimator.for_tpu(capacity=100_000, fastpath=fastpath,
                                        trace_cache=TraceCache())
            assert _estimate(est, shapes).oom

    def test_reduced_breakdown_matches_full(self, shapes):
        from repro.core.events import (periodic_breakdown_peaks,
                                       reduced_for_breakdown)
        est = XMemEstimator.for_tpu(iterations=64,
                                    trace_cache=TraceCache())
        rep = _estimate(est, shapes)
        pb = rep.composition
        assert pb.n_cycles == 62
        reduced = reduced_for_breakdown(pb)
        assert reduced.n_cycles == 4          # reduction applied
        assert periodic_breakdown_peaks(reduced) == \
            periodic_breakdown_peaks(pb)

    def test_cache_evicts_on_fn_death(self):
        # id-keyed entries (uncanonical fns) die with their function —
        # the weakref callback fires. Content-keyed entries survive: any
        # structurally identical future fn can still hit them (ISSUE 4).
        import gc
        import threading
        cache = TraceCache()
        est = XMemEstimator.for_tpu(trace_cache=cache)
        params = {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
        batch = {"x": jax.ShapeDtypeStruct((4, 8), jnp.float32)}

        def make():
            lock = threading.Lock()    # closure defeats content hashing
            def fn(p, b):
                assert lock is not None
                return (jnp.sum(b["x"] @ p["w"]), p)
            return fn
        fn = make()
        est.estimate_training(fn, params, batch)
        assert len(cache) == 1
        del fn
        gc.collect()
        assert len(cache) == 0                # weakref callback fired

        fn2 = (lambda p, b: (jnp.sum(b["x"] @ p["w"]), p))
        est.estimate_training(fn2, params, batch)
        assert len(cache) == 1
        del fn2
        gc.collect()
        assert len(cache) == 1                # content entry persists

    def test_materialize_matches_peak_live(self):
        cyc = [BlockLifecycle(1, 100, 10, 14, 1, Phase.FORWARD_BACKWARD),
               BlockLifecycle(2, 50, 12, 22, 1, Phase.OPTIMIZER)]
        pre = [BlockLifecycle(0, 70, 0, None, 0, Phase.INIT)]
        suf = [BlockLifecycle(3, 40, 50, 55, 5, Phase.FORWARD_BACKWARD)]
        pb = PeriodicBlocks(pre, cyc, 4, 10, suf,
                            meta={"cycle_start": 10})
        assert periodic_peak_live(pb) == peak_live_bytes(pb.materialize())


# ---------------------------------------------------------------------------
class TestMinFeasibleCapacity:
    def _composition(self, shapes, policy):
        est = XMemEstimator(allocator_policy=policy,
                            trace_cache=TraceCache())
        rep = _estimate(est, shapes)
        return rep.composition, est

    def _bisect_reference(self, sim, blocks, page, hi):
        lo, hi_k = page, hi // page
        lo_k = 1
        while lo_k < hi_k:
            mid = (lo_k + hi_k) // 2
            if sim.would_oom(blocks, mid * page):
                lo_k = mid + 1
            else:
                hi_k = mid
        return hi_k * page

    @pytest.mark.parametrize("policy", [CUDA_CACHING, XLA_BFC, TPU_ARENA],
                             ids=lambda p: p.name)
    def test_agrees_with_bisected_would_oom(self, shapes, policy):
        blocks, est = self._composition(shapes, policy)
        sim = MemorySimulator(policy)
        fast = sim.min_feasible_capacity(blocks)
        unbounded = MemorySimulator(policy).replay(blocks)
        hi = round_up(unbounded.peak_reserved, policy.device_page)
        ref = self._bisect_reference(MemorySimulator(policy), blocks,
                                     policy.device_page, hi)
        assert fast == ref
        # verdict sanity at the boundary
        assert not sim.would_oom(blocks, fast)
        assert sim.would_oom(blocks, fast - policy.device_page)

    def test_estimator_entrypoint(self, shapes):
        params, batch = shapes
        est = XMemEstimator.for_tpu(trace_cache=TraceCache())
        rep = _estimate(est, shapes)
        cap = est.min_feasible_capacity(_fwd_bwd, params, batch,
                                        update_fn=_adam,
                                        opt_init_fn=_adam_init, report=rep)
        assert 0 < cap <= rep.peak_bytes
        assert cap % TPU_ARENA.device_page == 0

    def test_capacity_constrained_report_not_trusted(self, shapes):
        """A report whose replay was capacity-limited (possibly OOM'd,
        peaks truncated) must not serve as the instrumented probe."""
        params, batch = shapes
        est = XMemEstimator.for_tpu(trace_cache=TraceCache())
        full = _estimate(est, shapes)
        true_min = est.min_feasible_capacity(
            _fwd_bwd, params, batch, update_fn=_adam,
            opt_init_fn=_adam_init, report=full)
        bad_rep = est.estimate_training(
            _fwd_bwd, params, batch, update_fn=_adam,
            opt_init_fn=_adam_init, capacity=max(true_min // 4, 4096))
        assert bad_rep.oom
        cap = est.min_feasible_capacity(
            _fwd_bwd, params, batch, update_fn=_adam,
            opt_init_fn=_adam_init, report=bad_rep)
        assert cap == true_min


# ---------------------------------------------------------------------------
class TestOutputRelease:
    def test_outputs_do_not_accumulate(self, shapes):
        """Step outputs die when the next iteration replaces them — the
        estimate is iteration-stable instead of growing with N."""
        r8 = _estimate(XMemEstimator.for_tpu(
            iterations=8, trace_cache=TraceCache()), shapes)
        r3 = _estimate(XMemEstimator.for_tpu(
            iterations=3, trace_cache=TraceCache()), shapes)
        assert r8.peak_bytes == r3.peak_bytes

    def test_legacy_persistent_outputs_opt_out(self, shapes):
        op = OrchestratorPolicy(release_outputs_next_iter=False)
        fast = XMemEstimator(orchestrator_policy=op, iterations=8,
                             trace_cache=TraceCache())
        slow = XMemEstimator(orchestrator_policy=dataclasses.replace(op),
                             iterations=8, fastpath=False)
        _assert_reports_equal(_estimate(fast, shapes),
                              _estimate(slow, shapes))
