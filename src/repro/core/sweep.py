"""Sweep service — batched estimation over many related jobs.

Sweep callers (hillclimb batch-size search, dry-run capacity gating, the
Monte-Carlo benchmark protocols) historically ran ``estimate_training``
one point at a time, paying a full ``make_jaxpr`` + jaxpr interpretation
for every probe even though the points differ only in one scalar (the
batch size). ``estimate_many`` removes that redundancy in three layers:

1. **Trace-cache dedup** — points sharing avals (and the batch-
   independent optimizer phases of every point) are traced once.
2. **Columnar trace interpolation** — for a 1-D sweep (batch size), the
   forward phase is traced at three probe points (min / median / max).
   If the three columnar traces are structurally identical (same events,
   ids, times, ops, scopes — everything except the size column) and the
   per-event sizes fit an integer affine model ``size = s0 + s1 * b``
   that reproduces the middle probe *exactly*, the remaining points'
   traces are synthesized by array arithmetic: no tracing at all. Every
   synthesized point is additionally cross-checked against its true
   input aval bytes, and any failed check falls back to a real trace —
   the model is an exact-or-bust shortcut, never an approximation.
   Classification, orchestration and replay still run per point (they
   are size-dependent), so results are identical to sequential
   ``estimate_training`` by construction (tests/test_columnar.py).
3. **Parallel replay fan-out** — stages 2-5 of non-probe points are
   pure functions of picklable ``TracedPhase`` payloads, so a
   ``SweepService`` with ``processes > 0`` ships them to a persistent
   process pool (spawned workers never run JAX tracing; reports from
   pooled points carry no usage curve to keep IPC lean).

Use ``SweepService`` when sweeping repeatedly (the pool and trace cache
stay warm across calls); ``estimate_many`` is the one-shot convenience.
"""
from __future__ import annotations

import dataclasses
import time
from concurrent.futures import ProcessPoolExecutor
from typing import Any, Callable, Sequence

import numpy as np

from .cache import BlockInfo, TracedPhase, trace_key
from .estimator import (EstimateReport, XMemEstimator, _coupling_from_jaxpr,
                        flatten_kinds)
from .events import BlockKind, ColumnarBlocks, Phase, Trace
from .simulator import SimResult


@dataclasses.dataclass
class SweepPoint:
    """One job of a sweep: the ``estimate_training`` argument tuple."""

    fwd_bwd_fn: Callable
    params: Any
    batch: Any
    update_fn: Callable | None = None
    opt_init_fn: Callable | None = None
    shard_factor_fn: Callable | None = None
    collective_specs: Sequence = ()
    capacity: int | None = None
    label: str = ""


@dataclasses.dataclass
class SweepResult:
    reports: list[EstimateReport]       # one per point, input order
    stats: dict                         # traced/interpolated/pooled counts

    def __iter__(self):
        return iter(self.reports)

    def __len__(self):
        return len(self.reports)


# -- mesh-topology sweep -----------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MeshTopology:
    """One (pod, data, model, fsdp) cell of a topology grid."""

    pod: int = 1
    data: int = 1
    model: int = 1
    fsdp: bool = False

    @property
    def n_devices(self) -> int:
        return self.pod * self.data * self.model

    @property
    def axis_sizes(self) -> dict:
        return {"pod": self.pod, "data": self.data, "model": self.model}

    @property
    def label(self) -> str:
        tag = f"{self.pod}x{self.data}x{self.model}"
        return tag + ("+fsdp" if self.fsdp else "")

    def sharding_policy(self):
        from ..distributed.sharding import ShardingPolicy
        fsdp_axes = (("data", "pod") if self.pod > 1 else ("data",))
        return ShardingPolicy(fsdp=self.fsdp, fsdp_axes=fsdp_axes,
                              batch_axes=("pod", "data"))


def topology_grid(n_devices: int, *, pods: Sequence[int] = (1,),
                  fsdp: Sequence[bool] = (False, True)
                  ) -> list[MeshTopology]:
    """All (pod, data, model, fsdp) cells whose device product equals
    ``n_devices`` — the default grid ``estimate_mesh_sweep`` callers
    batch over. fsdp=True cells are skipped when every fsdp axis has
    size 1 (they would duplicate the fsdp=False estimate bit-for-bit
    while claiming ZeRO-3 was modeled)."""
    out = []
    for pod in pods:
        if pod <= 0 or n_devices % pod:
            continue
        per_pod = n_devices // pod
        for model in range(1, per_pod + 1):
            if per_pod % model:
                continue
            data = per_pod // model
            for f in fsdp:
                if f and data * pod == 1:
                    continue
                out.append(MeshTopology(pod=pod, data=data,
                                        model=model, fsdp=f))
    return out


@dataclasses.dataclass
class MeshSweepResult:
    """Per-topology estimates from one cached trace."""

    topologies: list[MeshTopology]
    reports: list[EstimateReport]
    stats: dict

    def __iter__(self):
        return iter(zip(self.topologies, self.reports))

    def __len__(self):
        return len(self.reports)

    def admitted(self, capacity: int) -> list[MeshTopology]:
        """Topologies whose per-device estimate fits ``capacity``."""
        return [t for t, r in zip(self.topologies, self.reports)
                if r.fits(capacity)]

    def best(self, capacity: int
             ) -> tuple[MeshTopology, EstimateReport] | None:
        """Cheapest admitted topology: fewest devices, then lowest
        per-device peak."""
        fits = [(t, r) for t, r in zip(self.topologies, self.reports)
                if r.fits(capacity)]
        if not fits:
            return None
        return min(fits, key=lambda tr: (tr[0].n_devices,
                                         tr[1].peak_bytes))


@dataclasses.dataclass
class ServingSweepResult:
    """Per-knob serving estimates from one cached decode trace."""

    knobs: list            # ServingKnobs grid, aligned with estimates
    estimates: list        # ServingEstimate per knob point
    stats: dict

    def __iter__(self):
        return iter(zip(self.knobs, self.estimates))

    def __len__(self):
        return len(self.estimates)

    def admitted(self, capacity: int) -> list:
        return [k for k, e in zip(self.knobs, self.estimates)
                if e.fits(capacity)]


# -- affine trace model ------------------------------------------------------
def _fit_affine(y_lo, y_hi, b_lo: int, b_hi: int):
    """Integer affine fit through two probes, or None if non-integral."""
    y_lo = np.asarray(y_lo, dtype=np.int64)
    y_hi = np.asarray(y_hi, dtype=np.int64)
    db = b_hi - b_lo
    num = y_hi - y_lo
    if np.any(num % db):
        return None
    slope = num // db
    return y_lo - slope * b_lo, slope


def _eval_affine(model, b: int) -> np.ndarray:
    s0, s1 = model
    return s0 + s1 * b


class _PhaseModel:
    """Exact-or-bust affine model of one phase's trace over a scalar.

    Built from three structurally identical probe traces; synthesizes a
    ``TracedPhase`` for any scalar by rewriting the size columns (and the
    batch-varying out-shape dims). The middle probe must be reproduced
    bit-exactly by the two-point fit or the model rejects itself.
    """

    def __init__(self, probes: list[tuple[int, TracedPhase]]):
        import jax
        (b_lo, p_lo), (b_mid, p_mid), (b_hi, p_hi) = \
            sorted(probes, key=lambda x: x[0])
        self.template = p_lo
        # trusted scalar range: interpolation never extrapolates past the
        # outer probes (structure changes lurk at range boundaries, e.g.
        # dim-1 specialization at batch 1)
        self.b_lo, self.b_hi = b_lo, b_hi
        self.ok = False
        cols = [p.trace.columnar() for p in (p_lo, p_mid, p_hi)]
        if len({len(c) for c in cols}) != 1:
            return
        ref = cols[0]
        for c in cols[1:]:
            if not (np.array_equal(ref.kind, c.kind)
                    and np.array_equal(ref.block_id, c.block_id)
                    and np.array_equal(ref.t, c.t)
                    and np.array_equal(ref.phase, c.phase)
                    and np.array_equal(ref.block_kind, c.block_kind)
                    and np.array_equal(ref.op, c.op)
                    and np.array_equal(ref.scope, c.scope)
                    and np.array_equal(ref.shape, c.shape)
                    and np.array_equal(ref.space, c.space)
                    and ref.op_table == c.op_table
                    and ref.scope_table == c.scope_table):
                return
        lcs = [ColumnarBlocks.from_lifecycles(p.lifecycles)
               for p in (p_lo, p_mid, p_hi)]
        lref = lcs[0]
        for c in lcs[1:]:
            if not (len(lref) == len(c)
                    and np.array_equal(lref.block_id, c.block_id)
                    and np.array_equal(lref.alloc_t, c.alloc_t)
                    and np.array_equal(lref.free_t, c.free_t)
                    and np.array_equal(lref.block_kind, c.block_kind)
                    and np.array_equal(lref.shape, c.shape)
                    and np.array_equal(lref.space, c.space)
                    and np.array_equal(lref.shard_factor, c.shard_factor)):
                return

        def fit3(lo, mid, hi):
            m = _fit_affine(lo, hi, b_lo, b_hi)
            if m is None or not np.array_equal(
                    _eval_affine(m, b_mid), np.asarray(mid, np.int64)):
                return None
            return m

        def fit_shape_table(tables):
            """Affine model per shape-table entry (None entries must be
            None in every probe; dims fit like sizes)."""
            lo, mid, hi = tables
            if not (len(lo) == len(mid) == len(hi)):
                return None
            models: list = []
            for a, bb, c in zip(lo, mid, hi):
                if a is None or bb is None or c is None:
                    if not (a is None and bb is None and c is None):
                        return None
                    models.append(None)
                    continue
                if not (len(a) == len(bb) == len(c)):
                    return None
                m = fit3(a, bb, c)
                if m is None:
                    return None
                models.append(m)
            return models

        def fit_block_shapes(block_lists):
            """Affine per-block shape model over input/output BlockInfos."""
            lo, mid, hi = block_lists
            return fit_shape_table((tuple(b.shape for b in lo),
                                    tuple(b.shape for b in mid),
                                    tuple(b.shape for b in hi)))

        self.ev_sizes = fit3(cols[0].size, cols[1].size, cols[2].size)
        self.lc_sizes = fit3(lcs[0].size, lcs[1].size, lcs[2].size)
        self.in_sizes = fit3(*[[b.size for b in p.input_blocks]
                               for p in (p_lo, p_mid, p_hi)])
        self.out_sizes = fit3(*[[b.size for b in p.output_blocks]
                                for p in (p_lo, p_mid, p_hi)])
        self.ev_shapes = fit_shape_table([c.shape_table for c in cols])
        self.lc_shapes = fit_shape_table([c.shape_table for c in lcs])
        self.in_shapes = fit_block_shapes([p.input_blocks
                                           for p in (p_lo, p_mid, p_hi)])
        self.out_shapes = fit_block_shapes([p.output_blocks
                                            for p in (p_lo, p_mid, p_hi)])
        if None in (self.ev_sizes, self.lc_sizes, self.in_sizes,
                    self.out_sizes, self.ev_shapes, self.lc_shapes,
                    self.in_shapes, self.out_shapes):
            return
        if len({(b.bid, b.kind) for b in p_lo.input_blocks}
               ^ {(b.bid, b.kind) for b in p_hi.input_blocks}):
            return
        # out_shape: identical pytrees, per-leaf dims affine in b
        if len({jax.tree_util.tree_structure(p.out_shape)
                for p in (p_lo, p_mid, p_hi)}) != 1:
            return
        shapes = [[(tuple(l.shape), l.dtype)
                   for l in jax.tree_util.tree_leaves(p.out_shape)]
                  for p in (p_lo, p_mid, p_hi)]
        if len({len(s) for s in shapes}) != 1:
            return
        dims = []
        for i in range(len(shapes[0])):
            if len({len(s[i][0]) for s in shapes}) != 1 \
                    or len({s[i][1] for s in shapes}) != 1:
                return
            m = fit3(shapes[0][i][0], shapes[1][i][0], shapes[2][i][0])
            if m is None:
                return
            dims.append(m)
        self.out_dims = dims
        # constant out_shape -> the optimizer phases (keyed on the grads
        # avals) are provably shared across all points, so whole point
        # chunks can ship to pool workers with one upd/init payload
        self.out_constant = all(not s1.any() for _s0, s1 in dims)
        self.lc_template = lref
        self.ok = True

    def stripped(self) -> "_PhaseModel":
        """Picklable, lean copy for pool payloads: drops the template
        jaxpr and its object lifecycles (``synthesize`` rebuilds
        lifecycles from the columnar template, never from these)."""
        clone = _PhaseModel.__new__(_PhaseModel)
        clone.__dict__.update(self.__dict__)
        clone.template = dataclasses.replace(self.template,
                                             closed_jaxpr=None,
                                             lifecycles=())
        return clone

    def synthesize(self, b: int, expected_input_sizes: list[int]
                   ) -> TracedPhase | None:
        """Build the point's TracedPhase, or None when any exactness
        check fails (scalar outside the probed range, negative sizes,
        input-aval mismatch). The input sizes a real trace would record
        are fully determined by the point's avals, so the caller passes
        that ground truth in."""
        import jax
        if not (self.b_lo <= b <= self.b_hi):
            return None
        tp = self.template
        in_sizes = _eval_affine(self.in_sizes, b)
        if in_sizes.tolist() != expected_input_sizes:
            return None
        ev_sizes = _eval_affine(self.ev_sizes, b)
        lc_sizes = _eval_affine(self.lc_sizes, b)
        out_sizes = _eval_affine(self.out_sizes, b)
        if (ev_sizes < 0).any() or (lc_sizes < 0).any() \
                or (out_sizes < 0).any():
            return None

        def eval_shapes(models):
            out = []
            for m in models:
                if m is None:
                    out.append(None)
                    continue
                shape = tuple(int(d) for d in _eval_affine(m, b))
                if any(d < 0 for d in shape):
                    return None
                out.append(shape)
            return out

        ev_table = eval_shapes(self.ev_shapes)
        lc_table = eval_shapes(self.lc_shapes)
        in_shapes = eval_shapes(self.in_shapes)
        out_shapes = eval_shapes(self.out_shapes)
        if None in (ev_table, lc_table, in_shapes, out_shapes):
            return None
        new_leaves = []
        for leaf, dim_model in zip(
                jax.tree_util.tree_leaves(tp.out_shape), self.out_dims):
            shape = tuple(int(d) for d in _eval_affine(dim_model, b))
            if any(d < 0 for d in shape):
                return None
            new_leaves.append(jax.ShapeDtypeStruct(shape, leaf.dtype))
        out_shape = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(tp.out_shape), new_leaves)
        trace = Trace.from_columnar(
            dataclasses.replace(tp.trace.columnar().with_sizes(ev_sizes),
                                shape_table=ev_table),
            num_iterations=tp.trace.num_iterations,
            meta={k: v for k, v in tp.trace.meta.items()
                  if k != "_columns"})
        lifecycles = tuple(dataclasses.replace(
            self.lc_template.with_sizes(lc_sizes),
            shape_table=lc_table).to_lifecycles())
        return TracedPhase(
            trace=trace,
            lifecycles=lifecycles,
            input_blocks=tuple(
                BlockInfo(bi.bid, int(s), bi.kind, shp)
                for bi, s, shp in zip(tp.input_blocks, in_sizes,
                                      in_shapes)),
            output_blocks=tuple(
                BlockInfo(bi.bid, int(s), bi.kind, shp)
                for bi, s, shp in zip(tp.output_blocks, out_sizes,
                                      out_shapes)),
            out_shape=out_shape,
            closed_jaxpr=None,          # never shipped / re-analyzed
            arg_leaf_counts=tp.arg_leaf_counts,
        )


def _trace_sig(entry: TracedPhase) -> tuple:
    """Structural fingerprint of a phase trace — everything except the
    size columns and the shape *table* (whose dims vary with the sweep
    scalar; the interned shape index pattern must still match). Two
    traces with equal signatures differ only in sizes/shape dims, the
    precondition for the affine model."""
    c = entry.trace.columnar()
    return (len(c), c.kind.tobytes(), c.block_id.tobytes(), c.t.tobytes(),
            c.op.tobytes(), c.scope.tobytes(), c.phase.tobytes(),
            c.block_kind.tobytes(), c.shape.tobytes(), c.space.tobytes(),
            tuple(c.op_table), tuple(c.scope_table))


# -- scalar detection --------------------------------------------------------
def _leaf_sig(tree):
    import jax
    leaves = jax.tree_util.tree_leaves(tree)
    return (jax.tree_util.tree_structure(tree),
            tuple((tuple(getattr(l, "shape", ())),
                   str(getattr(l, "dtype", None))) for l in leaves))


def _aval_nbytes(leaf) -> int:
    """Byte size a real trace records for an input leaf — delegates to
    the tracer's own sizing so the interpolation cross-check can never
    drift from what tracing would have produced."""
    from .tracer import aval_bytes
    return aval_bytes(leaf)


def _sweep_scalars(points: list[SweepPoint]) -> list[int] | None:
    """Scalar parameter per point for a 1-D batch sweep, or None when
    the points do not form one (different treedefs / dtypes / ranks)."""
    sigs = [_leaf_sig(p.batch) for p in points]
    if len({s[0] for s in sigs}) != 1:
        return None
    ref = sigs[0][1]
    for _, leafsig in sigs:
        if len(leafsig) != len(ref):
            return None
        for (shape, dt), (rshape, rdt) in zip(leafsig, ref):
            if dt != rdt or len(shape) != len(rshape):
                return None
    varying = set()
    for _, leafsig in sigs:
        for li, (shape, _) in enumerate(leafsig):
            for di, d in enumerate(shape):
                if d != ref[li][0][di]:
                    varying.add((li, di))
    if not varying:
        return [0] * len(points)      # identical points: cache handles it
    li, di = sorted(varying)[0]
    return [int(s[1][li][0][di]) for s in sigs]


# -- process-pool worker -----------------------------------------------------
def _report_to_dict(rep: EstimateReport) -> dict:
    return {
        "peak_bytes": rep.peak_bytes,
        "peak_tensor_bytes": rep.peak_tensor_bytes,
        "persistent_bytes": rep.persistent_bytes,
        "oom": rep.oom,
        "breakdown": rep.breakdown,
        "num_events": rep.num_events,
        "sim_peak_reserved": rep.sim.peak_reserved,
        "sim_peak_allocated": rep.sim.peak_allocated,
        "sim_oom_at": rep.sim.oom_at,
        "sim_stats": rep.sim.stats,
        "sim_unbounded": getattr(rep, "sim_unbounded", False),
    }


def _pool_worker_chunk(payload: dict) -> list[dict | None]:
    """Stages 2-5 for a chunk of sweep points in a worker process: the
    point traces are synthesized in-worker from the shipped model (array
    arithmetic), then composed + orchestrated + replayed. No JAX tracing
    happens here; the shared upd/init payload is shipped once per chunk.
    A None result marks a point whose exactness check failed — the
    parent falls back to a real trace for it."""
    est = XMemEstimator(trace_cache=None, **payload["estimator"])
    model: _PhaseModel = payload["model"]
    upd, init = payload["upd"], payload["init"]
    out = []
    for pt in payload["points"]:
        fwd = model.synthesize(pt["b"], pt["expected_input_sizes"])
        if fwd is None:
            out.append(None)
            continue
        rep = est.estimate_from_phases(fwd, upd, init,
                                       capacity=pt["capacity"])
        out.append(_report_to_dict(rep))
    return out


def _pool_worker_jobs(payload: dict) -> list[dict]:
    """Full estimates (stage 1 included) for picklable jobs in a worker
    process — used for probe points (traced concurrently with the
    parent's own probe) and for whole non-interpolable sweeps."""
    est = XMemEstimator(**payload["estimator"])
    out = []
    for job in payload["jobs"]:
        rep = est.estimate_training(
            job["fwd_bwd_fn"], job["params"], job["batch"],
            update_fn=job["update_fn"], opt_init_fn=job["opt_init_fn"],
            capacity=job["capacity"])
        d = _report_to_dict(rep)
        if payload["want_phases"]:
            fwd, upd, init = est.trace_phases(
                job["fwd_bwd_fn"], job["params"], job["batch"],
                job["update_fn"], job["opt_init_fn"])
            if (upd is not None and upd.coupling is None
                    and upd.closed_jaxpr is not None):
                upd.coupling = _coupling_from_jaxpr(
                    upd.closed_jaxpr.jaxpr, upd.arg_leaf_counts[0],
                    upd.arg_leaf_counts[1])
            d["phases"] = tuple(
                SweepService._strip_for_pool(e)
                for e in (fwd, upd, init))
        out.append(d)
    return out


def _pool_warm(_i: int) -> bool:
    return True


def _pin_cpu() -> None:
    """Pool-worker initializer: workers only estimate, so on a chip host
    they keep off the accelerator the parent's jobs need."""
    import jax
    jax.config.update("jax_platforms", "cpu")


class _ColumnarLifecycles(Sequence):
    """Tuple-compatible lifecycles view backed by ``ColumnarBlocks`` —
    crosses process boundaries as arrays, materializes on first use."""

    def __init__(self, columns: ColumnarBlocks):
        self.columns = columns
        self._mat = None

    def _m(self):
        if self._mat is None:
            self._mat = self.columns.to_lifecycles()
        return self._mat

    def __len__(self):
        return len(self.columns)

    def __getitem__(self, i):
        return self._m()[i]

    def __iter__(self):
        return iter(self._m())

    def __reduce__(self):
        return (_ColumnarLifecycles, (self.columns,))


class SweepService:
    """Reusable sweep runner: shared trace cache, interpolation models
    and (optionally) a persistent process pool for replay fan-out."""

    def __init__(self, estimator: XMemEstimator | None = None,
                 processes: int = 0):
        self.estimator = estimator or XMemEstimator()
        if self.estimator.trace_cache is None:
            raise ValueError(
                "SweepService needs a fast-path estimator (fastpath=True): "
                "the sweep dedups work through its trace cache")
        self.processes = max(int(processes), 0)
        self._pool: ProcessPoolExecutor | None = None

    # -- pool lifecycle ------------------------------------------------------
    def _get_pool(self) -> ProcessPoolExecutor | None:
        if self.processes <= 0:
            return None
        if self._pool is None:
            import multiprocessing as mp
            # spawn: workers must not inherit JAX/XLA runtime threads
            self._pool = ProcessPoolExecutor(
                max_workers=self.processes,
                mp_context=mp.get_context("spawn"),
                initializer=_pin_cpu)
        return self._pool

    def warm_up(self) -> None:
        """Spin up pool workers (spawn + imports) ahead of timed work."""
        pool = self._get_pool()
        if pool is not None:
            list(pool.map(_pool_warm, range(self.processes)))

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown()
            self._pool = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- internals -----------------------------------------------------------
    def _fwd_key(self, p: SweepPoint):
        import jax
        est = self.estimator
        flat, kinds, _ = flatten_kinds(
            [(p.params, BlockKind.PARAM, "params"),
             (p.batch, BlockKind.INPUT, "batch")])
        treedefs = (jax.tree_util.tree_structure(p.params),
                    jax.tree_util.tree_structure(p.batch))
        return trace_key(p.fwd_bwd_fn, "fwd", flat, treedefs, kinds,
                         est.scan_unroll_cap, Phase.FORWARD_BACKWARD,
                         fusion_folding=est.orchestrator.policy.fusion_folding
                         ), flat

    def _estimate_full(self, p: SweepPoint) -> EstimateReport:
        return self.estimator.estimate_training(
            p.fwd_bwd_fn, p.params, p.batch, update_fn=p.update_fn,
            opt_init_fn=p.opt_init_fn, shard_factor_fn=p.shard_factor_fn,
            collective_specs=p.collective_specs, capacity=p.capacity)

    def _estimator_config(self) -> dict:
        est = self.estimator
        return dict(allocator_policy=est.allocator_policy,
                    orchestrator_policy=est.orchestrator.policy,
                    iterations=est.iterations,
                    scan_unroll_cap=est.scan_unroll_cap,
                    capacity=est.capacity,
                    engine=est.engine)

    @staticmethod
    def _strip_for_pool(entry: TracedPhase | None) -> TracedPhase | None:
        """Make a phase payload picklable and lean: drop the jaxpr (the
        coupling verdict must already be memoized on the entry) and ship
        lifecycles as columns — object pickling of hundreds of
        dataclasses is the slow part of the payload."""
        if entry is None:
            return None
        return dataclasses.replace(
            entry, closed_jaxpr=None,
            lifecycles=_ColumnarLifecycles(
                ColumnarBlocks.from_lifecycles(entry.lifecycles)))

    def _resolve_coupling(self, upd: TracedPhase | None) -> None:
        if (upd is not None and upd.coupling is None
                and upd.closed_jaxpr is not None):
            upd.coupling = _coupling_from_jaxpr(
                upd.closed_jaxpr.jaxpr, upd.arg_leaf_counts[0],
                upd.arg_leaf_counts[1])

    def _report_from_pool(self, d: dict) -> EstimateReport:
        sim = SimResult(
            peak_reserved=d["sim_peak_reserved"],
            peak_allocated=d["sim_peak_allocated"],
            oom=d["oom"], oom_at=d["sim_oom_at"],
            curve=[],                  # dropped for IPC leanness
            stats=d["sim_stats"], segments=[])
        rep = EstimateReport(
            peak_bytes=d["peak_bytes"],
            peak_tensor_bytes=d["peak_tensor_bytes"],
            persistent_bytes=d["persistent_bytes"],
            oom=d["oom"], sim=sim, breakdown=d["breakdown"],
            wall_time_s=0.0, num_events=d["num_events"])
        rep.sim_unbounded = d["sim_unbounded"]
        return rep

    @staticmethod
    def _picklable_jobs(gpoints: list[SweepPoint]) -> bool:
        """Can these jobs' functions/avals cross a process boundary?
        (Module-level step fns can; closures typically cannot.)"""
        import pickle
        try:
            p = gpoints[0]
            pickle.dumps((p.fwd_bwd_fn, p.update_fn, p.opt_init_fn,
                          p.params, p.batch))
            return True
        except Exception:   # noqa: BLE001 — any pickling failure
            return False

    def _job_payload(self, p: SweepPoint) -> dict:
        return {"fwd_bwd_fn": p.fwd_bwd_fn, "params": p.params,
                "batch": p.batch, "update_fn": p.update_fn,
                "opt_init_fn": p.opt_init_fn, "capacity": p.capacity}

    def _run_group(self, points, idxs, scalars, reports, stats) -> None:
        """Estimate one interpolation group (same fns / params)."""
        est = self.estimator
        pool = self._get_pool()
        gpoints = [points[i] for i in idxs]
        distinct = sorted(set(scalars)) if scalars is not None else []
        plain = all(p.shard_factor_fn is None and not p.collective_specs
                    for p in gpoints)
        picklable = (pool is not None and plain
                     and self._picklable_jobs(gpoints))

        if scalars is None or len(distinct) < 4:
            # no 1-D structure worth modeling: full estimates, fanned out
            # over the pool when the jobs can travel
            if picklable and len(idxs) > 1:
                self._pool_full_jobs(points, idxs, reports, stats)
            else:
                for i in idxs:
                    reports[i] = self._estimate_full(points[i])
                    stats["traced"] += 1
            return

        # --- probes: min / median / max scalars, traced for real -------
        probe_vals = [distinct[0], distinct[len(distinct) // 2],
                      distinct[-1]]
        probe_idx = {}
        for i, b in zip(idxs, scalars):
            if b in probe_vals and b not in probe_idx:
                probe_idx[b] = i
        probe_entries: list[tuple[int, TracedPhase]] = []
        upd_entry = init_entry = None

        def note_probe(b, fwd, upd, init):
            nonlocal upd_entry, init_entry
            if fwd is not None:
                probe_entries.append((b, fwd))
                upd_entry, init_entry = upd, init

        if picklable and len(probe_vals) > 1:
            # parent traces the min probe while workers trace the rest
            futures = [
                (b, probe_idx[b], pool.submit(_pool_worker_jobs, {
                    "estimator": self._estimator_config(),
                    "jobs": [self._job_payload(points[probe_idx[b]])],
                    "want_phases": True}))
                for b in probe_vals[1:]]
            b0 = probe_vals[0]
            reports[probe_idx[b0]] = self._estimate_full(
                points[probe_idx[b0]])
            stats["traced"] += 1
            key, _ = self._fwd_key(points[probe_idx[b0]])
            entry = est.trace_cache.get(points[probe_idx[b0]].fwd_bwd_fn,
                                        key)
            note_probe(b0, entry, *est.trace_phases(
                points[probe_idx[b0]].fwd_bwd_fn,
                points[probe_idx[b0]].params, points[probe_idx[b0]].batch,
                points[probe_idx[b0]].update_fn,
                points[probe_idx[b0]].opt_init_fn, fwd=entry)[1:])
            for b, i, fut in futures:
                d = fut.result()[0]
                reports[i] = self._report_from_pool(d)
                stats["traced"] += 1
                fwd, upd, init = d.pop("phases")
                note_probe(b, fwd, upd, init)
                # seed the parent cache so duplicate scalars /
                # fallbacks do not re-trace
                key, _ = self._fwd_key(points[i])
                if fwd is not None and key is not None:
                    est.trace_cache.put(points[i].fwd_bwd_fn, key, fwd)
        else:
            for b in probe_vals:
                i = probe_idx[b]
                reports[i] = self._estimate_full(points[i])
                stats["traced"] += 1
                key, _ = self._fwd_key(points[i])
                entry = est.trace_cache.get(points[i].fwd_bwd_fn, key)
                note_probe(b, entry, *est.trace_phases(
                    points[i].fwd_bwd_fn, points[i].params,
                    points[i].batch, points[i].update_fn,
                    points[i].opt_init_fn, fwd=entry)[1:])

        # build the model from a structurally consistent probe trio; if
        # one probe diverged structurally (e.g. batch-1 specialization),
        # trace one repair probe between the two consistent ones and
        # trust only that narrowed range
        model = None
        if len(probe_entries) == 3:
            sigs = [(b, e, _trace_sig(e)) for b, e in probe_entries]
            groups: dict = {}
            for b, e, s in sigs:
                groups.setdefault(s, []).append((b, e))
            consistent = max(groups.values(), key=len)
            if len(consistent) == 2:
                bl = min(b for b, _ in consistent)
                bh = max(b for b, _ in consistent)
                scalar_index = {}
                for i, b in zip(idxs, scalars):
                    scalar_index.setdefault(b, i)
                spare = [b for b in distinct
                         if bl < b < bh and b not in probe_idx]
                if spare:
                    bm = spare[len(spare) // 2]
                    i = scalar_index[bm]
                    reports[i] = self._estimate_full(points[i])
                    stats["traced"] += 1
                    probe_idx[bm] = i
                    key, _ = self._fwd_key(points[i])
                    e = est.trace_cache.get(points[i].fwd_bwd_fn, key)
                    if e is not None and _trace_sig(e) == \
                            _trace_sig(consistent[0][1]):
                        consistent.append((bm, e))
            if len(consistent) >= 3:
                model = _PhaseModel(sorted(consistent)[:3])
                if not model.ok:
                    model = None
        self._resolve_coupling(upd_entry)

        # --- remaining points ------------------------------------------
        rest = [(i, b) for i, b in zip(idxs, scalars) if i not in reports]
        chunk_points: list[tuple[int, dict]] = []
        full_left: list[int] = []
        for i, b in rest:
            p = points[i]
            if b in probe_idx:          # duplicate scalar: cache-hot
                reports[i] = self._estimate_full(p)
                stats["traced"] += 1
                continue
            if model is not None and not (model.b_lo <= b <= model.b_hi):
                full_left.append(i)     # outside the trusted probe range
                continue
            _key, flat = self._fwd_key(p)
            expected = [_aval_nbytes(leaf) for leaf in flat]
            if (picklable and model is not None and model.out_constant
                    and plain):
                chunk_points.append((i, {
                    "b": b, "expected_input_sizes": expected,
                    "capacity": p.capacity}))
                continue
            fwd = (model.synthesize(b, expected)
                   if model is not None else None)
            if fwd is None:
                full_left.append(i)
                continue
            stats["interpolated"] += 1
            fwd, upd, init = est.trace_phases(
                p.fwd_bwd_fn, p.params, p.batch, p.update_fn,
                p.opt_init_fn, fwd=fwd)
            self._resolve_coupling(upd)
            reports[i] = est.estimate_from_phases(
                fwd, upd, init, shard_factor_fn=p.shard_factor_fn,
                collective_specs=p.collective_specs, capacity=p.capacity)

        if chunk_points:
            # round-robin chunks: one payload per worker carries the
            # model and the shared optimizer phases exactly once; the
            # parent keeps one share and works it while the pool drains
            shared = {
                "estimator": self._estimator_config(),
                "model": model.stripped(),
                "upd": self._strip_for_pool(upd_entry),
                "init": self._strip_for_pool(init_entry),
            }
            n_chunks = max(min(self.processes + 1, len(chunk_points)), 1)
            chunks = [chunk_points[k::n_chunks] for k in range(n_chunks)]
            own, worker_chunks = chunks[-1], chunks[:-1]
            futures = []
            for chunk in worker_chunks:
                payload = dict(shared)
                payload["points"] = [meta for _i, meta in chunk]
                futures.append((chunk, pool.submit(_pool_worker_chunk,
                                                   payload)))
            for i, meta in own:
                fwd = model.synthesize(meta["b"],
                                       meta["expected_input_sizes"])
                if fwd is None:
                    full_left.append(i)
                    continue
                reports[i] = est.estimate_from_phases(
                    fwd, upd_entry, init_entry, capacity=meta["capacity"])
                stats["interpolated"] += 1
            for chunk, fut in futures:
                for (i, _meta), d in zip(chunk, fut.result()):
                    if d is None:   # in-worker exactness check failed
                        full_left.append(i)
                    else:
                        reports[i] = self._report_from_pool(d)
                        stats["pooled"] += 1
                        stats["interpolated"] += 1

        if full_left:
            stats["fallback"] += len(full_left)
            if picklable and len(full_left) > 1:
                self._pool_full_jobs(points, full_left, reports, stats)
            else:
                for i in full_left:
                    reports[i] = self._estimate_full(points[i])
                    stats["traced"] += 1

    def _pool_full_jobs(self, points, idxs, reports, stats) -> None:
        """Fan whole estimates out over the pool (picklable jobs only)."""
        pool = self._get_pool()
        n_chunks = max(min(self.processes, len(idxs)), 1)
        chunks = [idxs[k::n_chunks] for k in range(n_chunks)]
        futures = []
        for chunk in chunks:
            payload = {"estimator": self._estimator_config(),
                       "jobs": [self._job_payload(points[i])
                                for i in chunk],
                       "want_phases": False}
            futures.append((chunk, pool.submit(_pool_worker_jobs,
                                               payload)))
        for chunk, fut in futures:
            for i, d in zip(chunk, fut.result()):
                reports[i] = self._report_from_pool(d)
                stats["traced"] += 1
                stats["pooled"] += 1

    # -- public API ----------------------------------------------------------
    def estimate_mesh_sweep(self, fwd_bwd_fn, params, batch,
                            topologies: Sequence[MeshTopology], *,
                            update_fn=None, opt_init_fn=None, cfg=None,
                            shard_factors: str = "spec",
                            collectives: bool = True,
                            capacity: int | None = None) -> MeshSweepResult:
        """Per-device estimates for a grid of mesh topologies from ONE
        cached trace (ROADMAP: multi-device topologies as first-class
        estimation targets).

        Stage 1 (jaxpr tracing) is topology-independent: the phases are
        traced once (or served from the trace cache) and stages 2-5 —
        compose, spec-driven shard factors, per-axis collective
        injection, vectorized replay — re-run per topology. With
        ``shard_factors="spec"`` each topology's factors come from the
        PartitionSpecs the sharding engine would place at that mesh,
        divisibility fallbacks included; ``collectives=True`` injects
        the per-axis staging buffers (``mesh_collective_specs``).
        """
        from ..distributed.sharding import (mesh_collective_specs,
                                            shard_factor_fn)
        t0 = time.perf_counter()
        est = self.estimator
        cache = est.trace_cache
        h0, m0 = cache.hits, cache.misses
        fwd, upd, init = est.trace_phases(fwd_bwd_fn, params, batch,
                                          update_fn, opt_init_fn)
        self._resolve_coupling(upd)
        t_trace = time.perf_counter() - t0
        opt_state = init.out_shape if init is not None else None
        reports = []
        for topo in topologies:
            mesh = topo.axis_sizes
            pol = topo.sharding_policy()
            factor = shard_factor_fn(cfg, mesh, pol, mode=shard_factors,
                                     params=params, opt_state=opt_state,
                                     batch=batch)
            specs = (mesh_collective_specs(mesh, pol)
                     if collectives else ())
            reports.append(est.estimate_from_phases(
                fwd, upd, init, shard_factor_fn=factor,
                collective_specs=specs, capacity=capacity))
        stats = {
            "topologies": len(reports),
            "trace_s": t_trace,
            "trace_cache": {"hits": cache.hits - h0,
                            "misses": cache.misses - m0},
            "wall_s": time.perf_counter() - t0,
            "shard_factors": shard_factors,
        }
        return MeshSweepResult(list(topologies), reports, stats)

    def estimate_serving_sweep(self, decode_fn, params, cache, batch, *,
                               stream, knob_grid: Sequence,
                               kv_bytes_per_token: int,
                               resident_bytes_per_request: int = 0,
                               capacity: int | None = None
                               ) -> ServingSweepResult:
        """Serving estimates for a grid of :class:`ServingKnobs` from at
        most ONE fresh decode trace (the serving analogue of
        :meth:`estimate_mesh_sweep`).

        Tracing is knob-independent — page size, concurrency, and KV
        dtype only change the CPU-side request-stream lowering and the
        allocator replay, so the whole grid shares one cached trace.
        The fresh-trace count is reported in ``stats["trace_cache"]``
        and bench-asserted (``SERVING_TRACE_BUDGET``)."""
        t0 = time.perf_counter()
        est = self.estimator
        tcache = est.trace_cache
        h0, m0 = tcache.hits, tcache.misses
        estimates = [
            est.estimate_request_stream(
                decode_fn, params, cache, batch, stream=stream,
                knobs=k, kv_bytes_per_token=kv_bytes_per_token,
                resident_bytes_per_request=resident_bytes_per_request,
                capacity=capacity)
            for k in knob_grid]
        stats = {
            "knobs": len(estimates),
            "trace_cache": {"hits": tcache.hits - h0,
                            "misses": tcache.misses - m0},
            "wall_s": time.perf_counter() - t0,
        }
        return ServingSweepResult(list(knob_grid), estimates, stats)

    def estimate_many(self, points: Sequence[SweepPoint],
                      interpolate: bool = True) -> SweepResult:
        t0 = time.perf_counter()
        points = list(points)
        reports: dict[int, EstimateReport] = {}
        stats = {"points": len(points), "traced": 0, "interpolated": 0,
                 "fallback": 0, "pooled": 0,
                 "pool_workers": self.processes}

        # group points that can share an interpolation model: same fns,
        # same params signature
        groups: dict[tuple, list[int]] = {}
        for i, p in enumerate(points):
            key = (id(p.fwd_bwd_fn), id(p.update_fn), id(p.opt_init_fn),
                   _leaf_sig(p.params))
            groups.setdefault(key, []).append(i)

        for idxs in groups.values():
            gpoints = [points[i] for i in idxs]
            scalars = _sweep_scalars(gpoints) if interpolate else None
            self._run_group(points, idxs, scalars, reports, stats)

        stats["wall_s"] = time.perf_counter() - t0
        stats["cache"] = self.estimator.trace_cache.stats()
        return SweepResult([reports[i] for i in range(len(points))], stats)


def estimate_many(points: Sequence[SweepPoint],
                  estimator: XMemEstimator | None = None,
                  processes: int = 0,
                  interpolate: bool = True) -> SweepResult:
    """One-shot sweep: see :class:`SweepService`. Creating a service is
    preferable when sweeping repeatedly (warm pool + cache)."""
    svc = SweepService(estimator, processes=processes)
    try:
        return svc.estimate_many(points, interpolate=interpolate)
    finally:
        svc.close()
