"""xMem estimator — the public API tying the pipeline together.

``XMemEstimator.estimate_training`` reproduces the paper's workflow:

1. trace the job's phases on CPU (jaxpr interpretation — zero accelerator
   use, milliseconds even for trillion-parameter configs);
2. reconstruct + classify lifecycles (Analyzer);
3. compose N iterations on one timeline — optimizer state materializes at
   the first update and persists (why the paper analyzes >= 2 iterations;
   we default to 3 like the paper);
4. orchestrate lifecycles (persistence, grad_release, donation, fusion
   folding, collective injection, sharding);
5. replay through the two-level allocator simulation -> peak estimate,
   usage curve, OOM verdict.

The estimator is a *first-class framework feature*: ``launch/train.py``
gates job admission on it, and the sharding engine feeds it per-tensor
shard factors for per-device estimates (the paper's §6.2 extension).

Fast path (ISSUE 1, default ``fastpath=True``):

* per-phase traces are cached (``core/cache.py``) so repeated estimates
  with an unchanged job structure skip ``make_jaxpr`` + interpretation;
* each phase is traced exactly once — abstract output shapes come from
  the trace itself (``make_jaxpr(..., return_shape=True)``) instead of
  separate ``eval_shape`` passes, and the gradient-coupling taint
  analysis reuses the already-traced update jaxpr;
* iterations 2..N-1 are composed as a periodic template
  (``PeriodicBlocks``) instead of per-iteration lifecycle copies —
  composition is O(blocks), independent of N;
* the simulator replays the template with steady-state detection and
  extrapolates once the allocator fingerprint stabilizes (paper §3.1).

``fastpath=False`` preserves the original seed pipeline verbatim; the
equivalence tests (tests/test_fastpath.py) assert both paths produce
identical estimates.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Sequence

import jax

from ..obs import spans as obs_spans
from .allocator import AllocatorPolicy, CUDA_CACHING
from .analyzer import classify_blocks, phase_peaks
from .cache import (BlockInfo, GLOBAL_TRACE_CACHE, TraceCache, TracedPhase,
                    trace_key)
from .events import (KIND_CODE, BlockKind, BlockLifecycle, PeriodicBlocks,
                     Phase, liveness_curve, peak_live_bytes,
                     periodic_breakdown_peaks, periodic_breakdown_peaks_fast,
                     reduced_for_breakdown)
from .orchestrator import (CollectiveSpec, MemoryOrchestrator, OffloadPlan,
                           OrchestratorPolicy)
from .simulator import MemorySimulator, SimResult, split_blocks_by_space
from .tracer import trace_fn_with_shape


_EMPTY_TAINT: frozenset = frozenset()


def _taint_region(jaxpr, in_taints, state: dict,
                  const_taints=None) -> list:
    """Propagate per-gradient taint sets through one jaxpr region,
    recursing into call primitives (pjit / remat / custom_* / scan /
    while / cond). A union of more than one gradient index at a *plain*
    primitive marks the update as coupled; unioning at a call-primitive
    boundary does NOT — a ``pjit``-wrapped per-leaf update keeps its
    leaves separate inside the sub-jaxpr, which is where the verdict is
    decided (mis-reporting it as coupled forces all-grads-coexist and
    inflates the estimate). Returns the outvar taints."""
    from jax.extend import core as jcore
    taint: dict = {}
    for v, tt in zip(jaxpr.constvars, const_taints or ()):
        if tt:
            taint[v] = tt
    for v, tt in zip(jaxpr.invars, in_taints):
        if tt:
            taint[v] = tt

    def read(v):
        if isinstance(v, jcore.Literal):
            return _EMPTY_TAINT
        return taint.get(v, _EMPTY_TAINT)

    def closed_parts(j):
        if isinstance(j, jcore.ClosedJaxpr):
            return j.jaxpr, len(j.consts)
        return j, 0

    def run_fixpoint(body, consts_t, carry_t, xs_t, n_carry):
        """Scan/while bodies feed carry outputs back into carry inputs;
        iterate until the carry taints stop growing. Taint sets only
        grow and each pass moves taint at least one carry slot further,
        so the fixpoint arrives within n_carry+1 passes (a chain rotated
        through k carries needs k passes — two would miss couplings
        behind longer chains and underestimate)."""
        inner, n_inner_consts = closed_parts(body)
        carry_t = list(carry_t)
        outs = None
        for _ in range(n_carry + 1):
            outs = _taint_region(
                inner, list(consts_t) + carry_t + list(xs_t), state,
                const_taints=[_EMPTY_TAINT] * n_inner_consts)
            new_carry = [a | b for a, b in zip(carry_t, outs[:n_carry])]
            if new_carry == carry_t:
                break
            carry_t = new_carry
        return carry_t, outs[n_carry:] if outs else []

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        ins = [read(v) for v in eqn.invars]
        if name == "scan":
            p = eqn.params
            nc, ncar = p["num_consts"], p["num_carry"]
            carry_t, ys_t = run_fixpoint(
                p["jaxpr"], ins[:nc], ins[nc:nc + ncar],
                ins[nc + ncar:], ncar)
            out_taints = list(carry_t) + list(ys_t)
        elif name == "while":
            p = eqn.params
            cn, bn = p["cond_nconsts"], p["body_nconsts"]
            carry_t, _ = run_fixpoint(
                p["body_jaxpr"], ins[cn:cn + bn], ins[cn + bn:], (),
                len(ins) - cn - bn)
            # the loop condition runs too: a grad-norm convergence test
            # (`while norm(g) > eps`) unions gradients inside cond_jaxpr
            # — one pass over the converged carry taints catches it
            # (state flags only grow, cond feeds nothing back)
            cond_inner, cond_nc = closed_parts(p["cond_jaxpr"])
            _taint_region(cond_inner, list(ins[:cn]) + list(carry_t),
                          state, const_taints=[_EMPTY_TAINT] * cond_nc)
            out_taints = list(carry_t)
        elif name == "cond":
            branch_ins = ins[1:]
            out_taints = None
            for br in eqn.params["branches"]:
                inner, n_inner_consts = closed_parts(br)
                outs = _taint_region(
                    inner, branch_ins, state,
                    const_taints=[_EMPTY_TAINT] * n_inner_consts)
                out_taints = outs if out_taints is None else [
                    a | b for a, b in zip(out_taints, outs)]
            out_taints = out_taints or []
        else:
            sub = None
            for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                j = eqn.params.get(key)
                if isinstance(j, (jcore.Jaxpr, jcore.ClosedJaxpr)):
                    sub = j
                    break
            if sub is not None:
                inner, n_inner_consts = closed_parts(sub)
                out_taints = _taint_region(
                    inner, ins, state,
                    const_taints=[_EMPTY_TAINT] * n_inner_consts)
            else:
                union: frozenset = _EMPTY_TAINT
                for tt in ins:
                    if tt:
                        union = union | tt
                if len(union) > 1:
                    state["coupling"] = "coupled"
                if union and name == "convert_element_type":
                    iv = eqn.invars[0]
                    ov = eqn.outvars[0]
                    try:
                        if ov.aval.dtype.itemsize > iv.aval.dtype.itemsize:
                            state["upcasts"] = True  # f32 grad copies
                    except AttributeError:
                        pass
                out_taints = [union] * len(eqn.outvars)
        for ov, tt in zip(eqn.outvars, out_taints):
            if tt:
                taint[ov] = tt
    return [read(v) for v in jaxpr.outvars]


def _coupling_from_jaxpr(jaxpr, n_params: int, n_grads: int) -> dict:
    """Taint analysis over a (flat) update jaxpr — see
    ``update_grad_coupling`` for semantics. Recurses into nested call
    primitives: a jitted (pjit-wrapped) tree-mapped per-leaf optimizer
    stays "per_leaf" instead of being mis-unioned at the call boundary.
    """
    in_taints = []
    for i, _v in enumerate(jaxpr.invars):
        if n_params <= i < n_params + n_grads:
            in_taints.append(frozenset({i - n_params}))
        else:
            in_taints.append(_EMPTY_TAINT)
    state = {"coupling": "per_leaf", "upcasts": False}
    _taint_region(jaxpr, in_taints, state)
    return state


def update_grad_coupling(update_fn: Callable, params, grads,
                         opt_state) -> dict:
    """Taint analysis: does the optimizer update *couple* gradients?

    Per-leaf updates (SGD/Adam/... via tree.map) let XLA fuse each leaf's
    update into the backward pass, so gradients die eagerly. Cross-leaf
    coupling (global-norm clipping, Adafactor's global RMS) forces all
    gradients to coexist until the update. Also detects whether gradients
    are upcast to a wider dtype inside the update (f32 working copies —
    they add transient bytes during the optimizer phase).

    Returns {"coupling": "per_leaf"|"coupled", "upcasts": bool}.
    """
    args = (params, grads, opt_state) if opt_state is not None \
        else (params, grads)
    fn = update_fn if opt_state is not None \
        else (lambda p, g: update_fn(p, g, None))
    closed = jax.make_jaxpr(fn)(*args)
    n_params = len(jax.tree_util.tree_leaves(params))
    n_grads = len(jax.tree_util.tree_leaves(grads))
    return _coupling_from_jaxpr(closed.jaxpr, n_params, n_grads)


def fused_counts(*phases: TracedPhase | None) -> dict:
    """``fused_blocks``: the values the tracer kept inside XLA fusions
    (never allocated in HBM) over the given phase traces, and
    ``fused_bytes``, their bytes."""
    code = KIND_CODE[BlockKind.FUSED]
    n = nbytes = 0
    for ph in phases:
        if ph is None:
            continue
        cols = ph.trace.columnar()
        m = (cols.kind == 1) & (cols.block_kind == code)
        n += int(m.sum())
        nbytes += int(cols.size[m].sum())
    return {"fused_blocks": n, "fused_bytes": nbytes}


def collective_counts(blocks) -> dict:
    """``collective_blocks``: the collective staging buffers
    (``MemoryOrchestrator.inject_collectives``) live at the
    composition's peak of live bytes; ``collective_bytes``, their
    per-device bytes. ``blocks`` is a flat lifecycle list or a
    ``PeriodicBlocks`` composition."""
    out = {"collective_blocks": 0, "collective_bytes": 0}
    if isinstance(blocks, PeriodicBlocks):
        if not any(b.block_kind is BlockKind.COLLECTIVE
                   for b in blocks.iter_groups()):
            return out
        blocks = blocks.materialize()
    staged = [b for b in blocks if b.block_kind is BlockKind.COLLECTIVE]
    if not staged:
        return out
    # the first time live bytes reach their maximum
    t = max(liveness_curve(blocks), key=lambda tv: tv[1])[0]
    live = [b for b in staged
            if b.alloc_t <= t and (b.free_t is None or b.free_t > t)]
    return {"collective_blocks": len(live),
            "collective_bytes": sum(b.sharded_size for b in live)}


def flatten_kinds(args_with_kinds: Sequence[tuple]) -> tuple[list, list[BlockKind], list[str]]:
    """Flatten (pytree, kind, name) triples into tracer-aligned lists."""
    flat_args, kinds, scopes = [], [], []
    for tree, kind, name in args_with_kinds:
        leaves, _ = jax.tree_util.tree_flatten(tree)
        flat_args.extend(leaves)
        kinds.extend([kind] * len(leaves))
        scopes.extend([f"{name}[{i}]" for i in range(len(leaves))])
    return flat_args, kinds, scopes


@dataclasses.dataclass
class EstimateReport:
    peak_bytes: int               # reserved segments — THE estimate
    peak_tensor_bytes: int        # live-tensor peak (naive lower bound)
    persistent_bytes: int         # params + opt state + consts
    oom: bool
    sim: SimResult
    breakdown: dict               # per-kind / per-phase summary
    wall_time_s: float
    num_events: int
    cache_stats: dict = dataclasses.field(default_factory=dict)

    def fits(self, capacity: int) -> bool:
        return self.peak_bytes <= capacity


class XMemEstimator:
    """Peak-memory estimator. Target-specific presets:

    * ``XMemEstimator.for_tpu()``   — XLA/TPU target: static buffer
      assignment compacts memory, so the arena policy (reserved ≈ rounded
      live) plus fusion folding and donation model the runtime; this is
      the mode the framework's admission gate uses.
    * ``XMemEstimator.for_torch_gpu()`` — paper-faithful mode: PyTorch
      CUDACachingAllocator simulation, eager semantics (no fusion
      folding, no donation, grads till zero_grad). Used by the
      reproduction benchmarks.
    """

    def __init__(self,
                 allocator_policy: AllocatorPolicy = CUDA_CACHING,
                 orchestrator_policy: OrchestratorPolicy | None = None,
                 iterations: int = 3,
                 scan_unroll_cap: int = 3,
                 capacity: int = 1 << 62,
                 fastpath: bool = True,
                 trace_cache: TraceCache | None = None,
                 engine: str = "auto",
                 checkpoint: Callable[[str], None] | None = None):
        self.allocator_policy = allocator_policy
        self.orchestrator = MemoryOrchestrator(
            orchestrator_policy or OrchestratorPolicy())
        self.iterations = iterations
        self.scan_unroll_cap = scan_unroll_cap
        self.capacity = capacity
        self.fastpath = fastpath
        # replay engine: "auto" -> the columnar/vectorized engine on the
        # fast path, the per-event object interpreter on the reference
        # path (fastpath=False always replays through the object engine —
        # it IS the reference).
        if engine not in ("auto", "object", "columnar"):
            raise ValueError(f"unknown replay engine {engine!r}")
        if not fastpath:
            engine = "object"
        elif engine == "auto":
            engine = "columnar"
        self.engine = engine
        # fastpath estimators share the process-global cache by default so
        # per-decision estimator instances still hit warm traces; the
        # reference path never caches (seed semantics), including when a
        # cache is passed explicitly.
        # NOTE: explicit None check — an empty TraceCache is falsy
        # (__len__), so `trace_cache or GLOBAL_TRACE_CACHE` would
        # silently discard a fresh user-supplied cache
        self.trace_cache = ((GLOBAL_TRACE_CACHE if trace_cache is None
                             else trace_cache) if fastpath else None)
        # optional stage-boundary hook ("tracer" before a real trace,
        # "replay" before the allocator replay). The admission service
        # routes fault injection through it (ISSUE 6); None costs one
        # attribute test per stage and changes nothing.
        self.checkpoint = checkpoint

    @classmethod
    def for_tpu(cls, **kw) -> "XMemEstimator":
        from .allocator import TPU_ARENA
        kw.setdefault("allocator_policy", TPU_ARENA)
        kw.setdefault("orchestrator_policy", OrchestratorPolicy(
            grad_release="auto", donate_params=True, donate_opt_state=True,
            fusion_folding=True))
        return cls(**kw)

    def calibrate(self, samples: Sequence[tuple],
                  quantile: float = 0.9) -> float:
        """Fit the backend transient-scale constant from (job_kwargs,
        truth_bytes) pairs — the explicit version of the paper's Fig-6
        calibration loop. Model-independent: one constant per backend.

        ``quantile`` targets one-sided error: a scheduler pays far more
        for an underestimate (round-2 OOM, the PEF/MCP penalty of
        Eq. 5-7) than for slight headroom, so the default skews high —
        the same asymmetry the paper's allocator rounding induces.

        Each sample is ((fwd_bwd, params, batch, update_fn, opt_init_fn),
        truth). Returns the fitted scale (also applied to self)."""
        import numpy as _np
        ratios = []
        for (fwd_bwd, params, batch, update_fn, opt_init_fn), truth \
                in samples:
            rep = self.estimate_training(fwd_bwd, params, batch,
                                         update_fn=update_fn,
                                         opt_init_fn=opt_init_fn)
            t_est = rep.peak_tensor_bytes - rep.persistent_bytes
            t_true = truth - rep.persistent_bytes
            if t_est > 0 and t_true > 0:
                ratios.append(t_true / t_est)
        scale = float(_np.quantile(ratios, quantile)) if ratios else 1.0
        self.orchestrator.policy = dataclasses.replace(
            self.orchestrator.policy, transient_scale=scale)
        return scale

    @classmethod
    def for_torch_gpu(cls, grad_release: str = "at_update",
                      **kw) -> "XMemEstimator":
        kw.setdefault("allocator_policy", CUDA_CACHING)
        kw.setdefault("orchestrator_policy", OrchestratorPolicy(
            grad_release=grad_release, donate_params=False,
            donate_opt_state=False, fusion_folding=False))
        return cls(**kw)

    # -- phase tracing (fast path: cached TracedPhase entries) -----------------
    def _trace_phase(self, fn, args_with_kinds, phase,
                     out_kind_fn: Callable | None = None,
                     tag: str = "") -> TracedPhase:
        flat, kinds, scopes = flatten_kinds(args_with_kinds)
        treedefs = tuple(jax.tree_util.tree_structure(t)
                         for t, _, _ in args_with_kinds)
        cache = self.trace_cache
        fold = self.orchestrator.policy.fusion_folding
        key = None
        if cache is not None:
            key = trace_key(fn, tag, flat, treedefs, kinds,
                            self.scan_unroll_cap, phase,
                            fusion_folding=fold)
            hit = cache.get(fn, key)
            if hit is not None:
                return hit
        if self.checkpoint is not None:
            self.checkpoint("tracer")

        def flat_fn(*leaves):
            idx, rebuilt = 0, []
            for tree, _, _ in args_with_kinds:
                leaves_i, treedef = jax.tree_util.tree_flatten(tree)
                n = len(leaves_i)
                rebuilt.append(jax.tree_util.tree_unflatten(
                    treedef, leaves[idx:idx + n]))
                idx += n
            return fn(*rebuilt)

        with obs_spans.span("estimator.trace", phase=str(phase),
                            tag=tag):
            trace, tr, out_shape, closed = trace_fn_with_shape(
                flat_fn, *flat, arg_kinds=kinds, arg_scopes=scopes,
                scan_unroll_cap=self.scan_unroll_cap, phase=phase,
                fusion_folding=fold)
        out_kinds = out_kind_fn(out_shape) if out_kind_fn is not None else None
        kind_by_bid = {}
        if out_kinds is not None:
            for b, k in zip(tr.output_blocks, out_kinds):
                b.kind = k
                kind_by_bid[b.bid] = k
        if kind_by_bid:
            # push reassigned kinds back into the recorded alloc events
            # (only outputs change post-trace; inputs are kinded at birth).
            # The trace is columnar-backed: rewrite the kind column in one
            # searchsorted sweep, plus any already-materialized event
            # objects so both views agree.
            import numpy as np
            from .events import KIND_CODE, LazyEvents
            cols = trace.columnar()
            bids = np.fromiter(kind_by_bid, np.int64, len(kind_by_bid))
            codes = np.fromiter((KIND_CODE[k] for k in kind_by_bid.values()),
                                np.uint8, len(kind_by_bid))
            order = np.argsort(bids)
            bids, codes = bids[order], codes[order]
            pos = np.searchsorted(bids, cols.block_id)
            pos_c = np.minimum(pos, len(bids) - 1)
            hit = bids[pos_c] == cols.block_id
            cols.block_kind[hit] = codes[pos_c[hit]]
            ev = trace.events
            materialized = (ev._mat if isinstance(ev, LazyEvents) else ev)
            if materialized is not None:
                for e in materialized:
                    k = kind_by_bid.get(e.block_id)
                    if k is not None:
                        e.block_kind = k
        entry = TracedPhase(
            trace=trace,
            lifecycles=tuple(tr.lifecycles()),
            input_blocks=tuple(BlockInfo(b.bid, b.size, b.kind, b.shape)
                               for b in tr.input_blocks),
            output_blocks=tuple(BlockInfo(b.bid, b.size, b.kind, b.shape)
                                for b in tr.output_blocks),
            out_shape=out_shape,
            closed_jaxpr=closed,
            arg_leaf_counts=tuple(
                len(jax.tree_util.tree_leaves(t))
                for t, _, _ in args_with_kinds),
        )
        if cache is not None:
            cache.put(fn, key, entry)
        return entry

    @staticmethod
    def _expand_out_kinds(example_out, kind_map: Callable) -> list[BlockKind]:
        leaves = jax.tree_util.tree_leaves(example_out)
        return [kind_map(i, len(leaves)) for i in range(len(leaves))]

    # -- periodic composition (fast path) --------------------------------------
    def _compose_periodic(self, fwd: TracedPhase, upd: TracedPhase | None,
                          init: TracedPhase | None
                          ) -> tuple[PeriodicBlocks, dict]:
        """Stitch phase traces into an N-iteration timeline in O(blocks).

        Iterations {0, 1, N-1} are materialized concretely; iterations
        2..N-2 are exact shifted copies of iteration 1 represented by the
        (cycle, n_cycles, period) template. The last iteration stays
        concrete because grad-release has no next iteration to free into.
        """
        N = self.iterations
        cursor = 0
        next_bid = [0]
        update_start: dict[int, int] = {}
        bwd_start: dict[int, int] = {}
        iteration_ends: dict[int, int] = {}

        def fresh_bid() -> int:
            next_bid[0] += 1
            return next_bid[0]

        def place(entry: TracedPhase, it: int, phase: Phase, target: list,
                  output_kind: BlockKind | None = None) -> None:
            nonlocal cursor
            input_bids = {b.bid for b in entry.input_blocks}
            output_bids = ({b.bid for b in entry.output_blocks}
                           if output_kind is not None else ())
            cur = cursor
            bid = next_bid[0]
            append = target.append
            for lc in entry.lifecycles:
                lcb = lc.block_id
                if lcb in input_bids:
                    continue
                ft = lc.free_t
                bid += 1
                append(BlockLifecycle(
                    bid, lc.size, lc.alloc_t + cur,
                    None if ft is None else ft + cur, it, phase, lc.op,
                    lc.scope,
                    output_kind if lcb in output_bids else lc.block_kind,
                    lc.shard_factor, lc.shape))
            next_bid[0] = bid
            cursor = cur + len(entry.trace.events) + 1

        def one_iteration(it: int, target: list, with_init: bool) -> None:
            nonlocal cursor
            for b in fwd.input_blocks:
                if b.kind is BlockKind.INPUT and b.size > 0:
                    target.append(BlockLifecycle(
                        fresh_bid(), b.size, cursor, None, it, Phase.DATA,
                        "host_to_device", "batch", BlockKind.INPUT,
                        1.0, b.shape))
            cursor += 1
            bwd_start[it] = cursor
            place(fwd, it, Phase.FORWARD_BACKWARD, target)
            update_start[it] = cursor
            if with_init and init is not None:
                place(init, it, Phase.OPTIMIZER, target,
                      output_kind=BlockKind.OPT_STATE)
            if upd is not None:
                place(upd, it, Phase.OPTIMIZER, target,
                      output_kind=BlockKind.OUTPUT)
            iteration_ends[it] = cursor

        prefix: list[BlockLifecycle] = []
        cycle: list[BlockLifecycle] = []
        suffix: list[BlockLifecycle] = []

        # t=0: persistent parameter blocks (one per leaf, from fwd inputs)
        for b in fwd.input_blocks:
            if b.kind is BlockKind.PARAM and b.size > 0:
                prefix.append(BlockLifecycle(
                    fresh_bid(), b.size, 0, None, 0, Phase.INIT,
                    "init", "params", BlockKind.PARAM, 1.0, b.shape))
        cursor += 1

        one_iteration(0, prefix, with_init=True)
        period = 0
        cycle_start = cursor
        if N >= 3:
            one_iteration(1, cycle, with_init=False)
            period = cursor - cycle_start
            # iterations 2..N-2 are implicit template replicas; synthetic
            # next-iteration keys let grad_release="at_next_iter" and
            # output release resolve the template's frees one period
            # ahead (shift-consistent for every replica, including the
            # one feeding the last iteration)
            update_start[2] = update_start[1] + period
            iteration_ends[2] = iteration_ends[1] + period
            cursor = cycle_start + (N - 2) * period
        if N >= 2:
            one_iteration(N - 1, suffix, with_init=False)

        n_cycles = max(N - 2, 0)
        meta = dict(iteration_ends=iteration_ends,
                    update_start=update_start, bwd_start=bwd_start,
                    horizon=cursor + 2, cycle_start=cycle_start,
                    period=period, n_cycles=n_cycles)
        pb = PeriodicBlocks(prefix, cycle, n_cycles, period, suffix,
                            meta={"cycle_start": cycle_start})
        return pb, meta

    # -- composition (reference/seed path) -------------------------------------
    def _compose_reference(self, fwd: TracedPhase, upd: TracedPhase | None,
                           init: TracedPhase | None
                           ) -> tuple[list[BlockLifecycle], dict]:
        """Seed composition: every iteration materialized concretely."""
        blocks: list[BlockLifecycle] = []
        cursor = 0
        iteration_ends: dict[int, int] = {}
        update_start: dict[int, int] = {}
        bwd_start: dict[int, int] = {}
        next_bid = [0]

        def fresh_bid():
            next_bid[0] += 1
            return next_bid[0]

        def place(entry: TracedPhase, it, phase, output_kind=None):
            nonlocal cursor
            input_bids = {b.bid for b in entry.input_blocks}
            output_bids = {b.bid for b in entry.output_blocks}
            placed = []
            # the seed re-derived lifecycles from the event stream on
            # every placement; kept verbatim so this path stays an honest
            # baseline (the fast path reuses the phase's precomputed
            # lifecycles instead)
            from .analyzer import reconstruct_lifecycles
            for lc in reconstruct_lifecycles(entry.trace):
                if lc.block_id in input_bids:
                    continue
                kind = lc.block_kind
                if lc.block_id in output_bids and output_kind is not None:
                    kind = output_kind
                free_t = lc.free_t + cursor if lc.free_t is not None else None
                placed.append(dataclasses.replace(
                    lc, block_id=fresh_bid(), alloc_t=lc.alloc_t + cursor,
                    free_t=free_t, iteration=it, phase=phase,
                    block_kind=kind))
            cursor += len(entry.trace.events) + 1
            return placed

        # t=0: persistent parameter blocks (one per leaf, from fwd inputs)
        for b in fwd.input_blocks:
            if b.kind is BlockKind.PARAM and b.size > 0:
                blocks.append(BlockLifecycle(
                    fresh_bid(), b.size, 0, None, 0, Phase.INIT,
                    "init", "params", BlockKind.PARAM, 1.0, b.shape))
        cursor += 1

        for it in range(self.iterations):
            # batch data arrives
            for b in fwd.input_blocks:
                if b.kind is BlockKind.INPUT and b.size > 0:
                    blocks.append(BlockLifecycle(
                        fresh_bid(), b.size, cursor, None, it, Phase.DATA,
                        "host_to_device", "batch", BlockKind.INPUT,
                        1.0, b.shape))
            cursor += 1
            bwd_start[it] = cursor
            blocks.extend(place(fwd, it, Phase.FORWARD_BACKWARD))
            update_start[it] = cursor
            if it == 0 and init is not None:
                # optimizer state materializes at the first update
                blocks.extend(place(init, it, Phase.OPTIMIZER,
                                    output_kind=BlockKind.OPT_STATE))
            if upd is not None:
                blocks.extend(place(upd, it, Phase.OPTIMIZER,
                                    output_kind=BlockKind.OUTPUT))
            iteration_ends[it] = cursor
        bwd_start[self.iterations] = cursor + 1  # sentinel for last grads
        meta = dict(iteration_ends=iteration_ends, update_start=update_start,
                    bwd_start=bwd_start, horizon=cursor + 2)
        return blocks, meta

    # -- public API ------------------------------------------------------------------
    def estimate_training(self,
                          fwd_bwd_fn: Callable,     # (params, batch) -> (loss, grads)
                          params, batch,
                          update_fn: Callable | None = None,  # (params, grads, opt_state) -> (params, opt_state)
                          opt_init_fn: Callable | None = None,  # params -> opt_state
                          shard_factor_fn=None,
                          collective_specs: Sequence[CollectiveSpec] = (),
                          capacity: int | None = None) -> EstimateReport:
        t0 = time.perf_counter()
        _policy_before = self.orchestrator.policy  # restored at the end
        impl = (self._estimate_training if self.fastpath
                else self._estimate_training_reference)
        try:
            return impl(fwd_bwd_fn, params, batch, update_fn, opt_init_fn,
                        shard_factor_fn, collective_specs, capacity, t0)
        finally:
            self.orchestrator.policy = _policy_before

    def trace_phases(self, fwd_bwd_fn, params, batch, update_fn=None,
                     opt_init_fn=None, fwd: TracedPhase | None = None
                     ) -> tuple[TracedPhase, TracedPhase | None,
                                TracedPhase | None]:
        """Stage 1: per-phase CPU traces (cached). Passing ``fwd`` skips
        the forward trace — the sweep service enters here with an
        interpolated forward phase and still gets the optimizer phases
        resolved (normally cache hits, they are batch-independent)."""
        def fwd_out_kinds(out_shape):
            n_out = len(jax.tree_util.tree_leaves(out_shape))
            n_loss = len(jax.tree_util.tree_leaves(out_shape[0])) \
                if isinstance(out_shape, tuple) else 1
            return [BlockKind.OUTPUT] * n_loss + \
                   [BlockKind.GRAD] * (n_out - n_loss)

        if fwd is None:
            fwd = self._trace_phase(
                fwd_bwd_fn,
                [(params, BlockKind.PARAM, "params"),
                 (batch, BlockKind.INPUT, "batch")],
                Phase.FORWARD_BACKWARD, out_kind_fn=fwd_out_kinds, tag="fwd")
        fwd_out_shape = fwd.out_shape

        init = upd = None
        opt_state = None
        if opt_init_fn is not None:
            init = self._trace_phase(
                opt_init_fn, [(params, BlockKind.PARAM, "params")],
                Phase.OPTIMIZER,
                out_kind_fn=lambda s: [BlockKind.OPT_STATE] * len(
                    jax.tree_util.tree_leaves(s)),
                tag="init")
            opt_state = init.out_shape
        if update_fn is not None:
            grads = fwd_out_shape[1] if isinstance(fwd_out_shape, tuple) \
                else fwd_out_shape
            upd_args = [(params, BlockKind.PARAM, "params"),
                        (grads, BlockKind.GRAD, "grads")]
            if opt_state is not None:
                upd_args.append((opt_state, BlockKind.OPT_STATE, "opt_state"))
            upd = self._trace_phase(update_fn, upd_args, Phase.OPTIMIZER,
                                    tag="upd")
        return fwd, upd, init

    def _estimate_training(self, fwd_bwd_fn, params, batch, update_fn,
                           opt_init_fn, shard_factor_fn, collective_specs,
                           capacity, t0) -> EstimateReport:
        cache = self.trace_cache
        h0 = cache.hits if cache is not None else 0
        m0 = cache.misses if cache is not None else 0

        # --- stage 1: CPU traces (paper: profile first iterations) ---
        fwd, upd, init = self.trace_phases(fwd_bwd_fn, params, batch,
                                           update_fn, opt_init_fn)

        cache_stats = {}
        if cache is not None:
            cache_stats = {"hits": cache.hits - h0,
                           "misses": cache.misses - m0,
                           "global": cache.stats()}
        return self.estimate_from_phases(
            fwd, upd, init, shard_factor_fn=shard_factor_fn,
            collective_specs=collective_specs, capacity=capacity, t0=t0,
            cache_stats=cache_stats)

    def estimate_from_phases(self, fwd: TracedPhase,
                             upd: TracedPhase | None = None,
                             init: TracedPhase | None = None, *,
                             shard_factor_fn=None,
                             collective_specs: Sequence[CollectiveSpec] = (),
                             capacity: int | None = None,
                             t0: float | None = None,
                             cache_stats: dict | None = None
                             ) -> EstimateReport:
        """Stages 2-5 (compose, classify, orchestrate, simulate) from
        already-traced phases. ``estimate_training`` lands here after
        stage 1; the sweep service (``core/sweep.py``) enters directly
        with cached or interpolated ``TracedPhase`` entries — including
        from pool workers, where no JAX tracing must happen."""
        if t0 is None:
            t0 = time.perf_counter()
        _policy_before = self.orchestrator.policy
        try:
            return self._estimate_from_phases(
                fwd, upd, init, shard_factor_fn, collective_specs,
                capacity, t0, cache_stats or {})
        finally:
            self.orchestrator.policy = _policy_before

    def _estimate_from_phases(self, fwd, upd, init, shard_factor_fn,
                              collective_specs, capacity, t0,
                              cache_stats) -> EstimateReport:
        # --- stage 2+3: analyze & compose iterations (periodic) ---
        pb, meta = self._compose_periodic(fwd, upd, init)
        concrete = pb.prefix + pb.cycle + pb.suffix
        param_sizes = frozenset(
            b.size for b in fwd.input_blocks if b.kind is BlockKind.PARAM)
        concrete = classify_blocks(concrete, param_sizes)

        # --- stage 4: orchestrate ---
        phase_bounds = {}
        for it, end in meta["iteration_ends"].items():
            if it not in meta["bwd_start"]:
                continue   # synthetic template key (fast path), not a
                           # concretely composed iteration
            phase_bounds[(it, Phase.FORWARD_BACKWARD.value)] = (
                meta["bwd_start"][it], meta["update_start"][it])
            phase_bounds[(it, Phase.OPTIMIZER.value)] = (
                meta["update_start"][it], end)
        # Resolve "auto" grad release: per-leaf updates fuse into the
        # backward under XLA (eager grad death); coupled updates (global
        # clipping etc.) keep every grad alive until the optimizer phase.
        if self.orchestrator.policy.grad_release == "auto":
            mode = "eager_fused"
            upcasts = False
            if upd is not None:
                # reuse the already-traced flat update jaxpr (its invars
                # are params|grads|opt_state leaves in flatten order) —
                # no extra make_jaxpr; verdict memoized on the entry
                if upd.coupling is None:
                    upd.coupling = _coupling_from_jaxpr(
                        upd.closed_jaxpr.jaxpr,
                        upd.arg_leaf_counts[0], upd.arg_leaf_counts[1])
                info = upd.coupling
                mode = "eager_fused" if info["coupling"] == "per_leaf" \
                    else "at_update"
                upcasts = info["upcasts"]
            self.orchestrator.policy = dataclasses.replace(
                self.orchestrator.policy, grad_release=mode,
                optimizer_upcast_coexist=(
                    self.orchestrator.policy.optimizer_upcast_coexist
                    and upcasts))

        # grad_release="at_next_iter" frees iteration i's gradients only
        # when iteration i+1's update completes new ones — the
        # grad-accumulation / zero_grad-at-start idiom (paper Fig 1 POS1);
        # hence update_start is passed as the next-iteration release point.
        concrete = self.orchestrator.run(
            concrete,
            iteration_ends=meta["iteration_ends"],
            update_start=meta["update_start"],
            next_bwd_start=meta["update_start"],
            collective_specs=collective_specs,
            phase_bounds=phase_bounds,
            num_iterations=self.iterations,
            shard_factor_fn=shard_factor_fn,
        )
        # host-offload rewrite (separate pass so run == run_unfused holds);
        # only *concretely composed* iterations get staging blocks — the
        # synthetic template keys (e.g. update_start[2] on the fast path)
        # are release markers, not iterations that exist in the timeline
        offload_stats = None
        opolicy = self.orchestrator.policy
        if opolicy.offload is not None and opolicy.offload.enabled:
            us_concrete = {it: t for it, t in meta["update_start"].items()
                           if it in meta["bwd_start"]}
            concrete, offload_stats = self.orchestrator.apply_offload(
                concrete, us_concrete, meta["iteration_ends"])

        # --- stage 5: simulate ---
        num_events = (len(fwd.trace.events)
                      + (len(upd.trace.events) if upd else 0)
                      + (len(init.trace.events) if init else 0))
        if self.checkpoint is not None:
            self.checkpoint("replay")
        sim_runner = MemorySimulator(self.allocator_policy,
                                     capacity or self.capacity,
                                     engine=self.engine)
        N = self.iterations
        prefix = [b for b in concrete if b.iteration == 0]
        cyc = [b for b in concrete if b.iteration == 1] if N >= 3 else []
        suffix = ([b for b in concrete if b.iteration == N - 1]
                  if N >= 2 else [])
        pb = PeriodicBlocks(prefix, cyc, pb.n_cycles, pb.period, suffix,
                            meta=pb.meta)
        with obs_spans.span("estimator.replay", engine=self.engine,
                            num_blocks=pb.num_blocks):
            sim = sim_runner.replay_spaces(pb)
        is_cycle = (lambda b: N >= 3 and b.iteration == 1)
        persistent = sum(
            b.sharded_size * (pb.n_cycles if is_cycle(b) else 1)
            for b in concrete
            if b.free_t is None and b.block_kind in (
                BlockKind.PARAM, BlockKind.OPT_STATE))
        # peaks computed on a bounded-replica reduction when middle
        # iterations carry no net bytes — O(blocks), independent of N;
        # the vectorized sweep is output-identical to the dict-based one.
        # Under offload the per-kind/per-phase breakdown describes the
        # *device* composition (what the capacity verdict is about).
        bd_pb = pb
        if offload_stats is not None:
            from .events import MemorySpace
            bd_pb = split_blocks_by_space(pb).get(
                MemorySpace.DEVICE_HBM,
                PeriodicBlocks([], [], pb.n_cycles, pb.period, [],
                               dict(pb.meta)))
        bd_pb = reduced_for_breakdown(bd_pb)
        liveness_peak, phase_pk = periodic_breakdown_peaks_fast(bd_pb)
        breakdown = {
            "phase_peaks": phase_pk,
            "num_blocks": pb.num_blocks,
            "liveness_peak": liveness_peak,
            **fused_counts(fwd, upd),
            **collective_counts(bd_pb),
        }
        if offload_stats is not None:
            breakdown["space_peaks"] = sim.stats.get("space_peaks", {})
            breakdown["offload"] = offload_stats
        composition = pb
        report = EstimateReport(
            peak_bytes=sim.peak_reserved,
            peak_tensor_bytes=sim.peak_allocated,
            persistent_bytes=persistent,
            oom=sim.oom,
            sim=sim,
            breakdown=breakdown,
            wall_time_s=time.perf_counter() - t0,
            num_events=num_events,
            cache_stats=cache_stats or {},
        )
        report.composition = composition   # for capacity probing
        # min_feasible_capacity may reuse report.sim as its instrumented
        # probe, but only when this replay ran unconstrained
        report.sim_unbounded = (capacity or self.capacity) >= (1 << 62)
        return report

    def _estimate_training_reference(self, fwd_bwd_fn, params, batch,
                                     update_fn, opt_init_fn,
                                     shard_factor_fn, collective_specs,
                                     capacity, t0) -> EstimateReport:
        """Seed pipeline, preserved verbatim as the slow reference:
        separate ``eval_shape`` passes, a fresh coupling re-trace, fully
        materialized N-iteration composition, full event replay. The
        fast path must match it bit-for-bit on every estimate field
        (tests/test_fastpath.py)."""
        opolicy = self.orchestrator.policy
        if opolicy.offload is not None and opolicy.offload.enabled:
            raise NotImplementedError(
                "host offload needs the fast path (fastpath=True): the "
                "reference pipeline is frozen at seed semantics and has "
                "no multi-space replay")
        # --- stage 1: CPU traces (paper: profile first iterations) ---
        fwd_out_shape = jax.eval_shape(fwd_bwd_fn, params, batch)
        n_out = len(jax.tree_util.tree_leaves(fwd_out_shape))
        n_loss = len(jax.tree_util.tree_leaves(fwd_out_shape[0])) \
            if isinstance(fwd_out_shape, tuple) else 1
        fwd_out_kinds = [BlockKind.OUTPUT] * n_loss + \
                        [BlockKind.GRAD] * (n_out - n_loss)
        fwd = self._trace_phase(
            fwd_bwd_fn,
            [(params, BlockKind.PARAM, "params"),
             (batch, BlockKind.INPUT, "batch")],
            Phase.FORWARD_BACKWARD,
            out_kind_fn=lambda _s: fwd_out_kinds, tag="fwd")

        init = upd = None
        opt_state = None
        if opt_init_fn is not None:
            opt_state = jax.eval_shape(opt_init_fn, params)
            init = self._trace_phase(
                opt_init_fn, [(params, BlockKind.PARAM, "params")],
                Phase.OPTIMIZER,
                out_kind_fn=lambda _s: [BlockKind.OPT_STATE] * len(
                    jax.tree_util.tree_leaves(opt_state)),
                tag="init")
        if update_fn is not None:
            grads = fwd_out_shape[1] if isinstance(fwd_out_shape, tuple) \
                else fwd_out_shape
            upd_args = [(params, BlockKind.PARAM, "params"),
                        (grads, BlockKind.GRAD, "grads")]
            if opt_state is not None:
                upd_args.append((opt_state, BlockKind.OPT_STATE, "opt_state"))
            upd = self._trace_phase(update_fn, upd_args, Phase.OPTIMIZER,
                                    tag="upd")

        # --- stage 2+3: analyze & compose iterations ---
        blocks, meta = self._compose_reference(fwd, upd, init)
        param_sizes = frozenset(
            b.size for b in fwd.input_blocks if b.kind is BlockKind.PARAM)
        # frozen seed classifier: the baseline must not drift as the
        # shared analyzer gets optimized (same output, seed cost profile)
        classified = []
        for b in blocks:
            kind = b.block_kind
            if kind in (BlockKind.ACTIVATION, BlockKind.TEMP):
                in_bwd = any(m in b.scope for m in ("transpose", "backward"))
                if in_bwd and b.size in param_sizes:
                    kind = BlockKind.GRAD
            classified.append(dataclasses.replace(b, block_kind=kind))
        blocks = classified

        # --- stage 4: orchestrate ---
        phase_bounds = {}
        for it, end in meta["iteration_ends"].items():
            phase_bounds[(it, Phase.FORWARD_BACKWARD.value)] = (
                meta["bwd_start"][it], meta["update_start"][it])
            phase_bounds[(it, Phase.OPTIMIZER.value)] = (
                meta["update_start"][it], end)
        if self.orchestrator.policy.grad_release == "auto":
            mode = "eager_fused"
            upcasts = False
            if update_fn is not None:
                grads_shape = fwd_out_shape[1] \
                    if isinstance(fwd_out_shape, tuple) else fwd_out_shape
                info = update_grad_coupling(
                    update_fn, params, grads_shape, opt_state)
                mode = "eager_fused" if info["coupling"] == "per_leaf" \
                    else "at_update"
                upcasts = info["upcasts"]
            self.orchestrator.policy = dataclasses.replace(
                self.orchestrator.policy, grad_release=mode,
                optimizer_upcast_coexist=(
                    self.orchestrator.policy.optimizer_upcast_coexist
                    and upcasts))

        # frozen seed pass order (fold after the lifecycle passes) —
        # output-identical to the orchestrator's current fold-first
        # ``run``, kept verbatim so the baseline's cost profile is stable
        o = self.orchestrator
        blocks = o.mark_persistent(blocks)
        blocks = o.batch_per_iteration(blocks, meta["iteration_ends"])
        blocks = o.release_gradients(blocks, meta["update_start"],
                                     meta["update_start"])
        blocks = o.inject_optimizer_upcasts(blocks, meta["update_start"],
                                            meta["iteration_ends"])
        blocks = o.apply_donation(blocks)
        if o.policy.release_outputs_next_iter:
            blocks = o.release_step_outputs(blocks, meta["iteration_ends"])
        blocks = o.fold_fused(blocks)
        blocks = o.apply_transient_scale(blocks)
        if collective_specs and phase_bounds:
            blocks = o.inject_collectives(blocks, collective_specs,
                                          phase_bounds, self.iterations,
                                          shard_factor_fn)
        if shard_factor_fn is not None:
            blocks = o.apply_sharding(blocks, shard_factor_fn)

        # --- stage 5: simulate ---
        sim = MemorySimulator(self.allocator_policy,
                              capacity or self.capacity).replay(blocks)
        persistent = sum(b.sharded_size for b in blocks if b.free_t is None
                         and b.block_kind in (BlockKind.PARAM,
                                              BlockKind.OPT_STATE))
        report = EstimateReport(
            peak_bytes=sim.peak_reserved,
            peak_tensor_bytes=sim.peak_allocated,
            persistent_bytes=persistent,
            oom=sim.oom,
            sim=sim,
            breakdown={
                "phase_peaks": phase_peaks(blocks),
                "num_blocks": len(blocks),
                "liveness_peak": peak_live_bytes(blocks),
                **fused_counts(fwd, upd),
                **collective_counts(blocks),
            },
            wall_time_s=time.perf_counter() - t0,
            num_events=(len(fwd.trace.events)
                        + (len(upd.trace.events) if upd else 0)
                        + (len(init.trace.events) if init else 0)),
        )
        report.composition = blocks
        report.sim_unbounded = (capacity or self.capacity) >= (1 << 62)
        return report

    # -- capacity probing -------------------------------------------------------
    def min_feasible_capacity(self, fwd_bwd_fn, params, batch,
                              update_fn=None, opt_init_fn=None,
                              shard_factor_fn=None,
                              collective_specs=(),
                              report: EstimateReport | None = None) -> int:
        """Smallest device capacity the job fits in, from one instrumented
        replay (plus bounded verification) — see
        ``MemorySimulator.min_feasible_capacity``. Passing an existing
        ``report`` reuses its composition and unbounded replay."""
        if report is None or getattr(report, "composition", None) is None:
            report = self.estimate_training(
                fwd_bwd_fn, params, batch, update_fn=update_fn,
                opt_init_fn=opt_init_fn, shard_factor_fn=shard_factor_fn,
                collective_specs=collective_specs)
        sim_runner = MemorySimulator(self.allocator_policy, 1 << 62,
                                     engine=self.engine)
        probe = (report.sim
                 if getattr(report, "sim_unbounded", False)
                 and not report.sim.oom else None)
        # under offload the capacity question is about device HBM only —
        # the probe stays valid because replay_spaces' primary result IS
        # the device sub-composition's replay
        comp = report.composition
        groups = split_blocks_by_space(comp)
        if len(groups) > 1:
            from .events import MemorySpace
            comp = groups.get(MemorySpace.DEVICE_HBM, [])
        return sim_runner.min_feasible_capacity(comp, probe=probe)

    def estimate_serving(self, decode_fn: Callable, params, cache, batch,
                         shard_factor_fn=None,
                         collective_specs: Sequence[CollectiveSpec] = (),
                         capacity: int | None = None) -> EstimateReport:
        """Single-phase estimate for a decode step with a persistent cache."""
        t0 = time.perf_counter()
        entry = self._trace_phase(
            decode_fn,
            [(params, BlockKind.PARAM, "params"),
             (cache, BlockKind.CACHE, "cache"),
             (batch, BlockKind.INPUT, "batch")],
            Phase.DECODE, tag="decode")
        blocks = list(entry.lifecycles)
        blocks = self.orchestrator.mark_persistent(
            blocks, kinds=(BlockKind.PARAM, BlockKind.CACHE))
        blocks = self.orchestrator.fold_fused(blocks)
        if shard_factor_fn is not None:
            blocks = self.orchestrator.apply_sharding(blocks, shard_factor_fn)
        sim = MemorySimulator(self.allocator_policy,
                              capacity or self.capacity,
                              engine=self.engine).replay(blocks)
        return EstimateReport(
            peak_bytes=sim.peak_reserved, peak_tensor_bytes=sim.peak_allocated,
            persistent_bytes=sum(b.sharded_size for b in blocks
                                 if b.free_t is None),
            oom=sim.oom, sim=sim,
            breakdown={"num_blocks": len(blocks), **fused_counts(entry)},
            wall_time_s=time.perf_counter() - t0,
            num_events=len(entry.trace.events))

    def estimate_request_stream(self, decode_fn: Callable, params, cache,
                                batch, *, stream, knobs=None,
                                kv_bytes_per_token: int,
                                resident_bytes_per_request: int = 0,
                                base_dtype_bytes: int = 2,
                                shard_factor_fn=None,
                                capacity: int | None = None
                                ) -> "ServingEstimate":
        """Estimate a serving runtime over a request-driven timeline.

        Two CPU-side components compose additively:

        * **step working set** — the decode step is traced once via the
          SAME trace key as :meth:`estimate_serving` (so a knob sweep
          over page size / concurrency / KV dtype re-lowers the request
          stream but never re-traces); its transient peak (activations
          above params+cache) is scaled batch-linearly from the traced
          batch to ``knobs.max_concurrent`` — decode activations are
          per-sequence, so the linear model is exact for attention-free
          layers and a documented upper bound for the rest;
        * **paged KV pressure** — the request stream is lowered by the
          continuous-batching scheduler to page-granular allocations
          and replayed through the allocator simulator exactly (no
          approximation: join/extend/leave/evict at the tick each
          happens).

        ``worst_case_peak_bytes`` is what the admission gate must trust;
        ``steady_state_peak_bytes`` (median live paged bytes) is what a
        capacity planner provisions for sustained load.
        """
        from .orchestrator import (ContinuousBatchingScheduler,
                                   ServingKnobs)
        t0 = time.perf_counter()
        knobs = knobs or ServingKnobs()
        entry = self._trace_phase(
            decode_fn,
            [(params, BlockKind.PARAM, "params"),
             (cache, BlockKind.CACHE, "cache"),
             (batch, BlockKind.INPUT, "batch")],
            Phase.DECODE, tag="decode")
        blocks = list(entry.lifecycles)
        blocks = self.orchestrator.mark_persistent(
            blocks, kinds=(BlockKind.PARAM, BlockKind.CACHE))
        blocks = self.orchestrator.fold_fused(blocks)
        if shard_factor_fn is not None:
            blocks = self.orchestrator.apply_sharding(blocks,
                                                      shard_factor_fn)
        step_sim = MemorySimulator(self.allocator_policy, self.capacity,
                                   engine=self.engine).replay(blocks)
        persistent_all = sum(b.sharded_size for b in blocks
                             if b.free_t is None)
        params_bytes = sum(b.sharded_size for b in blocks
                           if b.free_t is None
                           and b.block_kind == BlockKind.PARAM)
        transient = max(step_sim.peak_allocated - persistent_all, 0)
        traced_batch = _leading_dim(batch)
        transient_scaled = -(-transient * knobs.max_concurrent
                             // max(traced_batch, 1))

        sched = ContinuousBatchingScheduler(knobs)
        rb = sched.lower(stream, kv_bytes_per_token,
                         resident_bytes_per_request=resident_bytes_per_request,
                         base_dtype_bytes=base_dtype_bytes)
        paged_sim = MemorySimulator(self.allocator_policy, self.capacity,
                                    engine=self.engine).replay(rb)
        live = [v for v in rb.meta["live_paged"] if v > 0]
        live.sort()
        paged_steady = live[len(live) // 2] if live else 0
        tok_b = rb.meta["kv_bytes_per_token"]
        monolithic = knobs.max_concurrent * (
            stream.max_seq_len * tok_b + int(resident_bytes_per_request))

        worst = params_bytes + transient_scaled + paged_sim.peak_reserved
        steady = params_bytes + transient_scaled + paged_steady
        cap = capacity if capacity is not None else self.capacity
        return ServingEstimate(
            steady_state_peak_bytes=int(steady),
            worst_case_peak_bytes=int(worst),
            persistent_bytes=int(params_bytes),
            step_transient_bytes=int(transient_scaled),
            paged_kv_peak_bytes=int(paged_sim.peak_reserved),
            paged_kv_steady_bytes=int(paged_steady),
            monolithic_cache_bytes=int(monolithic),
            oom=worst > cap,
            sim=paged_sim,
            breakdown={
                "num_blocks": rb.num_blocks,
                "ticks": rb.meta["ticks"],
                "evictions": rb.meta["evictions"],
                "max_occupancy": max(rb.meta["occupancy"], default=0),
                "page_bytes": rb.meta["page_bytes"],
                "knobs": rb.meta["knobs"],
            },
            wall_time_s=time.perf_counter() - t0,
            num_events=len(entry.trace.events) + 2 * rb.num_blocks)


def _leading_dim(tree) -> int:
    """Batch size of a traced decode input: leading dim of the first
    array leaf (1 for scalars/empty trees)."""
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = getattr(leaf, "shape", ())
        if shape:
            return int(shape[0])
    return 1


@dataclasses.dataclass
class ServingEstimate:
    """Serving-runtime estimate over a request-mix timeline.

    Unlike :class:`EstimateReport`'s single number, serving capacity has
    two operating points: the worst-case peak (admission gate — must fit
    or the server OOMs under the scripted burst) and the steady-state
    median (provisioning — what sustained load actually holds). The
    paged-vs-monolithic pair quantifies what paged attention buys."""

    steady_state_peak_bytes: int
    worst_case_peak_bytes: int
    persistent_bytes: int         # params (sharded) — always resident
    step_transient_bytes: int     # decode working set at max_concurrent
    paged_kv_peak_bytes: int      # allocator peak of the paged stream
    paged_kv_steady_bytes: int    # median live paged bytes
    monolithic_cache_bytes: int   # max_concurrent x max_seq full cache
    oom: bool
    sim: SimResult
    breakdown: dict
    wall_time_s: float
    num_events: int

    def fits(self, capacity: int) -> bool:
        return self.worst_case_peak_bytes <= capacity

    def to_json(self) -> dict:
        return {
            "steady_state_peak_bytes": self.steady_state_peak_bytes,
            "worst_case_peak_bytes": self.worst_case_peak_bytes,
            "persistent_bytes": self.persistent_bytes,
            "step_transient_bytes": self.step_transient_bytes,
            "paged_kv_peak_bytes": self.paged_kv_peak_bytes,
            "paged_kv_steady_bytes": self.paged_kv_steady_bytes,
            "monolithic_cache_bytes": self.monolithic_cache_bytes,
            "oom": self.oom,
            "breakdown": {k: v for k, v in self.breakdown.items()
                          if k != "knobs"},
            "knobs": self.breakdown.get("knobs", {}),
        }
