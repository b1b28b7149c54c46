"""Dynamic memory tracing of JAX programs on CPU (paper stage 1, adapted).

The paper profiles the first training iterations with the PyTorch profiler
and reconstructs the memory-event stream. In JAX the program *is* data —
a jaxpr — so we obtain the same dynamic event stream by interpreting the
jaxpr of the step function eqn-by-eqn in execution order:

* each equation's outputs become ``alloc`` events sized by their avals;
* refcount liveness (uses remaining) emits ``free`` events at last use —
  exactly the alloc/free interleaving an eager executor would produce;
* layer/operator attribution comes structurally from ``name_stack``
  (the paper needs time-window heuristics because traces lack linkage;
  we keep that fallback in ``analyzer.py`` for external traces).

Control flow is handled like an executor would:
* ``scan``/``while``  — stacked loop outputs are allocated up-front (XLA
  preallocates loop outputs), then the body is unrolled for
  ``min(length, unroll_cap)`` iterations. Allocator state stabilizes
  within 2–3 iterations — the same observation the paper makes about
  training iterations (§3.1 fn. 2) applies to loop bodies, which is what
  makes a small cap sound.
* ``cond``            — the branch with the largest memory footprint is
  traced (conservative for peak estimation).
* ``pjit``/``remat``/``custom_*`` — inlined.

Fusion (``fusion_folding=True``, the XLA presets): a value XLA computes
inside the fused kernel of its consumers never occupies HBM. Within each
region, a value is a *fusion interior* when its producer is in
``FUSIBLE_OPS``, it is not an output of the region (no scan/while carry,
stacked ``ys`` or jaxpr outvar), and every consumer in the region is
fusible or a reduction — or, for the layout-only producers, every
consumer is a ``dot_general``, which absorbs the layout in its dimension
numbers. Any other consumer (a call, loop, gather/scatter, custom or
Pallas kernel, ...) makes the value materialize. An interior value is
recorded as a ``BlockKind.FUSED`` block, which the orchestrator drops,
and it holds its operand blocks until its own last consumer has run: the
fused consumer reads them, recursively through chains.

No computation is performed: tracing a trillion-parameter step costs
milliseconds and zero accelerator involvement — the paper's "zero
target-GPU overhead" requirement, kept intact.
"""
from __future__ import annotations

import dataclasses
import math
from collections import defaultdict
from typing import Any, Callable, Sequence

import jax
import numpy as np
from jax.extend import core as jcore

try:  # DropVar is not re-exported via jax.extend.core
    from jax._src.core import DropVar as _DropVar
except ImportError:  # pragma: no cover - future-proofing
    _DropVar = ()

from .events import (KIND_CODE, PHASE_CODE, BlockKind, ColumnarTrace,
                     Phase, StringInterner, Trace)

# Primitive param keys that hold sub-jaxprs to inline.
_CALL_JAXPR_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")

# Elementwise/layout primitives XLA fuses into their consumers (``add_any``
# is autodiff's elementwise add of cotangents).
FUSIBLE_OPS = frozenset({
    "add", "sub", "mul", "div", "neg", "exp", "log", "tanh", "logistic",
    "max", "min", "pow", "integer_pow", "sqrt", "rsqrt", "abs", "sign",
    "convert_element_type", "select_n", "broadcast_in_dim", "reshape",
    "transpose", "squeeze", "expand_dims", "stop_gradient", "and", "or",
    "not", "xor", "eq", "ne", "ge", "gt", "le", "lt", "clamp", "erf",
    "floor", "ceil", "round", "is_finite", "copy", "real", "imag",
    "slice", "rev", "iota", "cos", "sin", "cumsum", "cumlogsumexp",
    "add_any", "square", "log1p", "rem", "le_to",
})
# Reductions read a fused elementwise producer inside their own kernel.
_REDUCTIONS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "argmax", "argmin",
})
# Layout-only producers a dot_general absorbs in its dimension numbers.
_LAYOUT_OPS = frozenset({"transpose", "reshape", "squeeze", "expand_dims"})


def fusion_interiors(jaxpr: jcore.Jaxpr) -> set:
    """The values of one region that XLA keeps inside a fusion (see the
    module docstring for the rule)."""
    consumers: dict[Any, list[str]] = defaultdict(list)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        for v in eqn.invars:
            if isinstance(v, jcore.Var):
                consumers[v].append(name)
    region_outs = {v for v in jaxpr.outvars if isinstance(v, jcore.Var)}
    interior = set()
    for eqn in jaxpr.eqns:
        op = eqn.primitive.name
        if op not in FUSIBLE_OPS:
            continue
        for v in eqn.outvars:
            uses = consumers.get(v)
            if not uses or v in region_outs:
                continue
            if (all(u in FUSIBLE_OPS or u in _REDUCTIONS for u in uses)
                    or (op in _LAYOUT_OPS
                        and all(u == "dot_general" for u in uses))):
                interior.add(v)
    return interior


def aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(math.prod(shape)) * dtype.itemsize if len(shape) else dtype.itemsize


def aval_shape(aval) -> tuple | None:
    """Dims of an aval as a plain int tuple (None when shapeless)."""
    shape = getattr(aval, "shape", None)
    if shape is None:
        return None
    return tuple(int(d) for d in shape)


@dataclasses.dataclass
class _Block:
    bid: int
    size: int
    refs: int
    pinned: bool = False
    kind: BlockKind = BlockKind.TEMP
    freed: bool = False
    # lifecycle bookkeeping so lifecycles need no event re-pairing
    alloc_t: int = 0
    free_t: int | None = None
    op: str = ""
    scope: str = ""
    shape: tuple | None = None    # producing aval dims (sharding input)
    holds: tuple = ()             # operands a fusion interior keeps alive


class JaxprMemoryTracer:
    """Interprets a jaxpr into an ordered stream of MemoryEvents."""

    def __init__(self, scan_unroll_cap: int = 3, phase: Phase = Phase.FORWARD_BACKWARD,
                 iteration: int = 0, fusion_folding: bool = False):
        self.cap = scan_unroll_cap
        self.fusion_folding = fusion_folding
        # per region jaxpr: (jaxpr, use counts, fusion interiors); the
        # jaxpr is kept so its id stays unique while the entry lives
        self._regions: dict[int, tuple] = {}
        self.phase = phase
        self.iteration = iteration
        # Events are emitted straight into primitive columns (the
        # ColumnarTrace SoA layout) — MemoryEvent objects materialize
        # lazily, only if a consumer iterates trace.events.
        self._ev_kind: list[int] = []    # 1 = alloc, 0 = free
        self._ev_bid: list[int] = []
        self._ev_size: list[int] = []
        self._ev_t: list[int] = []
        self._ev_op: list[int] = []
        self._ev_scope: list[int] = []
        self._ev_bkind: list[int] = []
        self._ev_shape: list[int] = []
        self._ops = StringInterner()
        self._scopes = StringInterner()
        self._shapes = StringInterner([None])
        self.t = 0
        self._next_bid = 0
        self.blocks: dict[int, _Block] = {}
        self.input_blocks: list[_Block] = []
        self.output_blocks: list[_Block] = []

    @property
    def num_events(self) -> int:
        return len(self._ev_kind)

    # ---- block machinery -------------------------------------------------
    def _new_block(self, size: int, refs: int, op: str, scope: str,
                   kind: BlockKind, pinned: bool = False,
                   shape: tuple | None = None) -> _Block:
        b = _Block(self._next_bid, size, refs, pinned, kind,
                   alloc_t=self.t, op=op, scope=scope, shape=shape)
        self._next_bid += 1
        self.blocks[b.bid] = b
        self._ev_kind.append(1)
        self._ev_bid.append(b.bid)
        self._ev_size.append(size)
        self._ev_t.append(self.t)
        self._ev_op.append(self._ops.intern(op))
        self._ev_scope.append(self._scopes.intern(scope))
        self._ev_bkind.append(KIND_CODE[kind])
        self._ev_shape.append(self._shapes.intern(shape))
        self.t += 1
        return b

    def _retain(self, b: _Block, n: int) -> None:
        b.refs += n

    def _release(self, b: _Block, n: int = 1, op: str = "", scope: str = "") -> None:
        b.refs -= n
        if b.refs <= 0 and not b.pinned and not b.freed:
            b.freed = True
            b.free_t = self.t
            self._ev_kind.append(0)
            self._ev_bid.append(b.bid)
            self._ev_size.append(b.size)
            self._ev_t.append(self.t)
            self._ev_op.append(self._ops.intern(op))
            self._ev_scope.append(self._scopes.intern(scope))
            self._ev_bkind.append(KIND_CODE[b.kind])
            self._ev_shape.append(self._shapes.intern(b.shape))
            self.t += 1
            for h in b.holds:
                self._release(h, 1, op, scope)

    # ---- use counting ------------------------------------------------------
    @staticmethod
    def _use_counts(jaxpr: jcore.Jaxpr) -> dict:
        counts: dict[Any, int] = defaultdict(int)
        for eqn in jaxpr.eqns:
            for v in eqn.invars:
                if isinstance(v, jcore.Var):
                    counts[v] += 1
        for v in jaxpr.outvars:
            if isinstance(v, jcore.Var):
                counts[v] += 1
        return counts

    def _region(self, jaxpr: jcore.Jaxpr) -> tuple[dict, set]:
        """Use counts and fusion interiors of a region, once per jaxpr
        (scan and while bodies are interpreted once per iteration)."""
        hit = self._regions.get(id(jaxpr))
        if hit is None:
            interior = (fusion_interiors(jaxpr) if self.fusion_folding
                        else set())
            hit = self._regions[id(jaxpr)] = (
                jaxpr, self._use_counts(jaxpr), interior)
        return hit[1], hit[2]

    # ---- region interpretation ---------------------------------------------
    def _interpret_region(self, jaxpr: jcore.Jaxpr, bindings: Sequence[_Block],
                          consts: Sequence[_Block] = ()) -> list[_Block]:
        """Interpret a jaxpr with invars bound to existing blocks.

        Contract: caller's blocks are retained by their internal use count
        (pre-paid); returned outvar blocks carry one ref per outvar
        occurrence which the caller must dispose of.
        """
        counts, interior = self._region(jaxpr)
        env: dict[Any, _Block] = {}
        for v, b in zip(jaxpr.constvars, consts):
            env[v] = b
            self._retain(b, counts.get(v, 0))
        for v, b in zip(jaxpr.invars, bindings):
            env[v] = b
            self._retain(b, counts.get(v, 0))

        def read(v) -> _Block | None:
            if isinstance(v, jcore.Literal):
                return None
            return env.get(v)

        for eqn in jaxpr.eqns:
            scope = self._scope_of(eqn)
            op = eqn.primitive.name
            sub = self._sub_jaxpr(eqn)
            if eqn.primitive.name == "scan":
                out_blocks = self._do_scan(eqn, read, counts, scope)
            elif eqn.primitive.name == "while":
                out_blocks = self._do_while(eqn, read, counts, scope)
            elif eqn.primitive.name == "cond":
                out_blocks = self._do_cond(eqn, read, counts, scope)
            elif sub is not None:
                if isinstance(sub, jcore.ClosedJaxpr):
                    inner, const_vals = sub.jaxpr, sub.consts
                else:
                    inner, const_vals = sub, []
                const_blocks = [
                    self._new_block(int(getattr(c, "nbytes", 0) or 0), 1,
                                    "const", scope, BlockKind.TEMP)
                    for c in const_vals
                ]
                args = [read(v) or self._literal_block(v, scope)
                        for v in eqn.invars]
                out_blocks = self._interpret_region(inner, args, const_blocks)
                for cb in const_blocks:
                    self._release(cb, 1, op, scope)
            else:
                # plain primitive: allocate outputs, sized by avals
                out_blocks = []
                for ov in eqn.outvars:
                    n_uses = counts.get(ov, 0)
                    if isinstance(ov, _DropVar) or n_uses == 0:
                        out_blocks.append(None)
                        continue
                    if ov in interior:
                        holds = tuple(b for b in map(read, eqn.invars)
                                      if b is not None)
                        for h in holds:
                            self._retain(h, 1)
                        b = self._new_block(
                            aval_bytes(ov.aval), n_uses, op, scope,
                            BlockKind.FUSED, shape=aval_shape(ov.aval))
                        b.holds = holds
                        out_blocks.append(b)
                        continue
                    out_blocks.append(self._new_block(
                        aval_bytes(ov.aval), n_uses, op, scope,
                        BlockKind.ACTIVATION, shape=aval_shape(ov.aval)))

            # bind outvars; region results need ref adjustment to use counts
            if sub is not None or eqn.primitive.name in ("scan", "while", "cond"):
                adjusted = []
                for ov, b in zip(eqn.outvars, out_blocks):
                    if b is None:
                        adjusted.append(None)
                        continue
                    n_uses = counts.get(ov, 0)
                    if isinstance(ov, _DropVar) or n_uses == 0:
                        self._release(b, 1, op, scope)
                        adjusted.append(None)
                        continue
                    self._retain(b, n_uses - 1)  # had 1 ownership ref
                    adjusted.append(b)
                out_blocks = adjusted

            for ov, b in zip(eqn.outvars, out_blocks):
                if b is not None and not isinstance(ov, _DropVar):
                    env[ov] = b

            # consume inputs (one release per occurrence — last use frees)
            for v in eqn.invars:
                b = read(v)
                if b is not None:
                    self._release(b, 1, op, scope)

        outs = []
        for v in jaxpr.outvars:
            if isinstance(v, jcore.Literal):
                outs.append(self._literal_block(v, "out"))
            else:
                outs.append(env[v])
        return outs

    def _literal_block(self, v, scope: str) -> _Block:
        # Literals are scalars embedded in the program — never materialized
        # as device buffers, so they carry zero size in the trace.
        return self._new_block(0, 1, "literal", scope, BlockKind.TEMP)

    # ---- control-flow handlers -------------------------------------------------
    def _do_scan(self, eqn, read, counts, scope) -> list[_Block]:
        p = eqn.params
        body: jcore.ClosedJaxpr = p["jaxpr"]
        length, n_const, n_carry = p["length"], p["num_consts"], p["num_carry"]
        inner = body.jaxpr
        in_blocks = [read(v) or self._literal_block(v, scope) for v in eqn.invars]
        consts = in_blocks[:n_const]
        carry = in_blocks[n_const:n_const + n_carry]
        xs = in_blocks[n_const + n_carry:]
        k = max(1, min(length, self.cap))

        # XLA preallocates stacked loop outputs (ys) before the loop runs.
        ys_vars = eqn.outvars[n_carry:]
        ys_blocks: list[_Block | None] = []
        for ov in ys_vars:
            if isinstance(ov, _DropVar):
                ys_blocks.append(None)
            else:
                ys_blocks.append(self._new_block(
                    aval_bytes(ov.aval), 1, "scan_ys", scope,
                    BlockKind.ACTIVATION, shape=aval_shape(ov.aval)))

        # _interpret_region is self-balancing on its bindings (it retains
        # internal uses itself), so consts need no pre-pay across
        # iterations. The per-iteration dynamic-slice of xs is consumption
        # *we* invent, so pre-pay one ref per simulated iteration.
        for b in xs:
            self._retain(b, k)

        owned_carry: list[_Block] | None = None
        cur_carry = carry
        for it in range(k):
            x_slices = []
            for xb, xv in zip(xs, inner.invars[n_const + n_carry:]):
                sl = self._new_block(aval_bytes(xv.aval), 1, "dynamic_slice",
                                     scope, BlockKind.ACTIVATION,
                                     shape=aval_shape(xv.aval))
                self._release(xb, 1, "dynamic_slice", scope)
                x_slices.append(sl)
            # body invars are [operand-consts..., carry..., x-slices...]
            body_out = self._interpret_region(
                inner, list(consts) + list(cur_carry) + x_slices,
                [self._new_block(getattr(c, "nbytes", 0), 1, "const", scope,
                                 BlockKind.TEMP) for c in body.consts])
            # x slices were consumed inside the body (pre-paid); drop our ref
            for sl in x_slices:
                self._release(sl, 1, "scan", scope)
            new_carry = body_out[:n_carry]
            y_out = body_out[n_carry:]
            # y slices are copied into the preallocated ys buffers
            for yb in y_out:
                if yb is not None:
                    self._release(yb, 1, "scan_ys_write", scope)
            # previous iteration's carry ownership is dropped
            if owned_carry is not None:
                for ob in owned_carry:
                    if ob not in new_carry:
                        self._release(ob, 1, "scan_carry", scope)
            owned_carry = new_carry
            cur_carry = new_carry

        out = list(cur_carry) + ys_blocks
        # carries produced by the body already carry an ownership ref; the
        # *initial* carries (k could be 0-trip in theory) are caller-owned,
        # so give them an extra ref to match the region contract.
        if owned_carry is None:
            for b in cur_carry:
                self._retain(b, 1)
        return out

    def _do_while(self, eqn, read, counts, scope) -> list[_Block]:
        p = eqn.params
        body: jcore.ClosedJaxpr = p["body_jaxpr"]
        cond: jcore.ClosedJaxpr = p["cond_jaxpr"]
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        in_blocks = [read(v) or self._literal_block(v, scope) for v in eqn.invars]
        body_consts = in_blocks[cn:cn + bn]
        carry = in_blocks[cn + bn:]
        k = max(1, self.cap)
        inner = body.jaxpr
        owned = None
        cur = carry
        for _ in range(k):
            out = self._interpret_region(
                inner, list(body_consts) + list(cur),
                [self._new_block(getattr(c, "nbytes", 0), 1, "const", scope,
                                 BlockKind.TEMP) for c in body.consts])
            if owned is not None:
                for ob in owned:
                    if ob not in out:
                        self._release(ob, 1, "while_carry", scope)
            owned = out
            cur = out
        if owned is None:
            for b in cur:
                self._retain(b, 1)
        return list(cur)

    def _do_cond(self, eqn, read, counts, scope) -> list[_Block]:
        branches = eqn.params["branches"]

        def footprint(br):
            return sum(aval_bytes(ov.aval) for e in br.jaxpr.eqns
                       for ov in e.outvars)

        br = max(branches, key=footprint)
        in_blocks = [read(v) or self._literal_block(v, scope)
                     for v in eqn.invars[1:]]  # drop predicate
        # release the predicate's eqn-level use happens in the epilogue
        return self._interpret_region(
            br.jaxpr, in_blocks,
            [self._new_block(getattr(c, "nbytes", 0), 1, "const", scope,
                             BlockKind.TEMP) for c in br.consts])

    # ---- helpers ------------------------------------------------------------
    @staticmethod
    def _sub_jaxpr(eqn):
        for key in _CALL_JAXPR_KEYS:
            if key in eqn.params:
                j = eqn.params[key]
                if isinstance(j, (jcore.Jaxpr, jcore.ClosedJaxpr)):
                    return j
        return None

    @staticmethod
    def _scope_of(eqn) -> str:
        try:
            return str(eqn.source_info.name_stack)
        except Exception:
            return ""

    def lifecycles(self):
        """BlockLifecycle records straight from the tracer's blocks —
        equivalent to ``reconstruct_lifecycles(trace)`` (alloc order is
        bid order; pinned/unfreed blocks are persistent) without
        re-pairing the event stream."""
        from .events import BlockLifecycle
        return [BlockLifecycle(b.bid, b.size, b.alloc_t, b.free_t,
                               self.iteration, self.phase, b.op, b.scope,
                               b.kind, 1.0, b.shape)
                for b in self.blocks.values()]

    # ---- top-level API --------------------------------------------------------
    def trace_closed_jaxpr(self, closed: jcore.ClosedJaxpr,
                           arg_kinds: Sequence[BlockKind] | None = None,
                           arg_scopes: Sequence[str] | None = None) -> Trace:
        jaxpr = closed.jaxpr
        counts = self._use_counts(jaxpr)
        const_blocks = []
        for c in closed.consts:
            b = self._new_block(int(getattr(c, "nbytes", 0)), 1, "const",
                                "consts", BlockKind.PARAM, pinned=True,
                                shape=aval_shape(c))
            const_blocks.append(b)
        in_blocks = []
        for i, v in enumerate(jaxpr.invars):
            kind = (arg_kinds[i] if arg_kinds is not None else BlockKind.INPUT)
            scope = (arg_scopes[i] if arg_scopes is not None else f"arg{i}")
            b = self._new_block(aval_bytes(v.aval), counts.get(v, 0), "input",
                                scope, kind, pinned=True,
                                shape=aval_shape(v.aval))
            in_blocks.append(b)
        self.input_blocks = in_blocks
        outs = self._interpret_region(jaxpr, in_blocks, const_blocks)
        for b in outs:
            if b is not None:
                b.pinned = True
                b.kind = b.kind if b.kind != BlockKind.ACTIVATION else BlockKind.OUTPUT
        self.output_blocks = [b for b in outs if b is not None]
        n = self.num_events
        # space column: the jaxpr interpreter only ever allocates device
        # memory — offload passes (orchestrator) rewrite spaces later
        columns = ColumnarTrace.from_columns(
            self._ev_kind, self._ev_bid, self._ev_size, self._ev_t,
            np.full(n, self.iteration, dtype=np.int64),
            np.full(n, PHASE_CODE[self.phase], dtype=np.uint8),
            self._ev_op, self._ev_scope, self._ev_bkind,
            self._ops.table, self._scopes.table,
            self._ev_shape, self._shapes.table,
            np.zeros(n, dtype=np.uint8))
        return Trace.from_columnar(columns, num_iterations=1,
                                   meta={"phase": self.phase.value})


def trace_fn(fn: Callable, *args, arg_kinds=None, arg_scopes=None,
             scan_unroll_cap: int = 3, phase: Phase = Phase.FORWARD_BACKWARD,
             iteration: int = 0, fusion_folding: bool = False,
             **kwargs) -> tuple[Trace, JaxprMemoryTracer]:
    """Trace ``fn(*args)`` into a memory-event stream.

    ``arg_kinds``/``arg_scopes`` are flat lists aligned with the flattened
    arguments (see ``estimator.flatten_kinds``).
    """
    trace, tr, _, _ = trace_fn_with_shape(
        fn, *args, arg_kinds=arg_kinds, arg_scopes=arg_scopes,
        scan_unroll_cap=scan_unroll_cap, phase=phase, iteration=iteration,
        fusion_folding=fusion_folding, **kwargs)
    return trace, tr


def trace_fn_with_shape(fn: Callable, *args, arg_kinds=None, arg_scopes=None,
                        scan_unroll_cap: int = 3,
                        phase: Phase = Phase.FORWARD_BACKWARD,
                        iteration: int = 0, fusion_folding: bool,
                        **kwargs
                        ) -> tuple[Trace, JaxprMemoryTracer, Any, Any]:
    """``trace_fn`` plus the abstract output pytree and the closed jaxpr.

    The single ``make_jaxpr(..., return_shape=True)`` call replaces the
    separate ``eval_shape`` passes the estimator's slow path needs — one
    trace per phase instead of two (estimation fast path, ISSUE 1).
    """
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args, **kwargs)
    tr = JaxprMemoryTracer(scan_unroll_cap=scan_unroll_cap, phase=phase,
                           iteration=iteration,
                           fusion_folding=fusion_folding)
    trace = tr.trace_closed_jaxpr(closed, arg_kinds, arg_scopes)
    return trace, tr, out_shape, closed
