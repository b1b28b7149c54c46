"""Trace cache — stage-1 memoization for the estimation fast path.

``estimate_training`` costs are dominated by re-tracing: every call runs
``jax.make_jaxpr`` plus eqn-by-eqn interpretation for each phase even
when the job's *structure* is unchanged. Repeated-call workloads
(hillclimb batch-size search, ``calibrate()`` loops, benchmark sweeps,
per-job admission gating in ``launch/train.py``, the admission service
daemon) therefore pay the full tracing cost over and over.

This module caches the complete per-phase tracing product — the event
stream, the reconstructed lifecycles, the input/output block summaries
and the abstract output pytree — keyed on

    (function identity, input avals + treedefs, arg kinds,
     scan_unroll_cap, phase, call-site tag)

Function identity is **content-addressed** whenever possible: a
structural digest over the function's code object (bytecode, consts,
nested code), defaults, closure cells and the module-level values it
references (``fn_identity`` -> ``("code", sha256-hex)``). Re-created
but structurally identical functions — the admission-gate pattern where
``make_estimator_hooks`` rebuilds closures per decision — therefore hit
the cache, and the same digests key the optional disk store so warm
traces survive process restarts. Functions whose closure/default values
cannot be canonically hashed fall back to the seed identity scheme: a
*weak* ``id(fn)`` reference (a hit then requires the stored function
object to still be the one presented, guarding against ``id()`` reuse).

Entries are immutable by contract — consumers copy
(``dataclasses.replace``) before rewriting lifecycles, exactly as the
Orchestrator already does.

The default process-global cache (``GLOBAL_TRACE_CACHE``) is shared by
every ``XMemEstimator`` unless an instance-specific cache is supplied,
so independent estimator instances created per admission decision still
share warm traces. A :class:`TraceCache` may additionally be layered
over a persistent store (``store=`` — see ``repro.service.store``):
content-keyed entries that miss in memory are looked up on disk, and
fresh traces are written through.
"""
from __future__ import annotations

import dataclasses
import enum as _enum
import functools
import hashlib
import threading
import types
import weakref
from collections import OrderedDict
from typing import Any, Sequence

from .events import BlockKind, BlockLifecycle, Trace


@dataclasses.dataclass(frozen=True)
class BlockInfo:
    """Lightweight summary of a tracer input/output block. ``shape``
    feeds the spec-driven sharding engine (None = unknown)."""

    bid: int
    size: int
    kind: BlockKind
    shape: tuple | None = None


@dataclasses.dataclass
class TracedPhase:
    """Everything downstream stages need from one phase trace.

    Treat every field as immutable: entries are shared across estimate
    calls. ``lifecycles`` are copied (``dataclasses.replace``) by the
    composer before any rewrite.
    """

    trace: Trace
    lifecycles: tuple[BlockLifecycle, ...]
    input_blocks: tuple[BlockInfo, ...]
    output_blocks: tuple[BlockInfo, ...]
    out_shape: Any                   # abstract output pytree (eval_shape-like)
    closed_jaxpr: Any                # for taint/coupling analysis
    arg_leaf_counts: tuple[int, ...]
    coupling: dict | None = None     # memoized update-coupling verdict

    @property
    def num_events(self) -> int:
        return len(self.trace.events)


#: numpy dtype __str__ walks the type registry on every call — at ~150
#: leaves per trace key that dominated warm estimates, so the string form
#: is memoized per dtype object (dtypes are interned by numpy/jax).
_DTYPE_STR: dict = {}


def _aval_sig(leaf) -> tuple:
    shape = tuple(getattr(leaf, "shape", ()))
    dtype = getattr(leaf, "dtype", None)
    s = _DTYPE_STR.get(dtype)
    if s is None:
        s = _DTYPE_STR[dtype] = str(dtype)
    return (shape, s)


# -- content-addressed function identity -------------------------------------
class _Uncanonical(Exception):
    """A value that cannot be hashed structurally (no content key)."""


_CANON_DEPTH_CAP = 12


def _canon_code(code, seen: frozenset, depth: int) -> tuple:
    consts = []
    for c in code.co_consts:
        if isinstance(c, types.CodeType):
            consts.append(_canon_code(c, seen, depth + 1))
        else:
            consts.append(_canon(c, seen, depth + 1))
    return ("code", code.co_name, code.co_code, tuple(consts),
            code.co_names, code.co_freevars)


def _code_names(code) -> set:
    names = set(code.co_names)
    for c in code.co_consts:
        if isinstance(c, types.CodeType):
            names |= _code_names(c)
    return names


def _canon_global(name: str, v, seen: frozenset, depth: int) -> tuple:
    """Lenient canonical form for a module-level value a function reads.

    Globals are usually modules, helper functions or literal constants —
    all canonically hashable. Unhashable values degrade to their type
    name instead of disqualifying the function: the digest then tracks
    code/closure changes but not mutations of that one global (the same
    trade JAX's persistent compilation cache makes)."""
    if isinstance(v, types.ModuleType):
        return (name, "mod", v.__name__)
    try:
        return (name, _canon(v, seen, depth))
    except _Uncanonical:
        return (name, "other", type(v).__qualname__)


def _canon_fn(fn, seen: frozenset, depth: int) -> tuple:
    if id(fn) in seen:          # recursive function: name-level reference
        return ("fnref", getattr(fn, "__qualname__", "?"))
    seen = seen | {id(fn)}
    code = fn.__code__
    cells = []
    for cell in fn.__closure__ or ():
        try:
            cells.append(_canon(cell.cell_contents, seen, depth + 1))
        except ValueError:      # empty cell
            cells.append(("emptycell",))
    gl = fn.__globals__
    globals_sig = tuple(
        _canon_global(n, gl[n], seen, depth + 1)
        for n in sorted(_code_names(code)) if n in gl)
    return ("fn", fn.__module__, fn.__qualname__,
            _canon_code(code, seen, depth + 1),
            _canon(fn.__defaults__ or (), seen, depth + 1),
            _canon(fn.__kwdefaults__ or {}, seen, depth + 1),
            tuple(cells), globals_sig)


def _canon(v, seen: frozenset, depth: int):
    """Canonical (deterministically reprable) structure for ``v``, or
    raise :class:`_Uncanonical`."""
    if depth > _CANON_DEPTH_CAP:
        raise _Uncanonical(f"depth cap at {type(v)!r}")
    if v is None or isinstance(v, (bool, int, float, str, bytes)):
        return ("v", repr(v))
    if isinstance(v, _enum.Enum):
        return ("enum", type(v).__qualname__, repr(v.value))
    if isinstance(v, (tuple, list)):
        return ("seq", type(v).__name__,
                tuple(_canon(x, seen, depth + 1) for x in v))
    if isinstance(v, (dict, types.MappingProxyType)):
        items = sorted(
            ((_canon(k, seen, depth + 1), _canon(x, seen, depth + 1))
             for k, x in v.items()), key=repr)
        return ("dict", tuple(items))
    if isinstance(v, types.FunctionType):
        return _canon_fn(v, seen, depth)
    if isinstance(v, types.MethodType):
        return ("method", _canon_fn(v.__func__, seen, depth),
                _canon(v.__self__, seen, depth + 1))
    if isinstance(v, types.BuiltinFunctionType):
        return ("builtin", getattr(v, "__module__", None) or "",
                v.__qualname__)
    if isinstance(v, functools.partial):
        return ("partial", _canon(v.func, seen, depth + 1),
                _canon(tuple(v.args), seen, depth + 1),
                _canon(dict(v.keywords or {}), seen, depth + 1))
    if isinstance(v, type):
        return ("type", getattr(v, "__module__", ""), v.__qualname__)
    if dataclasses.is_dataclass(v):
        return ("dc", type(v).__qualname__, tuple(
            (f.name, _canon(getattr(v, f.name), seen, depth + 1))
            for f in dataclasses.fields(v)))
    import numpy as np
    if isinstance(v, np.dtype):
        return ("dtype", str(v))
    shape = getattr(v, "shape", None)
    dtype = getattr(v, "dtype", None)
    if shape is not None and dtype is not None:
        try:                     # small concrete arrays: hash the bytes
            arr = np.asarray(v)
            if arr.size <= 256:
                return ("arr", arr.shape, str(arr.dtype), arr.tobytes())
        except Exception:        # noqa: BLE001 — abstract values
            pass
        return ("aval", tuple(int(d) for d in shape), str(dtype))
    raise _Uncanonical(f"unhashable {type(v)!r}")


#: memoized digests for function objects still alive (re-created
#: closures pay the ~10s-of-us canonicalization once per object).
_FN_DIGEST_MEMO: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_NO_DIGEST = object()


def fn_digest(fn) -> str | None:
    """Content digest of a function (sha256 hex), or None when its
    code/closure/defaults cannot be canonically hashed."""
    try:
        memo = _FN_DIGEST_MEMO.get(fn)
    except TypeError:
        memo = None
    if memo is not None:
        return None if memo is _NO_DIGEST else memo
    try:
        canon = _canon(fn, frozenset(), 0)
        digest = hashlib.sha256(repr(canon).encode()).hexdigest()
    except _Uncanonical:
        digest = None
    try:
        _FN_DIGEST_MEMO[fn] = _NO_DIGEST if digest is None else digest
    except TypeError:
        pass
    return digest


def fn_identity(fn) -> tuple | None:
    """Cache identity for ``fn``: ``("code", digest)`` when content-
    addressable, ``("id", id(fn))`` when only weak identity is safe,
    None when ``fn`` cannot be weak-referenced either (caching skipped).
    """
    digest = fn_digest(fn)
    if digest is not None:
        return ("code", digest)
    try:
        weakref.ref(fn)
    except TypeError:
        return None
    return ("id", id(fn))


def trace_key(fn, tag: str, flat_leaves: Sequence, treedefs: tuple,
              kinds: Sequence[BlockKind], scan_unroll_cap: int,
              phase) -> tuple | None:
    """Build a cache key, or None when ``fn`` has no safe identity."""
    ident = fn_identity(fn)
    if ident is None:
        return None
    return (
        ident, tag,
        tuple(_aval_sig(leaf) for leaf in flat_leaves),
        tuple(treedefs),                   # jax treedefs hash/compare fast
        tuple(k.value for k in kinds),
        scan_unroll_cap,
        getattr(phase, "value", phase),
    )


def key_is_content_addressed(key: tuple | None) -> bool:
    return key is not None and key[0][0] == "code"


def stable_key_digest(key: tuple) -> str:
    """Process-independent string digest for a content-addressed key —
    the persistent store's file name. Treedefs (the only non-reprable
    component) serialize via their deterministic ``str`` form."""
    ident, tag, avals, treedefs, kinds, cap, phase = key
    parts = (ident, tag, avals, tuple(str(t) for t in treedefs), kinds,
             cap, phase)
    return hashlib.sha256(repr(parts).encode()).hexdigest()


class TraceCache:
    """LRU cache of ``TracedPhase`` entries with hit/miss accounting.

    Thread-safe (the admission service serves concurrent estimates off
    one shared cache). ``store`` layers a persistent second level under
    the in-memory LRU: content-addressed keys that miss in memory are
    looked up in the store (``store_hits`` counts those), and fresh
    traces are written through, so warm estimates survive process
    restarts and are shared across worker processes.
    """

    def __init__(self, maxsize: int = 64, store=None):
        self.maxsize = maxsize
        self.store = store
        self._data: "OrderedDict[tuple, tuple[weakref.ref | None, TracedPhase]]" \
            = OrderedDict()
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0
        self.store_hits = 0
        # per-thread counters: concurrent service workers attribute
        # hits/misses to THEIR decision, not to whoever ran concurrently
        self._tstats = threading.local()

    def _tlocal(self):
        t = self._tstats
        if not hasattr(t, "hits"):
            t.hits = t.misses = t.store_hits = 0
        return t

    def thread_stats(self) -> dict:
        """Counters accumulated by the calling thread only — the right
        basis for per-request provenance deltas under concurrency."""
        t = self._tlocal()
        return {"hits": t.hits, "misses": t.misses,
                "store_hits": t.store_hits}

    def _count(self, field: str) -> None:
        setattr(self, field, getattr(self, field) + 1)
        t = self._tlocal()
        setattr(t, field, getattr(t, field) + 1)

    def get(self, fn, key: tuple | None) -> TracedPhase | None:
        if key is None:
            with self._lock:
                self._count("misses")
            return None
        probe_store = False
        with self._lock:
            ent = self._data.get(key)
            if ent is not None:
                ref, payload = ent
                if ref is None or ref() is fn:
                    self._count("hits")
                    self._data.move_to_end(key)
                    return payload
                del self._data[key]   # id() was recycled: stale entry
            probe_store = (self.store is not None
                           and key_is_content_addressed(key))
        if probe_store:
            # disk read + columnar decode happen OUTSIDE the lock so a
            # store miss/hit never stalls other threads' memory hits;
            # a racing duplicate load is benign (idempotent insert)
            payload = self.store.load(key)
            if payload is not None:
                with self._lock:
                    self._count("store_hits")
                    self._insert(key, None, payload)
                return payload
        with self._lock:
            self._count("misses")
        return None

    def _insert(self, key: tuple, ref, payload: TracedPhase) -> None:
        self._data[key] = (ref, payload)
        self._data.move_to_end(key)
        while len(self._data) > self.maxsize:
            self._data.popitem(last=False)

    def put(self, fn, key: tuple | None, payload: TracedPhase) -> None:
        if key is None:
            return
        if key_is_content_addressed(key):
            # content keys need no liveness guard: any function with the
            # same digest produces the same trace by construction
            with self._lock:
                self._insert(key, None, payload)
            if self.store is not None:
                self.store.save(key, payload)
            return
        data = self._data

        def _evict(_ref, _key=key):
            # the function died: its entry can never hit again (identity
            # check would fail) — drop the payload promptly instead of
            # letting dead traces linger until LRU pressure. Only drop if
            # the slot still holds THIS ref (a same-keyed newer entry may
            # have replaced it).
            with self._lock:
                ent = data.get(_key)
                if ent is not None and ent[0] is _ref:
                    del data[_key]

        try:
            ref = weakref.ref(fn, _evict)
        except TypeError:
            return
        with self._lock:
            self._insert(key, ref, payload)

    def clear(self) -> None:
        with self._lock:
            self._data.clear()
            self.hits = 0
            self.misses = 0
            self.store_hits = 0
            self._tstats = threading.local()

    def __len__(self) -> int:
        return len(self._data)

    def stats(self) -> dict:
        d = {"hits": self.hits, "misses": self.misses,
             "entries": len(self._data), "maxsize": self.maxsize,
             "store_hits": self.store_hits}
        if self.store is not None:
            d["store"] = self.store.stats()
        return d


#: Shared by all estimators by default — admission gates and sweeps that
#: construct a fresh ``XMemEstimator`` per decision still get warm traces.
GLOBAL_TRACE_CACHE = TraceCache()
