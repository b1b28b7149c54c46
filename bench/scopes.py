"""Device time of the training step by the program's named scopes.

The program labels the layers of its step with ``jax.named_scope``
(``models/model.py``: ``embed``, ``attn``, ``mlp`` or ``moe``,
``head_loss``; ``train/train_step.py``: ``optimizer``). XLA keeps each
instruction's name stack in its ``op_name`` metadata, where JAX marks the
passes too: forward operations under ``jvp(``, backward ones under
``transpose(jvp(``, recomputed forward ones under
``checkpoint/rematted_computation/``. The layer scan's own slicing and
stacking of weights, gradients and carries sits under ``while/body``
outside any named scope.

The profiler's trace names each device operation by its instruction
(``fusion.698``) but does not give its metadata, so the compiled text of
the very step the window ran (``Compiled.as_text()``) maps instruction to
``op_name``. Each operation's own time in the window
(``trace_reduce.self_times``) goes to exactly one bucket:

* a named scope, the innermost in its ``op_name``;
* ``layer_copy``: no named scope, inside a ``while`` body;
* ``other``: the rest;
* ``unmatched``: the trace's operation is no instruction of the module.

``remat`` is the own time of the operations whose ``op_name`` holds
``rematted_computation``, whatever their bucket: it overlaps ``attn`` and
``mlp`` on purpose, as the share of them that is recomputed.

A traced training run would call :func:`read` on its trace and
:func:`reduce` with the window and the step's compiled text, taken after
the window (``step_fn.lower(<the window's argument specs>).compile()
.as_text()``); ``bench/train_cell.py`` does not do so yet.
"""
from __future__ import annotations

import re
from collections import defaultdict

from . import trace_reduce as T

SCOPES = ("embed", "attn", "mlp", "moe", "head_loss", "optimizer")
BUCKETS = SCOPES + ("layer_copy", "other", "unmatched")   # exclusive
PASSES = ("forward", "backward", "recompute")

_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_BODY = re.compile(r"\bbody=%?([\w.\-]+)")
_WRAPPED = re.compile(r"^(?:[\w\-]+\()*([^()]*)\)*$")
_SCAN_BODY = re.compile(r"(?:^|/)while/body(?:/|$)")
REMAT = "rematted_computation"
MODULES_LINE = "XLA Modules"


def read(path: str) -> dict:
    """``trace_reduce.read`` of one xplane file, with ``"modules":
    {plane: [(start_ns, end_ns, program)]}`` from each device plane's
    ``XLA Modules`` line."""
    from jax.profiler import ProfileData
    modules = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(T.DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[plane.name] = sorted(
                        (s, s + d, n) for n, s, d in T._events(line))
    return {**T.read(path), "modules": modules}


def op_names(hlo_text: str) -> tuple[str, dict]:
    """The module's name and ``{instruction: op_name}`` for every
    instruction of the compiled text. An instruction without metadata in
    a ``while`` body (a copy XLA inserted for the loop's carry) takes its
    loop's ``op_name`` and ``/body``; elsewhere it maps to ""."""
    m = _MODULE.search(hlo_text)
    if m is None:
        raise ValueError("no HloModule line in the compiled text")
    own, where, caller = {}, {}, {}      # caller: body -> while instruction
    comp = None
    for line in hlo_text.splitlines():
        cm = _COMPUTATION.match(line)
        if cm:
            comp = cm.group(1)
            continue
        im = _INSTR.match(line)
        if im:
            name = im.group(1)
            om = _OP_NAME.search(line)
            own[name], where[name] = (om.group(1) if om else ""), comp
            bm = _BODY.search(line)
            if bm:
                caller[bm.group(1)] = name

    def effective(name: str, depth: int = 0) -> str:
        if own[name] or depth > len(caller):
            return own[name]
        loop = caller.get(where[name])
        return "" if loop is None else effective(loop, depth + 1) + "/body"
    return m.group(1), {name: effective(name) for name in own}


def scope_of(op_name: str) -> str | None:
    """The innermost named scope in ``op_name``: a path element that is,
    or wraps as ``jvp(attn)`` or ``transpose(jvp(attn))``, a name of
    :data:`SCOPES`."""
    found = None
    for part in op_name.split("/"):
        m = _WRAPPED.match(part)
        if m and m.group(1) in SCOPES:
            found = m.group(1)
    return found


def bucket_of(op_name: str) -> str:
    scope = scope_of(op_name)
    if scope is not None:
        return scope
    return "layer_copy" if _SCAN_BODY.search(op_name) else "other"


def pass_of(op_name: str) -> str:
    if REMAT in op_name:
        return "recompute"
    return "backward" if "transpose(" in op_name else "forward"


def module_names(trace: dict, lo: float, hi: float) -> set:
    """Names of the programs the trace's ``XLA Modules`` lines ran in
    ``[lo, hi)``, without the run id: ``jit_train_step(123)`` ->
    ``jit_train_step``."""
    return {name.split("(", 1)[0]
            for ivs in trace.get("modules", {}).values()
            for s, e, name in ivs if e > lo and s < hi}


def reduce(trace: dict, lo: float | None, hi: float | None,
           hlo_text: str, top: int = 5) -> dict:
    """The step's device time in ``[lo, hi)`` by bucket and pass, in
    seconds averaged over the chips, with each bucket's ``top``
    operations; without a window, the span of all device operations.
    ``named`` is false where the program carries none of the named
    scopes (the buckets of scopes are then empty and ``layer_copy`` holds
    whole layers)."""
    devs = trace["devices"]
    if lo is None or hi is None:
        lo = min(iv[0][0] for iv in devs.values() if iv)
        hi = max(max(e for _, e, _ in iv) for iv in devs.values() if iv)
    module, names = op_names(hlo_text)
    ran = module_names(trace, lo, hi)
    if ran and module not in ran:
        raise ValueError(f"the window ran {sorted(ran)}, not the compiled "
                         f"text's {module}")
    busy, per_op = 0.0, defaultdict(float)
    for ivs in devs.values():
        busy += sum(e - s for s, e in T.union(ivs, lo, hi)) * 1e-9
        for op, sec in T.self_times(ivs, lo, hi).items():
            per_op[op] += sec / len(devs)
    # an unmatched operation has no op_name, so no pass
    buckets = {b: {p: 0.0 for p in PASSES if b != "unmatched"}
               | {"total": 0.0, "top": []} for b in BUCKETS}
    remat = 0.0
    for op, sec in sorted(per_op.items(), key=lambda kv: -kv[1]):
        name = names.get(op)
        b = buckets["unmatched" if name is None else bucket_of(name)]
        if name is not None:
            b[pass_of(name)] += sec
            remat += sec if REMAT in name else 0.0
        b["total"] += sec
        if len(b["top"]) < top:
            b["top"].append([op, sec, name])
    return {
        "module": module,
        "modules_run": sorted(ran),
        "named": any(scope_of(n) for n in names.values()),
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy / len(devs),
        "buckets": buckets,
        "remat_s": remat,
        "other_s": buckets["other"]["total"],
        "unmatched_s": buckets["unmatched"]["total"],
    }

