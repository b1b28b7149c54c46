"""A training cell on a mesh: the per-device gate, then back-to-back
train steps of a job laid out over several chips.

Set-up builds the job as ``train_loop(mesh=...)`` builds it, through the
program's own ``repro.launch.train.mesh_job``, on a mesh of the first
chips shaped as the configuration's ``mesh`` says: the gate holds one
device's share (each block divided by its sharding, plus the mesh's
collective staging) to the smallest ``bytes_limit``, then the weights
from the seed and the optimizer state are placed in the program's
shardings and the step is jitted, donated, with sharded outputs. The
first steps compile it and give the readings that the reference checks,
and the window runs steps, each blocked as ``train_loop`` blocks it,
until ``--seconds`` have passed.

After the window: each device's peak, the bytes each holds with the
state live, and the measured need: one ballast, replicated on every
device, is bisected as in ``train_cell`` (``memory.py``), so the need is
that of the fullest device. Then the program's state is freed and the
plain reference (``reference/dense_lm_mesh.py``), laid out on the same
chips by its own table, runs the first steps from the same seed.

A traced run leaves the window untraced and, after it, traces
``TRACE_STEPS`` more steps of the same kind: four chips' operations over
a whole window make a trace that takes minutes to collect and read. The
traced steps are reduced on every device (``trace_reduce.py``) and the
collective operations bucketed by their HLO opcode; a collective's
exposed time is the part of it in which no other operation runs on its
device. The log gives the seconds the profiler and the reading took.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import re
import shutil
import statistics
import tempfile
import time
from functools import partial

from . import compare, memory, trace_reduce as T, weights as W
from .flops import config_step_flops
from .peaks import peak
from .train_cell import CHECK_STEPS, GcPauses, program_config, program_policy

MiB = 2**20
TRACED_SPAN = "bench.traced_steps"
TRACE_STEPS = 5
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
# operations whose interval holds those of their body on the same line
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")


@dataclasses.dataclass
class MeshProgram:
    """The system under test, as set-up builds it."""

    cfg: dict
    batch: int
    seq: int
    devices: list
    limit: int
    job: object = None           # repro.launch.train.MeshJob
    params: object = None
    opt_state: object = None
    steps_run: int = 0
    losses: list = dataclasses.field(default_factory=list)

    @property
    def gate(self):
        return self.job.report

    def build(self) -> None:
        import jax
        from repro.configs.base import ShapeSpec
        from repro.launch.mesh import make_mesh
        from repro.launch.train import mesh_job
        from repro.models import model as M
        mcfg = program_config(self.cfg)
        shape = ShapeSpec(self.cfg["name"], self.seq, self.batch, "train")
        mesh = make_mesh(self.cfg["mesh"], self.devices)
        self.job = mesh_job(mcfg, shape, program_policy(self.cfg), mesh,
                            hbm_bytes=self.limit)
        if self.job.policy.microbatches != 1:
            raise RuntimeError(f"the gate replanned to "
                               f"{self.job.policy.microbatches} "
                               "microbatches; the cell states one")
        want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype),
                                      M.abstract_params(mcfg))
        have = jax.eval_shape(lambda: W._make(self.cfg, W.seed_key(0)))
        have = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), have)
        if want != have:
            raise RuntimeError("the benchmark's weight layout differs from "
                               f"the program's: {have} vs {want}")

    @property
    def mesh(self):
        return self.job.mesh

    def start(self, seed: int) -> None:
        self.params, self.opt_state = self.job.place(
            partial(W._make, self.cfg), W.seed_key(seed))
        self.steps_run = 0
        self.losses = []

    def step(self, seed: int) -> float:
        """One step on batch ``steps_run``, blocked as ``train_loop``
        blocks it; returns its loss."""
        import jax
        host = W.batch(self.cfg["vocab_size"], self.batch, self.seq, seed,
                       self.steps_run)
        loss, self.params, self.opt_state = self.job.step(
            self.params, self.opt_state, self.job.put(host))
        jax.block_until_ready((loss, self.params, self.opt_state))
        self.steps_run += 1
        self.losses.append(float(loss))
        return self.losses[-1]

    def free(self) -> None:
        self.params = self.opt_state = None


def first_steps(prog: MeshProgram, seed: int) -> dict:
    """Steps 0..CHECK_STEPS-1 from the seed, with ``train_cell``'s
    readings: each loss, the first gradient as the optimizer got it
    (Adam's first moment after one step over ``1 - b1``), and the change
    of the weights after the last of them."""
    import jax
    from .reference import dense_lm_mesh
    prog.start(seed)
    grad_norms = None
    b1 = prog.cfg["train"]["adam_b1"]
    for i in range(CHECK_STEPS):
        prog.step(seed)
        if i == 0:
            m = jax.jit(W.leaf_norms)(prog.opt_state["m"])
            grad_norms = [float(x) / (1 - b1) for x in jax.device_get(m)]
    return {"losses": list(prog.losses), "grad_norms": grad_norms,
            "delta_norms": dense_lm_mesh.delta_norms(
                prog.cfg, prog.params, W.seed_key(seed))}


def reference_readings(prog: MeshProgram, seed: int, **kw) -> dict:
    from .reference import dense_lm_mesh
    return dense_lm_mesh.train_readings(prog.cfg, seed, CHECK_STEPS,
                                        prog.batch, prog.seq, prog.mesh,
                                        **kw)


def in_use(devices) -> list:
    from repro.launch import device as D
    return [D.bytes_in_use(d) for d in devices]


def measure_need(prog: MeshProgram, seed: int, used: int) -> dict:
    """``train_cell.measure_need`` with the ballast replicated on every
    device of the mesh, so that the fullest device decides each probe;
    ``used`` is the largest device's bytes in use."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    res = (prog.limit // 256) // MiB * MiB
    everywhere = NamedSharding(prog.mesh, P())

    def zeros(nbytes):
        return jax.jit(lambda: jnp.zeros((nbytes // 4,), jnp.float32),
                       out_shardings=everywhere)
    chunk = zeros(res)
    remade = [0]

    def alloc(nbytes: int) -> list:
        out = [chunk() for _ in range(nbytes // res)]
        rest = (nbytes % res) // MiB * MiB
        if rest:
            out.append(zeros(rest)())
        return jax.block_until_ready(out)

    def probe(nbytes: int) -> bool:
        ballast = None
        try:
            ballast = alloc(nbytes)
            prog.step(seed)
            return True
        except Exception as e:  # noqa: BLE001 — only an OOM is an answer
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            return False
        finally:
            del ballast
            leaves = jax.tree_util.tree_leaves((prog.params, prog.opt_state))
            if any(x.is_deleted() for x in leaves):
                remade[0] += 1
                prog.start(seed)

    t0 = time.perf_counter()
    est = prog.gate.peak_bytes
    held = max(prog.limit - est, 0)
    fits_estimate = probe(held)
    lo, hi = 0, (prog.limit - used) // res + 1
    if fits_estimate:
        lo = held // res
    else:
        hi = -(-held // res)
    k, probes = memory.bisect_ballast(lambda k: probe(k * res), lo, hi)
    return {"need": prog.limit - k * res, "resolution": res,
            "fits_estimate": fits_estimate, "probes": probes + 1,
            "remade": remade[0], "seconds": time.perf_counter() - t0}


def opcode(hlo_text: str) -> str:
    """``%all-gather-start.3 = (bf16[..], ..) all-gather-start(..)`` ->
    ``all-gather-start``; ``""`` where the text names none."""
    m = _OPCODE.search(" " + hlo_text.split(" = ", 1)[-1])
    return m.group(1) if m else ""


def collective_bucket(name: str, op: str) -> str | None:
    """The bucket of a collective operation: its opcode, or, for a
    collective wrapped in an async or fusion op named after it,
    ``<opcode>:<collective>``; ``None`` for any other operation."""
    if op.startswith(COLLECTIVES):
        return op
    for c in COLLECTIVES:
        if name.startswith(c):
            return f"{op}:{c}"
    return None


def read_trace(path: str) -> dict:
    """``trace_reduce.read``'s ``devices`` and ``host``, and
    ``ops``: per device plane, ``(start_ns, end_ns, name, opcode)``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, ops, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith(T.DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == T.OPS_LINE:
                    evs = sorted((float(e.start_ns),
                                  float(e.start_ns) + float(e.duration_ns),
                                  T.op_name(e.name), opcode(e.name))
                                 for e in line.events)
                    ops[plane.name] = evs
                    devices[plane.name] = [iv[:3] for iv in evs]
        elif plane.name == T.HOST_PLANE:
            for line in plane.lines:
                host.extend((float(e.start_ns),
                             float(e.start_ns) + float(e.duration_ns),
                             e.name) for e in line.events)
    return {"devices": devices, "host": host, "ops": ops}


def _covered(a, b) -> float:
    """Length of the merged intervals ``a`` that the merged intervals
    ``b`` overlap."""
    out, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            out += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return out


def collectives(ops: dict, lo: float, hi: float) -> dict:
    """Per device plane: the seconds of each collective bucket in
    ``[lo, hi)`` and ``exposed_s``, the union of the collectives'
    intervals less the intervals of every other operation but the
    containers (``while``, ``conditional``, ``call``)."""
    out = {}
    for plane, evs in ops.items():
        coll, other, by = [], [], {}
        for s, e, name, op in evs:
            b = collective_bucket(name, op)
            if b is not None:
                coll.append((s, e))
                d = min(e, hi) - max(s, lo)
                if d > 0:
                    by[b] = by.get(b, 0.0) + d * 1e-9
            elif op not in CONTAINERS:
                other.append((s, e))
        cu, ou = T.union(coll, lo, hi), T.union(other, lo, hi)
        total = sum(e - s for s, e in cu)
        out[plane] = {"exposed_s": (total - _covered(cu, ou)) * 1e-9,
                      "collective_s": total * 1e-9,
                      "by_opcode": dict(sorted(by.items()))}
    return out


def traced_steps(prog: MeshProgram, seed: int) -> tuple[dict, dict, dict]:
    """``TRACE_STEPS`` steps under the profiler, in the span
    ``TRACED_SPAN``: the trace's reduction over that span, each device's
    collectives in it, and the seconds each part of the tracing took."""
    import jax
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation(TRACED_SPAN):
            for _ in range(TRACE_STEPS):
                prog.step(seed)
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        t1 = time.perf_counter()
        tr = read_trace(T.find_xplane(trace_dir))
        t2 = time.perf_counter()
        win = T.window_of(tr["host"], TRACED_SPAN)
        red = T.reduce(tr, *(win or (None, None)))
        lo, hi = win or (min(iv[0][0] for iv in tr["ops"].values() if iv),
                         max(iv[-1][1] for iv in tr["ops"].values() if iv))
        coll = collectives(tr["ops"], lo, hi)
        t3 = time.perf_counter()
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return red, coll, {
        "steps": TRACE_STEPS, "span_found": win is not None,
        "device_events": sum(len(v) for v in tr["ops"].values()),
        "stop_s": t1 - t0, "read_s": t2 - t1, "reduce_s": t3 - t2}


def run(cell, seed: int, seconds: float, trace: bool, t_process: float,
        devices) -> dict:
    """One run of a mesh training cell. Returns the harness's result
    parts, as ``train_cell.run`` does."""
    import jax
    from repro.launch import device as D
    from repro.obs import spans as obs_spans

    t_run = time.perf_counter()
    cfg, traffic = cell.config, cell.traffic
    prog = MeshProgram(cfg=cfg, batch=cfg["train"]["batch"],
                       seq=traffic["seq_len"], devices=list(devices),
                       limit=min(D.hbm_bytes(d) for d in devices))
    baseline = in_use(prog.devices)
    prog.build()
    t_gate = time.perf_counter()
    spans = obs_spans.Tracer()
    with obs_spans.activate(spans):
        readings = first_steps(prog, seed)
    place_s = [s.duration_s for s in spans.spans() if s.name == "train.place"]
    gc.collect()
    gc.freeze()
    pauses = GcPauses()
    gc.callbacks.append(pauses)

    # the measured window
    t_window = time.perf_counter()
    setup_s = t_window - t_process
    n = 0
    ends = [t_window]
    while True:
        prog.step(seed)
        n += 1
        ends.append(time.perf_counter())
        if ends[-1] - t_window >= seconds:
            break
    window_s = ends[-1] - t_window
    steps_s = [b - a for a, b in zip(ends, ends[1:])]
    step_med = statistics.median(steps_s)
    gc.callbacks.remove(pauses)
    setup_parts = {"start_s": t_run - t_process, "gate_s": t_gate - t_run,
                   "first_steps_s": t_window - t_gate}
    tokens_per_s = n * prog.batch * prog.seq / window_s
    losses = list(prog.losses)
    if trace:
        red, coll, trace_parts = traced_steps(prog, seed)

    peaks = [D.peak_bytes_in_use(d) for d in prog.devices]
    used = in_use(prog.devices)
    persistent = max(u - b for u, b in zip(used, baseline))
    need = measure_need(prog, seed, max(used))
    prog.free()
    jax.clear_caches()

    t_ref = time.perf_counter()
    ref = reference_readings(prog, seed)
    reference_s = time.perf_counter() - t_ref
    checks = compare.train_checks(readings, ref, cfg)
    finite = all(math.isfinite(x) for x in losses)
    correct = finite and n > 0 and compare.passes(checks)

    gate = prog.gate
    est, est_p = gate.peak_bytes, gate.persistent_bytes
    under = not need["fits_estimate"]
    dev = prog.devices[0]
    ctx = {
        "tokens_per_s": tokens_per_s,
        "step_flops": config_step_flops(cfg, prog.batch, prog.seq),
        "tokens_per_step": prog.batch * prog.seq,
        "chips": len(prog.devices),
        "peak_flops_per_s": peak(dev.device_kind)["bf16_flops_per_s"],
        "estimate": {"peak": est, "persistent": est_p},
        "measured": {"need": need["need"], "persistent": persistent,
                     "resolution": need["resolution"]},
    }
    e2e = {
        "tokens_per_s": tokens_per_s,
        "est_err_pct": memory.rel_error_pct(est, need["need"],
                                            need["resolution"]),
        "setup_s": setup_s,
    }
    device = {"memory_peak_bytes": max(peaks)}
    breakdown = None
    if trace:
        ctx["trace"] = red
        ctx["collectives"] = {"window_s": red["window_s"], "devices": coll}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = {"device_ops": [list(x) for x in red["device_ops"]],
                     "idle_gaps": red["idle_gaps"],
                     "collectives": coll}
    bd = gate.breakdown
    log = {
        "gate_estimate_bytes": est, "gate_persistent_bytes": est_p,
        "gate_collective_blocks": bd.get("collective_blocks"),
        "gate_collective_bytes": bd.get("collective_bytes"),
        "gate_fused_blocks": bd.get("fused_blocks"),
        "gate_fused_bytes": bd.get("fused_bytes"),
        "place_s": place_s,
        "bytes_limit": prog.limit, "measured_need_bytes": need["need"],
        "measured_persistent_bytes": persistent,
        "bytes_in_use": used, "peak_bytes_in_use": peaks,
        "baseline_bytes": baseline,
        "fits_estimate": need["fits_estimate"],
        "bisection": need, "window_steps": n, "window_s": window_s,
        "setup_parts": setup_parts, "gc_window": pauses.summary(),
        "window_step_s": {"median": step_med, "max": max(steps_s),
                          "over_median": sum(steps_s) - n * step_med},
        "median_loss_window": statistics.median(losses[CHECK_STEPS:]),
        "gaps": compare.train_gaps(readings, ref),
        "readings": readings, "reference": ref, "reference_s": reference_s,
    }
    if trace:
        log["trace_parts"] = trace_parts
    return {"correct": correct, "attempted": n + 1,
            "failed": int(under) + (0 if finite else 1),
            "e2e": e2e, "ctx": ctx, "device": device,
            "breakdown": breakdown, "checks": checks, "log": log}
