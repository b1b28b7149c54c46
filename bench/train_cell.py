"""A training cell: the admission gate, then back-to-back train steps.

Set-up builds one object, the program's jitted and donated train step
(``repro.train.make_train_step``, jitted as ``launch/train.py:train_loop``
jits it) with its state, after the program's own gate
(``replan_if_needed`` against the device's ``bytes_limit``). It drives
that object from the seed's weights through its first steps, which
compile it and give the readings that the reference checks, and hands
the same object to the window. The window runs steps, each blocked as
``train_loop`` blocks it, until ``--seconds`` have passed.

After the window: the device's peak, the bytes held by the live
parameters and optimizer state, and the measured need of one more step
by the ballast bisection (``memory.py``). Then the program's state is
freed and the plain reference runs the first steps from the same seed.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import os
import shutil
import statistics
import tempfile
import time

from . import compare, memory, weights as W
from .flops import config_step_flops
from .peaks import peak

MiB = 2**20
CHECK_STEPS = 3       # steps the reference follows
WINDOW_SPAN = "bench.window"


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import AttentionConfig, ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=cfg["head_dim"],
        attention=AttentionConfig(rope_theta=cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["param_dtype"], remat=cfg["train"]["remat"])


def program_policy(cfg: dict):
    from repro.train import TrainPolicy
    tr = cfg["train"]
    return TrainPolicy(
        optimizer=tr["optimizer"], learning_rate=tr["learning_rate"],
        clip_norm=tr["clip_norm"],
        opt_kwargs=(("weight_decay", tr["weight_decay"]),
                    ("b1", tr["adam_b1"]), ("b2", tr["adam_b2"]),
                    ("eps", tr["adam_eps"])))


@dataclasses.dataclass
class Program:
    """The system under test, as set-up builds it."""

    cfg: dict
    batch: int
    seq: int
    device: object
    limit: int
    gate: object = None          # the gate's EstimateReport
    admitted: bool = False
    step_fn: object = None
    opt_init: object = None
    params: object = None
    opt_state: object = None
    steps_run: int = 0
    losses: list = dataclasses.field(default_factory=list)

    def build(self) -> None:
        import jax
        from repro.configs.base import ShapeSpec
        from repro.launch.train import replan_if_needed
        from repro.models import model as M
        from repro.train import make_train_step
        mcfg = program_config(self.cfg)
        policy = program_policy(self.cfg)
        shape = ShapeSpec(self.cfg["name"], self.seq, self.batch, "train")
        policy, self.gate = replan_if_needed(mcfg, policy, shape, self.limit)
        self.admitted = self.gate.peak_bytes <= self.limit
        if policy.microbatches != 1:
            raise RuntimeError(f"the gate replanned to {policy.microbatches}"
                               " microbatches; the cell states one")
        train_step, opt = make_train_step(mcfg, policy)
        self.step_fn = jax.jit(train_step, donate_argnums=(0, 1))
        self.opt_init = jax.jit(opt.init)
        want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype),
                                      M.abstract_params(mcfg))
        have = jax.eval_shape(lambda: W._make(self.cfg, W.seed_key(0)))
        have = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), have)
        if want != have:
            raise RuntimeError("the benchmark's weight layout differs from "
                               f"the program's: {have} vs {want}")

    def start(self, seed: int) -> None:
        self.params = W.make(self.cfg, seed, self.device)
        self.opt_state = self.opt_init(self.params)
        self.steps_run = 0
        self.losses = []

    def step(self, seed: int) -> float:
        """One step on batch ``steps_run``, blocked as ``train_loop``
        blocks it; returns its loss."""
        import jax
        import jax.numpy as jnp
        host = W.batch(self.cfg["vocab_size"], self.batch, self.seq, seed,
                       self.steps_run)
        batch = jax.tree_util.tree_map(jnp.asarray, host)
        loss, self.params, self.opt_state = self.step_fn(
            self.params, self.opt_state, batch)
        jax.block_until_ready((loss, self.params, self.opt_state))
        self.steps_run += 1
        self.losses.append(float(loss))
        return self.losses[-1]

    def free(self) -> None:
        self.params = self.opt_state = None


def first_steps(prog: Program, seed: int) -> dict:
    """Steps 0..CHECK_STEPS-1 from the seed, with the readings the
    reference checks: each loss, the first gradient as the optimizer got
    it (Adam's first moment after one step over ``1 - b1``), and the
    change of the weights after the last of them."""
    import jax
    prog.start(seed)
    grad_norms = None
    b1 = prog.cfg["train"]["adam_b1"]
    for i in range(CHECK_STEPS):
        prog.step(seed)
        if i == 0:
            m = jax.jit(W.leaf_norms)(prog.opt_state["m"])
            grad_norms = [float(x) / (1 - b1) for x in jax.device_get(m)]
    delta = W.delta_norms_fn(prog.cfg)(prog.params, W.seed_key(seed))
    return {"losses": list(prog.losses), "grad_norms": grad_norms,
            "delta_norms": [float(x) for x in jax.device_get(delta)]}


def measure_need(prog: Program, seed: int, in_use: int) -> dict:
    """The ballast bisection. A first probe holds the step to the gate's
    estimate (ballast ``bytes_limit - estimate``): if it fails, the
    estimate is below the need. The bisection then finds the largest
    ballast, in steps of ``bytes_limit/256``, with which one more step
    completes."""
    import jax
    import jax.numpy as jnp
    res = (prog.limit // 256) // MiB * MiB
    chunk = jax.jit(lambda: jnp.zeros((res // 4,), jnp.float32))
    remade = [0]

    def alloc(nbytes: int) -> list:
        out = [chunk() for _ in range(nbytes // res)]
        rest = (nbytes % res) // MiB * MiB
        if rest:
            out.append(jax.jit(lambda: jnp.zeros((rest // 4,),
                                                 jnp.float32))())
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
    lo, hi = 0, (prog.limit - in_use) // res + 1
    if fits_estimate:
        lo = held // res
    else:
        hi = -(-held // res)
    k, probes = memory.bisect_ballast(lambda k: probe(k * res), lo, hi)
    return {"need": prog.limit - k * res, "resolution": res,
            "fits_estimate": fits_estimate, "probes": probes + 1,
            "remade": remade[0], "seconds": time.perf_counter() - t0}


class GcPauses:
    """A ``gc.callbacks`` hook: each collection's generation and
    duration."""

    def __init__(self):
        self.pauses: list = []
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))

    def summary(self) -> dict:
        return {"collections": len(self.pauses),
                "full": sum(1 for g, _ in self.pauses if g == 2),
                "seconds": sum(t for _, t in self.pauses),
                "longest_s": max((t for _, t in self.pauses), default=0.0)}


def _trace_window(trace_dir: str) -> dict:
    from . import trace_reduce as T
    tr = T.read(T.find_xplane(trace_dir))
    win = T.window_of(tr["host"], WINDOW_SPAN)
    return T.reduce(tr, *(win or (None, None)))


def run(cell, seed: int, seconds: float, trace: bool, t_process: float,
        devices) -> dict:
    """One run of a training cell. Returns the harness's result parts."""
    import jax
    from repro.launch import device as D
    from .reference import dense_lm

    t_run = time.perf_counter()
    cfg, traffic = cell.config, cell.traffic
    dev = devices[0]
    prog = Program(cfg=cfg, batch=cfg["train"]["batch"],
                   seq=traffic["seq_len"], device=dev,
                   limit=D.hbm_bytes(dev))
    baseline = D.bytes_in_use(dev)
    prog.build()
    t_gate = time.perf_counter()
    if not prog.admitted:
        raise RuntimeError(f"the gate rejected the cell's job: estimate "
                           f"{prog.gate.peak_bytes} > {prog.limit}")
    readings = first_steps(prog, seed)
    # the set-up's objects (the gate's traces and replay, the programs)
    # go to the permanent generation, so that a full collection inside
    # the window does not walk them
    gc.collect()
    gc.freeze()
    pauses = GcPauses()
    gc.callbacks.append(pauses)

    # the measured window
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    t_window = time.perf_counter()
    setup_s = t_window - t_process
    n = 0
    ends = [t_window]
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
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
    # set-up by part: imports and the chip's start, the gate and the
    # step's tracing, weights with the compile and the checked steps
    setup_parts = {"start_s": t_run - t_process, "gate_s": t_gate - t_run,
                   "first_steps_s": t_window - t_gate}
    if trace:
        jax.profiler.stop_trace()
    tokens_per_s = n * prog.batch * prog.seq / window_s
    losses = list(prog.losses)

    peak_bytes = D.peak_bytes_in_use(dev)
    in_use = D.bytes_in_use(dev)
    persistent = in_use - baseline
    need = measure_need(prog, seed, in_use)
    prog.free()
    jax.clear_caches()

    t_ref = time.perf_counter()
    ref = dense_lm.train_readings(cfg, seed, CHECK_STEPS, prog.batch,
                                  prog.seq, device=dev)
    reference_s = time.perf_counter() - t_ref
    checks = compare.train_checks(readings, ref, cfg)
    finite = all(math.isfinite(x) for x in losses)
    correct = (finite and n > 0 and compare.passes(checks))

    est, est_p = prog.gate.peak_bytes, prog.gate.persistent_bytes
    under = not need["fits_estimate"]
    step_flops = config_step_flops(cfg, prog.batch, prog.seq)
    ctx = {
        "tokens_per_s": tokens_per_s,
        "step_flops": step_flops,
        "tokens_per_step": prog.batch * prog.seq,
        "chips": len(devices),
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
    device = {"memory_peak_bytes": peak_bytes}
    breakdown = None
    if trace:
        red = _trace_window(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx["trace"] = red
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = {"device_ops": [list(x) for x in red["device_ops"]],
                     "idle_gaps": red["idle_gaps"]}
    log = {
        "gate_estimate_bytes": est, "gate_persistent_bytes": est_p,
        "bytes_limit": prog.limit, "measured_need_bytes": need["need"],
        "measured_persistent_bytes": persistent,
        "fits_estimate": need["fits_estimate"],
        "bisection": need, "window_steps": n, "window_s": window_s,
        "setup_parts": setup_parts, "gc_window": pauses.summary(),
        "window_step_s": {"median": step_med, "max": max(steps_s),
                          "over_median": sum(steps_s) - n * step_med},
        "median_loss_window": statistics.median(losses[CHECK_STEPS:]),
        "gaps": compare.train_gaps(readings, ref),
        "readings": readings, "reference": ref, "reference_s": reference_s,
    }
    return {"correct": correct, "attempted": n + 1,
            "failed": int(under) + (0 if finite else 1),
            "e2e": e2e, "ctx": ctx, "device": device,
            "breakdown": breakdown, "checks": checks, "log": log}
