"""Training driver with the xMem admission gate (first-class feature).

Flow:
  1. resolve --arch config + shapes + mesh;
  2. **admission gate**: run the xMem estimator on the exact
     (fwd_bwd, update, opt_init) triple of this job; if the per-device
     estimate exceeds HBM, reject (or auto-replan: more microbatches)
     BEFORE touching devices — the paper's scheduler integration;
  3. init or restore from the newest valid checkpoint (fault tolerance);
  4. step loop with periodic checkpoints, straggler monitoring, and an
     emergency checkpoint on any exception.

On the CPU, use smoke-scale flags (the gate then holds the job to a
v5e's nominal 16 GiB unless ``--hbm-gib`` says otherwise):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-32b --smoke \
      --steps 20 --ckpt-dir /tmp/ckpt
``--mesh data=1,model=4`` lays the job out over the first four devices
(tensor-parallel, as ``launch/mesh.py:arch_sharding_policy`` places it)
and gates each device's share.
On a TPU the capacity is the device's own ``bytes_limit``;
``chip_smoke.py`` at the repo root drives this loop at full width.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp

from ..configs import get_config, get_smoke
from ..configs.base import ShapeSpec, smoke_shape, TRAIN_4K
from ..core.estimator import XMemEstimator
from ..models import model as M
from ..obs import spans as obs_spans
from ..train import (CheckpointManager, StragglerMonitor, SyntheticDataset,
                     TrainPolicy, make_estimator_hooks, make_train_step)
from . import device as D


def admission_check(cfg, policy: TrainPolicy, shape: ShapeSpec,
                    hbm_bytes: int = D.V5E_HBM_BYTES, shard_factor_fn=None,
                    verbose: bool = True, est: XMemEstimator | None = None,
                    service=None, return_decision: bool = False,
                    collective_specs=()):
    """xMem gate: estimate peak device memory a priori (CPU-only).

    Decisions route through the admission service
    (:mod:`repro.service.admission`): estimator hooks are re-created per
    decision, but the content-addressed trace cache makes structurally
    identical jobs warm (and, with a persistent store, warm across
    process restarts). Pass ``service`` to amortize across repeated
    gate decisions; ``est`` builds a one-off service around an existing
    estimator's cache (back-compat)."""
    from ..service import AdmissionRequest, AdmissionService
    fwd_bwd, update, opt_init = make_estimator_hooks(cfg, policy)
    from ..configs.registry import input_specs
    params = M.abstract_params(cfg)
    batch = input_specs(cfg, shape)
    if service is None:
        service = AdmissionService(
            workers=1, cache=est.trace_cache if est is not None else None)
    decision = service.decide(AdmissionRequest(
        job_id=f"{cfg.name}/{shape.name}/mb{policy.microbatches}",
        fwd_bwd_fn=fwd_bwd, params=params, batch=batch,
        update_fn=update, opt_init_fn=opt_init,
        shard_factor_fn=shard_factor_fn, collective_specs=collective_specs,
        capacity=hbm_bytes))
    rep = decision.report
    ok = decision.admit
    if verbose:
        tc = decision.provenance.get("trace_cache", {})
        cache_note = (f", trace cache {tc.get('hits', 0)}h/"
                      f"{tc.get('misses', 0)}m"
                      f" [{decision.provenance['source']}]")
        bd = rep.breakdown
        parts = (f", fused {bd['fused_blocks']} values "
                 f"{bd['fused_bytes']/2**30:.2f} GiB"
                 if "fused_blocks" in bd else "")
        if bd.get("collective_blocks"):
            parts += (f", collective staging {bd['collective_blocks']} "
                      f"buffers {bd['collective_bytes']/2**30:.2f} GiB")
        print(f"[xmem] estimated peak {rep.peak_bytes/2**30:.2f} GiB "
              f"(persistent {rep.persistent_bytes/2**30:.2f}{parts}) vs HBM "
              f"{hbm_bytes/2**30:.0f} GiB -> "
              f"{'ADMIT' if ok else 'REJECT'} "
              f"({decision.wall_s:.2f}s estimation{cache_note})")
    if return_decision:
        return ok, rep, decision
    return ok, rep


def replan_if_needed(cfg, policy: TrainPolicy, shape, hbm_bytes,
                     shard_factor_fn=None, service=None,
                     collective_specs=()):
    """Auto-replan a rejected job through the remediation planner.

    The planner's microbatch axis replaces the old ad-hoc doubling
    loop: candidates are the accumulation factors that still divide the
    global batch (``_split_microbatches`` requires even splits), they
    are probed cheapest-modeled-cost first, and ``early_stop`` bails at
    the first feasible offer — the same trace count as the doubling
    loop, but the chosen plan comes back with its modeled slowdown and
    is reproducible via ``CounterOffer.admission_request``. A sharded
    job passes its per-device ``shard_factor_fn`` and its mesh's
    ``collective_specs`` (``mesh_collective_specs``), which the gate and
    every replanned candidate are estimated with."""
    from ..plan import PlanSpace, RemediationPlanner
    from ..service import AdmissionService
    service = service or AdmissionService(workers=1)  # warm across probes
    ok, rep, decision = admission_check(cfg, policy, shape, hbm_bytes,
                                        shard_factor_fn, service=service,
                                        return_decision=True,
                                        collective_specs=collective_specs)
    if ok:
        return policy, rep
    # microbatch axis only: batch size and remat belong to the caller,
    # mirroring the replaced doubling loop's contract; the gate's own
    # rejection is the baseline, so the planner does not re-estimate it
    space = PlanSpace(batches=(), remat=(), devices=(), mb_doublings=3,
                      early_stop=True, max_offers=1)
    res = RemediationPlanner(service).plan(
        cfg, policy, shape, capacity=hbm_bytes, space=space,
        job_id=f"{cfg.name}/{shape.name}", baseline=decision,
        shard_factor_fn=shard_factor_fn, collective_specs=collective_specs)
    offer = res.best()
    if offer is not None:
        p = dataclasses.replace(policy, microbatches=offer.microbatches)
        print(f"[xmem] replanning: microbatches -> {p.microbatches} "
              f"(peak {offer.peak_bytes/2**30:.2f} GiB, modeled "
              f"slowdown x{offer.slowdown:.2f})")
        return p, offer.report
    return policy, rep


def gate(cfg, policy: TrainPolicy, shape, hbm_bytes, where: str = "",
         **kw):
    """:func:`replan_if_needed`'s ``(policy, report)``; raises
    ``MemoryError`` when even the replanned job does not fit."""
    policy, rep = replan_if_needed(cfg, policy, shape, hbm_bytes, **kw)
    if rep.peak_bytes > hbm_bytes:
        raise MemoryError(f"xmem gate: job will not fit{where} — rejected")
    return policy, rep


@dataclasses.dataclass
class MeshJob:
    """A training job laid out on a mesh, as :func:`mesh_job` builds
    it: the gate's per-device decision, the shardings of the parameters,
    optimizer state and batch, and the jitted, donated step."""

    mesh: object
    policy: TrainPolicy          # after any replan by the gate
    report: object | None        # the gate's per-device EstimateReport
    shardings: dict              # "params" / "opt_state" / "batch"
    abstract: dict               # the same keys: sharded ShapeDtypeStructs
    jitted: object               # the step: donated, sharded outputs
    opt_init: object             # jitted optimizer init, sharded outputs

    @contextlib.contextmanager
    def scope(self):
        """The mesh and the model's logical axis rules, bound while the
        step is traced."""
        from ..distributed.act_sharding import (DEFAULT_RULES,
                                                logical_axis_rules)
        with self.mesh, logical_axis_rules(self.mesh, DEFAULT_RULES):
            yield

    def place(self, init_fn, key):
        """``(params, opt_state)``: the weights ``init_fn(key)`` made on
        the mesh in their shardings, and their optimizer state."""
        with obs_spans.span("train.place", devices=self.mesh.size), \
                self.scope():
            params = jax.jit(init_fn,
                             out_shardings=self.shardings["params"])(key)
            opt_state = self.opt_init(params)
            jax.block_until_ready((params, opt_state))
        return params, opt_state

    def put(self, batch):
        """A host batch, laid out on the mesh."""
        return jax.device_put(batch, self.shardings["batch"])

    def step(self, params, opt_state, batch):
        """One step, ``(loss, params, opt_state)``; not blocked."""
        with self.scope():
            return self.jitted(params, opt_state, batch)

    def lower(self):
        """The step lowered for the sharded argument shapes."""
        with self.scope():
            return self.jitted.lower(self.abstract["params"],
                                     self.abstract["opt_state"],
                                     self.abstract["batch"])


def mesh_job(cfg, shape, policy: TrainPolicy, mesh, *,
             hbm_bytes: int | None = None,
             skip_gate: bool = False) -> MeshJob:
    """Lay a training job out on ``mesh`` behind the per-device gate.

    The sharding policy is :func:`repro.launch.mesh.arch_sharding_policy`'s;
    the parameter, optimizer-state and batch shardings are the rule
    engine's (``distributed/sharding.py``). The gate
    (:func:`replan_if_needed`) estimates one device's share: each block
    divided by the factor its ``PartitionSpec`` implies, plus the mesh's
    collective staging buffers. ``hbm_bytes`` defaults to the smallest
    ``bytes_limit`` of the mesh's devices. Raises ``MemoryError`` when
    the gate rejects the job."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..configs.registry import input_specs
    from ..distributed.sharding import (batch_shardings,
                                        mesh_collective_specs,
                                        opt_state_shardings,
                                        param_shardings, shard_factor_fn)
    from .mesh import arch_sharding_policy, mesh_axis_sizes

    sizes = mesh_axis_sizes(mesh)
    spol = arch_sharding_policy(cfg, mesh)
    if hbm_bytes is None:
        hbm_bytes = min(D.hbm_bytes(d) for d in mesh.devices.flat)
    _, opt = make_train_step(cfg, policy)
    aparams = M.abstract_params(cfg)
    aopt = jax.eval_shape(opt.init, aparams)
    abatch = input_specs(cfg, shape)
    rep = None
    if not skip_gate:
        policy, rep = gate(
            cfg, policy, shape, hbm_bytes, " on one device of the mesh",
            shard_factor_fn=shard_factor_fn(cfg, sizes, spol,
                                            params=aparams, opt_state=aopt,
                                            batch=abatch),
            collective_specs=mesh_collective_specs(sizes, spol))
    train_step, opt = make_train_step(cfg, policy)
    shardings = {"params": param_shardings(aparams, cfg, mesh, spol),
                 "opt_state": opt_state_shardings(aopt, mesh, spol),
                 "batch": batch_shardings(abatch, mesh, spol)}

    def on(tree, sh):
        return jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, sh)
    abstract = {k: on(t, shardings[k]) for k, t in
                (("params", aparams), ("opt_state", aopt),
                 ("batch", abatch))}
    jitted = jax.jit(train_step, donate_argnums=(0, 1), out_shardings=(
        NamedSharding(mesh, P()), shardings["params"],
        shardings["opt_state"]))
    return MeshJob(mesh=mesh, policy=policy, report=rep,
                   shardings=shardings, abstract=abstract, jitted=jitted,
                   opt_init=jax.jit(opt.init,
                                    out_shardings=shardings["opt_state"]))


@dataclasses.dataclass
class TrainResult:
    """What one :func:`train_loop` run did."""

    policy: TrainPolicy             # after any replan by the gate
    report: object | None           # the gate's EstimateReport (None: skipped)
    bytes_after_gate: int | None    # device bytes in use after the gate,
                                    # before init (None: not reported)
    start_step: int = 0
    losses: list = dataclasses.field(default_factory=list)  # per step run
    # blocked wall seconds per step; the first one includes compilation
    step_s: list = dataclasses.field(default_factory=list)
    ckpt_s: float = float("nan")    # the final checkpoint save

    @property
    def loss(self) -> float:
        return self.losses[-1] if self.losses else float("nan")


def train_loop(cfg, shape, policy: TrainPolicy, *, steps: int,
               ckpt_dir: str, ckpt_every: int = 20,
               hbm_bytes: int | None = None,
               skip_gate: bool = False, mesh=None) -> TrainResult:
    """The reusable training loop (admission gate -> resume -> steps ->
    checkpoints -> emergency save). ``hbm_bytes`` defaults to the
    device's own capacity (:func:`repro.launch.device.hbm_bytes`). With
    a ``mesh`` the job is laid out on it by :func:`mesh_job`, and the
    gate holds each device to its share."""
    if mesh is not None:
        job = mesh_job(cfg, shape, policy, mesh, hbm_bytes=hbm_bytes,
                       skip_gate=skip_gate)
        policy, rep = job.policy, job.report
        step_fn, put = job.step, job.put

        def init():
            return job.place(partial(M.init_params, cfg), jax.random.key(0))
    else:
        if hbm_bytes is None:
            hbm_bytes = D.hbm_bytes()
        rep = None
        if not skip_gate:
            policy, rep = gate(cfg, policy, shape, hbm_bytes)
        train_step, opt = make_train_step(cfg, policy)
        step_fn = jax.jit(train_step, donate_argnums=(0, 1))
        put = partial(jax.tree_util.tree_map, jnp.asarray)

        def init():
            params = M.init_params(cfg, jax.random.key(0))
            return params, opt.init(params)
    res = TrainResult(policy=policy, report=rep,
                      bytes_after_gate=D.bytes_in_use())
    ckpt = CheckpointManager(ckpt_dir)
    ds = SyntheticDataset(cfg, shape)
    monitor = StragglerMonitor(n_workers=1)
    params, opt_state = init()
    restored = ckpt.restore_latest({"params": params,
                                    "opt_state": opt_state})
    if restored is not None:
        res.start_step, state = restored
        if mesh is not None:
            state = jax.device_put(state, {
                "params": job.shardings["params"],
                "opt_state": job.shardings["opt_state"]})
        params, opt_state = state["params"], state["opt_state"]
        print(f"[ckpt] resumed from step {res.start_step}")
    step = res.start_step
    try:
        for step in range(res.start_step, steps):
            t0 = time.perf_counter()
            batch = put(ds.batch(step))
            loss, params, opt_state = step_fn(params, opt_state, batch)
            # time the device's work, not its enqueue
            jax.block_until_ready((loss, params, opt_state))
            dt = time.perf_counter() - t0
            monitor.record(0, dt)
            res.step_s.append(dt)
            res.losses.append(float(loss))
            if step % 10 == 0 or step == steps - 1:
                print(f"step {step:5d} loss {res.loss:.4f} "
                      f"({dt*1000:.0f} ms)")
            if (step + 1) % ckpt_every == 0:
                ckpt.save(step + 1, {"params": params,
                                     "opt_state": opt_state})
    except BaseException as exc:
        # after a failed step the donated buffers may be gone: a failing
        # emergency save is noted on the original error, never raised
        try:
            ckpt.emergency(step, {"params": params, "opt_state": opt_state})
            print(f"[ckpt] emergency checkpoint at step {step}")
        except Exception as save_exc:  # noqa: BLE001 — exc is re-raised
            exc.add_note(f"emergency checkpoint at step {step} failed: "
                         f"{type(save_exc).__name__}: {save_exc}")
        raise
    t0 = time.perf_counter()
    ckpt.save(steps, {"params": params, "opt_state": opt_state})
    res.ckpt_s = time.perf_counter() - t0
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--hbm-gib", type=float, default=None,
                    help="gate capacity (default: the device's own "
                         "bytes_limit; 16 on the CPU)")
    ap.add_argument("--skip-gate", action="store_true")
    ap.add_argument("--mesh", default=None, metavar="AXIS=N,...",
                    help="lay the job out on a mesh of the first devices, "
                         "e.g. data=1,model=4 (tensor-parallel over 4); "
                         "the gate then holds each device to its share")
    args = ap.parse_args()

    D.enable_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    shape = smoke_shape(args.seq, args.batch) if args.smoke else TRAIN_4K
    policy = TrainPolicy(optimizer=args.optimizer,
                         learning_rate=args.lr,
                         microbatches=args.microbatches)
    mesh = None
    if args.mesh is not None:
        from .mesh import make_mesh, parse_mesh
        mesh = make_mesh(parse_mesh(args.mesh))
    try:
        res = train_loop(
            cfg, shape, policy, steps=args.steps, ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every, skip_gate=args.skip_gate,
            hbm_bytes=(None if args.hbm_gib is None
                       else int(args.hbm_gib * 2**30)), mesh=mesh)
    except MemoryError as e:
        print(f"[xmem] {e}")
        return 2
    print("[done] final loss", res.loss)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
