import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any other import (jax locks the
device count at first init) — this is why the docstring sits below them.

Per cell this script:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod);
  2. materializes abstract params / optimizer state / batch as sharded
     ShapeDtypeStructs (zero allocation);
  3. ``jax.jit(step).lower(...).compile()`` — success proves the
     sharding config is coherent end-to-end;
  4. prints ``memory_analysis()`` (does it fit?) and ``cost_analysis()``
     (FLOPs/bytes for the roofline);
  5. parses the optimized HLO for collective operand bytes;
  6. writes a JSON artifact consumed by benchmarks/roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b \
      --shape train_4k [--multi-pod] [--out artifacts/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import argparse     # noqa: E402
import dataclasses  # noqa: E402
import json         # noqa: E402
import re           # noqa: E402
import time         # noqa: E402
import traceback    # noqa: E402

import jax          # noqa: E402

from ..configs import get_config                          # noqa: E402
from ..configs.base import (SHAPES_BY_NAME, ShapeSpec,    # noqa: E402
                            supports_long_context)
from ..configs.registry import (abstract_cache,           # noqa: E402
                                decode_input_specs, input_specs)
from ..distributed.act_sharding import (DEFAULT_RULES,    # noqa: E402
                                        logical_axis_rules)
from ..distributed.sharding import (batch_shardings,      # noqa: E402
                                    cache_shardings, opt_state_shardings,
                                    param_shardings)
from ..models import model as M                           # noqa: E402
from ..train.train_step import (TrainPolicy,              # noqa: E402
                                make_serve_step, make_train_step)
from .analytic import analytic_bytes, analytic_flops     # noqa: E402
from .hlo_analysis import (collective_bytes as hlo_collective_bytes,  # noqa: E402
                           cost_analysis_of)
from .mesh import (arch_sharding_policy,                 # noqa: E402
                   make_production_mesh, mesh_axis_sizes)

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


# ---------------------------------------------------------------------------
def arch_train_policy(arch: str, cfg) -> TrainPolicy:
    """Per-arch training policy a real team would pick at this scale."""
    n = cfg.param_count()
    if n > 80e9:
        return TrainPolicy(optimizer="adafactor", microbatches=8,
                           clip_norm=1.0)
    if n > 8e9:
        return TrainPolicy(optimizer="adamw", microbatches=4, clip_norm=1.0)
    return TrainPolicy(optimizer="adamw", microbatches=1, clip_norm=1.0)


def _with_moe_groups(cfg, mesh):
    if cfg.moe is None:
        return cfg
    sizes = mesh_axis_sizes(mesh)
    groups = sizes.get("data", 1) * sizes.get("pod", 1)
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, num_groups=groups))


def _sds(tree, shardings):
    """Attach shardings to ShapeDtypeStructs."""
    return jax.tree_util.tree_map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        tree, shardings)


# ---------------------------------------------------------------------------
def collective_bytes_from_hlo(hlo_text: str) -> dict:
    """Sum operand bytes of collective ops in optimized HLO.

    Builds a name->result-bytes table in one pass, then resolves each
    collective's operand names; falls back to the collective's own result
    shape when an operand is unresolvable.
    """
    dtype_bytes = {
        "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1,
        "f8e5m2": 1, "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8,
        "u32": 4, "u16": 2, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
    }

    def shape_bytes(ty: str, dims: str) -> int:
        n = 1
        for d in dims.split(","):
            d = d.strip()
            if d:
                n *= int(d)
        return n * dtype_bytes.get(ty, 4)

    name_bytes: dict[str, int] = {}
    result_re = re.compile(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(?:\()?([a-z0-9]+)\[([\d,\s]*)\]")
    tuple_re = re.compile(r"([a-z0-9]+)\[([\d,\s]*)\]")
    for line in hlo_text.splitlines():
        m = result_re.match(line)
        if m:
            name = m.group(1)
            if line.split("=", 1)[1].lstrip().startswith("("):
                # tuple result: sum element sizes
                rhs = line.split("=", 1)[1]
                paren = rhs[:rhs.find(")") + 1]
                total = sum(shape_bytes(t, d)
                            for t, d in tuple_re.findall(paren))
                name_bytes[name] = total
            else:
                name_bytes[name] = shape_bytes(m.group(2), m.group(3))

    out = {c: 0 for c in COLLECTIVES}
    count = {c: 0 for c in COLLECTIVES}
    op_re = re.compile(r"(" + "|".join(COLLECTIVES) + r")(?:-start|-done)?\(")
    for line in hlo_text.splitlines():
        m = op_re.search(line)
        if not m or "-done(" in line:
            continue  # count each start/fused op once
        kind = m.group(1)
        args = line[m.end():]
        depth, j = 1, 0
        while j < len(args) and depth:
            if args[j] == "(":
                depth += 1
            elif args[j] == ")":
                depth -= 1
            j += 1
        operand_names = re.findall(r"%?([\w.\-]+)", args[:j - 1])
        total = sum(name_bytes.get(n, 0) for n in operand_names)
        if total == 0:
            rm = result_re.match(line)
            if rm:
                total = name_bytes.get(rm.group(1), 0)
        out[kind] += total
        count[kind] += 1
    return {"bytes": out, "count": count,
            "total_bytes": sum(out.values())}


# ---------------------------------------------------------------------------
def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = "artifacts/dryrun",
             skip_existing: bool = True) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_tag}.json")
    if skip_existing and os.path.exists(path):
        with open(path) as f:
            return json.load(f)

    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    record = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
              "ok": False}
    if shape.name == "long_500k" and not supports_long_context(cfg):
        record.update(skipped=True, reason="full-attention arch: "
                      "long_500k requires sub-quadratic family "
                      "(DESIGN.md §4)")
        _write(path, record)
        return record

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        cfg = _with_moe_groups(cfg, mesh)
        spolicy = arch_sharding_policy(cfg, mesh)
        record["sharding"] = {"fsdp": spolicy.fsdp}
        aparams = M.abstract_params(cfg)
        pshard = param_shardings(aparams, cfg, mesh, spolicy)
        params_s = _sds(aparams, pshard)

        if shape.kind == "train":
            tpolicy = arch_train_policy(arch, cfg)
            record["train_policy"] = {
                "optimizer": tpolicy.optimizer,
                "microbatches": tpolicy.microbatches}
            step, opt = make_train_step(cfg, tpolicy)
            aopt = jax.eval_shape(opt.init, aparams)
            oshard = opt_state_shardings(aopt, mesh, spolicy)
            opt_s = _sds(aopt, oshard)
            bspecs = input_specs(cfg, shape)
            bshard = batch_shardings(bspecs, mesh, spolicy)
            batch_s = _sds(bspecs, bshard)
            with mesh, logical_axis_rules(mesh, DEFAULT_RULES):
                lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
                    params_s, opt_s, batch_s)
                compiled = lowered.compile()
        elif shape.kind == "prefill":
            from ..train.train_step import make_prefill_step
            step = make_prefill_step(cfg)
            bspecs = input_specs(cfg, shape)
            bshard = batch_shardings(bspecs, mesh, spolicy)
            batch_s = _sds(bspecs, bshard)
            with mesh, logical_axis_rules(mesh, DEFAULT_RULES):
                lowered = jax.jit(step).lower(params_s, batch_s)
                compiled = lowered.compile()
        else:  # decode
            step = make_serve_step(cfg, cache_len=shape.seq_len - 1)
            acache = abstract_cache(cfg, shape)
            cshard = cache_shardings(acache, mesh, spolicy)
            cache_s = _sds(acache, cshard)
            bspecs = decode_input_specs(cfg, shape)
            bshard = batch_shardings(bspecs, mesh, spolicy)
            batch_s = _sds(bspecs, bshard)
            with mesh, logical_axis_rules(mesh, DEFAULT_RULES):
                lowered = jax.jit(step, donate_argnums=(1,)).lower(
                    params_s, cache_s, batch_s)
                compiled = lowered.compile()

        ma = compiled.memory_analysis()
        print(ma)
        ca = cost_analysis_of(compiled)
        print({k: ca.get(k) for k in ("flops", "bytes accessed")})
        hlo = compiled.as_text()
        coll = hlo_collective_bytes(hlo)
        n_dev = mesh.devices.size
        sizes = mesh_axis_sizes(mesh)
        micro = record.get("train_policy", {}).get("microbatches", 1)
        fsdp_shards = (sizes.get("data", 1) * sizes.get("pod", 1)
                       if spolicy.fsdp else 1)
        a_flops = analytic_flops(cfg, shape) / n_dev
        a_bytes = analytic_bytes(
            cfg, shape, n_devices=n_dev,
            model_shards=sizes.get("model", 1), fsdp_shards=fsdp_shards,
            microbatches=micro)
        record.update(
            ok=True,
            compile_s=time.time() - t0,
            devices=int(n_dev),
            memory={
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
                "generated_code_bytes": int(
                    ma.generated_code_size_in_bytes),
                "per_device_bytes": int(
                    ma.argument_size_in_bytes + ma.output_size_in_bytes
                    + ma.temp_size_in_bytes - ma.alias_size_in_bytes),
            },
            cost={
                "flops": float(ca.get("flops", 0.0)),
                "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
                # cost_analysis counts while bodies once; analytic terms
                # are the corrected roofline inputs (launch/analytic.py)
                "analytic_flops_per_device": float(a_flops),
                "analytic_bytes_per_device": float(a_bytes),
            },
            collectives=coll,
            hlo_ops=len(hlo.splitlines()),
        )
    except Exception as e:  # noqa: BLE001 — record the failure, move on
        record.update(ok=False, error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-2000:],
                      compile_s=time.time() - t0)
    _write(path, record)
    return record


def _write(path: str, record: dict) -> None:
    with open(path + ".tmp", "w") as f:
        json.dump(record, f, indent=1)
    os.replace(path + ".tmp", path)


def iter_cells():
    from ..configs import ARCH_IDS
    for arch in ARCH_IDS:
        for shape_name in ("train_4k", "prefill_32k", "decode_32k",
                           "long_500k"):
            yield arch, shape_name


# ---------------------------------------------------------------------------
def xmem_gate(arch: str, hbm_gib: float = 0.25, seq: int = 64,
              batches: tuple = (1, 2, 4, 8, 12, 16, 24, 32, 48, 64),
              out_dir: str = "artifacts/dryrun", microbatches: int = 1,
              service=None, store_dir: str | None = None,
              obs=None, timeline_out: str | None = None) -> dict:
    """Estimator-side admission gate for a dry-run cell family: sweep
    the candidate batch sizes through the admission service's batched
    path (``AdmissionService.decide_sweep`` -> columnar trace
    interpolation + vectorized replay) BEFORE paying any XLA compile,
    and record which settings fit the device. Smoke-scale configs keep
    this runnable anywhere; the full-scale dry-run then only compiles
    settings the gate admits. With gradient accumulation the candidate
    grid snaps to multiples of ``microbatches`` (non-divisible batches
    cannot even be traced — ``_split_microbatches`` asserts)."""
    from ..configs import get_smoke
    from ..configs.base import smoke_shape
    from ..configs.registry import input_specs
    from ..models import model as M
    from ..service import AdmissionRequest, AdmissionService
    from ..train import TrainPolicy, make_estimator_hooks

    if service is not None and store_dir is not None:
        raise ValueError("pass either service= or store_dir=, not both "
                         "(a provided service keeps its own store)")
    cfg = get_smoke(arch)
    m = max(int(microbatches), 1)
    batches = tuple(b for b in batches if b % m == 0) or (m,)
    tpolicy = TrainPolicy(optimizer="adamw", microbatches=m)
    fwd_bwd, update, opt_init = make_estimator_hooks(cfg, tpolicy)
    params = M.abstract_params(cfg)
    svc = service or AdmissionService(workers=1, store_dir=store_dir,
                                      obs=obs)
    hbm = int(hbm_gib * 2**30)
    reqs = [AdmissionRequest(
        job_id=f"{cfg.name}-b{b}", fwd_bwd_fn=fwd_bwd, params=params,
        batch=input_specs(cfg, smoke_shape(seq_len=seq, global_batch=b)),
        update_fn=update, opt_init_fn=opt_init, capacity=hbm)
        for b in batches]
    decisions = svc.decide_sweep(reqs)
    record = {
        "arch": cfg.name, "kind": "xmem_gate", "hbm_bytes": hbm,
        "seq": seq, "microbatches": m,
        "sweep": decisions[0].provenance["sweep"] if decisions else {},
        "settings": [
            {"batch": b, "peak_bytes": d.peak_bytes, "fits": d.admit}
            for b, d in zip(batches, decisions)],
    }
    record["admitted"] = [s["batch"] for s in record["settings"]
                          if s["fits"]]
    cid = decisions[0].correlation_id if decisions else None
    if cid is not None:
        record["correlation_id"] = cid
    if timeline_out is not None:
        # Perfetto memory timeline of the largest batch's replay
        rep = next((d.report for d in reversed(decisions)
                    if d.report is not None), None)
        if rep is not None:
            from ..obs.timeline import write_timeline
            record["timeline"] = write_timeline(rep, timeline_out)
    os.makedirs(out_dir, exist_ok=True)
    _write(os.path.join(out_dir, f"{arch}__xmem_gate.json"), record)
    return record


def xmem_mesh_gate(arch: str, hbm_gib: float = 0.25, seq: int = 64,
                   batch: int = 32, devices: tuple = (8, 16, 32),
                   out_dir: str = "artifacts/dryrun") -> dict:
    """Per-device admission gate over mesh topologies: every
    (pod, data, model, fsdp) factorization of the candidate device
    counts is estimated from ONE cached trace with spec-driven shard
    factors and per-axis collective staging buffers — BEFORE paying any
    XLA compile. The full-scale dry-run then only compiles mesh cells
    the gate admits (smoke-scale configs keep this runnable anywhere)."""
    from ..configs import get_smoke
    from ..configs.base import smoke_shape
    from ..configs.registry import input_specs
    from ..core.estimator import XMemEstimator
    from ..core.sweep import SweepService, topology_grid
    from ..models import model as M
    from ..train import TrainPolicy, make_estimator_hooks

    cfg = get_smoke(arch)
    tpolicy = TrainPolicy(optimizer="adamw", microbatches=1)
    fwd_bwd, update, opt_init = make_estimator_hooks(cfg, tpolicy)
    params = M.abstract_params(cfg)
    batch_specs = input_specs(cfg, smoke_shape(seq_len=seq,
                                               global_batch=batch))
    grid = [t for n in devices for t in topology_grid(n)]
    svc = SweepService(XMemEstimator.for_tpu())
    result = svc.estimate_mesh_sweep(fwd_bwd, params, batch_specs, grid,
                                     update_fn=update,
                                     opt_init_fn=opt_init, cfg=cfg)
    hbm = int(hbm_gib * 2**30)
    record = {
        "arch": cfg.name, "kind": "xmem_mesh_gate", "hbm_bytes": hbm,
        "seq": seq, "batch": batch,
        "sweep": result.stats,
        "topologies": [
            {"topology": t.label, "devices": t.n_devices,
             "peak_bytes": rep.peak_bytes,
             "persistent_bytes": rep.persistent_bytes,
             "fits": rep.fits(hbm)}
            for t, rep in result],
    }
    record["admitted"] = [r["topology"] for r in record["topologies"]
                          if r["fits"]]
    best = result.best(hbm)
    if best is not None:
        record["best_topology"] = best[0].label
    os.makedirs(out_dir, exist_ok=True)
    _write(os.path.join(out_dir, f"{arch}__xmem_mesh_gate.json"), record)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--xmem-gate", metavar="ARCH",
                    help="run the estimator-side batch admission sweep "
                         "for ARCH (smoke scale, no compile) and exit")
    ap.add_argument("--xmem-mesh-gate", metavar="ARCH",
                    help="run the estimator-side mesh-topology admission "
                         "sweep for ARCH (smoke scale, no compile) and "
                         "exit")
    ap.add_argument("--xmem-plan", metavar="ARCH",
                    help="run the remediation planner for ARCH (smoke "
                         "scale, no compile): a job that misses the "
                         "--hbm-gib budget is answered with ranked "
                         "feasible counter-offers, written as an "
                         "artifact")
    ap.add_argument("--plan-batch", type=int, default=32,
                    help="rejected job's global batch for --xmem-plan")
    ap.add_argument("--plan-seq", type=int, default=48,
                    help="sequence length for --xmem-plan")
    ap.add_argument("--devices", default="8,16,32",
                    help="comma-separated device counts for "
                         "--xmem-mesh-gate")
    ap.add_argument("--hbm-gib", type=float, default=0.25,
                    help="capacity budget for --xmem-gate/"
                         "--xmem-mesh-gate (smoke scale)")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient-accumulation factor for --xmem-gate "
                         "(the candidate grid snaps to its multiples)")
    ap.add_argument("--timeline-out", default=None,
                    help="write a Perfetto/chrome-trace memory timeline "
                         "of the gated replay to this path "
                         "(--xmem-gate only)")
    args = ap.parse_args()

    if args.xmem_plan:
        from ..obs import Observability
        from ..plan import run_plan_search
        from ..service import AdmissionService
        devices = tuple(int(d) for d in args.devices.split(","))
        svc = AdmissionService(workers=1, obs=Observability(enabled=True))
        r = run_plan_search(args.xmem_plan, int(args.hbm_gib * 2**30),
                            seq=args.plan_seq, batch=args.plan_batch,
                            microbatches=args.microbatches,
                            devices=devices, service=svc)
        os.makedirs(args.out, exist_ok=True)
        _write(os.path.join(args.out, f"{args.xmem_plan}__xmem_plan.json"),
               r)
        s = r["stats"]
        if r["admit"]:
            print(f"[xmem-plan] {r['arch']}: already fits")
        else:
            print(f"[xmem-plan] {r['arch']}: {len(r['counter_offers'])} "
                  f"offers from {s['candidates']} candidates "
                  f"({s['fresh_traces']} fresh traces)")
        if r.get("correlation_id"):
            print(f"[xmem-plan] correlation_id={r['correlation_id']}")
        return

    if args.xmem_mesh_gate:
        devices = tuple(int(d) for d in args.devices.split(","))
        r = xmem_mesh_gate(args.xmem_mesh_gate, hbm_gib=args.hbm_gib,
                           devices=devices, out_dir=args.out)
        s = r["sweep"]
        print(f"[xmem-mesh-gate] {r['arch']}: "
              f"{len(r['admitted'])}/{s['topologies']} topologies "
              f"admitted (best {r.get('best_topology', '—')}; "
              f"{s['trace_cache']['misses']} phases traced, "
              f"{s['wall_s']*1e3:.0f} ms)")
        return

    if args.xmem_gate:
        from ..obs import Observability
        r = xmem_gate(args.xmem_gate, hbm_gib=args.hbm_gib,
                      out_dir=args.out, microbatches=args.microbatches,
                      obs=Observability(enabled=True),
                      timeline_out=args.timeline_out)
        s = r["sweep"]
        print(f"[xmem-gate] {r['arch']}: admitted batches "
              f"{r['admitted']} of "
              f"{[x['batch'] for x in r['settings']]} "
              f"({s['traced']} traced / {s['interpolated']} "
              f"interpolated)")
        if r.get("correlation_id"):
            print(f"[xmem-gate] correlation_id={r['correlation_id']}")
        if r.get("timeline"):
            print(f"[xmem-gate] timeline written to {r['timeline']}")
        return

    meshes = (False, True) if (args.both_meshes or args.all) \
        else (args.multi_pod,)
    cells = list(iter_cells()) if args.all else [(args.arch, args.shape)]
    results = []
    for arch, shape_name in cells:
        for mp in meshes:
            r = run_cell(arch, shape_name, mp, args.out,
                         skip_existing=not args.force)
            tag = "OK " if r.get("ok") else ("SKIP" if r.get("skipped")
                                             else "FAIL")
            extra = ""
            if r.get("ok"):
                extra = (f" mem/dev={r['memory']['per_device_bytes']/2**30:.2f}GiB"
                         f" flops={r['cost']['flops']:.3g}"
                         f" coll={r['collectives']['total_bytes']/2**30:.2f}GiB"
                         f" t={r['compile_s']:.0f}s")
            elif r.get("error"):
                extra = " " + r["error"][:120]
            print(f"[{tag}] {arch} {shape_name} "
                  f"{'2x16x16' if mp else '16x16'}{extra}", flush=True)
            results.append(r)
    n_ok = sum(1 for r in results if r.get("ok"))
    n_skip = sum(1 for r in results if r.get("skipped"))
    print(f"== {n_ok} ok, {n_skip} skipped, "
          f"{len(results) - n_ok - n_skip} failed ==")


if __name__ == "__main__":
    main()
