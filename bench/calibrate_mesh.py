#!/usr/bin/env python3
"""Readings from which a mesh training cell's limits are set.

  python3 bench/calibrate_mesh.py --workload <name> --seeds 4 \
      [--control-seeds 2] [--out chiprun_out/calib_mesh.json]
  python3 bench/calibrate_mesh.py --workload <name> --judge <file.json>

In one process on the cell's chips: the program's readings on each seed
(the first steps as a run's set-up makes them, ``train_mesh_cell``)
against the reference's (``reference/dense_lm_mesh.py``); on the first
``--control-seeds`` seeds the control (the reference in fp8) and two
faults of the reference, half of the batch left out (``half_batch``)
and one layer's row-parallel output projection not summed over the
model axis (``no_exchange``), each against the sound reference; and on
every seed the state-unchanged fault, the sound reading with the
weights' change read as zero. Each reading is put through the harness's
own comparison (``compare.train_checks`` and ``compare.passes``) with
the limits the configuration file states: sound readings have to come
out correct, the others not. Prints one line per reading and writes
them all as JSON, anew after each seed; exits 1 where a verdict is the
wrong way round.
``--judge`` reads such a file again and judges its readings with the
limits the configuration states now, without a chip. The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAULTS = (("control", {"precision": "fp8"}),
          ("half_batch", {"fault": "half_batch"}),
          ("no_exchange", {"fault": "no_exchange"}))


def unchanged(readings: dict) -> dict:
    """The state-unchanged fault: ``readings`` with no weight moved."""
    return {**readings, "delta_norms": [0.0] * len(readings["delta_norms"])}


def judge(rows: list, references: dict, cfg: dict) -> list:
    """Each row's verdict under ``cfg``'s limits; the kinds whose
    verdict is the wrong way round."""
    from bench import compare
    wrong = []
    for row in rows:
        checks = compare.train_checks(row["readings"],
                                      references[str(row["seed"])], cfg)
        row.update(checks=checks, correct=compare.passes(checks))
        if row["correct"] != (row["kind"] == "sound"):
            wrong.append(row["kind"])
    return sorted(set(wrong))


def summary(cfg: dict, wrong: list) -> str:
    return (f"calibrate_mesh: limits {cfg['limits']}: "
            + (f"wrong verdicts in {wrong}" if wrong else
               "every sound reading correct, every control and fault "
               "reading not correct"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--first-seed", type=int, default=3_100_000_000)
    ap.add_argument("--control-seeds", type=int, default=2)
    ap.add_argument("--out", default=None)
    ap.add_argument("--judge", default=None, metavar="FILE")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import spec
    cell = spec.resolve(spec.load_benchmark(ROOT), args.workload)
    cfg = cell.config
    if args.judge:
        with open(args.judge) as f:
            out = json.load(f)
        wrong = judge(out["rows"], out["reference"], cfg)
        for row in out["rows"]:
            print(row["kind"], row["seed"], row["correct"],
                  json.dumps(row["checks"]))
        print(summary(cfg, wrong))
        return 1 if wrong else 0

    from bench import harness
    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", harness.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices, err = harness.find_chips(cell.chips)
    if err:
        print(f"calibrate_mesh: {err}", file=sys.stderr)
        return 2
    from bench import compare, train_mesh_cell as C
    from repro.launch import device as D
    prog = C.MeshProgram(cfg=cfg, batch=cfg["train"]["batch"],
                         seq=cell.traffic["seq_len"], devices=devices,
                         limit=min(D.hbm_bytes(d) for d in devices))
    prog.build()
    out = {"workload": args.workload, "device": devices[0].device_kind,
           "chips": len(devices), "rows": [], "reference": {}}

    def save():
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(out, f, indent=1)

    def add(kind, seed, readings, ref, **extra):
        row = {"kind": kind, "seed": seed,
               **compare.train_gaps(readings, ref), **extra,
               "readings": readings}
        out["rows"].append(row)
        print(kind, json.dumps({k: v for k, v in row.items()
                                if k != "readings"}), flush=True)

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        mine = C.first_steps(prog, seed)
        t1 = time.perf_counter()
        prog.free()
        r = C.reference_readings(prog, seed)
        out["reference"][str(seed)] = r
        add("sound", seed, mine, r, program_s=t1 - t0,
            reference_s=time.perf_counter() - t1)
        add("unchanged", seed, unchanged(mine), r)
        if i < args.control_seeds:
            for kind, kw in FAULTS:
                t2 = time.perf_counter()
                add(kind, seed, C.reference_readings(prog, seed, **kw), r,
                    seconds=time.perf_counter() - t2)
        save()
    wrong = judge(out["rows"], out["reference"], cfg)
    save()
    if not cfg["limits"]:
        print("calibrate_mesh: the configuration states no limits yet",
              flush=True)
        return 0
    print(summary(cfg, wrong), flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
