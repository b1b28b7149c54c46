#!/usr/bin/env python3
"""Readings from which a training cell's limits are set.

  python3 bench/calibrate.py --workload <name> --seeds 12 \
      [--control-seeds 3] [--out chiprun_out/calib.json]

In one process on the chip: the program's readings on each seed (the
first steps as a run's set-up makes them) against the reference's, then
the control (the reference in fp8) and the half-batch fault (the
reference with half of the batch left out) on the first
``--control-seeds`` seeds, each against the reference. Each reading is
also put through the harness's own comparison (``compare.train_checks``
and ``compare.passes``) with the limits the configuration file states,
and its verdict recorded as ``correct``: sound readings have to come out
correct, the control's and the fault's not. Prints one line per reading
and writes them all as JSON; exits 1 where a verdict is the wrong way
round. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness, spec
    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", harness.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.resolve(spec.load_benchmark(ROOT), args.workload)
    devices, err = harness.find_chips(cell.chips)
    if err:
        print(f"calibrate: {err}", file=sys.stderr)
        return 2
    from bench import compare, train_cell
    from bench.reference import dense_lm
    from repro.launch import device as D
    cfg, dev = cell.config, devices[0]
    prog = train_cell.Program(cfg=cfg, batch=cfg["train"]["batch"],
                              seq=cell.traffic["seq_len"], device=dev,
                              limit=D.hbm_bytes(dev))
    prog.build()
    steps = train_cell.CHECK_STEPS
    out = {"workload": args.workload, "device": dev.device_kind,
           "sound": [], "control": [], "half_batch": []}

    wrong_way = []

    def verdict(kind, readings, r) -> dict:
        checks = compare.train_checks(readings, r, cfg)
        ok = compare.passes(checks)
        if ok != (kind == "sound"):
            wrong_way.append(kind)
        return {"correct": ok, "checks": checks}

    def ref(seed, **kw):
        return dense_lm.train_readings(cfg, seed, steps, prog.batch,
                                       prog.seq, device=dev, **kw)

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        mine = train_cell.first_steps(prog, seed)
        t1 = time.perf_counter()
        prog.free()
        r = ref(seed)
        t2 = time.perf_counter()
        row = {"seed": seed, **compare.train_gaps(mine, r),
               **verdict("sound", mine, r), "program_s": t1 - t0,
               "reference_s": t2 - t1, "program": mine, "reference": r}
        out["sound"].append(row)
        print("sound", json.dumps({k: v for k, v in row.items()
                                   if k not in ("program", "reference")}),
              flush=True)
        if i < args.control_seeds:
            for kind, kw in (("control", {"precision": "fp8"}),
                             ("half_batch", {"fault": "half_batch"})):
                t3 = time.perf_counter()
                bad = ref(seed, **kw)
                row = {"seed": seed, **compare.train_gaps(bad, r),
                       **verdict(kind, bad, r),
                       "seconds": time.perf_counter() - t3,
                       "readings": bad}
                out[kind].append(row)
                print(kind, json.dumps({k: v for k, v in row.items()
                                        if k != "readings"}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    if not cfg["limits"]:
        print("calibrate: the configuration states no limits yet",
              flush=True)
        return 0
    print(f"calibrate: limits {cfg['limits']}: "
          + (f"wrong verdicts in {sorted(set(wrong_way))}" if wrong_way
             else "every sound reading correct, every control and fault "
                  "reading not correct"), flush=True)
    return 1 if wrong_way else 0


if __name__ == "__main__":
    sys.exit(main())
