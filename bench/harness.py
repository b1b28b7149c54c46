#!/usr/bin/env python3
"""Run one benchmark cell once.

  python3 bench/harness.py --workload <name> --seed <n> --seconds <s> \
      --trace <0|1>

The cell, its configuration, its traffic mix and its per-layer metric
readers are found by name from ``BENCHMARK.json`` (``bench/spec.py``);
a mix of ``"kind": "<k>"`` is run by ``bench/<k>_cell.py``.
The run makes its weights and inputs from ``--seed``, warms up, measures
for ``--seconds``, then checks what the timed path produced against the
plain reference. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device`` and, traced, ``breakdown``; the numbers
compared come last, under ``checks``, and again as the last lines of
standard error.

It exits 2 and prints no result when JAX finds no TPU, fewer chips than
the cell asks for, or no program (``src/repro``) beside the benchmark.
JAX's compilation cache is kept at ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

T_IMPORT = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def process_start() -> float:
    """Wall-clock time at which this process started (Linux), else the
    time this module was imported."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return T_IMPORT


def fail(msg: str) -> int:
    print(f"bench: {msg}; nothing was measured", file=sys.stderr)
    return 2


def find_chips(n: int):
    """The first ``n`` TPU devices, or an error message."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return None, f"no TPU found (JAX's devices are {devices[0].platform})"
    if len(devices) < n:
        return None, f"the cell needs {n} chips; JAX sees {len(devices)}"
    return devices[:n], None


def main(argv=None) -> int:
    t_process = time.perf_counter() - (time.time() - process_start())
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from bench import spec
    try:
        cell = spec.resolve(spec.load_benchmark(ROOT), args.workload)
    except (KeyError, OSError) as e:
        return fail(str(e))
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        return fail(f"no program under {src}")
    sys.path.insert(0, src)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices, err = find_chips(cell.chips)
    if err:
        return fail(err)

    runner = importlib.import_module(f"bench.{cell.traffic['kind']}_cell")
    out = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                     t_process, devices)
    print(json.dumps(out["log"], sort_keys=True), file=sys.stderr)
    if args.trace:
        metrics = {}
        for m in cell.per_layer:
            value = cell.readers[m["name"]](out["ctx"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": out["e2e"][m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), **out["device"]}
    result = {"correct": bool(out["correct"]),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": device}
    if args.trace and out.get("breakdown"):
        result["breakdown"] = out["breakdown"]
    result["checks"] = out["checks"]
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
