#!/usr/bin/env python3
"""Chip smoke test: the admission-gated trainer at full width on a TPU.

Drives ``repro.launch.train.train_loop`` once: the xMem gate (host-side
trace + allocator replay) -> init -> jitted, donated train steps ->
checkpoint, on starcoder2-3b's one-chip share
(``repro.configs.starcoder2_3b.CHIP``: every published width, 4 of 30
layers, batch 2 x 4096, AdamW). It prints the gate's estimate beside the
device's measured ``peak_bytes_in_use``.

  python3 chip_smoke.py              # one chip
  python3 chip_smoke.py --chips 4    # only the 4-chip phase: the same job
                                     # on a data 1 x model 4 mesh, checked
                                     # against an unsharded forward

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The script exits non-zero, without that line, when no TPU is found, a
loss is not finite, the gate rejects the job, an admitted job runs out of
device memory, or (4 chips) the sharded loss disagrees with the
reference. JAX's compilation cache is kept where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``<repo root>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
GiB = 2**30
STEPS = 6     # one compile step + five timed steps

# Agreement of the sharded step-0 loss with the unsharded forward. Both
# programs compute in bf16 (8 significant bits) with f32 accumulation and
# an f32 loss; the sharded one sums the row-parallel partial products in
# another order and rounds them to bf16 before its all-reduce, so a logit
# may move by about one bf16 step (~4e-3 at the init's |logit| ~ 1).
# Those moves have either sign and the loss averages 8192 tokens, so the
# expected gap is ~1e-4. A misplaced or dropped shard changes the hidden
# states themselves, which moves the mean loss by ~1e-2 (logit spread
# ~1.1 over sqrt(8192) tokens). 2e-3 sits between the two.
LOSS_ATOL = 2e-3


class SmokeFailure(RuntimeError):
    pass


def _check_losses(losses) -> None:
    bad = [(i, x) for i, x in enumerate(losses) if not math.isfinite(x)]
    if bad:
        raise SmokeFailure(f"non-finite loss at steps {bad}")


def _oom_note(exc: BaseException, capacity: int) -> None:
    if "RESOURCE_EXHAUSTED" in str(exc):
        exc.add_note(
            f"the xMem gate ADMITTED this job against {capacity} bytes and "
            "it then ran out of device memory: the estimator's safety "
            "promise broke (the estimate is on the [xmem] line above)")


def compiled_step_memory(cfg, policy, shape):
    """``memory_analysis()`` of the train step as this device's compiler
    builds it (a compile-cache hit after ``train_loop`` compiled it)."""
    import jax

    from repro.configs.registry import input_specs
    from repro.models import model as M
    from repro.train import make_train_step

    step, opt = make_train_step(cfg, policy)
    params = M.abstract_params(cfg)
    return jax.jit(step, donate_argnums=(0, 1)).lower(
        params, jax.eval_shape(opt.init, params),
        input_specs(cfg, shape)).compile().memory_analysis()


def one_chip(cfg, shape, steps: int) -> int:
    """The main path on device 0. Returns the number of devices used."""
    import jax

    from repro.launch import device as D
    from repro.launch.train import train_loop
    from repro.train import TrainPolicy

    dev = jax.devices()[0]
    capacity = D.hbm_bytes(dev)
    print(f"capacity (bytes_limit): {capacity} ({capacity / GiB:.3f} GiB)")
    before_gate = D.bytes_in_use(dev)
    compiles = D.CompileCounter().install()
    # a fresh directory: a stale checkpoint would make restore skip steps
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        try:
            res = train_loop(cfg, shape, TrainPolicy(), steps=steps,
                             ckpt_dir=ckpt, hbm_bytes=capacity)
        except MemoryError as e:      # the gate rejected the job
            raise SmokeFailure(f"gate REJECT: {e}") from e
        except Exception as e:
            _oom_note(e, capacity)
            raise
    peak = D.peak_bytes_in_use(dev)
    rep = res.report
    _check_losses(res.losses)
    if res.start_step != 0 or len(res.step_s) != steps:
        raise SmokeFailure(f"ran {len(res.step_s)} of {steps} steps")
    print(f"gate: estimate {rep.peak_bytes} B ({rep.peak_bytes / GiB:.3f} "
          f"GiB), persistent {rep.persistent_bytes} B "
          f"({rep.persistent_bytes / GiB:.3f} GiB), microbatches "
          f"{res.policy.microbatches} -> ADMIT")
    print(f"device bytes_in_use before the gate {before_gate}, after it "
          f"(before init) {res.bytes_after_gate}: the gate added "
          f"{res.bytes_after_gate - before_gate} (expected 0)")
    print(f"first step (compile + run): {res.step_s[0]:.3f} s")
    print(f"median blocked step over steps 1..{steps - 1}: "
          f"{statistics.median(res.step_s[1:]) * 1e3:.1f} ms "
          f"(all: {[round(t * 1e3, 1) for t in res.step_s[1:]]})")
    print(f"loss first {res.losses[0]:.6f} last {res.losses[-1]:.6f} "
          f"(all: {[round(x, 4) for x in res.losses]})")
    print(f"final checkpoint save: {res.ckpt_s:.2f} s")
    print(f"programs compiled or loaded from the cache in the run: "
          f"{compiles.count} ({compiles.seconds:.2f} s)")
    compiles.remove()
    print(f"peak_bytes_in_use {peak} B ({peak / GiB:.3f} GiB) = "
          f"{peak / rep.peak_bytes:.4f} x the estimate")
    print(f"memory_stats: {json.dumps(dev.memory_stats(), sort_keys=True)}")
    # the step's temporaries do not show in peak_bytes_in_use on a TPU;
    # the compiler's own count of them, from this chip's compile:
    ma = compiled_step_memory(cfg, res.policy, shape)
    both = peak + ma.temp_size_in_bytes
    print(f"compiled step on this chip: arguments "
          f"{ma.argument_size_in_bytes} B, temporaries "
          f"{ma.temp_size_in_bytes} B, aliased {ma.alias_size_in_bytes} B")
    print(f"peak_bytes_in_use + compiled temporaries {both} B "
          f"({both / GiB:.3f} GiB) = {both / rep.peak_bytes:.4f} x the "
          f"estimate")
    return 1


def four_chips(cfg, shape, steps: int, n: int = 4) -> int:
    """The same job sharded over ``n`` chips (data 1 x model n), placed
    as ``launch/dryrun.py`` places it; per-device peaks beside the gate's
    per-device estimate, and the step-0 loss against an unsharded forward
    on one device. Returns the number of devices used."""
    from functools import partial

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.registry import input_specs
    from repro.distributed.act_sharding import (DEFAULT_RULES,
                                                logical_axis_rules)
    from repro.distributed.sharding import (ShardingPolicy, batch_shardings,
                                            mesh_collective_specs,
                                            opt_state_shardings,
                                            param_shardings, shard_factor_fn)
    from repro.launch import device as D
    from repro.launch.mesh import make_smoke_mesh, mesh_axis_sizes
    from repro.launch.train import admission_check
    from repro.models import model as M
    from repro.train import SyntheticDataset, TrainPolicy, make_train_step

    mesh = make_smoke_mesh(n)
    devs = list(mesh.devices.flat)
    sizes = mesh_axis_sizes(mesh)
    # no FSDP below 8 B parameters, as dryrun.arch_sharding_policy decides
    spol = ShardingPolicy(batch_axes=("data",))
    policy = TrainPolicy()
    step, opt = make_train_step(cfg, policy)
    aparams = M.abstract_params(cfg)
    aopt = jax.eval_shape(opt.init, aparams)
    bspecs = input_specs(cfg, shape)
    pshard = param_shardings(aparams, cfg, mesh, spol)
    oshard = opt_state_shardings(aopt, mesh, spol)
    bshard = batch_shardings(bspecs, mesh, spol)
    capacity = min(D.hbm_bytes(d) for d in devs)
    print(f"mesh {sizes}; capacity per device {capacity} B")
    before_gate = [D.bytes_in_use(d) for d in devs]

    ok, rep = admission_check(
        cfg, policy, shape, capacity,
        shard_factor_fn=shard_factor_fn(cfg, sizes, spol, params=aparams,
                                        opt_state=aopt, batch=bspecs),
        collective_specs=mesh_collective_specs(sizes, spol))
    if not ok:
        raise SmokeFailure("gate REJECT (per-device estimate)")
    print(f"device bytes_in_use before the gate {before_gate}, after it "
          f"{[D.bytes_in_use(d) for d in devs]} (expected unchanged)")

    ds = SyntheticDataset(cfg, shape)
    losses, step_s = [], []
    compiles = D.CompileCounter().install()
    after_first = None
    try:
        with mesh, logical_axis_rules(mesh, DEFAULT_RULES):
            params = jax.jit(partial(M.init_params, cfg),
                             out_shardings=pshard)(jax.random.key(0))
            opt_state = jax.jit(opt.init, out_shardings=oshard)(params)
            host_params = jax.device_get(params)     # for the reference
            step_fn = jax.jit(step, donate_argnums=(0, 1), out_shardings=(
                NamedSharding(mesh, P()), pshard, oshard))
            for i in range(steps):
                t0 = time.perf_counter()
                batch = jax.device_put(ds.batch(i), bshard)
                loss, params, opt_state = step_fn(params, opt_state, batch)
                jax.block_until_ready((loss, params, opt_state))
                step_s.append(time.perf_counter() - t0)
                losses.append(float(loss))
                if i == 0:
                    after_first = compiles.count
    except Exception as e:
        _oom_note(e, capacity)
        raise
    _check_losses(losses)
    peaks = [D.peak_bytes_in_use(d) for d in devs]
    del params, opt_state

    def on(tree, shardings):
        return jax.tree_util.tree_map(
            lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
            tree, shardings)
    with mesh, logical_axis_rules(mesh, DEFAULT_RULES):
        temps = step_fn.lower(on(aparams, pshard), on(aopt, oshard),
                              on(bspecs, bshard)).compile(
        ).memory_analysis().temp_size_in_bytes
    print(f"gate per-device estimate {rep.peak_bytes} B "
          f"({rep.peak_bytes / GiB:.3f} GiB), persistent "
          f"{rep.persistent_bytes} B; compiled temporaries per device "
          f"{temps} B")
    for d, pk in zip(devs, peaks):
        print(f"device {d.id}: peak_bytes_in_use {pk} B ({pk / GiB:.3f} "
              f"GiB) = {pk / rep.peak_bytes:.4f} x the estimate; + compiled "
              f"temporaries {(pk + temps) / GiB:.3f} GiB = "
              f"{(pk + temps) / rep.peak_bytes:.4f} x")
    print(f"first step (compile + run): {step_s[0]:.3f} s; median blocked "
          f"step after it: {statistics.median(step_s[1:]) * 1e3:.1f} ms")
    print(f"programs compiled in steps 1..{steps - 1}: "
          f"{compiles.count - after_first} (expected 0)")
    compiles.remove()
    print(f"losses: {[round(x, 4) for x in losses]}")

    # what it is compared with: an unsharded forward of the same params
    # and batch on one device
    ref_dev = devs[0]
    ref_loss = float(jax.jit(partial(M.loss_fn, cfg=cfg))(
        jax.device_put(host_params, ref_dev),
        jax.device_put(ds.batch(0), ref_dev)))
    gap = abs(losses[0] - ref_loss)
    print(f"step-0 loss sharded {losses[0]:.6f} vs unsharded {ref_loss:.6f}"
          f": |gap| {gap:.3e} (tolerance {LOSS_ATOL:.0e})")
    if not gap <= LOSS_ATOL:
        raise SmokeFailure(f"sharded loss off the reference by {gap:.3e}")
    return len(devs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded 4-chip phase")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's devices are "
              f"{dev.platform}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro.configs.starcoder2_3b import CHIP, CHIP_SHAPE
    from repro.launch.device import enable_compile_cache
    print(f"device: platform {dev.platform}, kind {dev.device_kind}, "
          f"count {len(devices)}; compile cache {enable_compile_cache()}")
    print(f"job: {CHIP.name} at published widths, n_layers "
          f"{CHIP.n_layers}, batch {CHIP_SHAPE.global_batch} x seq "
          f"{CHIP_SHAPE.seq_len}, {CHIP.param_count()} parameters")
    try:
        if args.chips == 1:
            used = one_chip(CHIP, CHIP_SHAPE, STEPS)
        else:
            used = four_chips(CHIP, CHIP_SHAPE, STEPS, args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": used}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
