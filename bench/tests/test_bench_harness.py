"""The harness without a chip: it refuses to measure, and a run driven
past its look for a chip, at a small size on the CPU, sees ``correct``
come out false when the timed path is broken underneath."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from bench import peaks, spec, train_cell

HARNESS = os.path.join(spec.BENCH_DIR, "harness.py")
CELLS = ["deepseek-llm-7b.train4k", "phi4-mini-3.8b.train4k"]


def _run_harness(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_COMPILATION_CACHE_DIR")}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/harness.py", "--workload", CELLS[0],
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_json(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_no_tpu_means_no_result():
    proc = _run_harness(spec.ROOT)
    assert proc.returncode != 0
    assert _no_json(proc.stdout)
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_harness(str(tmp_path))
    assert proc.returncode != 0
    assert _no_json(proc.stdout)


@pytest.fixture
def cpu_run(monkeypatch):
    """``train_cell.run`` past the look for a chip, at a small size of a
    cell's configuration, on a stand-in device of 256 MiB."""
    from repro.launch import device as D
    monkeypatch.setattr(D, "hbm_bytes", lambda d=None: 256 * 2**20)
    monkeypatch.setattr(D, "bytes_in_use", lambda d=None: 0)
    monkeypatch.setattr(D, "peak_bytes_in_use", lambda d=None: 1)
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        {"bf16_flops_per_s": 1e12})
    # the CPU never runs out of memory: the bisection is tested against
    # a fake allocator in test_bench_units; here the need is the estimate
    monkeypatch.setattr(train_cell, "measure_need", lambda prog, seed, used: {
        "need": prog.gate.peak_bytes, "resolution": 2**20,
        "fits_estimate": True, "probes": 0, "remade": 0, "seconds": 0.0})

    def run(name, seed=2**31 + 99):
        cell = spec.resolve(spec.load_benchmark(), name)
        kv = 2 if cell.config["num_key_value_heads"] == 2 else 4
        cell.config.update(hidden_size=256, intermediate_size=1024,
                           num_attention_heads=4, num_key_value_heads=kv,
                           head_dim=64, num_hidden_layers=2,
                           vocab_size=4096)
        cell.traffic["seq_len"] = 256
        # limits for this size on the CPU, whose bf16 rounds otherwise
        # than the chip's: sound runs read at most 3.8e-4, 7.4e-4 and
        # 9.5e-4, the fp8 control at least 2.3e-3, 5.8e-3 and 1.7e-3
        cell.config["limits"] = {"loss_gap": 1.5e-3, "grad_gap": 2.5e-3,
                                 "delta_gap": 2e-3}
        return train_cell.run(cell, seed, 0.2, False, time.perf_counter(),
                              jax.devices()[:1])
    return run


def _break_step(monkeypatch, broken):
    import repro.train as T
    make = T.make_train_step

    def make_broken(cfg, policy):
        step, opt = make(cfg, policy)
        return broken(step), opt
    monkeypatch.setattr(T, "make_train_step", make_broken)


def _unchanged(step):
    def run(params, opt_state, batch):
        loss, _, _ = step(params, opt_state, batch)
        return loss, params, opt_state
    return run


def _half_batch(step):
    def run(params, opt_state, batch):
        b, s = batch["tokens"].shape
        cut = (lambda x: x[:b // 2]) if b >= 2 else (lambda x: x[:, :s // 2])
        return step(params, opt_state, {k: cut(v) for k, v in batch.items()})
    return run


@pytest.mark.parametrize("name", CELLS)
def test_a_sound_run_is_correct(cpu_run, name):
    out = cpu_run(name)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 2
    assert set(out["e2e"]) == {"tokens_per_s", "est_err_pct", "setup_s"}


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("broken", [_unchanged, _half_batch])
def test_a_broken_step_is_not_correct(cpu_run, monkeypatch, name, broken):
    _break_step(monkeypatch, broken)
    out = cpu_run(name)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_the_control_in_the_programs_place_is_not_correct(
        cpu_run, monkeypatch, name):
    """The reference in fp8, put where the program's readings go."""
    from bench.reference import dense_lm
    first_steps = train_cell.first_steps

    def control(prog, seed):
        first_steps(prog, seed)
        return dense_lm.train_readings(prog.cfg, seed, train_cell.CHECK_STEPS,
                                       prog.batch, prog.seq,
                                       precision="fp8")
    monkeypatch.setattr(train_cell, "first_steps", control)
    out = cpu_run(name)
    assert not out["correct"], out["checks"]
