"""The mesh training cell without a chip.

At a small size on four CPU devices, ``train_mesh_cell.run`` driven past
its look for a chip sees ``correct`` come out true for a sound run and
false where the timed path is broken underneath (the state left
unchanged, half of the batch left out, one layer's row-parallel output
projection not summed over the model axis) or where the fp8 control
stands in for the program; and the mesh reference reads what
``dense_lm`` reads on one device. Those runs are made in one subprocess
that asks XLA for four host devices (this file, run as a script), so
the suite's own device count does not change. Besides: the collective
bucket of the trace reduction on made-up intervals, its metric, and the
calibration's verdicts.
"""
import json
import os
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "deepseek-llm-7b-tp4.train4k"
SEED = 2**31 + 99
SMALL = dict(hidden_size=256, intermediate_size=1024, num_attention_heads=4,
             num_key_value_heads=4, head_dim=64, num_hidden_layers=2,
             vocab_size=4096)
# limits for this size on the CPU, whose bf16 rounds otherwise than the
# chip's: the one-chip cells' CPU limits (test_bench_harness.py); sound
# mesh runs read at most 4.8e-4, 5.8e-4 and 5.0e-4 here
CPU_LIMITS = {"loss_gap": 1.5e-3, "grad_gap": 2.5e-3, "delta_gap": 2e-3}
BREAKS = ("unchanged", "half_batch", "no_exchange", "control")


def _small_cell():
    from bench import spec
    cell = spec.resolve(spec.load_benchmark(ROOT), CELL)
    cell.config.update(SMALL)
    cell.config["limits"] = dict(CPU_LIMITS)
    cell.traffic["seq_len"] = 256
    return cell


def _run_case(case: str) -> dict:
    """One CPU run of the cell with ``case`` broken underneath."""
    import jax

    import repro.launch.train as LT
    from bench import peaks, train_mesh_cell as C
    from bench.reference import dense_lm_mesh
    from repro.launch import device as D
    from repro.models import model as M
    D.hbm_bytes = lambda d=None: 256 * 2**20
    D.bytes_in_use = lambda d=None: 0
    D.peak_bytes_in_use = lambda d=None: 1
    peaks.PEAKS[jax.devices()[0].device_kind] = {"bf16_flops_per_s": 1e12}
    # the CPU never runs out of memory: the need is the estimate
    C.measure_need = lambda prog, seed, used: {
        "need": prog.gate.peak_bytes, "resolution": 2**20,
        "fits_estimate": True, "probes": 0, "remade": 0, "seconds": 0.0}
    make, loss_fn, first = LT.make_train_step, M.loss_fn, C.first_steps
    read_trace = C.read_trace

    def broken(wrap):
        def make_broken(cfg, policy):
            step, opt = make(cfg, policy)
            return wrap(step), opt
        LT.make_train_step = make_broken

    if case == "unchanged":
        def wrap(step):
            def run(params, opt_state, batch):
                loss, _, _ = step(params, opt_state, batch)
                return loss, params, opt_state
            return run
        broken(wrap)
    elif case == "half_batch":
        def wrap(step):
            def run(params, opt_state, batch):
                s = batch["tokens"].shape[1]
                return step(params, opt_state,
                            {k: v[:, :s // 2] for k, v in batch.items()})
            return run
        broken(wrap)
    elif case == "no_exchange":
        M.loss_fn = lambda params, batch, cfg: loss_fn(
            dense_lm_mesh.exchange_left_out(params, 4), batch, cfg)
    elif case == "control":
        def control(prog, seed):
            first(prog, seed)
            return C.reference_readings(prog, seed, precision="fp8")
        C.first_steps = control
    elif case == "traced":
        C.read_trace = lambda path: _with_made_up_device(read_trace(path))
        # four devices' baseline, then bytes held with the state live
        calls = iter(range(10**6))
        D.bytes_in_use = lambda d=None: 0 if next(calls) < 4 else 2**20
    cell = _small_cell()
    try:
        out = C.run(cell, SEED, 0.2, case == "traced", time.perf_counter(),
                    jax.devices()[:4])
    finally:
        LT.make_train_step, M.loss_fn, C.first_steps = make, loss_fn, first
        C.read_trace = read_trace
    if case == "traced":
        return {"metrics": {m["name"]: cell.readers[m["name"]](out["ctx"])
                            for m in cell.per_layer},
                "trace_parts": out["log"]["trace_parts"],
                "window_steps": out["log"]["window_steps"],
                "losses": len(out["log"]["readings"]["losses"]),
                "device": sorted(out["device"]),
                "breakdown": sorted(out["breakdown"])}
    return {"correct": out["correct"], "checks": out["checks"],
            "failed": out["failed"], "attempted": out["attempted"],
            "e2e": sorted(out["e2e"]), "memory_peak_bytes":
            out["device"]["memory_peak_bytes"],
            "log": {k: out["log"][k] for k in (
                "gate_collective_blocks", "gate_collective_bytes",
                "place_s", "gaps")}}


def _with_made_up_device(tr: dict) -> dict:
    """The CPU's trace holds no device plane: ``read_trace``'s host
    events, with one device whose only operation, an all-reduce, takes
    the middle half of the traced steps' span."""
    from bench import trace_reduce as T, train_mesh_cell as C
    lo, hi = T.window_of(tr["host"], C.TRACED_SPAN)
    q = (hi - lo) / 4
    op = (lo + q, hi - q, "all-reduce.1", "all-reduce")
    tr["ops"] = {"/device:TPU:0": [op]}
    tr["devices"] = {"/device:TPU:0": [op[:3]]}
    return tr


def _reference_gaps() -> dict:
    """The mesh reference against ``dense_lm`` on one device."""
    import jax

    from bench import compare
    from bench.reference import dense_lm, dense_lm_mesh
    from repro.launch.mesh import make_mesh
    cfg = _small_cell().config
    mesh = make_mesh(cfg["mesh"])
    out = {}
    for prec, fault in (("f32", None), ("f32", "half_batch")):
        a = dense_lm_mesh.train_readings(cfg, SEED, 3, 1, 256, mesh, prec,
                                         fault)
        b = dense_lm.train_readings(cfg, SEED, 3, 1, 256, prec, fault,
                                    device=jax.devices()[0])
        out[fault or prec] = compare.train_gaps(a, b)
    return out


def main() -> None:
    results = {case: _run_case(case)
               for case in ("sound",) + BREAKS + ("traced",)}
    results["reference"] = _reference_gaps()
    print(json.dumps(results))


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_a_sound_mesh_run_is_correct(runs):
    out = runs["sound"]
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 2
    assert out["e2e"] == ["est_err_pct", "setup_s", "tokens_per_s"]
    # the gate staged the tensor-parallel all-gather at its peak, and the
    # weights were placed under the program's span
    assert out["log"]["gate_collective_blocks"] == 1
    assert out["log"]["gate_collective_bytes"] > 0
    assert len(out["log"]["place_s"]) == 1


@pytest.mark.parametrize("case", BREAKS)
def test_a_broken_mesh_step_is_not_correct(runs, case):
    out = runs[case]
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", ["f32", "half_batch"])
def test_the_mesh_reference_reads_what_dense_lm_reads(runs, fault):
    """Same mathematics, partitioned: the sums over shards differ from
    one device's only in their last bits (~1e-6 relative), and a weight
    stored in bf16 may round the other way where they do (one bf16 step
    of a changed weight, ~4e-3 of its change, in one of its elements)."""
    gaps = runs["reference"][fault]
    assert gaps["loss_gap"] < 1e-4
    assert gaps["grad_gap"] < 1e-4
    assert gaps["delta_gap"] < 1e-3


def test_a_traced_run_reports_every_metric_over_its_traced_steps(runs):
    """The profiler runs only over the traced steps after the window, in
    their own span; every per-layer metric the cell lists is read from
    the runner's ``ctx`` (here the made-up device: an all-reduce over
    half of the span, the device idle the other half)."""
    from bench import spec
    from bench.train_mesh_cell import TRACE_STEPS
    out = runs["traced"]
    names = [m["name"] for m in spec.resolve(spec.load_benchmark(ROOT),
                                             CELL).per_layer]
    assert sorted(out["metrics"]) == sorted(names)
    assert all(v is not None for v in out["metrics"].values()), out
    assert out["metrics"]["collective_pct.train"] == pytest.approx(50.0)
    assert out["metrics"]["device_idle_pct.train"] == pytest.approx(50.0)
    parts = out["trace_parts"]
    assert parts["steps"] == TRACE_STEPS and parts["span_found"]
    assert parts["device_events"] == 1
    assert out["window_steps"] >= 1 and out["losses"] == 3
    assert {"busy_s", "window_s", "memory_peak_bytes"} <= set(out["device"])
    assert out["breakdown"] == ["collectives", "device_ops", "idle_gaps"]


# -- the collective bucket, on made-up intervals ---------------------------
def test_opcode_and_bucket():
    from bench.train_mesh_cell import collective_bucket, opcode
    text = ("%all-gather-start.3 = (bf16[8,128]{1,0:T(8,128)(2,1)}, "
            "bf16[32,128]{1,0}) all-gather-start(bf16[8,128]{1,0} %p), "
            "dimensions={0}")
    assert opcode(text) == "all-gather-start"
    assert opcode("%fusion.1 = f32[4]{0:T(256)} fusion(f32[4] %a)") == \
        "fusion"
    assert opcode("%copy-done = bf16[512,512]{1,0:T(8,128)(2,1)S(1)} "
                  "copy-done((bf16[512,512]) %copy-start)") == "copy-done"
    assert collective_bucket("all-gather-start.3", "all-gather-start") == \
        "all-gather-start"
    assert collective_bucket("all-reduce.7", "all-reduce") == "all-reduce"
    assert collective_bucket("reduce-scatter.2", "async-start") == \
        "async-start:reduce-scatter"
    assert collective_bucket("fusion.4", "fusion") is None
    assert collective_bucket("while.1", "while") is None


def test_exposed_collective_time_leaves_out_what_compute_covers():
    from bench.train_mesh_cell import collectives
    ops = {"/device:TPU:0": [
        (0, 100, "while.1", "while"),             # a container: ignored
        (0, 10, "fusion.1", "fusion"),
        (10, 30, "all-reduce-start.1", "all-reduce-start"),
        (20, 25, "fusion.2", "fusion"),           # covers 5 of the 20
        (30, 40, "all-reduce-done.1", "all-reduce-done"),
        (90, 110, "all-gather.2", "all-gather"),  # 10 of it in the window
    ], "/device:TPU:1": [
        (0, 50, "fusion.1", "fusion"),
        (40, 60, "all-reduce.1", "all-reduce"),   # 10 exposed
    ]}
    got = collectives(ops, 0, 100)
    d0 = got["/device:TPU:0"]
    assert d0["exposed_s"] == pytest.approx((15 + 10 + 10) * 1e-9)
    assert d0["collective_s"] == pytest.approx(40e-9)
    assert d0["by_opcode"] == pytest.approx({
        "all-gather": 10e-9, "all-reduce-done": 10e-9,
        "all-reduce-start": 20e-9})
    assert got["/device:TPU:1"]["exposed_s"] == pytest.approx(10e-9)


def test_read_trace_on_a_recorded_step():
    """On a v5e recording of a one-chip step: the device and host events
    are ``trace_reduce.read``'s, each operation carries its opcode, and
    no collective is found where there is none."""
    from bench import trace_reduce as T
    from bench.train_mesh_cell import collectives, read_trace
    path = os.path.join(ROOT, "bench", "tests", "data",
                        "scoped_step.trace.pb")
    mine, theirs = read_trace(path), T.read(path)
    assert mine["devices"] == theirs["devices"]
    assert mine["host"] == theirs["host"]
    ops = mine["ops"]["/device:TPU:0"]
    assert {"fusion", "copy-start", "while", "custom-call"} <= \
        {op for *_, op in ops}
    got = collectives(mine["ops"], ops[0][0], max(e for _, e, *_ in ops))
    assert got["/device:TPU:0"] == {"exposed_s": 0.0, "collective_s": 0.0,
                                    "by_opcode": {}}


def test_collective_pct_reads_the_largest_device():
    from bench import spec
    read = spec.load_reader("collective_pct.train")
    ctx = {"collectives": {"window_s": 2.0, "devices": {
        "a": {"exposed_s": 0.1}, "b": {"exposed_s": 0.3}}}}
    assert read(ctx) == pytest.approx(15.0)
    assert read({}) is None
    assert read({"trace": {"busy_s": 1.0, "window_s": 2.0}}) is None


def test_calibration_judges_each_kind_its_own_way():
    from bench import calibrate_mesh
    cfg = {"limits": {"grad_gap": 1e-3, "delta_gap": 1e-2}}
    ref = {"losses": [5.0, 4.9, 4.8], "grad_norms": [1.0, 2.0, 3.0],
           "delta_norms": [0.1, 0.2, 0.3]}
    sound = {**ref, "grad_norms": [1.0, 2.0, 3.0005]}
    rows = [{"kind": "sound", "seed": 1, "readings": sound},
            {"kind": "unchanged", "seed": 1,
             "readings": calibrate_mesh.unchanged(sound)},
            {"kind": "control", "seed": 1,
             "readings": {**ref, "grad_norms": [1.0, 2.0, 3.1]}}]
    assert calibrate_mesh.judge(rows, {"1": ref}, cfg) == []
    assert [r["correct"] for r in rows] == [True, False, False]
    rows[2]["kind"] = "sound"          # a reading judged the wrong way
    assert calibrate_mesh.judge(rows, {"1": ref}, cfg) == ["sound"]


if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    main()
