"""The trainer on a mesh (``train_loop(mesh=...)``, ``--mesh``).

On four CPU devices, in subprocesses that ask XLA for them so that the
suite's own device count does not change (this file, run as a script,
and the trainer's command line): a data 1 x model 4 job follows the
plain mesh reference (``bench/reference/dense_lm_mesh.py``) over three
steps, and its per-device gate, at DeepSeek LLM 7B's published widths
over 15 layers, is byte for byte the decision the admission service
gives on the same job as the daemon and the planner build it. In this
process: without a mesh the gate's bytes are those it gave before
meshes reached the trainer.
"""
import json
import os
import subprocess
import sys
import tempfile

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")
SEQ = 256
SMALL = dict(hidden_size=256, intermediate_size=1024, num_attention_heads=4,
             num_key_value_heads=4, head_dim=64, num_hidden_layers=2,
             vocab_size=4096)
# The gate for bench/configs/deepseek-llm-7b-tp4.json (1 x 4096, data 1 x
# model 4), per device, on the CPU: with the mesh's collective staging
# (the tensor-parallel all-gather of the largest activation), and the
# same job without it, as the trainer's gate gave it before it took the
# mesh's collective specs.
TP4_WITH_STAGING = 15407423488
TP4_WITHOUT_STAGING = 13729701888
# The one-chip cells' gate (CPU estimate), unchanged by the mesh path.
ONE_CHIP = {"deepseek-llm-7b": 15002365952, "phi4-mini-3.8b": 10528751616}


def _cfg(name: str, **overrides) -> dict:
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    cfg.update(overrides)
    return cfg


def _job(cfg: dict, seq: int):
    from bench.train_cell import program_config, program_policy
    from repro.configs.base import ShapeSpec
    return (program_config(cfg), program_policy(cfg),
            ShapeSpec(cfg["name"], seq, cfg["train"]["batch"], "train"))


def _follows_the_reference() -> dict:
    """Three steps of ``train_loop`` on the mesh, and the reference's
    three steps from the same weights over the same batches."""
    import jax
    import numpy as np

    from bench.reference import dense_lm_mesh
    from repro.launch.mesh import make_mesh
    from repro.launch.train import train_loop
    from repro.models import model as M
    from repro.train import CheckpointManager, SyntheticDataset
    cfg = _cfg("deepseek-llm-7b-tp4", **SMALL)
    mcfg, policy, shape = _job(cfg, SEQ)
    mesh = make_mesh(cfg["mesh"])
    p0 = jax.device_get(M.init_params(mcfg, jax.random.key(0)))
    with tempfile.TemporaryDirectory() as ckpt:
        res = train_loop(mcfg, shape, policy, steps=3, ckpt_dir=ckpt,
                         mesh=mesh)
        _, state = CheckpointManager(ckpt).restore_latest(
            {"params": p0, "opt_state": _opt_init(mcfg, policy, p0)})
    ds = SyntheticDataset(mcfg, shape)
    start = jax.device_put(p0, dense_lm_mesh.shardings(cfg, mesh))
    losses, _, ref = dense_lm_mesh.train(cfg, start,
                                         [ds.batch(i) for i in range(3)],
                                         mesh)

    def moved(p):
        return {k: float(np.linalg.norm(
            np.asarray(a, np.float32) - np.asarray(b, np.float32)))
            for (k, a), (_, b) in zip(_flat(p), _flat(p0))}
    return {"losses": res.losses, "ref_losses": losses,
            "moved": moved(state["params"]), "ref_moved": moved(ref)}


def _opt_init(mcfg, policy, params):
    from repro.train import make_train_step
    return make_train_step(mcfg, policy)[1].init(params)


def _flat(tree):
    import jax
    return [(jax.tree_util.keystr(k), v) for k, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def _gate_against_the_daemon() -> dict:
    """The trainer's per-device gate at full width, the service's
    decision on the request a topology offer reproduces, and the mesh
    sweep's estimate, for the same job."""
    import jax

    from repro.configs.registry import input_specs
    from repro.core.sweep import MeshTopology
    from repro.launch.mesh import make_mesh
    from repro.launch.train import mesh_job, replan_if_needed
    from repro.models import model as M
    from repro.plan.planner import _factor_kwargs
    from repro.service import AdmissionRequest, AdmissionService
    from repro.train import make_estimator_hooks
    cfg = _cfg("deepseek-llm-7b-tp4")
    mcfg, policy, shape = _job(cfg, 4096)
    job = mesh_job(mcfg, shape, policy, make_mesh(cfg["mesh"]),
                   hbm_bytes=16909336064)
    fwd, upd, init = make_estimator_hooks(mcfg, policy)
    params, batch = M.abstract_params(mcfg), input_specs(mcfg, shape)
    topo = MeshTopology(data=1, model=4)
    service = AdmissionService(workers=1)
    wire = service.decide(AdmissionRequest(
        "tp4", fwd, params, batch, update_fn=upd, opt_init_fn=init,
        capacity=16909336064,
        **_factor_kwargs(mcfg, params, batch, topo, init)))
    sweep = service.mesh_sweep(fwd, params, batch, [topo], update_fn=upd,
                               opt_init_fn=init, cfg=mcfg)
    from repro.distributed.sharding import shard_factor_fn
    from repro.launch.mesh import arch_sharding_policy
    mesh = job.mesh
    bare = replan_if_needed(
        mcfg, policy, shape, 16909336064, service=service,
        shard_factor_fn=shard_factor_fn(
            mcfg, dict(mesh.shape), arch_sharding_policy(mcfg, mesh),
            params=params, opt_state=jax.eval_shape(init, params),
            batch=batch))[1]
    rep = job.report
    return {"trainer": rep.peak_bytes, "wire": wire.peak_bytes,
            "sweep": sweep.reports[0].peak_bytes,
            "without_staging": bare.peak_bytes,
            "collective_blocks": rep.breakdown["collective_blocks"],
            "collective_bytes": rep.breakdown["collective_bytes"],
            "trainer_breakdown": rep.breakdown,
            "wire_breakdown": wire.breakdown}


def main() -> None:
    print(json.dumps({"reference": _follows_the_reference(),
                      "gate": _gate_against_the_daemon()}))


def _env() -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, SRC]))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


@pytest.fixture(scope="module")
def four_devices():
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          cwd=ROOT, env=_env(), capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_mesh_train_loop_follows_the_reference(four_devices):
    """Loss within 1.5e-3 nats, and each leaf's change within 2e-3 of the
    reference's (over the larger of its and the median leaf's change):
    the program computes in bf16 with f32 accumulation, the reference in
    f32 at HIGHEST, and at this size on the CPU sound runs read at most
    5e-4 on both (the bench's CPU limits, test_train_mesh_cell.py); half
    of the batch left out or the exchange left out reads 2e-2 and more."""
    r = four_devices["reference"]
    assert len(r["losses"]) == 3
    for a, b in zip(r["losses"], r["ref_losses"]):
        assert abs(a - b) <= 1.5e-3
    ref = r["ref_moved"]
    med = sorted(ref.values())[len(ref) // 2]
    for k, v in r["moved"].items():
        assert abs(v - ref[k]) / max(ref[k], med) <= 2e-3, k


def test_trainer_gate_is_the_daemons_decision(four_devices):
    g = four_devices["gate"]
    assert g["trainer"] == g["wire"] == g["sweep"] == TP4_WITH_STAGING
    assert g["trainer_breakdown"] == g["wire_breakdown"]
    assert g["without_staging"] == TP4_WITHOUT_STAGING
    # the staging buffer live at the peak is the whole difference
    assert g["collective_blocks"] == 1
    assert g["collective_bytes"] == 1677721600


def test_train_cli_takes_a_mesh(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "qwen3-32b",
         "--smoke", "--steps", "2", "--ckpt-dir", str(tmp_path / "ckpt"),
         "--mesh", "data=1,model=4"],
        cwd=ROOT, env=dict(_env(), JAX_COMPILATION_CACHE_DIR=str(
            tmp_path / "jax_cache")),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "collective staging 1 buffers" in proc.stdout
    assert "[done] final loss" in proc.stdout


@pytest.mark.parametrize("spec, sizes", [
    ("data=1,model=4", {"data": 1, "model": 4}),
    ("data=2, model=2", {"data": 2, "model": 2})])
def test_parse_mesh(spec, sizes):
    from repro.launch.mesh import parse_mesh
    assert parse_mesh(spec) == sizes
    assert list(parse_mesh(spec)) == list(sizes)


@pytest.mark.parametrize("spec", ["data", "model=0", "=4", "data=x"])
def test_parse_mesh_refuses(spec):
    from repro.launch.mesh import parse_mesh
    with pytest.raises(ValueError):
        parse_mesh(spec)


@pytest.mark.parametrize("name", sorted(ONE_CHIP))
def test_replan_without_a_mesh_keeps_its_bytes(name):
    from repro.launch.device import V5E_HBM_BYTES
    from repro.launch.train import replan_if_needed
    cfg = _cfg(name)
    mcfg, policy, shape = _job(cfg, 4096)
    _, rep = replan_if_needed(mcfg, policy, shape, V5E_HBM_BYTES)
    assert rep.peak_bytes == ONE_CHIP[name]
    assert rep.breakdown["collective_blocks"] == 0
    assert rep.breakdown["collective_bytes"] == 0


if __name__ == "__main__":
    sys.path[:0] = [ROOT, SRC]
    main()
