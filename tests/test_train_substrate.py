"""Training-substrate tests: optimizers, checkpointing, elasticity, data."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis "
    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.configs import get_smoke
from repro.configs.base import smoke_shape
from repro.models import model as M
from repro.train import (CheckpointManager, DataConfig, MeshPlan,
                         StragglerMonitor, SyntheticDataset, TrainPolicy,
                         get_optimizer, make_train_step, replan_mesh)


# ---------------------------------------------------------------------------
class TestOptimizers:
    @pytest.mark.parametrize("name", ["sgd", "sgd_momentum", "adam",
                                      "adamw", "rmsprop", "adagrad",
                                      "adafactor"])
    def test_reduces_quadratic_loss(self, name):
        opt = get_optimizer(name, lr=0.1)
        params = {"w": jnp.ones((8, 8)) * 3.0}
        state = opt.init(params)

        def loss(p):
            return jnp.sum(p["w"] ** 2)

        l0 = loss(params)
        for _ in range(25):
            g = jax.grad(loss)(params)
            params, state = opt.update(params, g, state)
        threshold = 0.9 if name == "adagrad" else 0.5
        assert float(loss(params)) < float(l0) * threshold

    def test_adafactor_state_is_factored(self):
        opt = get_optimizer("adafactor")
        params = {"w": jnp.zeros((64, 128))}
        st_ = opt.init(params)
        leaves = jax.tree_util.tree_leaves(st_)
        state_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
        param_bytes = 64 * 128 * 4
        assert state_bytes < 0.1 * param_bytes  # rows+cols only

    def test_adam_state_doubles_params(self):
        opt = get_optimizer("adam")
        params = {"w": jnp.zeros((64, 128), jnp.float32)}
        st_ = opt.init(params)
        state_bytes = sum(x.size * x.dtype.itemsize
                          for x in jax.tree_util.tree_leaves(st_))
        assert state_bytes >= 2 * 64 * 128 * 4


# ---------------------------------------------------------------------------
class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        state = {"params": {"w": jnp.arange(12.0).reshape(3, 4)},
                 "opt": (jnp.ones((2,)),)}
        mgr.save(10, state)
        got = mgr.restore(10, state)
        np.testing.assert_array_equal(np.asarray(got["params"]["w"]),
                                      np.asarray(state["params"]["w"]))

    def test_latest_step_ignores_torn_manifest(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        state = {"w": jnp.ones((2,))}
        mgr.save(5, state)
        # torn manifest: truncated json
        with open(os.path.join(str(tmp_path),
                               "ckpt_step0000000009_shard0.manifest.json"),
                  "w") as f:
            f.write('{"step": 9, "comp')
        assert mgr.latest_step() == 5

    def test_integrity_check(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        state = {"w": jnp.ones((4,))}
        base = mgr.save(3, state)
        with open(base + ".npz", "r+b") as f:
            f.seek(50)
            f.write(b"\xff\xff")  # corrupt payload
        with pytest.raises(IOError):
            mgr.restore(3, state)

    def test_emergency_preferred_when_newer(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        state = {"w": jnp.zeros((2,))}
        mgr.save(10, state)
        mgr.emergency(17, {"w": jnp.ones((2,))})
        step, got = mgr.restore_latest(state)
        assert step == 17
        assert float(got["w"][0]) == 1.0

    def test_retention(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        state = {"w": jnp.zeros((2,))}
        for s in (1, 2, 3, 4):
            mgr.save(s, state)
        assert mgr.latest_step() == 4
        manis = [f for f in os.listdir(str(tmp_path))
                 if f.startswith("ckpt") and f.endswith("manifest.json")]
        assert len(manis) == 2

    def test_resume_equivalence(self, tmp_path):
        """Training N steps == training k, restoring, training N-k —
        the fault-tolerance contract (incl. data order)."""
        cfg = get_smoke("starcoder2-3b")
        shape = smoke_shape(seq_len=32, global_batch=2)
        step_fn, opt = make_train_step(cfg, TrainPolicy(optimizer="adam"))
        jit_step = jax.jit(step_fn)
        ds = SyntheticDataset(cfg, shape)

        def run(params, opt_state, a, b):
            for s in range(a, b):
                batch = jax.tree_util.tree_map(jnp.asarray, ds.batch(s))
                loss, params, opt_state = jit_step(params, opt_state, batch)
            return loss, params, opt_state

        p0 = M.init_params(cfg, jax.random.key(0))
        s0 = opt.init(p0)
        loss_full, pf, _ = run(p0, s0, 0, 6)

        p1 = M.init_params(cfg, jax.random.key(0))
        s1 = opt.init(p1)
        _, p1, s1 = run(p1, s1, 0, 3)
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(3, {"params": p1, "opt": s1})
        got = mgr.restore(3, {"params": p1, "opt": s1})
        loss_resumed, pr, _ = run(got["params"], got["opt"], 3, 6)
        assert float(loss_full) == pytest.approx(float(loss_resumed),
                                                 rel=1e-5)


# ---------------------------------------------------------------------------
class TestElastic:
    def test_replan_keeps_model_axis(self):
        plan = MeshPlan(pod=2, data=16, model=16)
        new = replan_mesh(plan, available_devices=256)
        assert new.model == 16
        assert new.devices <= 256

    def test_replan_rejects_too_few(self):
        with pytest.raises(ValueError):
            replan_mesh(MeshPlan(1, 1, 16), available_devices=8)

    @settings(max_examples=50, deadline=None)
    @given(avail=st.integers(min_value=16, max_value=1024))
    def test_replan_property(self, avail):
        plan = MeshPlan(pod=2, data=8, model=16)
        if avail < plan.model:
            return
        new = replan_mesh(plan, avail)
        assert new.devices <= avail
        assert new.model == plan.model
        assert new.devices % new.model == 0

    def test_straggler_detection(self):
        mon = StragglerMonitor(n_workers=8)
        for step in range(16):
            for w in range(8):
                mon.record(w, 1.0 + (5.0 if w == 3 else 0.0))
        assert mon.stragglers() == [3]
        plan = mon.reassignment_plan()
        assert 3 in plan and plan[3] != 3


# ---------------------------------------------------------------------------
class TestData:
    def test_determinism_across_restarts(self):
        cfg = get_smoke("qwen3-32b")
        shape = smoke_shape(seq_len=32, global_batch=4)
        a = SyntheticDataset(cfg, shape).batch(7)
        b = SyntheticDataset(cfg, shape).batch(7)
        np.testing.assert_array_equal(a["tokens"], b["tokens"])

    def test_shards_disjoint_streams(self):
        cfg = get_smoke("qwen3-32b")
        shape = smoke_shape(seq_len=32, global_batch=4)
        a = SyntheticDataset(cfg, shape, num_shards=2, shard_index=0).batch(0)
        b = SyntheticDataset(cfg, shape, num_shards=2, shard_index=1).batch(0)
        assert not np.array_equal(a["tokens"], b["tokens"])
        assert a["tokens"].shape[0] == 2  # local batch

    def test_labels_are_shifted_tokens(self):
        cfg = get_smoke("qwen3-32b")
        ds = SyntheticDataset(cfg, smoke_shape(seq_len=16, global_batch=2))
        b = ds.batch(0)
        assert b["tokens"].shape == b["labels"].shape
        assert (b["tokens"] < cfg.vocab).all()

    def test_family_specific_batches(self):
        for arch in ("internvl2-1b", "musicgen-medium"):
            cfg = get_smoke(arch)
            ds = SyntheticDataset(cfg, smoke_shape(seq_len=32,
                                                   global_batch=2))
            b = ds.batch(0)
            if cfg.family == "vlm":
                assert "patch_embeds" in b
            else:
                assert b["codes"].shape[-1] == cfg.num_codebooks


# ---------------------------------------------------------------------------
class TestTrainLoop:
    """``launch/train.py``: the gate -> init -> steps -> checkpoint path."""

    def test_gate_moves_nothing_to_the_device(self):
        from repro.launch.train import admission_check
        # the gate only traces and replays: any host-to-device transfer
        # (a concrete PRNG key, a constant) would raise here
        with jax.transfer_guard("disallow"):
            ok, rep = admission_check(get_smoke("starcoder2-3b"),
                                      TrainPolicy(), smoke_shape(32, 2),
                                      verbose=False)
        assert ok and rep.peak_bytes > rep.persistent_bytes > 0

    def test_result_records_every_step(self, tmp_path):
        from repro.launch.train import train_loop
        res = train_loop(get_smoke("starcoder2-3b"), smoke_shape(32, 2),
                         TrainPolicy(), steps=3, ckpt_dir=str(tmp_path))
        assert res.start_step == 0 and res.report is not None
        assert len(res.losses) == len(res.step_s) == 3
        assert all(np.isfinite(res.losses)) and res.loss == res.losses[-1]
        assert min(res.step_s) > 0 and res.ckpt_s > 0
        assert CheckpointManager(str(tmp_path)).latest_step() == 3

    def test_failed_emergency_save_keeps_the_original_error(
            self, tmp_path, monkeypatch):
        from repro.launch.train import train_loop

        def bad_batch(self, step):
            raise RuntimeError("step failed")

        def bad_save(self, step, state):
            raise IOError("disk gone")
        monkeypatch.setattr(SyntheticDataset, "batch", bad_batch)
        monkeypatch.setattr(CheckpointManager, "emergency", bad_save)
        with pytest.raises(RuntimeError, match="step failed") as ei:
            train_loop(get_smoke("starcoder2-3b"), smoke_shape(32, 2),
                       TrainPolicy(), steps=2, ckpt_dir=str(tmp_path),
                       skip_gate=True)
        assert any("emergency checkpoint at step 0 failed: OSError: "
                   "disk gone" in n for n in ei.value.__notes__)
