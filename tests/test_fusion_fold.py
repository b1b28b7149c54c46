"""Fusion interiors: the tracer decides which values XLA keeps inside a
fusion from their consumers, an interior value holds its operands until
its own last consumer has run, and the orchestrator drops it.

The last tests guard the two on-chip benchmark shapes and chip_smoke.py's
job: the gate's estimate must stay at or above the need a chip measured
for each.
"""
import jax
import jax.numpy as jnp
import pytest
from jax import lax

from repro.configs import ARCH_IDS, get_smoke
from repro.configs.base import AttentionConfig, ModelConfig, ShapeSpec, \
    smoke_shape
from repro.configs.registry import input_specs
from repro.core import (BlockKind, TraceCache, XMemEstimator, trace_fn,
                        trace_key)
from repro.launch.train import admission_check, make_estimator_hooks
from repro.models import model as M
from repro.train import TrainPolicy

FUSED = BlockKind.FUSED
f32, bf16 = jnp.float32, jnp.bfloat16


def _sds(shape, dtype=f32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _lifecycles(fn, *args, fold=True):
    _, tr = trace_fn(fn, *args, fusion_folding=fold)
    return tr.lifecycles()


def _only(blocks, op):
    found = [b for b in blocks if b.op == op]
    assert len(found) == 1, (op, found)
    return found[0]


def _chain(a, w):
    x = a @ w                          # materialized: the chain's operand
    y = x.astype(f32) * 2.0            # convert -> mul
    return jnp.exp(y).sum(axis=-1)     # exp -> reduce_sum


class TestChain:
    def test_chain_is_folded(self):
        blocks = _lifecycles(_chain, _sds((64, 128), bf16),
                             _sds((128, 256), bf16))
        for op in ("convert_element_type", "mul", "exp"):
            assert _only(blocks, op).block_kind is FUSED
        assert _only(blocks, "dot_general").block_kind is not FUSED
        assert _only(blocks, "reduce_sum").block_kind is not FUSED

    def test_operand_lives_until_the_reduction(self):
        args = (_sds((64, 128), bf16), _sds((128, 256), bf16))
        folded = _lifecycles(_chain, *args)
        x, red = _only(folded, "dot_general"), _only(folded, "reduce_sum")
        assert x.free_t > red.alloc_t
        # without folding the operand dies at its only reader, the convert
        plain = _lifecycles(_chain, *args, fold=False)
        x, conv = (_only(plain, "dot_general"),
                   _only(plain, "convert_element_type"))
        red = _only(plain, "reduce_sum")
        assert conv.alloc_t < x.free_t < red.alloc_t
        assert not any(b.block_kind is FUSED for b in plain)


def _tanh_feeds_dot(a, w):
    return jnp.tanh(a) @ w


def _transpose_feeds_dot(a, w):
    return a @ w.T


def _reshape_feeds_dot(a, w):
    return a.reshape(64, 128) @ w


def _transpose_feeds_add(a, w):
    return (a @ w) + w.T.sum()


@pytest.mark.parametrize("fn,args,op,fused", [
    (_tanh_feeds_dot, ((64, 128), (128, 256)), "tanh", False),
    (_transpose_feeds_dot, ((64, 128), (256, 128)), "transpose", True),
    (_reshape_feeds_dot, ((8, 8, 128), (128, 256)), "reshape", True),
    (_transpose_feeds_add, ((64, 128), (128, 64)), "transpose", True),
], ids=["tanh-materializes", "transpose-absorbed", "reshape-absorbed",
        "transpose-into-reduction"])
def test_dot_general_consumer(fn, args, op, fused):
    """A dot_general makes its operand materialize, except a layout-only
    producer, whose layout the dot takes in its dimension numbers."""
    blocks = _lifecycles(fn, *(_sds(s) for s in args))
    assert (_only(blocks, op).block_kind is FUSED) is fused


# An expensive producer (exp) read by two consumers. The TPU compiler
# recomputes it inside each consumer's fusion, or emits both reductions
# from one fusion: it never writes it to HBM (test_tpu_compile.py holds
# the compiler to that). Each function's exp reads ``x = a @ b``; the
# string names the exp's last consumer.
def _softmax_dot(a, b, w):
    x = a @ b
    e = jnp.exp(x - lax.stop_gradient(x.max(axis=-1, keepdims=True)))
    return (e / e.sum(axis=-1, keepdims=True)) @ w


def _exp_two_reductions(a, b, w):
    e = jnp.exp(a @ b)
    return e.sum(axis=-1) + e.max(axis=-1)


def _exp_reduction_and_dot(a, b, w):
    e = jnp.exp(a @ b)
    return e.sum(axis=-1), (e * 2.0).astype(bf16) @ w.astype(bf16)


MULTI_CONSUMER_EXP = pytest.mark.parametrize("fn,last", [
    (_softmax_dot, "div"),
    (_exp_two_reductions, "reduce_max"),
    (_exp_reduction_and_dot, "mul"),
], ids=["softmax-into-dot", "two-reductions", "reduction-and-dot"])


@MULTI_CONSUMER_EXP
def test_exp_with_two_consumers_is_folded(fn, last):
    """Every consumer fuses the exp, so it is an interior value, and the
    operand it reads stays live until its last consumer has run."""
    blocks = _lifecycles(fn, _sds((256, 64)), _sds((64, 512)),
                         _sds((512, 128)))
    assert _only(blocks, "exp").block_kind is FUSED
    x = min((b for b in blocks if b.op == "dot_general"),
            key=lambda b: b.alloc_t)
    assert x.block_kind is not FUSED
    assert x.free_t > _only(blocks, last).alloc_t


def _scan(c0, xs):
    def body(c, x):
        c2 = c * 2.0 + x               # mul fuses into the carry's add
        return c2, jnp.exp(c2)         # exp is a stacked output
    return lax.scan(body, c0, xs)


def _while(c0):
    def body(ic):
        i, c = ic
        return i + 1, jnp.tanh(c) * 1.5    # tanh fuses into the carry
    return lax.while_loop(lambda ic: ic[0] < 4, body, (0, c0))


@pytest.mark.parametrize("fn,args,kept,folded", [
    (_scan, ((32, 64), (5, 32, 64)), ("add", "exp"), ("mul",)),
    (_while, ((32, 64),), ("add", "mul"), ("tanh",)),
], ids=["scan", "while"])
def test_loop_outputs_never_fold(fn, args, kept, folded):
    blocks = _lifecycles(fn, *(_sds(s) for s in args))
    for op in kept:
        assert all(b.block_kind is not FUSED for b in blocks if b.op == op)
    for op in folded:
        ours = [b for b in blocks if b.op == op]
        assert ours and all(b.block_kind is FUSED for b in ours)
    assert all(b.block_kind is not FUSED for b in blocks
               if b.op == "scan_ys")


# -- presets -----------------------------------------------------------------
# for_torch_gpu estimates of the smoke architectures (seq 32, batch 2,
# TrainPolicy()), as the estimator gave them before fusion interiors
# existed: peak_bytes, peak_tensor_bytes, persistent_bytes, num_events,
# liveness_peak. PyTorch eager materializes every op, so none may move.
TORCH_ESTIMATES = {
    "kimi-k2-1t-a32b": (6291456, 5684224, 1571968, 4045, 5676184),
    "phi3.5-moe-42b-a6.6b": (6291456, 5661696, 1565824, 4045, 5653656),
    "qwen3-32b": (4194304, 3814400, 1068800, 2878, 3798296),
    "phi4-mini-3.8b": (4194304, 3804160, 1068160, 2386, 3796120),
    "starcoder2-3b": (4194304, 3804160, 1068160, 2386, 3796120),
    "gemma3-4b": (8388608, 6481920, 1808000, 3141, 6475416),
    "jamba-1.5-large-398b": (27262976, 25512960, 7299712, 21059, 25500312),
    "internvl2-1b": (4194304, 3806208, 1068160, 2395, 3798040),
    "xlstm-1.3b": (8388608, 6352384, 1840480, 36442, 6340088),
    "musicgen-medium": (6291456, 4083712, 1150080, 2412, 4076184),
}


def test_torch_table_covers_the_registry():
    assert set(TORCH_ESTIMATES) == set(ARCH_IDS)


@pytest.mark.parametrize("arch", sorted(TORCH_ESTIMATES))
def test_torch_preset_folds_nothing(arch):
    cfg = get_smoke(arch)
    fwd_bwd, update, opt_init = make_estimator_hooks(cfg, TrainPolicy())
    est = XMemEstimator.for_torch_gpu(trace_cache=TraceCache())
    rep = est.estimate_training(
        fwd_bwd, M.abstract_params(cfg),
        input_specs(cfg, smoke_shape(seq_len=32, global_batch=2)),
        update_fn=update, opt_init_fn=opt_init)
    got = (rep.peak_bytes, rep.peak_tensor_bytes, rep.persistent_bytes,
           rep.num_events, rep.breakdown["liveness_peak"])
    assert got == TORCH_ESTIMATES[arch]
    assert rep.breakdown["fused_blocks"] == rep.breakdown["fused_bytes"] == 0


def _fwd_bwd(p, b):
    y = jnp.exp(b["x"].astype(f32) * 2.0)    # three f32[64, 128] interiors
    loss = y.sum()
    return loss, {"w": p["w"] * loss}


_PARAMS = {"w": _sds((128, 256))}
_BATCH = {"x": _sds((64, 128), bf16)}


class TestTraceCache:
    def test_key_carries_the_flag(self):
        flat = [_PARAMS["w"], _BATCH["x"]]
        kinds = [BlockKind.PARAM, BlockKind.INPUT]
        keys = [trace_key(_fwd_bwd, "fwd", flat, (), kinds, 3, "fwd_bwd",
                          fusion_folding=fold) for fold in (False, True)]
        assert keys[0] != keys[1]
        with pytest.raises(TypeError, match="fusion_folding"):
            trace_key(_fwd_bwd, "fwd", flat, (), kinds, 3, "fwd_bwd")

    def test_folded_and_unfolded_traces_kept_apart(self):
        cache = TraceCache()
        tpu = XMemEstimator.for_tpu(trace_cache=cache)
        torch = XMemEstimator.for_torch_gpu(trace_cache=cache)
        first = [e.estimate_training(_fwd_bwd, _PARAMS, _BATCH)
                 for e in (tpu, torch)]
        assert cache.misses == 2 and cache.hits == 0
        again = [e.estimate_training(_fwd_bwd, _PARAMS, _BATCH)
                 for e in (tpu, torch)]
        assert cache.misses == 2 and cache.hits == 2
        assert [r.breakdown["fused_blocks"] for r in first] == [3, 0]
        assert [r.peak_bytes for r in again] == [r.peak_bytes for r in first]


class TestCounters:
    def test_fused_counts(self):
        rep = XMemEstimator.for_tpu(trace_cache=TraceCache()) \
            .estimate_training(_fwd_bwd, _PARAMS, _BATCH)
        assert rep.breakdown["fused_blocks"] == 3
        assert rep.breakdown["fused_bytes"] == 3 * 64 * 128 * 4
        comp = rep.composition
        assert not any(b.block_kind is FUSED for b in comp.materialize())

    def test_reference_path_counts_the_same(self):
        kw = dict(trace_cache=TraceCache())
        fast = XMemEstimator.for_tpu(**kw).estimate_training(
            _fwd_bwd, _PARAMS, _BATCH)
        ref = XMemEstimator.for_tpu(fastpath=False).estimate_training(
            _fwd_bwd, _PARAMS, _BATCH)
        assert ref.breakdown["fused_blocks"] == fast.breakdown["fused_blocks"]
        assert ref.breakdown["fused_bytes"] == fast.breakdown["fused_bytes"]
        assert ref.peak_bytes == fast.peak_bytes

    def test_gate_line_reports_them(self, capsys):
        cfg = get_smoke("qwen3-32b")
        ok, rep = admission_check(cfg, TrainPolicy(),
                                  smoke_shape(seq_len=32, global_batch=2),
                                  1 << 34)
        line = capsys.readouterr().out
        n, gib = rep.breakdown["fused_blocks"], rep.breakdown["fused_bytes"]
        assert ok and n > 0
        assert f"fused {n} values {gib / 2**30:.2f} GiB" in line


# -- the on-chip benchmark's shapes --------------------------------------------
# Each config is the benchmark's cell as the program runs it (published
# widths; layers and vocabulary cut to one chip's share). The last number
# is the need: the least device memory in which one more step completed
# on a TPU v5e, bisected by the benchmark harness (`measured_need_bytes`).
# A PR that changes the step updates these numbers from its own chip run.
V5E_BYTES_LIMIT = 16909336064
BENCH_CELLS = {
    "deepseek-llm-7b": (dict(n_layers=4, d_model=4096, n_heads=32,
                             n_kv_heads=32, d_ff=11008, vocab=25600,
                             tie_embeddings=False),
                        dict(clip_norm=1.0, learning_rate=4.2e-4,
                             opt_kwargs=(("weight_decay", 0.1),
                                         ("b1", 0.9), ("b2", 0.95),
                                         ("eps", 1e-8))),
                        14373879296),
    "phi4-mini-3.8b": (dict(n_layers=4, d_model=3072, n_heads=24,
                            n_kv_heads=8, d_ff=8192, vocab=100032,
                            tie_embeddings=True),
                       dict(clip_norm=1.0, learning_rate=3e-4,
                            opt_kwargs=(("weight_decay", 0.01),
                                        ("b1", 0.9), ("b2", 0.999),
                                        ("eps", 1e-8))),
                       10018094592),
}


@pytest.mark.parametrize("name", sorted(BENCH_CELLS))
def test_bench_shape_estimate_covers_the_chip_need(name):
    widths, train, need = BENCH_CELLS[name]
    cfg = ModelConfig(name=name, family="dense", head_dim=128,
                      attention=AttentionConfig(rope_theta=10000.0),
                      param_dtype="bfloat16", remat="full", **widths)
    policy = TrainPolicy(optimizer="adamw", **train)
    ok, rep = admission_check(cfg, policy, ShapeSpec(name, 4096, 1, "train"),
                              V5E_BYTES_LIMIT, verbose=False)
    assert ok
    assert rep.peak_bytes >= need
    assert rep.breakdown["fused_blocks"] > 0


# chip_smoke.py's job: starcoder2-3b's one-chip share (published widths, 4
# layers, 2 x 4096) under the default TrainPolicy. Its need was bisected
# on a TPU v5e the same way, with ballast beside the live state; a PR that
# changes this step updates the number from its own chip run.
CHIP_SMOKE_NEED = 12228492800


def test_chip_smoke_estimate_covers_the_chip_need():
    from repro.configs.starcoder2_3b import CHIP, CHIP_SHAPE
    ok, rep = admission_check(CHIP, TrainPolicy(), CHIP_SHAPE,
                              V5E_BYTES_LIMIT, verbose=False)
    assert ok
    assert rep.peak_bytes >= CHIP_SMOKE_NEED
