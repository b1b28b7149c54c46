"""Device time by named scope, on a step recorded on a TPU v5e.

``data/scoped_step.trace.pb`` and ``data/scoped_step.hlo.txt.gz`` were
made by :func:`record` (``python3 -m bench.tests.test_scopes`` on a one-chip
v5e): the program's own train step (2 layers of width 256 scanned with
full remat, chunked attention over chunks of 128 queries and 256 keys at
sequence 512, vocabulary 1024, AdamW with clipping), jitted and donated
as the benchmark jits it, warmed up, then profiled for three steps inside
the host span ``window``; and the compiled text of that step, taken as the
module's docstring gives (``lower(...).compile().as_text()``).
"""
import gzip
import os

import pytest

from bench import scopes as S
from bench import trace_reduce as T

DATA = os.path.join(os.path.dirname(__file__), "data")
TRACE = os.path.join(DATA, "scoped_step.trace.pb")
HLO = os.path.join(DATA, "scoped_step.hlo.txt.gz")
WINDOW = "window"


@pytest.fixture(scope="module")
def recorded():
    trace = S.read(TRACE)
    with gzip.open(HLO, "rt") as f:
        text = f.read()
    lo, hi = T.window_of(trace["host"], WINDOW)
    return trace, text, S.reduce(trace, lo, hi, text), (lo, hi)


def test_the_buckets_and_idle_fill_the_window(recorded):
    trace, _, sc, (lo, hi) = recorded
    assert sc["module"] == "jit_train_step"
    assert sc["modules_run"] == ["jit_train_step"]
    assert sc["named"]
    total = sum(b["total"] for b in sc["buckets"].values())
    idle = sc["window_s"] - sc["busy_s"]
    assert total + idle == pytest.approx(sc["window_s"], rel=5e-3)
    # the same busy time as the idle metric's reduction
    assert sc["busy_s"] == pytest.approx(T.reduce(trace, lo, hi)["busy_s"])
    for name in ("embed", "attn", "mlp", "head_loss", "optimizer"):
        assert sc["buckets"][name]["total"] > 0, name


def test_nothing_is_unmatched(recorded):
    assert recorded[2]["unmatched_s"] == 0


def test_recompute_lies_inside_attention_and_mlp(recorded):
    b = recorded[2]["buckets"]
    remat = recorded[2]["remat_s"]
    assert remat > 0
    assert remat <= b["attn"]["total"] + b["mlp"]["total"]
    assert sum(t.get("recompute", 0.0) for t in b.values()) == \
        pytest.approx(remat)
    assert b["optimizer"]["recompute"] == b["head_loss"]["recompute"] == 0


def test_the_scans_slices_land_in_layer_copy(recorded):
    trace, text, sc, (lo, hi) = recorded
    _, names = S.op_names(text)
    ran = {op for ivs in trace["devices"].values() for s, e, op in ivs
           if e > lo and s < hi}
    slices = [op for op in ran
              if names[op].endswith("while/body/dynamic_slice")
              and S.scope_of(names[op]) is None]
    assert slices
    assert {S.bucket_of(names[op]) for op in slices} == {"layer_copy"}
    assert sc["buckets"]["layer_copy"]["total"] > 0


def test_op_names_and_scopes():
    text = """HloModule jit_train_step, is_scheduled=true

%body.1 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]) parameter(0)
  %copy.2 = f32[4]{0} copy(%x)
  ROOT %ds.3 = f32[1]{0} dynamic-slice(%y), metadata={op_name="jit(f)/transpose(jvp())/while/body/dynamic_slice"}
}

ENTRY %main.9 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %while.4 = (s32[], f32[4]) while(%t), condition=%cond.1, body=%body.1, metadata={op_name="jit(f)/transpose(jvp())/while"}
  ROOT %fusion.5 = f32[4]{0} fusion(%a), metadata={op_name="jit(f)/transpose(jvp(head_loss))/dot_general" source_line=3}
}
"""
    module, names = S.op_names(text)
    assert module == "jit_train_step"
    # a copy XLA put in a loop body takes its loop's name
    assert names["copy.2"] == "jit(f)/transpose(jvp())/while/body"
    assert S.bucket_of(names["copy.2"]) == "layer_copy"
    assert S.pass_of(names["copy.2"]) == "backward"
    assert S.bucket_of(names["fusion.5"]) == "head_loss"
    assert names["a"] == ""
    assert S.bucket_of("") == "other"
    assert S.scope_of("a/jvp()/while/body/closed_call/attn/mlp_like/x") \
        == "attn"
    assert S.scope_of("a/checkpoint/rematted_computation/mlp/jit(silu)") \
        == "mlp"
    assert S.scope_of("params['layers']['attn']['wq']") is None
    assert S.pass_of("a/transpose(jvp())/b/rematted_computation/c") \
        == "recompute"


def test_a_trace_of_another_program_is_refused(recorded):
    trace, text, _, (lo, hi) = recorded
    other = {**trace, "modules": {
        plane: [(s, e, "jit_other(1)") for s, e, _ in ivs]
        for plane, ivs in trace["modules"].items()}}
    with pytest.raises(ValueError, match="jit_other"):
        S.reduce(other, lo, hi, text)


def test_without_module_lines_the_step_is_not_checked(recorded):
    trace, text, sc, (lo, hi) = recorded
    bare = {k: v for k, v in trace.items() if k != "modules"}
    assert S.reduce(bare, lo, hi, text)["buckets"] == sc["buckets"]


def record(out_dir: str = DATA) -> None:
    """Make the recording on a chip (see the module's docstring)."""
    import glob
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    from repro.configs.base import AttentionConfig, ModelConfig
    from repro.models import model as M
    from repro.train import TrainPolicy, make_train_step

    cfg = ModelConfig(
        name="scoped", family="dense", n_layers=2, d_model=256, n_heads=4,
        n_kv_heads=4, d_ff=512, vocab=1024, head_dim=64,
        attention=AttentionConfig(dense_threshold=256, chunk_q=128,
                                  chunk_kv=256),
        param_dtype="bfloat16", remat="full")
    step, opt = make_train_step(cfg, TrainPolicy())
    step_fn = jax.jit(step, donate_argnums=(0, 1))
    params = M.init_params(cfg, jax.random.key(0))
    state = jax.jit(opt.init)(params)
    tokens = jax.random.randint(jax.random.key(1), (1, 512), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    for _ in range(3):
        loss, params, state = step_fn(params, state, batch)
    jax.block_until_ready((loss, params, state))
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation(WINDOW):
        for _ in range(3):
            loss, params, state = step_fn(params, state, batch)
            jax.block_until_ready((loss, params, state))
    jax.profiler.stop_trace()
    specs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        (params, state, batch))
    text = step_fn.lower(*specs).compile().as_text()
    (found,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)
    shutil.copy(found, os.path.join(out_dir, "scoped_step.trace.pb"))
    with gzip.open(os.path.join(out_dir, "scoped_step.hlo.txt.gz"), "wt",
                   compresslevel=9) as f:
        f.write(text)
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    import sys
    record(sys.argv[1] if len(sys.argv) > 1 else DATA)
