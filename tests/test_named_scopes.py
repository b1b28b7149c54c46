"""The training step names its layers, and the gate's spans can be read.

The step's layers carry ``jax.named_scope`` labels (``models/model.py``,
``train/train_step.py``) into the compiled program's ``op_name``
metadata, where a device trace's time can be put down to them. The
estimator traces the same functions and reads ``transpose`` and
``backward`` in a name stack as the backward pass, so no scope may carry
either. An admission service with observability on records the gate's
spans and decides exactly as one with it off.
"""
import ast
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke
from repro.configs.base import smoke_shape
from repro.configs.registry import input_specs
from repro.core.analyzer import _BWD_MARKERS
from repro.core.cache import TraceCache
from repro.launch.device import CompileCounter
from repro.launch.train import replan_if_needed
from repro.models import model as M
from repro.obs import Observability
from repro.service import AdmissionService
from repro.train import TrainPolicy, make_train_step

PKG = os.path.join(os.path.dirname(__file__), os.pardir, "src", "repro")


def _compiled_op_names(cfg) -> set:
    step, opt = make_train_step(cfg, TrainPolicy())
    params = M.abstract_params(cfg)
    text = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, jax.eval_shape(opt.init, params),
        input_specs(cfg, smoke_shape(64, 2))).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


def _has_scope(names: set, scope: str) -> bool:
    # a path element that is the scope, or wraps it: jvp(embed)
    pat = re.compile(rf"(?:^|/|\()({scope})(?:\)|/|$)")
    return any(pat.search(n) for n in names)


@pytest.mark.parametrize("arch,scopes", [
    ("starcoder2-3b", ("embed", "attn", "mlp", "head_loss", "optimizer")),
    ("phi3.5-moe-42b-a6.6b", ("attn", "moe")),
])
def test_the_compiled_step_names_its_layers(arch, scopes):
    names = _compiled_op_names(get_smoke(arch))
    for scope in scopes:
        assert _has_scope(names, scope), scope
    # the passes JAX marks itself, which bench/scopes.py splits by
    assert any("transpose(jvp(" in n for n in names)
    assert any("rematted_computation" in n for n in names)


def _scope_names(path: str) -> list[str]:
    """Every string a ``named_scope(...)`` call in ``path`` can pass."""
    tree = ast.parse(open(path).read())
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and getattr(
                node.func, "attr", getattr(node.func, "id", "")) \
                == "named_scope":
            out += [c.value for a in node.args for c in ast.walk(a)
                    if isinstance(c, ast.Constant)
                    and isinstance(c.value, str)]
    return out


def test_no_scope_reads_as_the_backward_pass():
    found = []
    for sub in ("models", "train"):
        d = os.path.join(PKG, sub)
        for f in sorted(os.listdir(d)):
            if f.endswith(".py"):
                found += _scope_names(os.path.join(d, f))
    assert {"embed", "attn", "mlp", "moe", "head_loss",
            "optimizer"} <= set(found)
    assert not [s for s in found if any(m in s for m in _BWD_MARKERS)]


def test_an_observed_gate_decides_as_a_bare_one():
    cfg, policy = get_smoke("phi4-mini-3.8b"), TrainPolicy()
    shape = smoke_shape(64, 4)
    bare = AdmissionService(workers=1, cache=TraceCache())
    seen = AdmissionService(workers=1, cache=TraceCache(),
                            obs=Observability(enabled=True))
    try:
        p0, r0 = replan_if_needed(cfg, policy, shape, 16 << 30,
                                  service=bare)
        p1, r1 = replan_if_needed(cfg, policy, shape, 16 << 30,
                                  service=seen)
        assert p0 == p1
        for f in dataclasses.fields(r0):
            if f.name != "wall_time_s":
                assert getattr(r1, f.name) == getattr(r0, f.name), f.name
        spans = seen.obs.tracer.spans()
        names = [s.name for s in spans]
        assert names.count("estimator.trace") == 3   # fwd+bwd, upd, init
        assert names.count("estimator.replay") == 1
        # the gate's tree: decide > exact rung > tracer and replay, and
        # nothing else on the exact path
        assert set(names) == {"service.decide", "rung.exact",
                              "estimator.trace", "estimator.replay"}
        by_id = {s.span_id: s for s in spans}
        for s in spans:
            if s.name.startswith("estimator."):
                assert by_id[s.parent_id].name == "rung.exact"
        assert bare.obs.tracer.started == 0
    finally:
        bare.close()
        seen.close()


def test_the_compile_counter_counts_compiles_only():
    counter = CompileCounter().install()
    try:
        f = jax.jit(lambda x: x * 3 + 1)
        x = jnp.ones((7, 5))
        f(x).block_until_ready()
        n = counter.count
        assert n >= 1 and counter.seconds > 0
        f(x).block_until_ready()          # cached: no new program
        assert counter.count == n
        f(jnp.ones((7, 6))).block_until_ready()   # a new shape compiles
        assert counter.count > n
    finally:
        counter.remove()
    n = counter.count
    jax.jit(lambda x: x - 2)(jnp.ones(3)).block_until_ready()
    assert counter.count == n
