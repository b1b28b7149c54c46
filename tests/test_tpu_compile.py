"""Compile the chip path for a described TPU v5e, with no chip attached.

The TPU compiler refuses here what interpret mode and the CPU backend
accept: a kernel that cannot lower to Mosaic, or a step program that does
not fit the chip's memory. Nothing runs, so these tests say nothing about
results or times.

The topology is described inside a fixture (never at import, in a
``skipif`` or in ``parametrize``): only one process at a time may load
the TPU library, and pytest-xdist workers each import every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import input_specs
from repro.configs.starcoder2_3b import CHIP, CHIP_SHAPE
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.launch.device import V5E_HBM_BYTES
from repro.models import model as M
from repro.train import TrainPolicy, make_train_step
from test_fusion_fold import MULTI_CONSUMER_EXP


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("window", [None, 1024])
def test_flash_kernel_lowers_to_mosaic(one_chip, window):
    # starcoder2-3b attention: 24 query heads over 2 KV heads x 128, seq 4096
    q = jax.ShapeDtypeStruct((1, 24, 4096, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 2, 4096, 128), jnp.bfloat16,
                              sharding=one_chip)
    compiled = flash_attention_bhsd.lower(
        q, kv, kv, interpret=False, causal=True, window=window).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_chip_train_step_fits_one_v5e(one_chip):
    step, opt = make_train_step(CHIP, TrainPolicy())
    params = M.abstract_params(CHIP)
    opt_state = jax.eval_shape(opt.init, params)
    batch = input_specs(CHIP, CHIP_SHAPE)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        _on(params, one_chip), _on(opt_state, one_chip),
        _on(batch, one_chip)).compile()
    ma = compiled.memory_analysis()
    # donation aliases params and optimizer state to the outputs
    assert ma.alias_size_in_bytes > 0.9 * ma.argument_size_in_bytes
    on_device = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                 - ma.alias_size_in_bytes)
    assert on_device < V5E_HBM_BYTES, f"{on_device / 2**30:.2f} GiB"


@MULTI_CONSUMER_EXP
def test_exp_with_two_consumers_stays_out_of_hbm(one_chip, fn, last):
    """The tracer folds an exp that two fusible consumers read; the chip's
    compiler must agree. ``x = a @ b`` is f32[4096, 32768] (512 MiB); the
    exp of it is as large. The compiled temporaries hold x at most: an
    exp written to HBM beside it would double them."""
    a, b, w = (4096, 512), (512, 32768), (32768, 128)
    compiled = jax.jit(fn).lower(*(
        jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
        for s in (a, b, w))).compile()
    x_bytes = 4096 * 32768 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * x_bytes
