"""Traffic, FLOP counts, peaks and the ballast bisection, on the CPU."""
import numpy as np
import pytest

from bench import flops, memory, peaks, weights


def test_traffic_is_a_function_of_the_seed():
    big = 2**31 + 12345
    a = weights.batch(49152, 2, 4096, big, 3)
    b = weights.batch(49152, 2, 4096, big, 3)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    np.testing.assert_array_equal(a["labels"], b["labels"])
    c = weights.batch(49152, 2, 4096, big + 1, 3)
    d = weights.batch(49152, 2, 4096, big, 4)
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert not np.array_equal(a["tokens"], d["tokens"])
    assert not np.array_equal(a["tokens"][0], a["tokens"][1])
    assert a["tokens"].shape == (2, 4096) and a["tokens"].dtype == np.int32
    assert 0 <= a["tokens"].min() and a["tokens"].max() < 49152
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])


def test_weights_are_a_function_of_the_seed():
    import jax
    cfg = {"hidden_size": 16, "intermediate_size": 32,
           "num_attention_heads": 2, "num_key_value_heads": 1,
           "head_dim": 8, "num_hidden_layers": 2, "vocab_size": 64,
           "tie_word_embeddings": False, "param_dtype": "bfloat16"}
    a = weights.make(cfg, 2**31 + 7)
    b = weights.make(cfg, 2**31 + 7)
    c = weights.make(cfg, 7)
    same = jax.tree_util.tree_map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree_util.tree_leaves(same))
    assert not bool((a["embed"] == c["embed"]).all())
    assert list(weights.flatten(a)) == list(weights.leaf_specs(cfg))
    with pytest.raises(ValueError):
        weights.seed_key(-1)


def test_flops_equal_a_hand_count_at_the_starcoder2_chip_shape():
    # per layer: q 3072x3072, k and v 3072x256 each, o 3072x3072,
    # gated MLP 3 x 3072x12288 -> 133,693,440 weights a token passes
    layer = 3072 * 3072 * 2 + 3072 * 256 * 2 + 3 * 3072 * 12288
    assert layer == 133_693_440
    assert flops.dense_layer_matmul_params(3072, 24, 2, 128, 12288) == layer
    tokens = 2 * 4096
    matmul = 2 * tokens * (4 * layer + 3072 * 49152)
    attn = 4 * 2 * 2 * 2 * 24 * 128 * (4096 * 4097 // 2)
    hand = 3 * (matmul + attn)
    # 3 x (11,235,634,446,336 matmul + 824,835,047,424 attention)
    assert hand == 36_181_408_481_280
    got = flops.dense_train_step_flops(
        d_model=3072, n_heads=24, n_kv_heads=2, head_dim=128, d_ff=12288,
        n_layers=4, vocab=49152, batch=2, seq=4096)
    assert got == hand


def test_flops_of_a_configuration_file_equal_a_hand_count():
    # deepseek-llm-7b's chip share: per layer q, k, v, o 4096x4096 each,
    # gated MLP 3 x 4096x11008 -> 202,375,168 weights a token passes
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008
    assert layer == 202_375_168
    tokens = 4096
    matmul = 2 * tokens * (4 * layer + 4096 * 25600)
    attn = 4 * 2 * 2 * 1 * 32 * 128 * (4096 * 4097 // 2)
    hand = 3 * (matmul + attn)
    from bench import spec
    cfg = spec.resolve(spec.load_benchmark(),
                       "deepseek-llm-7b.train4k").config
    assert flops.config_step_flops(cfg, 1, 4096) == hand


def test_unknown_device_kind_raises():
    assert peaks.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peak("cpu")


class FakeAllocator:
    """A device of ``limit`` bytes holding ``live`` bytes, on which a
    step needs ``need`` bytes in all."""

    def __init__(self, limit, live, need):
        self.limit, self.live, self.need = limit, live, need
        self.probes = 0

    def probe(self, ballast: int) -> bool:
        self.probes += 1
        if self.live + ballast > self.limit:       # ballast cannot be held
            return False
        return ballast + self.need <= self.limit


@pytest.mark.parametrize("need", [8_400_000_001, 13_823_000_000,
                                  14_000_000_000, 16_000_000_000])
def test_ballast_bisection_converges_to_the_resolution(need):
    limit, live = 16_909_336_064, 8_400_000_000
    res = limit // 256
    dev = FakeAllocator(limit, live, need)
    k, probes = memory.bisect_ballast(lambda k: dev.probe(k * res), 0,
                                      (limit - live) // res + 1)
    measured = limit - k * res
    assert need <= measured < need + res
    assert probes == dev.probes <= 8


def test_estimate_error_is_floored_at_the_resolution():
    assert memory.rel_error_pct(100, 100, floor=5) == 5.0
    assert memory.rel_error_pct(120, 100, floor=5) == 20.0


def test_only_numbers_with_a_limit_are_compared():
    from bench import compare
    ref = {"losses": [11.0, 10.0], "grad_norms": [1.0, 2.0, 1e-6],
           "delta_norms": [0.5, 1.0, 0.25]}
    prog = {"losses": [11.5, 10.0], "grad_norms": [1.1, 2.0, 0.0],
            "delta_norms": [0.5, 1.0, 1.0]}
    gaps = compare.train_gaps(prog, ref)
    # the third leaf's reference gradient is under a thousandth of the
    # median's: its change is round-off and is left out
    assert gaps == {"loss_gap": 0.5, "grad_gap": pytest.approx(0.1 / 1.0),
                    "delta_gap": 0.0}
    checks = compare.train_checks(prog, ref, {"limits": {"grad_gap": 0.05}})
    assert list(checks) == ["grad_gap"]
    assert not compare.passes(checks)
