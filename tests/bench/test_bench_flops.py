"""The benchmark's operation and byte counts against hand counts at small
shapes, and its table of chip peaks."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import flops  # noqa: E402


def test_ocean_per_agent_step_by_hand():
    # layers 4->3, 3->3, 3->2 (action), 3->1 (value): forward 2*(12+9+6+3)
    # = 60; backward 2*60 less the first layer's input gradient 2*12 = 96;
    # one rollout forward plus one bootstrap forward per unroll of 1, and
    # one epoch of forward + backward
    assert flops.ocean_flops_per_agent_step(4, 3, 2, 1, 1) == 60 * 2 + 156


def test_ocean_squared_preset_count():
    got = flops.ocean_flops_per_agent_step(25, 64, 5, 4, 64)
    fwd = 2 * (25 * 64 + 64 * 64 + 64 * 5 + 64)
    bwd = 2 * fwd - 2 * 25 * 64
    assert got == pytest.approx(fwd * (1 + 1 / 64) + 4 * (fwd + bwd))
    assert got == 145470


TINY = {"hidden_size": 2, "num_hidden_layers": 1, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 1, "intermediate_size": 3,
        "vocab_size": 5}


def test_lm_per_token_by_hand():
    # q, o: 2*2*1 each; k, v: 2*1*1 each -> 12; MLP 3*2*3 = 18; head 2*5;
    # value head 2: N = 42. Attention 6 * L * H * hd * (seq + 1) at seq 4
    assert flops.lm_matmul_params(TINY) == 42
    assert flops.lm_train_flops_per_token(TINY, 4) == 6 * 42 + 6 * 2 * 5


def test_qwen3_0_6b_matmul_params_by_hand():
    # Qwen/Qwen3-0.6B config.json: per layer q 1024x2048, k and v
    # 1024x1024, o 2048x1024, gate/up/down 1024x3072; tied head 1024x151936
    cfg = {"hidden_size": 1024, "num_hidden_layers": 28,
           "num_attention_heads": 16, "num_key_value_heads": 8,
           "head_dim": 128, "intermediate_size": 3072, "vocab_size": 151936}
    layer = 1024 * 2048 * 2 + 1024 * 1024 * 2 + 3 * 1024 * 3072
    assert flops.lm_matmul_params(cfg) == 28 * layer + 1024 * 151936 + 1024
    assert flops.lm_matmul_params(cfg) == 595_985_408


def test_flash_attention_cost_by_hand():
    # B=1, T=2, H=K=1, hd=1: QK^T and PV over 3 causal pairs, 2 ops each
    ops, nbytes = flops.flash_attention_cost(1, 2, 1, 1, 1)
    assert ops == 12
    assert nbytes == 2 * 2 * 4          # q, k, v, o: 2 elements, 2 bytes


def test_gae_cost_by_hand():
    ops, nbytes = flops.gae_cost(2, 3)
    assert ops == 36
    assert nbytes == 4 * (4 * 6 + 3)


def test_least_time_names_its_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_time(1000, 10, peak) == (10.0, "compute")
    assert flops.least_time(10, 1000, peak) == (100.0, "memory")


def test_peaks_are_keyed_by_device_kind_with_a_source():
    table = json.loads(flops.PEAKS.read_text())
    assert "TPU v5e" in table["source"]
    v5e = flops.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks(kind)


def test_step_mfu_divides_by_the_traced_busy_time():
    from bench import harness
    mfu = harness.reader("mfu.ocean")
    ctx = {"trace": {"busy_s": 2.0, "window_s": 4.0}, "chips": 1,
           "work": 10, "info": {"flops_per_unit": 1e12},
           "peak": {"bf16_flops": 1e13}}
    assert mfu.read(ctx) == pytest.approx(50.0)
    ctx["trace"]["busy_s"] = 0.0
    assert mfu.read(ctx) is None
