"""``counts.py`` against sizes worked out by hand from the published keys."""

import json
import pathlib

import pytest

from benchmark.lib import counts, counts_looped, peaks

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def load(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


# By hand.  InternLM2-1.8B, one layer: q 2048*16*128 = 4,194,304;
# k and v 2 * 2048*8*128 = 4,194,304; o 4,194,304; MLP 3 * 2048*8192 =
# 50,331,648; two norms 4,096: 62,918,656.  24 layers = 1,510,047,744;
# embedding and head 2 * 92,544*2048 = 379,060,224; final norm 2,048.
# Mistral-7B, one layer: q 4096*32*128 = 16,777,216; k, v 8,388,608;
# o 16,777,216; MLP 3 * 4096*14336 = 176,160,768; norms 8,192:
# 218,112,000.  16 layers = 3,489,792,000; tables 2 * 32,768*4096 =
# 268,435,456; final norm 4,096.
HAND = {
    "internlm2-1.8b": dict(layer=62_918_656, total=1_889_110_016,
                           kv=2 * 24 * 8 * 128 * 2),
    "mistral-7b-v0.3-l16": dict(layer=218_112_000, total=3_758_231_552,
                                kv=2 * 16 * 8 * 128 * 2),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_parameters_and_cache_bytes(name):
    c, hand = load(name), HAND[name]
    assert counts.layer_params(c) == hand["layer"]
    assert counts.total_params(c) == hand["total"]
    assert counts.weight_bytes(c) == 2 * hand["total"]
    assert counts.kv_bytes_per_token(c) == hand["kv"]


def test_flops_internlm2_by_hand():
    c = load("internlm2-1.8b")
    # Matmul parameters: 24 * (62,918,656 - 4,096) + 92,544*2048 (the head;
    # the input embedding is a lookup) = 1,699,479,552.
    assert counts.matmul_params(c) == 1_699_479_552
    # A token attending to 1,000 keys: 2 * 1,699,479,552 + 4 * 24 * 16 * 128
    # * 1,000 = 3,398,959,104 + 196,608,000.
    assert counts.forward_flops_per_token(c, 1000) == 3_595_567_104
    # Training at sequence 4096: mean context 2048, three times forward.
    assert counts.train_flops_per_token(c, 4096) == 3 * (
        3_398_959_104 + 4 * 24 * 16 * 128 * 2048)


def test_decode_step_bytes_and_roofline():
    c = load("mistral-7b-v0.3-l16")
    # 16 * (218,112,000 - 8,192) + 32,768*4096 = 3,623,878,656 weights
    # read, 2 bytes each, + 10,000 attended positions * 65,536 B.
    assert counts.decode_step_bytes(c, 10_000) == \
        2 * 3_623_878_656 + 10_000 * 65_536
    seconds, bound = counts.roofline_seconds(
        1e9, 819e9, peaks.peak("TPU v5 lite", "bf16_flops_per_s"),
        peaks.peak("TPU v5 lite", "hbm_bytes_per_s"))
    assert bound == "memory" and seconds == pytest.approx(1.0)


def test_one_mistral_round_by_hand():
    """A fused round of 3 steps whose sequences attended 12,000 positions in
    all: 3 * 7,247,757,312 B of weights + 12,000 * 65,536 B of keys and
    values = 22,529,703,936 B, 27.51 ms at 819 GB/s; one sequence's FLOPs a
    step, 3 * (7,247,757,312 + 4 * 16 * 32 * 128 * 4,000) = 24,889,...: 0.13
    ms at 197 TFLOP/s.  Memory bounds it.  ``decode_rounds_roofline`` holds
    every traced call to this (``lib/traced_rounds.py``)."""
    c = load("mistral-7b-v0.3-l16")
    seconds, bound = counts_looped.decode_round_seconds(
        c, 3, 12_000, 197e12, 819e9)
    assert bound == "memory"
    assert seconds == pytest.approx(22_529_703_936 / 819e9)
    assert round(seconds * 1e3, 2) == 27.51
    # The same from this file's counts: a dense stack is a loop of one.
    assert (seconds, bound) == counts.roofline_seconds(
        3 * counts.forward_flops_per_token(c, 4_000),
        3 * counts.decode_step_bytes(c, 4_000), 197e12, 819e9)
    assert 3 * counts.forward_flops_per_token(c, 4_000) == \
        3 * (7_247_757_312 + 4 * 16 * 32 * 128 * 4_000)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peak("TPU v9", "hbm_bytes_per_s")
