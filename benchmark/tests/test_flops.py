import json
from pathlib import Path

import pytest

from benchmark import flops, peaks
from benchmark.flops import bert

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def spec(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_bert_base_macs_match_the_hand_count():
    per_token_layer = 4 * 768**2 + 2 * 768 * 3072 + 2 * 48 * 768
    assert per_token_layer == 7_151_616
    encoder = 12 * 48 * per_token_layer
    pooler_and_head = 768 * 768 + 768
    assert bert.forward_macs_per_row(spec("bert-base")) == encoder + pooler_and_head
    assert abs(flops.forward_flops_per_row(spec("bert-base")) / 8.24e9 - 1) < 0.001
    with pytest.raises(KeyError):
        flops.forward_flops_per_row({"model_config": {"family": "no-such-family"}})


def test_peaks_known_kind_and_unknown_kind():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
