"""The yardstick's operation and byte counts against hand-worked shapes."""

import json
import os

import counts
import weights as W
from conftest import BENCH

HAND = {"hidden_size": 4, "intermediate_size": 8, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 2, "num_hidden_layers": 1,
        "vocab_size": 10}


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_matmul_params_by_hand():
    # q 4·2·2 + k, v 2·(4·1·2) + o 2·2·4 + MLP 3·4·8 = 144; head 4·10 = 40
    assert counts.matmul_params(HAND) == 184


def test_train_flops_per_token_by_hand():
    # 6·184, plus attention 12·L·H·Dh·(S + 1)/2 = 12·1·2·2·2 at S = 3
    assert counts.train_flops_per_token(HAND, 3) == 6 * 184 + 96


def test_qwen2_matmul_params_and_flops():
    cfg = _config("qwen2-1.5b")
    per_layer = 1536 * 1536 * 2 + 2 * 1536 * 256 + 3 * 1536 * 8960
    assert counts.matmul_params(cfg) == 28 * per_layer + 1536 * 151936
    fpt = counts.train_flops_per_token(cfg, 1024)
    assert 9.4e9 < fpt < 9.6e9


def test_qwen3_share_flops():
    cfg = _config("qwen3-14b-l4-v1of4")
    per_layer = 5120 * 5120 * 2 + 2 * 5120 * 1024 + 3 * 5120 * 17408
    assert counts.matmul_params(cfg) == 4 * per_layer + 5120 * 37984
    assert 9.3e9 < counts.train_flops_per_token(cfg, 2048) < 9.4e9


def test_whatif_event_bytes():
    # c = 30 bf16 pulls, prev and written rows, a and w*, state, residue
    assert counts.whatif_event_bytes(1000, 30, 2, True, True) == 88_000
    assert counts.whatif_event_bytes(1000, 1, 2, True, True) == 30_000
    # fp32 ring, sgd: no state, no residue
    assert counts.whatif_event_bytes(10, 4, 4, False, False) == 10 * 32


def test_weight_counts_of_the_configurations():
    assert W.param_count(_config("qwen2-1.5b")) == 1_543_910_912
    cfg = _config("qwen3-14b-l4-v1of4")
    assert 1.70e9 < W.param_count(cfg) < 1.72e9
