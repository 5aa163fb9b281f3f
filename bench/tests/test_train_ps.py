"""The "train_ps" kind (the DeepSeek-V3 block through the program's
softsync step) on the CPU at a tiny size, and its roofline reader."""

import importlib

import pytest

import harness as H
import tracereduce

CELL = "moonlight-16b-a3b-l5-e8of64-v1of8.train-4softsync-8l-4k"
# set from the tiny size's own readings (program against reference):
# loss 1.1e-3, update 2.4e-2, change 2.7e-2, bias 0.21 at most (the bias
# left unchanged reads 1)
TINY_LIMITS = {"loss_gap": 5e-3, "update_gap": 0.1, "change_gap": 0.15,
               "bias_gap": 0.5}


@pytest.fixture(scope="module")
def tiny():
    cell = H.find_cell(H.load_manifest(), CELL)
    cell.config = dict(
        cell.config, hidden_size=64, intermediate_size=128, kv_lora_rank=16,
        moe_intermediate_size=32, num_attention_heads=2,
        num_key_value_heads=2, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=8, num_hidden_layers=3, vocab_size=512,
        n_routed_experts=4, num_experts_per_tok=2,
        published=dict(cell.config["published"], n_routed_experts=8))
    cell.traffic = dict(cell.traffic, seq_len=32, base_lr=0.05)
    cell.limits = TINY_LIMITS
    return cell


def test_tiny_run_is_correct_and_counts_its_window(tiny):
    import jax
    run = importlib.import_module("run")
    line, outcome = run.execute(tiny, 2 ** 31 + 17, 1.0, False,
                                jax.devices()[:1])
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"updates_per_s", "peak_hbm_gib",
                                    "setup_s"}
    ctx = outcome.layer_ctx
    assert outcome.notes["events"] == 4 * outcome.attempted
    # the window's held slots, from the program's counters
    assert 0 < outcome.notes["held_slots"] <= (
        ctx["tokens"] * 2 * 2)                     # tokens × k × MoE layers
    assert ctx["expert_flops"] == outcome.notes["held_slots"] * 4 * 6 * 64 * 32


@pytest.fixture(scope="module")
def planted(tiny):
    return H.kind_module("train_ps").planted_readings(tiny, 5)


def test_half_batch_fault_fails_a_limit(tiny, planted):
    got = planted["fault_half_batch"]
    assert any(v > tiny.limits[k] for k, v in got.items()), got


def test_frozen_bias_fails_only_the_bias_limit(tiny, planted):
    """The routing bias left unchanged moves no gradient of the first
    round and barely the change after two: only ``bias_gap`` sees it."""
    got = planted["fault_bias_frozen"]
    assert got["bias_gap"] == pytest.approx(1.0)
    assert [k for k, v in got.items() if v > tiny.limits[k]] == ["bias_gap"]


def test_expert_roofline_reads_the_ragged_dot_kernels():
    read = H.metric_reader("expert_mm_roofline.train_ps")
    red = tracereduce.Reduction(
        window_s=1.0, busy_s=1.0, devices=1,
        op_seconds={"%ragged-dot-none.1": 0.2, "%ragged-dot-none": 0.3,
                    "%ragged-dot-metadata": 0.4, "%fusion.3": 0.1},
        gaps=[], spans={})
    peaks = {"bf16_flops": 100.0}
    assert read({"reduction": red, "expert_flops": 25.0,
                 "peaks": peaks}) == pytest.approx(50.0)
    assert read({"reduction": red, "peaks": peaks}) is None
    none = tracereduce.Reduction(1.0, 1.0, 1, {"%fusion": 1.0}, [], {})
    assert read({"reduction": none, "expert_flops": 1.0,
                 "peaks": peaks}) is None
