"""The control of each kind of cell comes out as not correct: the
reference put in the program's place and computed one precision below
the configuration's (float8 for the bfloat16 decoder, bfloat16 for the
float32 what-if update), held to the same limits a run is held to."""

import pytest

import harness as H


@pytest.mark.parametrize("name", ["tiny2.train", "tiny3.train"])
def test_train_control_fails_a_limit(name, tiny_cell):
    cell = tiny_cell(name)
    kind = H.kind_module("train")
    for seed in (5, 6, 7):
        sound = kind.program_reading(cell, seed)
        assert all(v <= cell.limits[k] for k, v in sound.items()), sound
        got = kind.planted_readings(cell, seed)
        ctl = got["control_fp8"]
        assert any(v > cell.limits[k] for k, v in ctl.items()), ctl
        half = got["fault_half_batch"]
        assert any(v > cell.limits[k] for k, v in half.items()), half


def test_whatif_control_fails_the_limit(tiny_cell):
    cell = tiny_cell("tiny.whatif")
    kind = H.kind_module("whatif")
    for seed in (5, 6, 7):
        got = kind.planted_readings(cell, seed, 400)
        for name in ("control_bf16", "fault_half_batch"):
            assert got[name]["weight_gap"] > cell.limits["weight_gap"], got
