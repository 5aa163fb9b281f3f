"""A new configuration, traffic mix, kind of traffic and per-layer metric
are new files only: the harness finds each by the name the manifest
gives it, and no file that is already there changes."""

import json
import os

import harness as H
from conftest import TINY_CONFIGS, TINY_TRAFFIC, tiny_manifest, write_bench


def test_new_files_are_found_by_name(tmp_path):
    root = str(tmp_path)
    m = tiny_manifest()
    write_bench(root, m)
    # the new pieces: a configuration, a mix of a new kind, its limits,
    # the kind's runner and a per-layer metric, each a file of its own
    cfg = dict(TINY_CONFIGS["tiny-qwen3"], name="toy", num_hidden_layers=3)
    (tmp_path / "configs" / "toy.json").write_text(json.dumps(cfg))
    mix = dict(TINY_TRAFFIC["tiny-train"], kind="toykind", seq_len=16)
    (tmp_path / "traffic" / "toy-mix.json").write_text(json.dumps(mix))
    (tmp_path / "limits" / "toy.toy-mix.json").write_text(
        json.dumps({"loss_gap": 1.0}))
    (tmp_path / "kinds").mkdir()
    (tmp_path / "kinds" / "toykind.py").write_text(
        "def run(cell, seed, seconds, devices, trace_dir=None):\n"
        "    return cell.config['num_hidden_layers'] * seed\n")
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "toy_share.train.py").write_text(
        "def read(ctx):\n"
        "    return 100.0 * ctx['a'] / ctx['b'] if 'a' in ctx else None\n")
    m["configs"].append({"name": "toy", "file": "configs/toy.json"})
    m["workloads"].append({"name": "toy.toy-mix", "config": "toy",
                           "traffic": "toy-mix", "chips": 1})
    m["end_to_end"][0]["workloads"].append("toy.toy-mix")
    m["per_layer"].append({"name": "toy_share.train", "unit": "%",
                           "better": "higher", "source": "host_clock",
                           "layer": "device", "moves": "tokens_per_s",
                           "workloads": ["toy.toy-mix"]})

    cell = H.find_cell(m, "toy.toy-mix", root=root, bench_dir=root)
    assert cell.config["num_hidden_layers"] == 3
    assert cell.traffic["seq_len"] == 16
    assert cell.limits == {"loss_gap": 1.0}
    assert [x["name"] for x in cell.per_layer] == ["toy_share.train"]
    assert "tokens_per_s" in [x["name"] for x in cell.end_to_end]

    kind = H.kind_module(cell.traffic["kind"], bench_dir=root)
    assert kind.run(cell, 7, 1.0, None) == 21
    read = H.metric_reader("toy_share.train", bench_dir=root)
    assert read({"a": 1.0, "b": 4.0}) == 25.0
    assert read({}) is None          # nothing to read: left out


def test_cells_report_their_own_metrics(tiny_cell):
    train, whatif = tiny_cell("tiny2.train"), tiny_cell("tiny.whatif")
    assert {m["name"] for m in train.end_to_end} == {
        "tokens_per_s", "peak_hbm_gib", "setup_s"}
    assert {m["name"] for m in whatif.end_to_end} == {
        "updates_per_s", "peak_hbm_gib", "setup_s"}
    assert all(m["name"].endswith(".train") for m in train.per_layer)
    assert {m["name"] for m in whatif.per_layer} == {
        "replay_ring_whatif_roofline", "mfu_hbm.whatif", "idle_share.whatif"}


def test_every_manifest_name_has_its_files():
    m = H.load_manifest()
    for c in m["configs"]:
        assert os.path.isfile(os.path.join(H.ROOT, c["file"]))
    for w in m["workloads"]:
        cell = H.find_cell(m, w["name"])
        assert H.kind_module(cell.traffic["kind"]).run
    for x in m["per_layer"]:
        assert callable(H.metric_reader(x["name"]))
