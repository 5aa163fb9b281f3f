"""The benchmark's own tests: on the CPU, at sizes a test run holds.

    python -m pytest bench/tests -q
"""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIGS = {
    "tiny-qwen2": {"name": "tiny-qwen2", "model_type": "qwen2",
                   "hidden_size": 64, "intermediate_size": 128,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "num_hidden_layers": 2, "vocab_size": 300,
                   "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
                   "tie_word_embeddings": True, "torch_dtype": "bfloat16"},
    "tiny-qwen3": {"name": "tiny-qwen3", "model_type": "qwen3",
                   "hidden_size": 64, "intermediate_size": 128,
                   "head_dim": 32, "num_attention_heads": 4,
                   "num_key_value_heads": 2, "num_hidden_layers": 2,
                   "vocab_size": 300, "rope_theta": 10000.0,
                   "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
                   "torch_dtype": "bfloat16"},
}
TINY_TRAFFIC = {
    "tiny-train": {"kind": "train", "protocol": "softsync",
                   "n_learners": 8, "n_softsync": 4, "seqs_per_learner": 1,
                   "seq_len": 32, "optimizer": "sgd",
                   "lr_policy": "staleness_inverse", "base_lr": 0.3,
                   "engine": "sequential", "remat": True,
                   "check_rounds": 2},
    "tiny-whatif": {"kind": "whatif", "protocol": "softsync",
                    "n_learners": 6, "n_softsync": 1, "ps_shards": 4,
                    "optimizer": "momentum", "momentum": 0.9,
                    "base_lr": 0.05, "lr_policy": "staleness_inverse",
                    "ring_dtype": "bf16", "duration_model": "homogeneous",
                    "schedule_seed": 1, "problem_seed": 1,
                    "events": 20000, "segment": 8,
                    "check_columns": 4096},
}
# set from the tiny sizes' own readings (program against reference):
# loss 7e-4, update 4e-3, change 2.5e-2 at most, weight 4e-5
TINY_LIMITS = {
    "train": {"loss_gap": 3e-3, "update_gap": 2e-2, "change_gap": 0.1},
    "whatif": {"weight_gap": 4e-4},
}
TINY_CELLS = {"tiny2.train": ("tiny-qwen2", "tiny-train"),
              "tiny3.train": ("tiny-qwen3", "tiny-train"),
              "tiny.whatif": ("tiny-qwen2", "tiny-whatif")}


def write_bench(root: str, manifest: dict) -> None:
    """A benchmark tree of tiny cells under ``root``, laid out as
    ``bench/`` is: configs, traffic and limits found by name."""
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(root, sub), exist_ok=True)
    for name, cfg in TINY_CONFIGS.items():
        with open(os.path.join(root, "configs", name + ".json"), "w") as f:
            json.dump(cfg, f)
    for name, tr in TINY_TRAFFIC.items():
        with open(os.path.join(root, "traffic", name + ".json"), "w") as f:
            json.dump(tr, f)
    for cell, (_, tr) in TINY_CELLS.items():
        kind = TINY_TRAFFIC[tr]["kind"]
        with open(os.path.join(root, "limits", cell + ".json"), "w") as f:
            json.dump(TINY_LIMITS[kind], f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)


# the train kind's metrics: no cell of BENCHMARK.json trains yet, so the
# tiny train cells bring their own
TRAIN_END_TO_END = [
    {"name": "tokens_per_s", "unit": "tokens/s", "better": "higher",
     "bound": 0.01, "source": "host_clock", "workloads": []}]
TRAIN_PER_LAYER = [
    {"name": n, "unit": "%", "better": b, "source": s, "layer": layer,
     "moves": "tokens_per_s", "workloads": []}
    for n, b, s, layer in (
        ("mfu.train", "higher", "host_clock",
         "core.distributed softsync round"),
        ("idle_share.train", "lower", "device_trace", "device"),
        ("input_wait_share.train", "lower", "host_clock", "data.pipeline"))]


def tiny_manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    m["end_to_end"] = TRAIN_END_TO_END + m["end_to_end"]
    m["per_layer"] = m["per_layer"] + TRAIN_PER_LAYER
    m["configs"] = [{"name": n, "file": f"configs/{n}.json"}
                    for n in TINY_CONFIGS]
    m["workloads"] = [{"name": c, "config": cfg, "traffic": tr, "chips": 1}
                      for c, (cfg, tr) in TINY_CELLS.items()]
    train = [c for c in TINY_CELLS if c.endswith(".train")]
    whatif = [c for c in TINY_CELLS if c.endswith(".whatif")]
    for e in m["end_to_end"] + m["per_layer"]:
        if "workloads" in e:
            moves = e.get("moves", e["name"])
            e["workloads"] = list(train if moves == "tokens_per_s"
                                  else whatif)
    return m


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    """(root, manifest) of the tiny benchmark tree."""
    root = str(tmp_path_factory.mktemp("bench"))
    m = tiny_manifest()
    write_bench(root, m)
    return root, m


@pytest.fixture
def tiny_cell(tiny_bench):
    import harness as H
    root, m = tiny_bench

    def get(name):
        return H.find_cell(m, name, root=root, bench_dir=root)
    return get


@pytest.fixture(autouse=True)
def fresh_replay_cache():
    """Compiled replay scans are cached per configuration; a test that
    plants a fault in the program must not find a sound scan there."""
    from repro.core import engine
    engine._make_scan_fn.cache_clear()
    yield
    engine._make_scan_fn.cache_clear()
