"""Dry-run machinery on a small faked-device mesh, via subprocess (the
XLA_FLAGS device-count override must NOT leak into the main test process)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow   # subprocess lowering: the heavy lane

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, dataclasses
    import jax
    from repro.config import INPUT_SHAPES, InputShape
    from repro.configs import get_smoke
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.specs import build_lowerable, make_run_config
    from repro.launch import roofline as rl

    mesh = make_debug_mesh(2, 4)
    cfg = get_smoke("{arch}")
    shape = InputShape("mini_{kind}", {seq}, {batch}, "{kind}")
    run, eng = make_run_config(cfg, shape, mesh, protocol="softsync",
                               n_softsync=2, num_microbatches=1,
                               attn_q_chunk=32, attn_kv_chunk=32)
    with mesh:
        fn, specs = build_lowerable(cfg, shape, mesh, run, engine=eng)
        compiled = fn.lower(*specs).compile()
        cost = rl.normalize_cost_analysis(compiled.cost_analysis())
        coll = rl.collective_bytes(compiled.as_text())
        mem = compiled.memory_analysis()
    print(json.dumps({{
        "flops": float(cost.get("flops", 0)),
        "coll_total": coll["total"],
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
    }}))
""")


def _run(arch: str, kind: str, batch: int = 8, seq: int = 64) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(arch=arch, kind=kind,
                                             batch=batch, seq=seq)],
        capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("arch,kind", [
    ("qwen2_1_5b", "train"),          # seq-parallel dense
    ("zamba2_7b", "train"),           # head-parallel hybrid
    ("llama4_maverick_400b_a17b", "train"),   # expert-parallel MoE
    ("qwen2_1_5b", "decode"),
    ("rwkv6_7b", "decode"),
])
def test_lower_compile_small_mesh(arch, kind):
    res = _run(arch, kind)
    assert res["flops"] > 0
    assert res["temp_bytes"] >= 0


def test_train_step_induces_gradient_collectives():
    """Data-parallel gradients must produce cross-learner reduction traffic."""
    res = _run("qwen2_1_5b", "train")
    assert res["coll_total"] > 0
