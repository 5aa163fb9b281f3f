"""Weights from the seed, made by the benchmark in the program's layout.

The layout is the one the program's decoder takes (stacked per-layer
leaves under ``units/block_0``, the vocabulary padded to a multiple of
256); the values are the benchmark's own: one jitted call on the device,
each leaf drawn from its own fold of the seed's key, in bfloat16, the
type the configuration trains in.  The plain reference makes the same
weights again from the seed; it never reads the program's.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import numpy as np

VOCAB_PAD = 256


def key_of(seed: int):
    """A JAX key from any whole-number seed (also beyond 32 bits)."""
    import jax
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.PRNGKey(word & 0x7FFFFFFF)


def dims(cfg: dict) -> dict:
    m, h = cfg["hidden_size"], cfg["num_attention_heads"]
    v = cfg["vocab_size"]
    return {"M": m, "H": h, "KV": cfg["num_key_value_heads"],
            "Dh": cfg.get("head_dim") or m // h,
            "F": cfg["intermediate_size"], "L": cfg["num_hidden_layers"],
            "V": v, "Vp": -(-v // VOCAB_PAD) * VOCAB_PAD}


def qkv_bias(cfg: dict) -> bool:
    return cfg["model_type"] == "qwen2" or bool(cfg.get("attention_bias"))


def qk_norm(cfg: dict) -> bool:
    return cfg["model_type"] == "qwen3"


def layout(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """``path -> (shape, init)``; init is "normal:<std>", "ones" (a norm
    scale, drawn near 1) or "embed"."""
    d = dims(cfg)
    M, H, KV, Dh, F, L = d["M"], d["H"], d["KV"], d["Dh"], d["F"], d["L"]
    u = "units/block_0/"
    out = {"embed": ((d["Vp"], M), "normal:0.02"),
           u + "norm1/scale": ((L, M), "ones"),
           u + "attn/w_q": ((L, M, H, Dh), f"normal:{M ** -0.5}"),
           u + "attn/w_k": ((L, M, KV, Dh), f"normal:{M ** -0.5}"),
           u + "attn/w_v": ((L, M, KV, Dh), f"normal:{M ** -0.5}"),
           u + "attn/w_o": ((L, H, Dh, M), f"normal:{(H * Dh) ** -0.5}"),
           u + "norm2/scale": ((L, M), "ones"),
           u + "mlp/w_gate": ((L, M, F), f"normal:{M ** -0.5}"),
           u + "mlp/w_up": ((L, M, F), f"normal:{M ** -0.5}"),
           u + "mlp/w_down": ((L, F, M), f"normal:{F ** -0.5}"),
           "final_norm/scale": ((M,), "ones")}
    if qkv_bias(cfg):
        out[u + "attn/b_q"] = ((L, H, Dh), "normal:0.02")
        out[u + "attn/b_k"] = ((L, KV, Dh), "normal:0.02")
        out[u + "attn/b_v"] = ((L, KV, Dh), "normal:0.02")
    if qk_norm(cfg):
        out[u + "attn/q_norm"] = ((L, Dh), "ones")
        out[u + "attn/k_norm"] = ((L, Dh), "ones")
    if not cfg["tie_word_embeddings"]:
        out["head"] = ((M, d["Vp"]), f"normal:{M ** -0.5}")
    return dict(sorted(out.items()))


def param_count(cfg: dict) -> int:
    return sum(int(np.prod(s)) for s, _ in layout(cfg).values())


def nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        p = prefix + k
        if isinstance(v, dict):
            out.update(flatten(v, p + "/"))
        else:
            out[p] = v
    return out


@functools.lru_cache(maxsize=4)
def _maker(items: Tuple):
    import jax
    import jax.numpy as jnp

    def make(key):
        flat = {}
        for i, (path, shape, init) in enumerate(items):
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, shape, jnp.float32)
            if init == "ones":
                x = 1.0 + 0.02 * z
            else:
                x = float(init.split(":")[1]) * z
            flat[path] = x.astype(jnp.bfloat16)
        return nest(flat)
    return jax.jit(make)


def make_weights(cfg: dict, seed: int) -> dict:
    """The bf16 parameter pytree of ``cfg`` drawn from ``seed``, made on
    the device in one jitted call."""
    items = tuple((p, s, i) for p, (s, i) in layout(cfg).items())
    return _maker(items)(key_of(seed))


@functools.lru_cache(maxsize=1)
def _diff_norms():
    import jax
    import jax.numpy as jnp

    def f(a, b):
        fa, fb = flatten(a), flatten(b)
        return {p: jnp.sqrt(jnp.sum(jnp.square(
            fa[p].astype(jnp.float32) - fb[p].astype(jnp.float32))))
            for p in sorted(fa)}
    return jax.jit(f)


def diff_norms(a: dict, b: dict) -> Dict[str, float]:
    """Per-leaf ‖a − b‖ in fp32, as host floats."""
    out = _diff_norms()(a, b)
    return {p: float(v) for p, v in out.items()}
