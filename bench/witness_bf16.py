"""Does a training round at the traffic's rate move the program's weights?

    python3 bench/witness_bf16.py --config bench/configs/<config>.json \
        --traffic bench/traffic/<mix>.json --seeds 1 2 3

Not part of a run.  For each seed, one softsync round from the same
weights and tokens, read four ways, one JSON line each:

- ``program``: the program's jitted step (bfloat16 weights, each event's
  update rounded to bfloat16): the norm of each leaf's change and the
  share of its elements that changed;
- ``program_grad``: the program's own gradient of each group at the
  round's start (``grad_with_accum``, as its step takes it), as per-leaf
  norms beside the reference's, and their worst-leaf gap;
- ``ref_master``: the float32 reference keeping a float32 master copy: the
  change each leaf should take, and the share of elements whose change
  would survive being stored in bfloat16 (at the rate, and at ten times
  it, the most that a momentum of 0.9 can add within a round);
- ``ref_bf16``: the float32 reference rounding each event's weights to
  bfloat16, as the program stores them.

Ratios are of whole-model norms of the change, against ``ref_master``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _norm(leaves: dict) -> float:
    return sum(v * v for v in leaves.values()) ** 0.5


def _moved_shares(new: dict, old: dict, scale: float = 1.0):
    """Per leaf: (share of elements whose bfloat16 value changes when
    ``old + scale·(new − old)`` is stored, element count)."""
    import jax
    import jax.numpy as jnp
    import weights as W

    @jax.jit
    def one(a, b):
        d = a.astype(jnp.float32) - b.astype(jnp.float32)
        stored = (b.astype(jnp.float32) + scale * d).astype(jnp.bfloat16)
        return jnp.sum(stored != b.astype(jnp.bfloat16))

    fa, fb = W.flatten(new), W.flatten(old)
    return {k: (int(one(fa[k], fb[k])), int(fb[k].size)) for k in fb}


def _share(counts: dict) -> float:
    return (sum(c for c, _ in counts.values())
            / sum(n for _, n in counts.values()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import run as R
    R._prepare()
    R._enable_cache()
    import jax
    import jax.numpy as jnp
    import harness as H
    import ref_decoder
    import tokens as T
    import weights as W
    from repro.core import distributed
    from repro.models import model_loss

    cfg, tr = _load(args.config), _load(args.traffic)
    cell = H.Cell(workload={"name": "witness", "chips": 1}, config=cfg,
                  traffic=tr, limits={}, end_to_end=[], per_layer=[])
    kind = H.kind_module("train")
    n = int(tr["n_softsync"])
    platform = jax.devices()[0].platform

    for seed in args.seeds:
        prog = kind.Program(cell, seed)
        mcfg, run = prog.mcfg, prog.run

        def loss_fn(p, b, sample_weights=None):
            return model_loss(mcfg, run, p, b, sample_weights=sample_weights)

        @jax.jit
        def grad_sq(p, b):
            g = distributed.grad_with_accum(loss_fn, p, b,
                                            run.num_microbatches)[2]
            return {k: jnp.sum(jnp.square(v.astype(jnp.float32)))
                    for k, v in W.flatten(g).items()}

        theta0 = prog.params
        batch = next(prog.feed)
        grouped = jax.tree.map(
            lambda x: distributed.split_interleaved(jnp.asarray(x), n), batch)
        gsq_prog = {}
        for j in range(n):
            part = jax.tree.map(lambda x: x[j], grouped)
            for k, v in grad_sq(theta0, part).items():
                gsq_prog[k] = gsq_prog.get(k, 0.0) + float(v)
        loss_prog = float(prog.round(batch))
        d_prog = W.diff_norms(prog.params, theta0)
        moved_prog = _moved_shares(prog.params, theta0)
        del prog, theta0, batch, grouped
        gc.collect()

        dec = ref_decoder.Decoder(cfg)
        params = W.make_weights(cfg, seed)
        batch_rows = tr["n_learners"] * tr["seqs_per_learner"]
        tk, lb = T.lm_batch(cfg["vocab_size"], batch_rows, tr["seq_len"],
                            seed, 0)
        lrs = ref_decoder.event_lrs(tr)
        gsq_ref = {}
        new, loss_ref = dec.round(params, tk, lb, lrs, gsq=gsq_ref,
                                  master=True)
        d_master = W.diff_norms(new, params)
        keep1 = _share(_moved_shares(new, params))
        keep10 = _share(_moved_shares(new, params, 10.0))
        del new
        gc.collect()
        new, _ = dec.round(params, tk, lb, lrs)
        d_bf16 = W.diff_norms(new, params)
        del new, params
        gc.collect()

        g_prog = {k: v ** 0.5 for k, v in gsq_prog.items()}
        g_ref = {k: float(v) ** 0.5 for k, v in gsq_ref.items()}
        grad_gaps = {k: abs(g_prog[k] - g_ref[k]) / g_ref[k] for k in g_ref}
        ratio = {k: d_prog[k] / d_master[k] for k in d_master
                 if d_master[k] > 0}
        whole = _norm(d_master)
        base = {"seed": seed, "platform": platform,
                "config": cfg["name"], "lrs": lrs[:1]}
        lines = [
            {"reading": "program", "loss": loss_prog,
             "change_ratio": _norm(d_prog) / whole,
             "moved_share": _share(moved_prog),
             "leaf_ratio_median": statistics.median(ratio.values()),
             "leaves_over_half": sorted(k for k, v in ratio.items()
                                        if v > 0.5)},
            {"reading": "program_grad",
             "grad_gap_worst_leaf": max(grad_gaps.values()),
             "grad_gap_median_leaf": statistics.median(grad_gaps.values()),
             "grad_norm_program": _norm(g_prog), "grad_norm_ref": _norm(g_ref)},
            {"reading": "ref_master", "loss": loss_ref, "change_norm": whole,
             "stored_share": keep1, "stored_share_10x": keep10},
            {"reading": "ref_bf16", "change_ratio": _norm(d_bf16) / whole,
             "gap_to_program": abs(_norm(d_bf16) - _norm(d_prog))
             / max(_norm(d_bf16), 1e-30)},
        ]
        for line in lines:
            print(json.dumps({**base, **line}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, BENCH_DIR)
    sys.exit(main())
