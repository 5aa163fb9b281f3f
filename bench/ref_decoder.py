"""Plain float32 reference of the dense decoder and its softsync rounds.

The decoder as Qwen2 and Qwen3 publish it: RMSNorm, rotary positions
(rotate-half, θ from the config), grouped-query attention with QKV bias
(Qwen2) or a per-head RMSNorm on queries and keys (Qwen3), SwiGLU, a tied
or untied head and the mean cross-entropy over the real vocabulary.
Every matrix product runs at ``Precision.HIGHEST``, so a float32 product
stays float32 on a TPU.  Nothing here imports the program.

A round of n-softsync is n update events, each the SGD step of one
learner group's mean loss, every group's gradient taken at the round's
starting weights (group j holds rows j, j + n, …).  The weights are
stored in bfloat16 as the configuration trains them: each event rounds
``w − lr·g`` to bfloat16.  To fit one chip the reference runs group by
group and, within a group, layer by layer: the forward pass keeps each
layer's input, the backward pass takes one layer's gradient at a time and
applies it at once.

``quant="fp8"`` is the control: every matrix product takes its operands
rounded to float8 (e4m3 forward, e5m2 for the backward's cotangents,
each with a per-tensor scale), the step below the configuration's
bfloat16.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import weights as W

HIGHEST = lax.Precision.HIGHEST
UNITS = "units/block_0/"


def _scaled_round(x, dtype):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    return _scaled_round(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_scaled_round(g, jnp.float8_e5m2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


class Decoder:
    """The reference for one configuration (``bench/configs`` JSON)."""

    def __init__(self, cfg: dict, quant: str = "f32"):
        if quant not in ("f32", "fp8"):
            raise ValueError(f"unknown quant {quant!r}")
        self.cfg, self.quant = cfg, quant
        self.d = W.dims(cfg)
        self.eps = float(cfg["rms_norm_eps"])
        self.theta = float(cfg["rope_theta"])
        self.bias, self.qknorm = W.qkv_bias(cfg), W.qk_norm(cfg)
        self.tied = bool(cfg["tie_word_embeddings"])
        self._jits()

    # -- math -------------------------------------------------------------
    def mm(self, eq, a, b):
        if self.quant == "fp8":
            a, b = _fp8(a), _fp8(b)
        return jnp.einsum(eq, a, b, precision=HIGHEST,
                          preferred_element_type=jnp.float32)

    def rms(self, x, scale):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * lax.rsqrt(var + self.eps) * scale

    def rope(self, x):
        """x: (S, heads, Dh), positions 0 … S−1."""
        S, _, dh = x.shape
        freqs = 1.0 / (self.theta ** (np.arange(0, dh, 2, dtype=np.float64)
                                      / dh))
        ang = np.arange(S, dtype=np.float64)[:, None] * freqs[None, :]
        cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
        sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
        x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1)

    def attention(self, p, h):
        """One sequence: h (S, M) → (S, M)."""
        q = self.mm("sm,mhd->shd", h, p["attn/w_q"])
        k = self.mm("sm,mhd->shd", h, p["attn/w_k"])
        v = self.mm("sm,mhd->shd", h, p["attn/w_v"])
        if self.bias:
            q, k, v = q + p["attn/b_q"], k + p["attn/b_k"], v + p["attn/b_v"]
        if self.qknorm:
            q, k = self.rms(q, p["attn/q_norm"]), self.rms(k, p["attn/k_norm"])
        q, k = self.rope(q), self.rope(k)
        rep = self.d["H"] // self.d["KV"]
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        S = h.shape[0]
        s = self.mm("qhd,khd->hqk", q, k) / np.sqrt(self.d["Dh"])
        causal = np.tril(np.ones((S, S), bool))
        s = jnp.where(causal[None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = self.mm("hqk,khd->qhd", a, v)
        return self.mm("shd,hdm->sm", o, p["attn/w_o"])

    def layer(self, p, x):
        """One sequence through one decoder layer: x (S, M) fp32."""
        x = x + self.attention(p, self.rms(x, p["norm1/scale"]))
        h = self.rms(x, p["norm2/scale"])
        g = self.mm("sm,mf->sf", h, p["mlp/w_gate"])
        u = self.mm("sm,mf->sf", h, p["mlp/w_up"])
        return x + self.mm("sf,fm->sm", jax.nn.silu(g) * u, p["mlp/w_down"])

    def head_loss(self, final_scale, headw, x, labels, mask):
        """Mean cross-entropy over the masked positions of a group:
        x (B, S, M); ``headw`` is the (Vp, M) embedding when tied, else
        the (M, Vp) head."""
        V = self.d["V"]
        h = self.rms(x, final_scale)
        if self.tied:
            logits = self.mm("bsm,vm->bsv", h, headw[:V])
        else:
            logits = self.mm("bsm,mv->bsv", h, headw[:, :V])
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.sum((logz - ll) * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    # -- jitted pieces ----------------------------------------------------
    def _jits(self):
        f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)

        def layer_params(units, l):
            return f32({k[len(UNITS):]: lax.dynamic_index_in_dim(
                v, l, 0, keepdims=False) for k, v in units.items()})

        def embed(table, tokens):
            return table[tokens].astype(jnp.float32)

        def layer_fwd(units, l, x):
            p = layer_params(units, l)
            return jax.vmap(lambda xb: self.layer(p, xb))(x)

        def head(final_scale, headw, x, labels, mask):
            loss, vjp = jax.vjp(
                lambda fs, hw, xx: self.head_loss(fs, hw, xx, labels, mask),
                final_scale.astype(jnp.float32), headw.astype(jnp.float32),
                x)
            d_fs, d_hw, dx = vjp(jnp.float32(1.0))
            return loss, dx, d_fs, d_hw

        def layer_bwd(units0, run, l, x, dx, lr):
            p = layer_params(units0, l)

            def one(acc, inp):
                xb, db = inp
                _, vjp = jax.vjp(self.layer, p, xb)
                dp, dxb = vjp(db)
                return jax.tree.map(jnp.add, acc, dp), dxb

            zero = jax.tree.map(jnp.zeros_like, p)
            dp, dx_in = lax.scan(one, zero, (x, dx))
            new_run, sq = {}, {}
            for k, v in run.items():
                g = dp[k[len(UNITS):]]
                old = lax.dynamic_index_in_dim(v, l, 0, keepdims=False)
                new = (old.astype(jnp.float32) - lr * g).astype(v.dtype)
                new_run[k] = lax.dynamic_update_index_in_dim(v, new, l, 0)
                sq[k] = jnp.sum(g * g)
            return dx_in, new_run, sq

        def leaf_step(run_leaf, g, lr):
            new = (run_leaf.astype(jnp.float32) - lr * g).astype(
                run_leaf.dtype)
            return new, jnp.sum(g * g)

        def embed_grad(shape, tokens, dx0):
            z = jnp.zeros(shape, jnp.float32)
            return z.at[tokens.reshape(-1)].add(dx0.reshape(-1, shape[1]))

        prec = functools.partial(jax.default_matmul_precision, "highest")

        def wrap(fn, **kw):
            j = jax.jit(fn, **kw)

            def call(*a):
                with prec():
                    return j(*a)
            return call

        self._embed = wrap(embed)
        self._layer_fwd = wrap(layer_fwd)
        self._head = wrap(head)
        self._layer_bwd = wrap(layer_bwd, donate_argnums=(1,))
        self._leaf_step = wrap(leaf_step, donate_argnums=(0,))
        self._embed_grad = wrap(embed_grad, static_argnums=(0,))

    # -- one group, one round ---------------------------------------------
    def _event(self, theta0: dict, run: dict, tokens, labels, mask, lr,
               gsq: Optional[Dict[str, float]]) -> float:
        """One update event: group gradient at ``theta0`` applied to
        ``run`` (flat dicts, updated in place).  Returns the group loss."""
        L = self.d["L"]
        units0 = {k: v for k, v in theta0.items() if k.startswith(UNITS)}
        xs = [self._embed(theta0["embed"], tokens)]
        for l in range(L):
            xs.append(self._layer_fwd(units0, l, xs[-1]))
        headw = theta0["embed"] if self.tied else theta0["head"]
        loss, dx, d_fs, d_hw = self._head(theta0["final_norm/scale"], headw,
                                          xs.pop(), labels, mask)
        run_units = {k: run.pop(k) for k in list(run) if k.startswith(UNITS)}
        lr32 = jnp.float32(lr)
        for l in reversed(range(L)):
            dx, run_units, sq = self._layer_bwd(units0, run_units, l,
                                                xs.pop(), dx, lr32)
            if gsq is not None:
                for k, v in sq.items():
                    gsq[k] = gsq.get(k, 0.0) + v
        run.update(run_units)
        d_embed = self._embed_grad(tuple(theta0["embed"].shape), tokens, dx)
        if self.tied:
            d_embed = d_embed + d_hw
        else:
            run["head"], s = self._leaf_step(run["head"], d_hw, lr32)
            if gsq is not None:
                gsq["head"] = gsq.get("head", 0.0) + s
        run["embed"], s = self._leaf_step(run["embed"], d_embed, lr32)
        if gsq is not None:
            gsq["embed"] = gsq.get("embed", 0.0) + s
        run["final_norm/scale"], s = self._leaf_step(
            run["final_norm/scale"], d_fs, lr32)
        if gsq is not None:
            gsq["final_norm/scale"] = gsq.get("final_norm/scale", 0.0) + s
        return loss

    def round(self, params: dict, tokens: np.ndarray, labels: np.ndarray,
              lrs, keep: Optional[str] = None,
              gsq: Optional[Dict[str, float]] = None, master: bool = False):
        """One softsync round from ``params`` (nested, bf16).  Returns
        (new params, round loss = mean of the group losses).  ``keep=
        "half"`` plants a fault: each group's loss is the mean over half
        of its rows (half of its positions where it has one row).
        ``master=True`` keeps the updated weights in float32, as a float32
        master copy would, instead of rounding each event's to bfloat16."""
        n = len(lrs)
        theta0 = W.flatten(params)
        run = {k: v.astype(jnp.float32) if master else jnp.copy(v)
               for k, v in theta0.items()}
        total = 0.0
        for j in range(n):
            tk, lb = tokens[j::n], labels[j::n]
            mask = np.ones(tk.shape, np.float32)
            if keep == "half":
                if tk.shape[0] >= 2:
                    mask[tk.shape[0] // 2:] = 0.0
                else:
                    mask[:, tk.shape[1] // 2:] = 0.0
            total = total + self._event(theta0, run, jnp.asarray(tk),
                                        jnp.asarray(lb), jnp.asarray(mask),
                                        lrs[j], gsq)
        return W.nest(run), float(total) / n


def event_lrs(traffic: dict) -> List[float]:
    """The per-event learning rates of one round: the paper's
    staleness-inverse policy divides α₀ by the round's mean staleness
    (n − 1)/2 (at least 1); ``per_gradient`` gives event j α₀/max(1, j)."""
    n, base = int(traffic["n_softsync"]), float(traffic["base_lr"])
    policy = traffic["lr_policy"]
    if policy == "staleness_inverse":
        return [base / max(1.0, (n - 1) / 2.0)] * n
    if policy == "per_gradient":
        return [base / max(1.0, float(j)) for j in range(n)]
    if policy == "const":
        return [base] * n
    raise ValueError(f"no reference learning rate for policy {policy!r}")


def follow(cfg: dict, traffic: dict, seed: int, rounds: int,
           quant: str = "f32", keep: Optional[str] = None,
           alter_token: bool = False) -> dict:
    """The reference's own first ``rounds`` rounds from the seed: the
    round losses, the per-leaf norm of the first round's change (the
    first gradient as SGD applies it), of the change after all rounds,
    and of the first round's gradients (root of the sum over events of
    their squared norms)."""
    import tokens as T

    dec = Decoder(cfg, quant)
    batch = traffic["n_learners"] * traffic["seqs_per_learner"]
    lrs = event_lrs(traffic)
    params = W.make_weights(cfg, seed)
    losses, gsq = [], {}
    d1 = None
    for r in range(rounds):
        tk, lb = T.lm_batch(cfg["vocab_size"], batch, traffic["seq_len"],
                            seed, r)
        if alter_token and r == 0:
            lb = lb.copy()
            lb[0] = (lb[0] + 1) % cfg["vocab_size"]
        new, loss = dec.round(params, tk, lb, lrs, keep=keep,
                              gsq=gsq if r == 0 else None)
        if r == 0:
            d1 = W.diff_norms(new, params)
        params = new
        losses.append(loss)
    del new
    d_all = W.diff_norms(params, W.make_weights(cfg, seed))
    return {"losses": losses, "d1": d1, "d_all": d_all,
            "grad": {k: float(v) ** 0.5 for k, v in gsq.items()}}
