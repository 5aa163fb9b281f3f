"""Plain float32 reference of the DeepSeek-V3-style decoder and of its
softsync rounds: latent attention (MLA), a dropless fine-grained MoE with
shared experts, sigmoid routing with a balancing bias, leading dense
layers.  A test oracle (as ``kernels/ref.py`` is), and the benchmark's
reference: ``bench/ref_mla_moe.py`` is this file, byte for byte, and
imports nothing of the program.

The configuration is the model's published ``config.json`` keys (a
``deepseek_v3`` dict).  ``n_routed_experts`` counts the experts held here,
``first_held_expert`` (default 0) is the first of them, and the router
keeps the published count (``published["n_routed_experts"]``) and its
top-k.  What the experts held elsewhere would add is left out.  The
training values the config lacks come from ``assumed``:
``bias_update_speed`` (γ) and ``seq_aux_alpha`` (α).

Per layer, one sequence of S tokens, every matrix product in float32 at
``Precision.HIGHEST``:

- MLA: q = x W_q (heads of Dn + Dr); [c, k_r] = x W_kv_a;
  [k_n, v] = RMSNorm(c) W_kv_b; rotate-half RoPE on the Dr channels of q
  and on k_r, which every head shares; causal softmax(q·k / √(Dn + Dr)) v;
  output W_o.  DeepSeek's released weights interleave the rope channels
  and permute them on load; with weights drawn from a seed that is only a
  relabelling of channels.
- MoE: scores s = sigmoid(x W_r) over all experts; the k experts of the
  largest s + b (the bias picks, nothing else); gates = the picked s over
  their sum, × ``routed_scaling_factor``.  Each held expert's SwiGLU runs
  over every token and is weighted by that token's gate for it (0 where
  it was not picked): dense, no sorting, no capacity.  The shared experts
  (one SwiGLU of n_shared × width) add once.
- Balance loss, per sequence: Σ_i f_i P_i with f_i = E/(k S) × the
  sequence's slots that picked i (no gradient) and P_i the mean over its
  tokens of s normalised over the experts; the loss adds α × its mean over
  sequences, summed over the MoE layers.

A round of n-softsync is n update events, event j the momentum step of
learner group j's mean loss (rows j, j + n, …), every gradient taken at
the round's starting weights.  The loss of an event is the mean over its
rows of each row's token-mean cross-entropy plus its balance terms, which
is the program's mean over micro-batches of whole rows.  The weights are
a float32 master; those a learner reads are the master rounded to
bfloat16 after each event.  After each event the bias moves by
b_i ← b_i + γ·sign(mean load − load_i), the loads those of the event's
rows.  The reference runs row by row and layer by layer to fit one chip.

``quant="fp8"`` is the control: every matrix product takes its operands
rounded to float8 (e4m3 forward, e5m2 for the backward's cotangents, each
with a per-tensor scale), the step below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
VOCAB_PAD = 256
UNITS = "units/block_0/"
BIAS = UNITS + "moe/e_bias"


def lead(i: int) -> str:
    return f"lead/layer_{i}/"


# ---------------------------------------------------------------------------
# sizes, weights and operation counts
# ---------------------------------------------------------------------------
def dims(cfg: dict) -> dict:
    held = cfg["n_routed_experts"]
    v = cfg["vocab_size"]
    return {"M": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "R": cfg["kv_lora_rank"], "Dn": cfg["qk_nope_head_dim"],
            "Dr": cfg["qk_rope_head_dim"], "Dv": cfg["v_head_dim"],
            "F": cfg["intermediate_size"],
            "Fe": cfg["moe_intermediate_size"],
            "Fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "E": cfg.get("published", {}).get("n_routed_experts", held),
            "first": cfg.get("first_held_expert", 0), "held": held,
            "K": cfg["num_experts_per_tok"],
            "L0": cfg["first_k_dense_replace"],
            "Lm": cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
            "V": v, "Vp": -(-v // VOCAB_PAD) * VOCAB_PAD}


def layout(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """``path -> (shape, init)`` in the program's layout: leading layers
    under ``lead/layer_<i>``, the MoE layers stacked under
    ``units/block_0``.  init is "normal:<std>", "ones" (a norm scale,
    drawn near 1) or "zeros" (the float32 routing bias)."""
    d = dims(cfg)
    M, H, R, Dn, Dr, Dv = d["M"], d["H"], d["R"], d["Dn"], d["Dr"], d["Dv"]

    def attn(pre, lead_shape):
        return {pre + "norm1/scale": (lead_shape + (M,), "ones"),
                pre + "attn/w_q": (lead_shape + (M, H, Dn + Dr),
                                   f"normal:{M ** -0.5}"),
                pre + "attn/w_kv_a": (lead_shape + (M, R + Dr),
                                      f"normal:{M ** -0.5}"),
                pre + "attn/kv_norm": (lead_shape + (R,), "ones"),
                pre + "attn/w_kv_b": (lead_shape + (R, H, Dn + Dv),
                                      f"normal:{R ** -0.5}"),
                pre + "attn/w_o": (lead_shape + (H, Dv, M),
                                   f"normal:{(H * Dv) ** -0.5}"),
                pre + "norm2/scale": (lead_shape + (M,), "ones")}

    def swiglu(pre, lead_shape, f):
        return {pre + "w_gate": (lead_shape + (M, f), f"normal:{M ** -0.5}"),
                pre + "w_up": (lead_shape + (M, f), f"normal:{M ** -0.5}"),
                pre + "w_down": (lead_shape + (f, M), f"normal:{f ** -0.5}")}

    out = {"embed": ((d["Vp"], M), "normal:0.02"),
           "head": ((M, d["Vp"]), f"normal:{M ** -0.5}"),
           "final_norm/scale": ((M,), "ones")}
    for i in range(d["L0"]):
        out.update(attn(lead(i), ()))
        out.update(swiglu(lead(i) + "mlp/", (), d["F"]))
    Lm, held, Fe = (d["Lm"],), d["held"], d["Fe"]
    out.update(attn(UNITS, Lm))
    out.update({UNITS + "moe/w_router": (Lm + (M, d["E"]),
                                         f"normal:{M ** -0.5}"),
                BIAS: (Lm + (d["E"],), "zeros")})
    out.update(swiglu(UNITS + "moe/", Lm + (held,), Fe))
    out.update(swiglu(UNITS + "moe/shared/", Lm, d["Fs"]))
    return dict(sorted(out.items()))


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs per trained token, forward and backward: 6 per weight
    that a token meets in a matrix product (the held experts at the k·held/E
    that an even router gives each token), plus causal attention's scores
    and weighted sum over the (S + 1)/2 keys a query sees on average (2
    FLOPs per key and channel, ×3 for the backward).  Recomputed work
    (remat) is not counted."""
    d = dims(cfg)
    M, H = d["M"], d["H"]
    mla = (M * H * (d["Dn"] + d["Dr"]) + M * (d["R"] + d["Dr"])
           + d["R"] * H * (d["Dn"] + d["Dv"]) + H * d["Dv"] * M)
    experts = d["K"] * d["held"] / d["E"] * 3 * M * d["Fe"]
    moe = M * d["E"] + 3 * M * d["Fs"] + experts
    weights = (d["L0"] * (mla + 3 * M * d["F"]) + d["Lm"] * (mla + moe)
               + M * d["V"])
    attn = (6.0 * (d["L0"] + d["Lm"]) * H * (d["Dn"] + d["Dr"] + d["Dv"])
            * (seq_len + 1) / 2.0)
    return 6.0 * weights + attn


def expert_flops_per_slot(cfg: dict) -> float:
    """FLOPs of one pass of a held expert's three SwiGLU products over one
    routed slot (forward; the backward is two such passes)."""
    d = dims(cfg)
    return 6.0 * d["M"] * d["Fe"]


def key_of(seed: int):
    """A JAX key from any whole-number seed (also beyond 32 bits)."""
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.PRNGKey(word & 0x7FFFFFFF)


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
    def make(key):
        flat = {}
        for i, (path, shape, init) in enumerate(items):
            if init == "zeros":
                flat[path] = jnp.zeros(shape, jnp.float32)
                continue
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            x = 1.0 + 0.02 * z if init == "ones" else \
                float(init.split(":")[1]) * z
            flat[path] = x.astype(jnp.bfloat16)
        return nest(flat)
    return jax.jit(make)


def make_weights(cfg: dict, seed: int) -> dict:
    """The parameter pytree of ``cfg`` drawn from ``seed`` (bfloat16, the
    routing bias float32 zeros), made on the device in one jitted call."""
    items = tuple((p, s, i) for p, (s, i) in layout(cfg).items())
    return _maker(items)(key_of(seed))


# ---------------------------------------------------------------------------
# the float8 control
# ---------------------------------------------------------------------------
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


# ---------------------------------------------------------------------------
# the decoder
# ---------------------------------------------------------------------------
class Decoder:
    """The reference for one configuration (a ``deepseek_v3`` dict)."""

    def __init__(self, cfg: dict, quant: str = "f32"):
        if quant not in ("f32", "fp8"):
            raise ValueError(f"unknown quant {quant!r}")
        self.cfg, self.quant = cfg, quant
        self.d = dims(cfg)
        self.eps = float(cfg["rms_norm_eps"])
        self.theta = float(cfg["rope_theta"])
        self.scaling = float(cfg["routed_scaling_factor"])
        self.norm_topk = bool(cfg["norm_topk_prob"])
        self.gamma = float(cfg["assumed"]["bias_update_speed"])
        self.alpha = float(cfg["assumed"]["seq_aux_alpha"])
        self._jits()

    # -- math, one sequence ------------------------------------------------
    def mm(self, eq, a, b):
        if self.quant == "fp8":
            a, b = _fp8(a), _fp8(b)
        return jnp.einsum(eq, a, b, precision=HIGHEST,
                          preferred_element_type=jnp.float32)

    def rms(self, x, scale):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * lax.rsqrt(var + self.eps) * scale

    def rope(self, x):
        """x: (S, heads, Dr), positions 0 … S−1, rotate-half."""
        S, _, dr = x.shape
        freqs = 1.0 / (self.theta ** (np.arange(0, dr, 2, dtype=np.float64)
                                      / dr))
        ang = np.arange(S, dtype=np.float64)[:, None] * freqs[None, :]
        cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
        sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
        x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1)

    def attention(self, p, h):
        """h (S, M) → (S, M)."""
        d = self.d
        R, Dn = d["R"], d["Dn"]
        q = self.mm("sm,mhd->shd", h, p["attn/w_q"])
        kv_a = self.mm("sm,mr->sr", h, p["attn/w_kv_a"])
        c = self.rms(kv_a[:, :R], p["attn/kv_norm"])
        kv = self.mm("sr,rhd->shd", c, p["attn/w_kv_b"])
        k_rope = self.rope(kv_a[:, None, R:])
        q = jnp.concatenate([q[..., :Dn], self.rope(q[..., Dn:])], axis=-1)
        k = jnp.concatenate(
            [kv[..., :Dn], jnp.broadcast_to(k_rope, kv.shape[:2]
                                            + k_rope.shape[-1:])], axis=-1)
        v = kv[..., Dn:]
        S = h.shape[0]
        causal = np.tril(np.ones((S, S), bool))
        scale = 1.0 / np.sqrt(d["Dn"] + d["Dr"])

        @jax.checkpoint
        def head(qh, kh, vh):
            s = self.mm("qd,kd->qk", qh, kh) * scale
            a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            return self.mm("qk,kd->qd", a, vh)

        o = lax.map(lambda t: head(*t), (q.transpose(1, 0, 2),
                                         k.transpose(1, 0, 2),
                                         v.transpose(1, 0, 2)))
        return self.mm("hsd,hdm->sm", o, p["attn/w_o"])

    def swiglu(self, h, wg, wu, wd):
        g = self.mm("sm,mf->sf", h, wg)
        u = self.mm("sm,mf->sf", h, wu)
        return self.mm("sf,fm->sm", jax.nn.silu(g) * u, wd)

    def moe(self, p, bias, h):
        """h (S, M) → (out (S, M), load (E,) slots per expert, balance
        term of this sequence)."""
        d = self.d
        E, K, S = d["E"], d["K"], h.shape[0]
        s = jax.nn.sigmoid(self.mm("sm,me->se", h, p["moe/w_router"]))
        _, idx = lax.top_k(s + bias, K)
        gates = jnp.take_along_axis(s, idx, axis=-1)
        if self.norm_topk:
            gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
        gates = gates * self.scaling
        picked = jax.nn.one_hot(idx, E, dtype=jnp.float32)      # (S, K, E)
        gate_of = jnp.einsum("ske,sk->se", picked, gates)        # (S, E)
        out = self.swiglu(h, p["moe/shared/w_gate"], p["moe/shared/w_up"],
                          p["moe/shared/w_down"])
        for j in range(d["held"]):
            y = self.swiglu(h, p["moe/w_gate"][j], p["moe/w_up"][j],
                            p["moe/w_down"][j])
            out = out + gate_of[:, d["first"] + j, None] * y
        load = jnp.sum(picked, axis=(0, 1))
        f = lax.stop_gradient(load * (E / (K * S)))
        P = jnp.mean(s / jnp.sum(s, -1, keepdims=True), axis=0)
        return out, load, jnp.sum(f * P)

    def dense_layer(self, p, x):
        x = x + self.attention(p, self.rms(x, p["norm1/scale"]))
        h = self.rms(x, p["norm2/scale"])
        return x + self.swiglu(h, p["mlp/w_gate"], p["mlp/w_up"],
                               p["mlp/w_down"])

    def moe_layer(self, p, bias, x):
        """→ ((x out, balance term), load)."""
        x = x + self.attention(p, self.rms(x, p["norm1/scale"]))
        out, load, bal = self.moe(p, bias, self.rms(x, p["norm2/scale"]))
        return (x + out, bal), load

    def head_loss(self, final_scale, headw, x, labels):
        """Sum over rows of each row's token-mean cross-entropy over the
        real vocabulary: x (B, S, M), headw (M, Vp)."""
        h = self.rms(x, final_scale)
        logits = self.mm("bsm,mv->bsv", h, headw[:, :self.d["V"]])
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.sum(jnp.mean(logz - ll, axis=-1))

    # -- jitted pieces -----------------------------------------------------
    def _jits(self):
        f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)

        def unit_params(units, l):
            return f32({k[len(UNITS):]: lax.dynamic_index_in_dim(
                v, l, 0, keepdims=False) for k, v in units.items()
                if k != BIAS})

        def embed(table, tokens):
            return table[tokens].astype(jnp.float32)

        def lead_fwd(p, x):
            return jax.vmap(lambda xb: self.dense_layer(f32(p), xb))(x)

        def unit_fwd(units, bias, l, x):
            p, b = unit_params(units, l), bias[l]
            (y, bal), load = jax.vmap(
                lambda xb: self.moe_layer(p, b, xb))(x)
            return y, jnp.sum(load, axis=0), bal

        def head(final_scale, headw, x, labels, scale):
            loss, vjp = jax.vjp(
                lambda fs, hw, xx: self.head_loss(fs, hw, xx, labels),
                final_scale.astype(jnp.float32), headw.astype(jnp.float32),
                x)
            d_fs, d_hw, dx = vjp(scale)
            return loss * scale, dx, d_fs, d_hw

        def step(run, master, vel, g, lr, mom):
            """One momentum step of stacked or plain leaves; run rounded
            from the float32 master."""
            v = mom * vel + g
            w = master - lr * v
            return w.astype(run.dtype), w, v

        def lead_bwd(p0, x, dx, lr, mom, run, master, vel):
            p = f32(p0)

            def one(acc, inp):
                xb, db = inp
                _, vjp = jax.vjp(self.dense_layer, p, xb)
                dp, dxb = vjp(db)
                return jax.tree.map(jnp.add, acc, dp), dxb

            dp, dx_in = lax.scan(one, jax.tree.map(jnp.zeros_like, p),
                                 (x, dx))
            new, sq = {}, {}
            for k in run:
                new[k] = step(run[k], master[k], vel[k], dp[k], lr, mom)
                sq[k] = jnp.sum(dp[k] * dp[k])
            return (dx_in, {k: t[0] for k, t in new.items()},
                    {k: t[1] for k, t in new.items()},
                    {k: t[2] for k, t in new.items()}, sq)

        def unit_bwd(units0, bias, l, x, dx, dbal, lr, mom, run, master,
                     vel):
            p, b = unit_params(units0, l), bias[l]

            def one(acc, inp):
                xb, db = inp
                _, vjp, _ = jax.vjp(lambda pp, xx: self.moe_layer(pp, b, xx),
                                    p, xb, has_aux=True)
                dp, dxb = vjp((db, dbal))
                return jax.tree.map(jnp.add, acc, dp), dxb

            dp, dx_in = lax.scan(one, jax.tree.map(jnp.zeros_like, p),
                                 (x, dx))
            new_run, new_master, new_vel, sq = {}, {}, {}, {}
            for k in run:
                g = dp[k[len(UNITS):]]
                at = functools.partial(lax.dynamic_index_in_dim, index=l,
                                       axis=0, keepdims=False)
                r, w, v = step(at(run[k]), at(master[k]), at(vel[k]), g, lr,
                               mom)
                put = functools.partial(lax.dynamic_update_index_in_dim,
                                        index=l, axis=0)
                new_run[k] = put(run[k], r)
                new_master[k] = put(master[k], w)
                new_vel[k] = put(vel[k], v)
                sq[k] = jnp.sum(g * g)
            return dx_in, new_run, new_master, new_vel, sq

        def leaf_bwd(run, master, vel, g, lr, mom):
            r, w, v = step(run, master, vel, g, lr, mom)
            return r, w, v, jnp.sum(g * g)

        def embed_grad(shape, tokens, dx0):
            z = jnp.zeros(shape, jnp.float32)
            return z.at[tokens.reshape(-1)].add(dx0.reshape(-1, shape[1]))

        def bias_step(bias, loads):
            return bias + self.gamma * jnp.sign(
                jnp.mean(loads, -1, keepdims=True) - loads)

        prec = functools.partial(jax.default_matmul_precision, "highest")

        def wrap(fn, **kw):
            j = jax.jit(fn, **kw)

            def call(*a):
                with prec():
                    return j(*a)
            return call

        self._embed = wrap(embed)
        self._lead_fwd = wrap(lead_fwd)
        self._unit_fwd = wrap(unit_fwd)
        self._head = wrap(head)
        self._lead_bwd = wrap(lead_bwd, donate_argnums=(5, 6, 7))
        self._unit_bwd = wrap(unit_bwd, donate_argnums=(8, 9, 10))
        self._leaf_bwd = wrap(leaf_bwd, donate_argnums=(0, 1, 2))
        self._embed_grad = wrap(embed_grad, static_argnums=(0,))
        self._bias_step = wrap(bias_step)

    # -- one event, one round ---------------------------------------------
    def _event(self, theta0: dict, state: dict, tokens, labels, lr: float,
               mom: float, gsq: Optional[Dict[str, float]]) -> float:
        """One update event: the mean loss of ``tokens`` (B, S) at
        ``theta0``, its gradient applied to ``state`` (flat dicts "run",
        "master", "velocity", updated in place), then the bias rule.
        Returns the event's loss."""
        d = self.d
        rows = tokens.shape[0]
        run, master, vel = state["run"], state["master"], state["velocity"]
        lr32, mom32 = jnp.float32(lr), jnp.float32(mom)

        def acc(sq):
            if gsq is not None:
                for k, v in sq.items():
                    gsq[k] = gsq.get(k, 0.0) + v

        def take(prefix, *dicts):
            return [{k: dd.pop(k) for k in list(dd) if k.startswith(prefix)
                     and k != BIAS} for dd in dicts]

        units0 = {k: v for k, v in theta0.items() if k.startswith(UNITS)}
        bias0 = theta0[BIAS]
        xs = [self._embed(theta0["embed"], tokens)]
        for i in range(d["L0"]):
            p = {k[len(lead(i)):]: v for k, v in theta0.items()
                 if k.startswith(lead(i))}
            xs.append(self._lead_fwd(p, xs[-1]))
        loads, bal = [], 0.0
        for l in range(d["Lm"]):
            y, load, b = self._unit_fwd(units0, bias0, l, xs[-1])
            xs.append(y)
            loads.append(load)
            bal = bal + jnp.sum(b)
        scale = jnp.float32(1.0 / rows)
        ce, dx, d_fs, d_hw = self._head(theta0["final_norm/scale"],
                                        theta0["head"], xs.pop(), labels,
                                        scale)
        dbal = jnp.float32(self.alpha / rows)
        u_run, u_master, u_vel = take(UNITS, run, master, vel)
        for l in reversed(range(d["Lm"])):
            dx, u_run, u_master, u_vel, sq = self._unit_bwd(
                units0, bias0, l, xs.pop(), dx, dbal, lr32, mom32, u_run,
                u_master, u_vel)
            acc(sq)
        run.update(u_run), master.update(u_master), vel.update(u_vel)
        for i in reversed(range(d["L0"])):
            pre = lead(i)
            p0 = {k[len(pre):]: v for k, v in theta0.items()
                  if k.startswith(pre)}
            l_run, l_master, l_vel = [{k[len(pre):]: v for k, v in t.items()}
                                      for t in take(pre, run, master, vel)]
            dx, l_run, l_master, l_vel, sq = self._lead_bwd(
                p0, xs.pop(), dx, lr32, mom32, l_run, l_master, l_vel)
            for t, new in ((run, l_run), (master, l_master),
                           (vel, l_vel)):
                t.update({pre + k: v for k, v in new.items()})
            acc({pre + k: v for k, v in sq.items()})
        grads = {"head": d_hw, "final_norm/scale": d_fs,
                 "embed": self._embed_grad(tuple(theta0["embed"].shape),
                                           tokens, dx)}
        for k, g in grads.items():
            run[k], master[k], vel[k], s = self._leaf_bwd(
                run[k], master[k], vel[k], g, lr32, mom32)
            acc({k: s})
        run[BIAS] = master[BIAS] = self._bias_step(run[BIAS],
                                                   jnp.stack(loads))
        return float(ce) + self.alpha * float(bal) / rows

    def init_state(self, params: dict) -> dict:
        """The training state of nested ``params``: stored weights, their
        float32 master and zero velocity."""
        flat = flatten(params)
        return {"run": dict(flat),
                "master": {k: jnp.array(v, jnp.float32, copy=True)
                           for k, v in flat.items()},
                "velocity": {k: jnp.zeros(v.shape, jnp.float32)
                             for k, v in flat.items()}}

    def round(self, state: dict, tokens: np.ndarray, labels: np.ndarray,
              lrs, momentum: float, keep: Optional[str] = None,
              gsq: Optional[Dict[str, float]] = None) -> float:
        """One softsync round on ``state`` (``init_state``, updated in
        place); returns the round loss (the mean of its events').
        ``keep="half"`` plants a fault: each event's loss is over the first
        half of its rows only."""
        n = len(lrs)
        theta0 = state["run"]
        state["run"] = {k: jnp.copy(v) for k, v in theta0.items()}
        total = 0.0
        for j in range(n):
            tk, lb = tokens[j::n], labels[j::n]
            if keep == "half":
                tk, lb = tk[:max(1, len(tk) // 2)], lb[:max(1, len(lb) // 2)]
            total += self._event(theta0, state, jnp.asarray(tk),
                                 jnp.asarray(lb), lrs[j], momentum, gsq)
        return total / n

    def forward(self, params: dict, tokens) -> Tuple[jax.Array, dict]:
        """Logits over the real vocabulary (B, S, V) and, per MoE layer,
        the loads (Lm, E) and balance terms (Lm, B) of ``tokens`` (B, S)."""
        flat = flatten(params)
        d = self.d
        x = self._embed(flat["embed"], jnp.asarray(tokens))
        for i in range(d["L0"]):
            x = self._lead_fwd({k[len(lead(i)):]: v for k, v in flat.items()
                                if k.startswith(lead(i))}, x)
        units = {k: v for k, v in flat.items() if k.startswith(UNITS)}
        loads, bals = [], []
        for l in range(d["Lm"]):
            x, load, bal = self._unit_fwd(units, flat[BIAS], l, x)
            loads.append(load)
            bals.append(bal)
        with jax.default_matmul_precision("highest"):
            h = self.rms(x, flat["final_norm/scale"].astype(jnp.float32))
            logits = self.mm("bsm,mv->bsv", h,
                             flat["head"][:, :d["V"]].astype(jnp.float32))
        return logits, {"load": jnp.stack(loads), "bal": jnp.stack(bals)}


def stored(state: dict) -> dict:
    """The nested weights a learner reads."""
    return nest(state["run"])


__all__ = ["Decoder", "dims", "layout", "make_weights", "nest", "flatten",
           "stored", "train_flops_per_token", "expert_flops_per_slot",
           "key_of"]
