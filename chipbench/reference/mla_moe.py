"""Plain float32 reference of a DeepSeek-V3 decoder, and the teacher-forced
gaps of served tokens that decide `correct`.

Straightforward `jax.numpy`, written from the published architecture
(arXiv:2412.19437 section 2.1 and the published config.json) and sharing
no code with the program: token embedding; per layer pre-RMSNorm,
multi-head latent attention in its non-absorbed form (queries through
the q low-rank path and its norm, keys and values lifted from the
normed latent c_kv, a shared rope key, YaRN frequencies and softmax
scale, causal), a residual add, pre-RMSNorm and either a SwiGLU MLP (the
first `first_k_dense_replace` layers) or the MoE (sigmoid scores over
all routed experts, selection by score + correction bias within the best
`topk_group` of `n_group` groups, gates normalised and scaled, the
shared expert, and the routed experts this configuration holds); a
final RMSNorm and an untied head.  No cache, no pages, no kernel.
Rope in rotate-half form.

Every matrix product runs at `Precision.HIGHEST`.  `fp8=True` is the
control: every matrix product's operands rounded to float8 (e4m3) under
one absmax scale per operand (`decoder._fp8`), the step below the
configuration's bfloat16.

The model does not fit the chip in float32, so the reference is run a
layer at a time: each layer's weights are made from the seed
(`weights_mla_moe`), applied to the hidden states of every sequence and
freed before the next.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import weights_mla_moe as W
from chipbench.reference.decoder import HIGHEST, _fp8

Q_BLOCK = 512            # query rows scored at once


def ein(spec: str, a, b, fp8: bool = False):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms_norm(x, gain, eps: float):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def yarn_scaling(conf: dict):
    """(inverse frequencies of the rope lanes, softmax scale, cos/sin
    magnitude) under the published YaRN settings."""
    rs = conf["rope_scaling"]
    dim, base = conf["qk_rope_head_dim"], float(conf["rope_theta"])
    factor, orig = float(rs["factor"]), rs["original_max_position_embeddings"]

    def corr_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(corr_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(rs["beta_slow"])), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = extra * (1 - ramp) + extra / factor * ramp

    def mscale(m):
        return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0
    scale = mscale(rs["mscale_all_dim"]) ** 2 / math.sqrt(
        conf["qk_nope_head_dim"] + dim)
    return jnp.asarray(inv, jnp.float32), scale, \
        mscale(rs["mscale"]) / mscale(rs["mscale_all_dim"])


def rope(x, positions, inv, mag):
    """x (S, N, dim); positions (S,)."""
    half = x.shape[-1] // 2
    ang = positions[:, None].astype(jnp.float32) * inv
    cos, sin = (jnp.cos(ang) * mag)[:, None], (jnp.sin(ang) * mag)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(conf: dict, lw: dict, x, fp8: bool):
    """Causal MLA of one sequence x (S, d)."""
    s = W.dims(conf)
    H, nope, rp, r, v = s["H"], s["nope"], s["rope"], s["r"], s["v"]
    eps = conf["rms_norm_eps"]
    inv, scale, mag = yarn_scaling(conf)
    S = x.shape[0]
    pos = jnp.arange(S)
    cq = rms_norm(ein("sd,dq->sq", x, lw["wq_a"], fp8), lw["q_norm"], eps)
    q = ein("sq,qf->sf", cq, lw["wq_b"], fp8).reshape(S, H, nope + rp)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, inv, mag)],
                        -1)
    kv_a = ein("sd,dc->sc", x, lw["wkv_a"], fp8)
    c = rms_norm(kv_a[:, :r], lw["kv_norm"], eps)
    k_rope = rope(kv_a[:, None, r:], pos, inv, mag)
    kv = ein("sr,rf->sf", c, lw["wkv_b"], fp8).reshape(S, H, nope + v)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (S, H, rp))], -1)
    vals = kv[..., nope:]

    nq = min(Q_BLOCK, S)

    def block(q0):
        qb = lax.dynamic_slice_in_dim(q, q0, nq, 0)
        sc = ein("qhd,khd->hqk", qb, k, fp8) * scale
        mask = jnp.arange(S)[None, :] <= (q0 + jnp.arange(nq))[:, None]
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), -1)
        return ein("hqk,khd->qhd", p, vals, fp8)
    o = lax.map(block, jnp.arange(0, S, nq)).reshape(S, H * v)
    return ein("sf,fd->sd", o, lw["wo"], fp8)


def swiglu(x, w_gate, w_up, w_down, fp8: bool):
    a = jax.nn.silu(ein("sd,df->sf", x, w_gate, fp8)) \
        * ein("sd,df->sf", x, w_up, fp8)
    return ein("sf,fd->sd", a, w_down, fp8)


def _choose(sel, kept, per: int, k: int):
    """The k best s + b among the kept groups' experts: a mask (S, E), and
    the k-th and (k+1)-th best eligible values (S,)."""
    eligible = jnp.where(jnp.repeat(kept, per, axis=1), sel, -jnp.inf)
    top = lax.top_k(eligible, k + 1)[0]
    return eligible >= top[:, k - 1:k], top[:, k - 1], top[:, k]


def held_margin(conf: dict, sel, gscore, kept):
    """How far each row's routing lies from changing which held experts it
    selects, in units of one s + b (S,): the smallest of
      - each eligible held expert's distance to the selection's edge (a
        selected one's s + b minus the best unselected eligible, an
        unselected one's the other way round);
      - for the swap of the weakest kept group with the strongest dropped
        one, and of each held group with the edge, half the gap of the
        two groups' scores (a group scores the sum of two s + b), where
        that swap changes the held experts selected.
    +inf where no such change lies within one swap: a row whose selection
    of other chips' experts is near a tie still gets this chip's share of
    the output unchanged, but for its gates' normalisation."""
    s = W.dims(conf)
    E, G, k = s["E"], conf["n_group"], conf["num_experts_per_tok"]
    per, lo, hi = E // G, s["offset"], s["offset"] + s["held"]
    chosen, kth, nxt = _choose(sel, kept, per, k)
    mine = chosen[:, lo:hi]
    elig = jnp.repeat(kept, per, axis=1)[:, lo:hi]
    h = sel[:, lo:hi]
    m = jnp.where(mine, h - nxt[:, None],
                  jnp.where(elig, kth[:, None] - h, jnp.inf)).min(-1)
    rows = jnp.arange(sel.shape[0])
    kept_score = jnp.where(kept, gscore, jnp.inf)
    dropped_score = jnp.where(kept, -jnp.inf, gscore)
    weakest, g_in = jnp.argmin(kept_score, -1), jnp.min(kept_score, -1)
    strongest, g_out = (jnp.argmax(dropped_score, -1),
                        jnp.max(dropped_score, -1))
    swaps = [(weakest, strongest, g_in - g_out)]
    for g in sorted({e // per for e in range(lo, hi)}):
        inside = kept[:, g]
        swaps.append((jnp.where(inside, g, weakest),
                      jnp.where(inside, strongest, g),
                      jnp.where(inside, gscore[:, g] - g_out,
                                g_in - gscore[:, g])))
    for out_g, in_g, gap in swaps:
        alt = kept.at[rows, out_g].set(False).at[rows, in_g].set(True)
        moved = jnp.any(_choose(sel, alt, per, k)[0][:, lo:hi] != mine, -1)
        m = jnp.where(moved, jnp.minimum(m, gap / 2), m)
    return m


def route(conf: dict, lw: dict, x, fp8: bool = False):
    """Sigmoid scores (S, E), the selected experts (S, k), their gates
    (S, k), and each row's routing margin (S,) for this configuration's
    held experts (`held_margin`)."""
    s = W.dims(conf)
    E, G, kg = s["E"], conf["n_group"], conf["topk_group"]
    k = conf["num_experts_per_tok"]
    scores = jax.nn.sigmoid(ein("sd,de->se", x, lw["router"], fp8))
    sel = scores + lw["router_bias"]
    grouped = sel.reshape(-1, G, E // G)
    gscore = jnp.sort(grouped, -1)[..., -2:].sum(-1)           # (S, G)
    kept = gscore >= jnp.sort(gscore, -1)[:, G - kg][:, None]
    chosen = _choose(sel, kept, E // G, k)[0]
    experts = jnp.argsort(-jnp.where(chosen, sel, -jnp.inf), -1)[:, :k]
    gates = jnp.take_along_axis(scores, experts, -1)
    gates = gates / gates.sum(-1, keepdims=True) \
        * conf["routed_scaling_factor"]
    return scores, experts, gates, held_margin(conf, sel, gscore, kept)


def moe(conf: dict, lw: dict, x, fp8: bool):
    """The MoE layer's output on this configuration's held experts, and
    each row's routing margin for them."""
    s = W.dims(conf)
    _, experts, gates, margin = route(conf, lw, x, fp8)
    out = swiglu(x, lw["shared/w_gate"], lw["shared/w_up"],
                 lw["shared/w_down"], fp8)
    for e in range(s["held"]):
        w = jnp.sum(jnp.where(experts == s["offset"] + e, gates, 0.0), -1)
        out = out + w[:, None] * swiglu(x, lw["experts/w_gate"][e],
                                        lw["experts/w_up"][e],
                                        lw["experts/w_down"][e], fp8)
    return out, margin


def layer(conf: dict, fp8: bool, lw: dict, x):
    """One decoder layer on one sequence x (S, d) -> (x, routing margin
    (S,), +inf for a dense layer)."""
    eps = conf["rms_norm_eps"]
    x = x + attention(conf, lw, rms_norm(x, lw["ln1"], eps), fp8)
    h = rms_norm(x, lw["ln2"], eps)
    if "router" in lw:
        m, margin = moe(conf, lw, h, fp8)
    else:
        m = swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"], fp8)
        margin = jnp.full(x.shape[:1], jnp.inf)
    return x + m, margin


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def forward(conf: dict, key, token_rows, fp8: bool = False):
    """Final-normed hidden states of each sequence of token ids (each
    (S,)), each position's smallest held-expert routing margin over the
    MoE layers (`held_margin`),
    and the global leaves, layer by layer (one layer's float32 weights
    live at once)."""
    glob = jax.jit(lambda k: _f32(W.make_global(conf, k)))(key)
    xs = [jnp.take(glob["embed"], t, axis=0) for t in token_rows]
    margins = [jnp.full(t.shape, jnp.inf) for t in token_rows]
    run = jax.jit(lambda lw, x: layer(conf, fp8, lw, x))
    for i in range(conf["num_hidden_layers"]):
        lw = jax.jit(lambda k, i=i: _f32(W.make_layer(conf, k, i)))(key)
        outs = [run(lw, x) for x in xs]
        xs = [o[0] for o in outs]
        margins = [jnp.minimum(m, o[1]) for m, o in zip(margins, outs)]
        del lw, outs
    hs = [rms_norm(x, glob["final_norm"], conf["rms_norm_eps"]) for x in xs]
    return hs, margins, glob


def served_gaps(conf: dict, seed_key, seqs, *, pad_to: int,
                control: bool = False):
    """seqs: [(prompt, served)] int arrays.  Returns (gaps, margins,
    control_gaps): per served token, reference best logit minus the
    reference logit of the served token; the routing margin at the
    position that predicted it; and, with `control`, the gap of the fp8
    path's first token at the same position (None otherwise)."""
    rows, firsts = [], []
    for prompt, served in seqs:
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        if len(seq) > pad_to:
            raise ValueError(f"sequence of {len(seq)} > {pad_to}")
        toks = np.zeros(pad_to, np.int32)
        toks[:len(seq)] = seq
        rows.append(jnp.asarray(toks))
        firsts.append(len(prompt) - 1)         # predicts served[0]
    with jax.default_matmul_precision("highest"):
        hs, margins, glob = forward(conf, seed_key, rows)
        hs8 = forward(conf, seed_key, rows, fp8=True)[0] if control else None

        @jax.jit
        def read(head, h, h8, pos, toks):
            lg = ein("sd,dv->sv", h[pos], head)
            best = jnp.max(lg, -1)
            gap = best - jnp.take_along_axis(lg, toks[:, None], -1)[:, 0]
            if h8 is None:
                return gap, gap
            first8 = jnp.argmax(ein("sd,dv->sv", h8[pos], head, True), -1)
            return gap, best - jnp.take_along_axis(lg, first8[:, None],
                                                   -1)[:, 0]

        gaps, mar, cgaps = [], [], []
        for n, (_, served) in enumerate(seqs):
            pos = np.zeros(pad_to, np.int32)
            pos[:len(served)] = firsts[n] + np.arange(len(served))
            tk = np.zeros(pad_to, np.int32)
            tk[:len(served)] = served
            g, cg = read(glob["head"], hs[n], None if hs8 is None
                         else hs8[n], pos, tk)
            gaps.append(np.asarray(g)[:len(served)])
            cgaps.append(np.asarray(cg)[:len(served)])
            mar.append(np.asarray(margins[n])[pos[:len(served)]])
    return (np.concatenate(gaps), np.concatenate(mar),
            np.concatenate(cgaps) if control else None)
