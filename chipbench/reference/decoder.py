"""Plain float32 reference of a dense GQA decoder (Qwen2, Llama, Mistral).

Straightforward `jax.numpy`, written from the published architecture and
sharing no code with the program: token embedding, then per layer
pre-RMSNorm, grouped-query attention with rotary embeddings (rotate-half
form), an optional QKV bias and an optional sliding window, a residual
add, pre-RMSNorm and a SwiGLU MLP with a residual add; a final RMSNorm
and a tied or untied head.  No cache, no pages, no batching tricks.

Every matrix product runs at `Precision.HIGHEST`, so a TPU computes it in
float32 and not in bfloat16 passes; callers also trace under
`jax.default_matmul_precision("highest")`.  `fp8=True` is the control:
the same model with every matrix product's operands rounded to float8
(e4m3) under one absmax scale per operand, the precision step below the
configuration's bfloat16 compute.  It exists to show that the comparison
in the benchmark fails such a path.

Weights are the flat dicts of `chipbench.weights`.  Layer weights are
stacked and scanned.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
FP8_MAX = 448.0                          # largest finite float8_e4m3fn


def _fp8(x):
    """x rounded to float8 e4m3 under one absmax scale, back in float32."""
    scale = jnp.max(jnp.abs(x)) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def ein(spec: str, a, b, fp8: bool = False):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms_norm(x, gain, eps: float):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope(x, positions, theta: float):
    """x (B, S, N, hd); positions (B, S)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window, fp8: bool):
    """Causal GQA attention; q (B, S, H, hd), k and v (B, S, K, hd)."""
    group = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = ein("bqhd,bkhd->bhqk", q, k, fp8) / math.sqrt(q.shape[-1])
    qi = jnp.arange(q.shape[1])[:, None]
    ki = jnp.arange(k.shape[1])[None, :]
    mask = ki <= qi
    if window:
        mask &= ki > qi - window
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return ein("bhqk,bkhd->bqhd", p, v, fp8)


def _layer(conf: dict, fp8: bool, x, lw):
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    hd = conf.get("head_dim") or conf["hidden_size"] // \
        conf["num_attention_heads"]
    window = conf.get("sliding_window") if conf.get("use_sliding_window") \
        else None
    B, S, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h = rms_norm(x, lw["ln1"], eps)
    q, k, v = (ein("bsd,df->bsf", h, lw[w], fp8) for w in ("wq", "wk", "wv"))
    if "bq" in lw:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    q = rope(q.reshape(B, S, -1, hd), pos, theta)
    k = rope(k.reshape(B, S, -1, hd), pos, theta)
    v = v.reshape(B, S, -1, hd)
    o = attention(q, k, v, window, fp8).reshape(B, S, -1)
    x = x + ein("bsf,fd->bsd", o, lw["wo"], fp8)
    h = rms_norm(x, lw["ln2"], eps)
    a = jax.nn.silu(ein("bsd,df->bsf", h, lw["w_gate"], fp8)) \
        * ein("bsd,df->bsf", h, lw["w_up"], fp8)
    return x + ein("bsf,fd->bsd", a, lw["w_down"], fp8), None


def hidden(conf: dict, w: dict, tokens, fp8: bool = False):
    """Final-normed hidden states (B, S, d) of token ids (B, S)."""
    layers = {k.split("/", 1)[1]: v for k, v in w.items()
              if k.startswith("layers/")}
    x = jnp.take(w["embed"], tokens, axis=0)
    x, _ = lax.scan(functools.partial(_layer, conf, fp8), x, layers)
    return rms_norm(x, w["final_norm"], conf["rms_norm_eps"])


def logits(conf: dict, w: dict, h, fp8: bool = False):
    head = w["embed"].T if conf["tie_word_embeddings"] else w["head"]
    return ein("...d,dv->...v", h, head, fp8)


def loss(conf: dict, w: dict, tokens, targets):
    """Mean next-token cross-entropy over every position."""
    lg = logits(conf, w, hidden(conf, w, tokens))
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)

