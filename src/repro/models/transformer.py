"""Model assembly: layer stacks per family, train/prefill/decode entries.

Layer stacks are lax.scan'd over stacked parameters so the traced HLO is
one layer deep regardless of depth (compile-time hygiene for the 512-chip
dry-run).  Heterogeneous patterns scan over their repeating unit:

  dense          scan [attn, mlp] x L
  gemma2         scan [local-SWA pair: attn_l, mlp, attn_g, mlp] x L/2
  moe            unrolled first_dense layers + scan [attn, moe] x rest
  ssm            scan [mamba2] x L
  hybrid/zamba2  scan [mamba2] x period, shared attn block between segments
  audio          scan [attn (non-causal), mlp] x L (encoder)
  vlm            dense stack; image embeds from the stub frontend are
                 scattered over the first n_frontend_tokens positions
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..parallel.comm import Comm
from . import layers as L
from .config import ModelConfig

Params = dict


def _maybe_remat(cfg: ModelConfig, fn):
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "selective":
        # keep matmul outputs, recompute elementwise chains (§Perf P5)
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return fn


def _scan(cfg, body, carry, xs):
    """lax.scan, fully unrolled when probing so cost_analysis sees every
    iteration (XLA counts a while body once)."""
    length = jax.tree.leaves(xs)[0].shape[0]
    return lax.scan(body, carry, xs,
                    unroll=length if cfg.probe_unroll else 1)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _stack_init(key, n: int, init_fn):
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def init_params(key, cfg: ModelConfig, tp: int, dp: int = 1) -> Params:
    """LOCAL parameter shards (call inside shard_map, with the key folded
    by model-rank for sharded leaves — see parallel/sharding.py)."""
    ks = jax.random.split(key, 8)
    p: Params = {"embed": L.init_embedding(ks[0], cfg, tp),
                 "final_norm": jnp.zeros((cfg.d_model,), jnp.float32)}
    fam = cfg.family

    if fam in ("dense", "vlm", "audio"):
        def one(k):
            k1, k2 = jax.random.split(k)
            return {"attn": L.init_attention(k1, cfg, tp),
                    "mlp": L.init_mlp(k2, cfg, tp),
                    "ln1": jnp.zeros((cfg.d_model,), jnp.float32),
                    "ln2": jnp.zeros((cfg.d_model,), jnp.float32)}
        if cfg.local_global_period:
            assert cfg.n_layers % 2 == 0
            p["pairs"] = {"local": _stack_init(ks[1], cfg.n_layers // 2, one),
                          "global": _stack_init(ks[2], cfg.n_layers // 2, one)}
        else:
            p["layers"] = _stack_init(ks[1], cfg.n_layers, one)

    elif fam == "moe":
        def one_dense(k):
            k1, k2 = jax.random.split(k)
            attn = (L.init_mla(k1, cfg, tp) if cfg.attn == "mla"
                    else L.init_attention(k1, cfg, tp))
            return {"attn": attn, "mlp": L.init_mlp(k2, cfg, tp),
                    "ln1": jnp.zeros((cfg.d_model,), jnp.float32),
                    "ln2": jnp.zeros((cfg.d_model,), jnp.float32)}

        def one_moe(k):
            k1, k2 = jax.random.split(k)
            attn = (L.init_mla(k1, cfg, tp) if cfg.attn == "mla"
                    else L.init_attention(k1, cfg, tp))
            return {"attn": attn, "moe": L.init_moe(k2, cfg, tp, dp),
                    "ln1": jnp.zeros((cfg.d_model,), jnp.float32),
                    "ln2": jnp.zeros((cfg.d_model,), jnp.float32)}
        nd = cfg.moe.first_dense_layers
        if nd:
            p["dense_layers"] = _stack_init(ks[1], nd, one_dense)
        p["layers"] = _stack_init(ks[2], cfg.n_layers - nd, one_moe)
        if cfg.mtp:
            k1, k2 = jax.random.split(ks[3])
            p["mtp"] = {"proj": jax.random.normal(
                k1, (2 * cfg.d_model, cfg.d_model), jnp.float32)
                / math.sqrt(2 * cfg.d_model),
                "block": one_dense(k2),
                "ln": jnp.zeros((cfg.d_model,), jnp.float32)}

    elif fam == "ssm":
        def one(k):
            return {"mamba": L.init_mamba2(k, cfg, tp),
                    "ln": jnp.zeros((cfg.d_model,), jnp.float32)}
        p["layers"] = _stack_init(ks[1], cfg.n_layers, one)

    elif fam == "hybrid":
        def one(k):
            return {"mamba": L.init_mamba2(k, cfg, tp),
                    "ln": jnp.zeros((cfg.d_model,), jnp.float32)}
        p["layers"] = _stack_init(ks[1], cfg.n_layers, one)
        k1, k2 = jax.random.split(ks[2])
        p["shared_attn"] = {"attn": L.init_attention(k1, cfg, tp),
                            "mlp": L.init_mlp(k2, cfg, tp),
                            "ln1": jnp.zeros((cfg.d_model,), jnp.float32),
                            "ln2": jnp.zeros((cfg.d_model,), jnp.float32)}
    else:
        raise ValueError(fam)
    if cfg.param_dtype != jnp.float32:
        p = jax.tree.map(
            lambda w: w.astype(cfg.param_dtype) if w.ndim >= 2 else w, p)
    return p


# ---------------------------------------------------------------------------
# forward blocks
# ---------------------------------------------------------------------------

def _fsdp_gather(comm: Comm, cfg: ModelConfig, bp):
    """ZeRO-3: block weights live sharded over `data` (dim 0 of every 2-D
    leaf); gather them just-in-time inside the layer (transient in scan).
    The VJP of the gather reduce-scatters the cotangents across data, so
    fsdp leaves arrive in the gradient tree already summed over the data
    axis (train.py skips grad_sync for them)."""
    if not cfg.fsdp:
        return bp
    return jax.tree.map(
        lambda w: comm.allgather(w, comm.axes.data, concat_axis=0)
        if w.ndim == 2 else w, bp)


def _attn_block(comm, cfg, bp, x, positions, is_local=False):
    bp = _fsdp_gather(comm, cfg, bp)
    h = L.rms_norm(x, bp["ln1"])
    if cfg.attn == "mla":
        a = L.mla_attention(comm, cfg, bp["attn"], h, positions)
    else:
        a = L.attention(comm, cfg, bp["attn"], h, positions,
                        is_local_layer=is_local)
    x = x + a
    h = L.rms_norm(x, bp["ln2"])
    if "moe" in bp:
        m, aux = L.moe(comm, cfg, bp["moe"], h)
        return x + m, aux
    return x + L.mlp(comm, cfg, bp["mlp"], h), jnp.zeros((), jnp.float32)


def _mamba_block(comm, cfg, bp, x):
    bp = _fsdp_gather(comm, cfg, bp)
    return x + L.mamba2(comm, cfg, bp["mamba"], L.rms_norm(x, bp["ln"]))


def _embed_scaled(comm, cfg, params, tokens):
    x = L.embed(comm, cfg, params["embed"], tokens)
    if cfg.local_global_period:      # gemma2 scales embeddings by sqrt(d)
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.dtype)
    return x


def forward(comm: Comm, cfg: ModelConfig, params: Params, tokens=None, *,
            frames=None, frontend_embeds=None) -> tuple:
    """Full-sequence forward -> (hidden (B,L,d), aux_loss scalar)."""
    if cfg.frontend == "audio":
        x = frames.astype(cfg.dtype)
        B, seq = x.shape[0], x.shape[1]
    else:
        x = _embed_scaled(comm, cfg, params, tokens)
        B, seq = tokens.shape
    if cfg.frontend == "vision" and frontend_embeds is not None:
        nf = frontend_embeds.shape[1]
        x = jnp.concatenate([frontend_embeds.astype(cfg.dtype), x[:, nf:]], 1)
    positions = jnp.broadcast_to(jnp.arange(seq)[None], (B, seq))
    aux_total = jnp.zeros((), jnp.float32)
    fam = cfg.family

    if fam in ("dense", "vlm", "audio"):
        if cfg.local_global_period:
            def pair(x, bp):
                x, _ = _attn_block(comm, cfg, bp[0], x, positions,
                                   is_local=True)
                x, _ = _attn_block(comm, cfg, bp[1], x, positions)
                return x, ()
            pair = _maybe_remat(cfg, pair)
            x, _ = _scan(cfg, pair, x,
                         (params["pairs"]["local"],
                          params["pairs"]["global"]))
        else:
            def step(x, bp):
                x, _ = _attn_block(comm, cfg, bp, x, positions)
                return x, ()
            step = _maybe_remat(cfg, step)
            x, _ = _scan(cfg, step, x, params["layers"])

    elif fam == "moe":
        nd = cfg.moe.first_dense_layers
        if nd:
            for i in range(nd):
                bp = jax.tree.map(lambda a: a[i], params["dense_layers"])
                blk = _maybe_remat(
                    cfg, lambda x, bp=bp: _attn_block(comm, cfg, bp, x,
                                                      positions))
                x, _ = blk(x)

        def step(carry, bp):
            x, aux = carry
            x, a = _attn_block(comm, cfg, bp, x, positions)
            return (x, aux + a), ()
        step = _maybe_remat(cfg, step)
        (x, aux_total), _ = _scan(cfg, step, (x, aux_total),
                                  params["layers"])

    elif fam == "ssm":
        def step(x, bp):
            return _mamba_block(comm, cfg, bp, x), ()
        step = _maybe_remat(cfg, step)
        x, _ = _scan(cfg, step, x, params["layers"])

    elif fam == "hybrid":
        period = cfg.hybrid_attn_period
        n = cfg.n_layers
        starts = list(range(0, n, period))
        def seg_step(x, bp):
            return _mamba_block(comm, cfg, bp, x), ()
        seg_step = _maybe_remat(cfg, seg_step)
        for s0 in starts:
            seg_len = min(period, n - s0)
            seg = jax.tree.map(lambda a: a[s0:s0 + seg_len], params["layers"])
            x, _ = _scan(cfg, seg_step, x, seg)
            shared = _maybe_remat(
                cfg, lambda x: _attn_block(comm, cfg, params["shared_attn"],
                                           x, positions)[0])
            x = shared(x)
    else:
        raise ValueError(fam)

    x = L.rms_norm(x, params["final_norm"])
    return x, aux_total


def train_loss(comm: Comm, cfg: ModelConfig, params: Params, batch: dict):
    """Token-mean cross-entropy (+ MoE aux, + MTP head when configured)."""
    h, aux = forward(comm, cfg, params, batch.get("tokens"),
                     frames=batch.get("frames"),
                     frontend_embeds=batch.get("frontend_embeds"))
    logits = L.lm_logits(comm, cfg, params["embed"], h)
    targets = batch["targets"]
    tok_loss = L.sharded_xent(comm, cfg, logits, targets)
    loss = jnp.mean(tok_loss)
    if cfg.mtp and "mtp" in params:
        # depth-1 MTP: combine h_t with emb(target_t) to predict t+2
        emb_next = L.embed(comm, cfg, params["embed"], targets)
        proj = _fsdp_gather(comm, cfg, {"w": params["mtp"]["proj"]})["w"]
        hm = L._dense(jnp.concatenate(
            [L.rms_norm(h, params["mtp"]["ln"]), emb_next], -1), proj)
        B, seq = targets.shape
        positions = jnp.broadcast_to(jnp.arange(seq)[None], (B, seq))
        hm, _ = _attn_block(comm, cfg, params["mtp"]["block"], hm, positions)
        lg2 = L.lm_logits(comm, cfg, params["embed"], hm[:, :-1])
        mtp_loss = jnp.mean(L.sharded_xent(comm, cfg, lg2, targets[:, 1:]))
        loss = loss + 0.1 * mtp_loss
    if cfg.moe is not None:
        loss = loss + 0.01 * aux / max(cfg.n_layers, 1)
    return loss


# ---------------------------------------------------------------------------
# caches + decode
# ---------------------------------------------------------------------------

def _layer_cache(cfg: ModelConfig, tp: int, batch_local: int, cache_len: int,
                 kind: str, seq_shards: int = 1, is_local=False):
    S = cache_len // seq_shards
    if kind == "mla":
        return L.init_mla_cache(cfg, batch_local, S)
    if kind == "mamba":
        return L.init_mamba_cache(cfg, tp, batch_local)
    wb = None
    if cfg.window is not None:
        wb = cfg.window
    if is_local and cfg.local_global_period is not None:
        wb = cfg.local_window
    return L.init_attn_cache(cfg, tp, batch_local, S, window_bound=wb)


def init_cache(cfg: ModelConfig, tp: int, batch_local: int, cache_len: int,
               seq_shards: int = 1) -> Params:
    """Stacked (per scanned layer group) decode caches."""
    fam = cfg.family
    def stack(n, fn):
        one = fn()
        return jax.tree.map(lambda a: jnp.broadcast_to(
            a[None], (n,) + a.shape).copy(), one)

    if fam in ("dense", "vlm"):
        if cfg.local_global_period:
            return {"pairs_local": stack(
                        cfg.n_layers // 2,
                        lambda: _layer_cache(cfg, tp, batch_local, cache_len,
                                             "gqa", seq_shards, True)),
                    "pairs_global": stack(
                        cfg.n_layers // 2,
                        lambda: _layer_cache(cfg, tp, batch_local, cache_len,
                                             "gqa", seq_shards))}
        return {"layers": stack(cfg.n_layers, lambda: _layer_cache(
            cfg, tp, batch_local, cache_len, "gqa", seq_shards))}
    if fam == "moe":
        kind = "mla" if cfg.attn == "mla" else "gqa"
        nd = cfg.moe.first_dense_layers
        out = {"layers": stack(cfg.n_layers - nd, lambda: _layer_cache(
            cfg, tp, batch_local, cache_len, kind, seq_shards))}
        if nd:
            out["dense_layers"] = stack(nd, lambda: _layer_cache(
                cfg, tp, batch_local, cache_len, kind, seq_shards))
        return out
    if fam == "ssm":
        return {"layers": stack(cfg.n_layers, lambda: _layer_cache(
            cfg, tp, batch_local, cache_len, "mamba"))}
    if fam == "hybrid":
        n_shared = len(range(0, cfg.n_layers, cfg.hybrid_attn_period))
        return {"layers": stack(cfg.n_layers, lambda: _layer_cache(
                    cfg, tp, batch_local, cache_len, "mamba")),
                "shared": stack(n_shared, lambda: _layer_cache(
                    cfg, tp, batch_local, cache_len, "gqa", seq_shards))}
    raise ValueError(fam)


def _attn_decode_block(comm, cfg, bp, x, cache, position, is_local=False,
                       seq_shards=1):
    h = L.rms_norm(x, bp["ln1"])
    if cfg.attn == "mla":
        a, cache = L.mla_decode(comm, cfg, bp["attn"], h, cache, position)
    else:
        a, cache = L.attention_decode(comm, cfg, bp["attn"], h, cache,
                                      position, is_local_layer=is_local,
                                      seq_shards=seq_shards)
    x = x + a
    h = L.rms_norm(x, bp["ln2"])
    if "moe" in bp:
        m, _ = L.moe(comm, cfg, bp["moe"], h)
        x = x + m
    else:
        x = x + L.mlp(comm, cfg, bp["mlp"], h)
    return x, cache


def decode_step(comm: Comm, cfg: ModelConfig, params: Params, cache: Params,
                tokens, positions, *, seq_shards: int = 1):
    """One decode step: tokens (B,1), positions (B,) -> (logits, new_cache)."""
    x = _embed_scaled(comm, cfg, params, tokens)
    fam = cfg.family

    if fam in ("dense", "vlm"):
        if cfg.local_global_period:
            def pair(x, bps):
                bp_l, bp_g, c_l, c_g = bps
                x, c_l = _attn_decode_block(comm, cfg, bp_l, x, c_l,
                                            positions, is_local=True,
                                            seq_shards=seq_shards)
                x, c_g = _attn_decode_block(comm, cfg, bp_g, x, c_g,
                                            positions, seq_shards=seq_shards)
                return x, (c_l, c_g)
            x, (cl, cg) = _scan(cfg, pair, x,
                                (params["pairs"]["local"],
                                 params["pairs"]["global"],
                                 cache["pairs_local"],
                                 cache["pairs_global"]))
            new_cache = {"pairs_local": cl, "pairs_global": cg}
        else:
            def step(x, bc):
                bp, c = bc
                x, c = _attn_decode_block(comm, cfg, bp, x, c, positions,
                                          seq_shards=seq_shards)
                return x, c
            x, nc = _scan(cfg, step, x, (params["layers"],
                                         cache["layers"]))
            new_cache = {"layers": nc}

    elif fam == "moe":
        nd = cfg.moe.first_dense_layers
        new_cache = {}
        if nd:
            dcs = []
            for i in range(nd):
                bp = jax.tree.map(lambda a: a[i], params["dense_layers"])
                c = jax.tree.map(lambda a: a[i], cache["dense_layers"])
                x, c = _attn_decode_block(comm, cfg, bp, x, c, positions,
                                          seq_shards=seq_shards)
                dcs.append(c)
            new_cache["dense_layers"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *dcs)
        def step(x, bc):
            bp, c = bc
            x, c = _attn_decode_block(comm, cfg, bp, x, c, positions,
                                      seq_shards=seq_shards)
            return x, c
        x, nc = _scan(cfg, step, x, (params["layers"], cache["layers"]))
        new_cache["layers"] = nc

    elif fam == "ssm":
        def step(x, bc):
            bp, c = bc
            h = L.rms_norm(x, bp["ln"])
            y, c = L.mamba2_decode(comm, cfg, bp["mamba"], h, c)
            return x + y, c
        x, nc = _scan(cfg, step, x, (params["layers"], cache["layers"]))
        new_cache = {"layers": nc}

    elif fam == "hybrid":
        period = cfg.hybrid_attn_period
        n = cfg.n_layers
        def seg_step(x, bc):
            bp, c = bc
            h = L.rms_norm(x, bp["ln"])
            y, c = L.mamba2_decode(comm, cfg, bp["mamba"], h, c)
            return x + y, c
        nc_layers, nc_shared = [], []
        for si, s0 in enumerate(range(0, n, period)):
            seg_len = min(period, n - s0)
            seg_p = jax.tree.map(lambda a: a[s0:s0 + seg_len],
                                 params["layers"])
            seg_c = jax.tree.map(lambda a: a[s0:s0 + seg_len],
                                 cache["layers"])
            x, c = _scan(cfg, seg_step, x, (seg_p, seg_c))
            nc_layers.append(c)
            sc = jax.tree.map(lambda a: a[si], cache["shared"])
            x, sc = _attn_decode_block(comm, cfg, params["shared_attn"], x,
                                       sc, positions, seq_shards=seq_shards)
            nc_shared.append(sc)
        new_cache = {
            "layers": jax.tree.map(lambda *xs: jnp.concatenate(xs, 0),
                                   *nc_layers),
            "shared": jax.tree.map(lambda *xs: jnp.stack(xs), *nc_shared)}
    else:
        raise ValueError(fam)

    x = L.rms_norm(x, params["final_norm"])
    logits = L.lm_logits(comm, cfg, params["embed"], x)
    return logits, new_cache


def prefill(comm: Comm, cfg: ModelConfig, params: Params, tokens=None, *,
            frames=None, frontend_embeds=None):
    """Prefill forward: returns last-position logits (cache fill is modeled
    by the forward pass itself; serving keeps the KV as activations)."""
    h, _ = forward(comm, cfg, params, tokens, frames=frames,
                   frontend_embeds=frontend_embeds)
    logits = L.lm_logits(comm, cfg, params["embed"], h[:, -1:])
    return logits


# ---------------------------------------------------------------------------
# paged KV (serving engine, DESIGN.md §15)
# ---------------------------------------------------------------------------

def paged_families() -> tuple[str, ...]:
    """Families the paged-KV serving path supports: attention KV caches,
    and MoE with MLA, whose latent rows are paged (SSM state is not
    paged — the engine guards on `paged_supported`)."""
    return ("dense", "vlm", "moe")


def paged_supported(cfg: ModelConfig) -> bool:
    """Whether the paged serving path runs this model: a family of
    `paged_families`, the MoE one with MLA attention."""
    return cfg.family in paged_families() and (
        cfg.family != "moe" or cfg.attn == "mla")


def init_kv_pool(cfg: ModelConfig, tp: int, num_pages: int,
                 page_size: int) -> Params:
    """The paged KV pool: one stack per leaf over all `n_layers`, indexed
    by the global layer index (local/global pairs: local layer i of the
    scan is layer 2i, the global one 2i+1; MoE: the dense layers first).
    GQA keeps {"k", "v"}, each (layers, num_pages, page_size, K*hd) with
    the K heads merged into the lane dim, so a page of one layer is one
    contiguous tile the decode kernel moves in one DMA.  MLA pages its
    latent rows: {"latent": (layers, num_pages, page_size,
    L.latent_width)}, not split into K and V.  Page p of every sequence
    lives at the SAME physical index in every layer, so one page table
    serves the whole stack."""
    if not paged_supported(cfg):
        raise ValueError(
            f"paged KV supports {paged_families()} (moe with MLA), not "
            f"{cfg.family!r} with {cfg.attn!r} attention")
    lanes = ({"latent": L.latent_width(cfg)} if cfg.attn == "mla" else
             {c: a.shape[2] * a.shape[3]
              for c, a in L.init_attn_cache(cfg, tp, 1, 1).items()})
    return {c: jnp.zeros((cfg.n_layers, num_pages, page_size, w), cfg.dtype)
            for c, w in lanes.items()}


def kv_pool_specs(cfg: ModelConfig):
    """The pool's partition over the model axis: every head reads the one
    MLA latent row, so it is replicated; K/V pools are split by head."""
    if cfg.attn == "mla":
        return {"latent": P()}
    return dict.fromkeys(("k", "v"), P(None, None, None, "model"))


def _paged_stack(comm, cfg, params, pool, page_table, x, positions,
                 page_size):
    """Run the layer stack against the paged pool: one scan over each
    param group the model has (`layers`; `pairs.local` + `pairs.global`,
    two blocks a step; `dense_layers` then `layers`), carrying x and
    taking each block's global layer index as scan input.  The whole pool
    is a constant of the scans.  Prefill (L = prompt bucket, from
    position 0) attends the prompt's own rows; decode (L = 1) reads the
    pool in place by layer index.  Each layer's new rows leave the scans
    as outputs and are written into the pool in one scatter per leaf
    after them.  MoE layers compute their held expert share
    (`L.moe_held`); in decode, slots at position 0 (the engine's empty
    ones) route nowhere.  Returns (x, pool, expert rows (MoE layers,
    experts_held), or None without MoE layers)."""
    decode = x.shape[1] == 1
    valid = positions > 0 if decode else None

    def block(x, bp, layer, is_local):
        h = L.rms_norm(x, bp["ln1"])
        if cfg.attn == "mla" and decode:
            a, rows = L.mla_paged_decode(comm, cfg, bp["attn"], h, pool,
                                         layer, page_table, positions,
                                         page_size=page_size)
        elif cfg.attn == "mla":
            a, rows = L.mla_paged_prefill(comm, cfg, bp["attn"], h,
                                          positions)
        elif decode:
            a, rows = L.attention_paged_decode(
                comm, cfg, bp["attn"], h, pool, layer, page_table, positions,
                page_size=page_size, is_local_layer=is_local)
        else:
            a, rows = L.attention_paged_prefill(
                comm, cfg, bp["attn"], h, positions,
                keys=page_table.shape[1] * page_size, is_local_layer=is_local)
        x = x + a
        h = L.rms_norm(x, bp["ln2"])
        if "moe" in bp:
            m, experts = L.moe_held(comm, cfg, bp["moe"], h, valid)
            return x + m, rows, experts
        return x + L.mlp(comm, cfg, bp["mlp"], h), rows, None

    def step(x, xs):
        bps, layers = xs
        rows = []
        for i, bp in enumerate(bps):            # a pair's first is local
            x, r, experts = block(x, bp, layers[i], len(bps) == 2 and i == 0)
            rows.append(r)
        return x, (jax.tree.map(lambda *r: jnp.stack(r), *rows), experts)

    if cfg.local_global_period:
        groups = [(params["pairs"]["local"], params["pairs"]["global"])]
    else:
        groups = [(params[k],) for k in ("dense_layers", "layers")
                  if k in params]
    rows, expert_rows, first = [], None, 0
    for bps in groups:
        n = jax.tree.leaves(bps)[0].shape[0] * len(bps)
        layers = jnp.arange(first, first + n, dtype=jnp.int32)
        x, (r, experts) = _scan(cfg, step, x,
                                (bps, layers.reshape(-1, len(bps))))
        rows.append(jax.tree.map(lambda a: a.reshape((n,) + a.shape[2:]), r))
        expert_rows = experts if experts is not None else expert_rows
        first += n
    rows = jax.tree.map(lambda *r: jnp.concatenate(r), *rows)
    pool = {c: L.paged_kv_write_block(pool[c], page_table, rows[c],
                                      positions, page_size) for c in pool}
    return x, pool, expert_rows


def prefill_paged(comm: Comm, cfg: ModelConfig, params: Params, pool: Params,
                  page_table, tokens, positions, *, page_size: int):
    """Paged prefill fast-path: ONE forward pass over the whole prompt
    bucket that also fills the sequence's KV pages (vs the seed launcher's
    per-token teacher forcing).  tokens: (B, L_bucket); positions: (B,
    L_bucket), from 0.  Returns (full-bucket logits (B, L, vocab_local),
    pool).  Rows past the true prompt length write garbage K/V into the
    row's own reserved (or null) pages; decode overwrites each position
    before the causal mask can ever expose it."""
    x = _embed_scaled(comm, cfg, params, tokens)
    x, pool, _ = _paged_stack(comm, cfg, params, pool, page_table, x,
                              positions, page_size)
    x = L.rms_norm(x, params["final_norm"])
    return L.lm_logits(comm, cfg, params["embed"], x), pool


def decode_step_paged(comm: Comm, cfg: ModelConfig, params: Params,
                      pool: Params, page_table, tokens, positions, *,
                      page_size: int):
    """One paged decode step: tokens (B,1), positions (B,) -> (logits
    (B,1,vocab_local), pool), and for a model with MoE layers a third
    output, the token rows each held expert of each MoE layer computed
    (MoE layers, experts_held).  Identical to `decode_step` numerics on a
    full-length cache; reads are page-table indexed."""
    x = _embed_scaled(comm, cfg, params, tokens)
    x, pool, expert_rows = _paged_stack(comm, cfg, params, pool, page_table,
                                        x, positions[:, None], page_size)
    x = L.rms_norm(x, params["final_norm"])
    logits = L.lm_logits(comm, cfg, params["embed"], x)
    return (logits, pool) + (() if expert_rows is None else (expert_rows,))
