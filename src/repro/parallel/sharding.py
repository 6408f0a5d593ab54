"""PartitionSpec rules for parameters, caches, and step inputs.

The param tree produced by models.transformer.init_params is mapped to
PartitionSpecs by leaf-path rules:

  * TP dims follow the local sizing in models/layers.py (q heads, FFN
    hidden, vocab, SSM heads over `model`);
  * replicated-over-model leaves (KV proj when n_kv < tp, MLA latents,
    routers, norms) get None there;
  * cfg.fsdp adds `data` on dim 0 of every 2-D block leaf (ZeRO-3),
    matching models.transformer._fsdp_gather;
  * MoE expert leaves are sharded over the EP group (model, or data+model
    when ep_over_data);
  * stacked-layer leading dims are unsharded (scanned).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..models.config import ModelConfig, SHAPES
from ..models import layers as L


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    data: str = "data"
    model: str | None = "model"   # None = dp_only (params replicated)
    pod: str | None = None


# leaf-name -> (model-sharded dims) base rules; dims index into the leaf
# shape *without* the stacked-layer prefix.
def _base_spec(path: tuple[str, ...], leaf, cfg: ModelConfig,
               ax: MeshAxes, tp: int) -> P:
    name = path[-1]
    in_moe = "moe" in path and "shared" not in path
    ep = ((ax.data, ax.model) if (cfg.moe and cfg.moe.ep_over_data)
          else ax.model)
    nd = leaf.ndim
    fsdp0 = cfg.fsdp and nd == 2 and "embed" not in path and name != "proj_mtp"

    def with_fsdp(spec_dims):
        dims = list(spec_dims)
        if fsdp0:
            d0 = dims[0]
            if d0 is None:
                dims[0] = ax.data
            elif isinstance(d0, tuple):
                dims[0] = d0 + (ax.data,)
            else:
                dims[0] = (d0, ax.data)
        return P(*dims)

    if in_moe and name in ("w_gate", "w_up", "w_down"):
        return P(ep, None, None)
    if name == "router":
        return with_fsdp((None, None))
    if name in ("wq", "w_gate", "w_up", "wq_b", "wkv_b", "w_in", "conv_w"):
        return with_fsdp((None, ax.model))
    if name in ("wo", "w_down", "w_out"):
        return with_fsdp((ax.model, None))
    if name in ("wk", "wv"):
        # replicated when kv heads don't divide tp (gathered per q head)
        _, _, repl = L._gqa_dims(cfg, tp)
        return with_fsdp((None, None) if repl else (None, ax.model))
    if name in ("bk", "bv"):
        _, _, repl = L._gqa_dims(cfg, tp)
        return P(None) if repl else P(ax.model)
    if name in ("bq", "a_log", "dt_bias", "d_skip", "norm_w", "conv_b"):
        return P(ax.model)
    if name in ("wq_a", "wkv_a", "proj"):
        return with_fsdp((None, None))
    if name == "table":
        return P(ax.model, None)
    if name == "head":
        return P(None, ax.model)
    if name in ("q_norm", "kv_norm", "ln", "ln1", "ln2", "final_norm",
                "router_bias"):
        return P(None)
    if nd == 1:
        return P(None)
    raise ValueError(f"no sharding rule for param {'/'.join(path)}")


_STACKED = ("layers", "dense_layers", "pairs", "local", "global")


def _is_stacked(path: tuple[str, ...]) -> bool:
    return any(p in _STACKED for p in path[:-1])


def param_specs(cfg: ModelConfig, params_shape: Any, ax: MeshAxes,
                tp: int):
    """Specs tree matching init_params output (pass a shape tree from
    jax.eval_shape)."""
    def one(kp, leaf):
        path = tuple(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in kp)
        base = _base_spec(path, leaf, cfg, ax, tp)
        if _is_stacked(path):
            return P(*((None,) + tuple(base)))
        return base
    return jax.tree_util.tree_map_with_path(one, params_shape)


def is_fsdp_leaf(cfg: ModelConfig, path: tuple[str, ...], nd_eff: int) -> bool:
    """The single fsdp predicate shared by specs, init localization, and
    gradient-sync masking (must mirror transformer._fsdp_gather)."""
    return cfg.fsdp and nd_eff == 2 and "embed" not in path


def fsdp_localize(cfg: ModelConfig, params_shape: Any, dp: int):
    """init_params produces model-local/data-full leaves; divide dim0 of
    fsdp leaves by dp to get the true per-chip local shapes."""
    def one(kp, leaf):
        path = tuple(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in kp)
        stacked = _is_stacked(path)
        nd_eff = leaf.ndim - (1 if stacked else 0)
        if not is_fsdp_leaf(cfg, path, nd_eff):
            return leaf
        dim = 1 if stacked else 0
        shape = list(leaf.shape)
        assert shape[dim] % dp == 0, (path, leaf.shape, dp)
        shape[dim] //= dp
        return jax.ShapeDtypeStruct(tuple(shape), leaf.dtype)
    return jax.tree_util.tree_map_with_path(one, params_shape)


def fsdp_shard_init(cfg: ModelConfig, params: Any, data_rank, dp: int):
    """Slice freshly-initialized (data-full) fsdp leaves down to this
    chip's shard — used inside shard_map by the init fn."""
    import jax.lax as lax

    def one(kp, leaf):
        path = tuple(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in kp)
        stacked = _is_stacked(path)
        nd_eff = leaf.ndim - (1 if stacked else 0)
        if not is_fsdp_leaf(cfg, path, nd_eff):
            return leaf
        dim = 1 if stacked else 0
        size = leaf.shape[dim] // dp
        return lax.dynamic_slice_in_dim(leaf, data_rank * size, size,
                                        axis=dim)
    return jax.tree_util.tree_map_with_path(one, params)


def needs_data_sync(cfg: ModelConfig, params_shape: Any):
    """Bool tree: which grad leaves are replicated over `data` and need
    grad_sync.  fsdp 2-D leaves and EP-over-data expert leaves arrive
    already reduced/sharded."""
    def one(kp, leaf):
        path = tuple(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in kp)
        nd_eff = leaf.ndim - (1 if _is_stacked(path) else 0)
        in_moe = "moe" in path and "shared" not in path
        if in_moe and path[-1] in ("w_gate", "w_up", "w_down") \
                and cfg.moe.ep_over_data:
            return False
        if cfg.fsdp and nd_eff == 2 and "embed" not in path:
            return False
        return True
    return jax.tree_util.tree_map_with_path(one, params_shape)


# ---------------------------------------------------------------------------
# cache + batch specs
# ---------------------------------------------------------------------------

def cache_specs(cfg: ModelConfig, cache_shape: Any, ax: MeshAxes,
                seq_shards: int = 1):
    """Decode caches: batch over data (or sequence over data when
    seq_shards > 1), heads/latents over model where applicable."""
    def one(kp, leaf):
        path = tuple(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in kp)
        name = path[-1]
        batch_dim = ax.data if seq_shards == 1 else None
        seq_dim = None if seq_shards == 1 else ax.data
        if name in ("k", "v"):          # (layers, B, S, H_local, hd)
            return P(None, batch_dim, seq_dim, ax.model, None)
        if name in ("c_kv", "k_rope"):   # (layers, B, S, r) — model-repl.
            return P(None, batch_dim, seq_dim, None)
        if name == "conv":               # (layers, B, w, conv_local)
            return P(None, batch_dim, None, ax.model)
        if name == "ssm":                # (layers, B, H_local, P, N)
            return P(None, batch_dim, ax.model, None, None)
        raise ValueError(f"no cache rule for {'/'.join(path)}")
    return jax.tree_util.tree_map_with_path(one, cache_shape)


def batch_specs(cfg: ModelConfig, batch: dict, ax: MeshAxes,
                kind: str, seq_shards: int = 1) -> dict:
    """Input sharding: global batch over (pod, data); decode positions
    replicated over model.  When the cache is sequence-sharded
    (seq_shards > 1, long-context decode with tiny batch) the token batch
    is replicated instead."""
    ddims = (ax.data,) if ax.model is not None else (ax.data, "model")
    if ax.pod:
        ddims = (ax.pod,) + ddims
    bdim = None if seq_shards > 1 else \
        (ddims if len(ddims) > 1 else ddims[0])
    out = {}
    for k, v in batch.items():
        if k in ("tokens", "targets"):
            out[k] = P(bdim, None)
        elif k == "positions":
            out[k] = P(bdim)
        elif k in ("frames", "frontend_embeds"):
            out[k] = P(bdim, None, None)
        else:
            raise ValueError(k)
    return out
