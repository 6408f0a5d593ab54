"""Serve-step builders: batched prefill and single-token decode.

Both builders thread the tuning stack (DESIGN.md §13) through the
`Comm` they construct, so sequence-sharded decode's per-step softmax
reductions run on tuned embedded schedules and land in the profiler's
timeline when one is attached."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..models import transformer
from ..models.config import ModelConfig
from ..parallel.comm import AxisSpec, Comm


def build_prefill(cfg: ModelConfig, axes: AxisSpec, backend: str, *,
                  allreduce_algo: str = "paper", topo=None, link=None,
                  embedding=None, tuner=None, profile=None):
    def fn(params, batch):
        comm = Comm(axes, backend, allreduce_algo=allreduce_algo,
                    topo=topo, link=link, embedding=embedding,
                    tuner=tuner, profile=profile)
        return transformer.prefill(
            comm, cfg, params, batch.get("tokens"),
            frames=batch.get("frames"),
            frontend_embeds=batch.get("frontend_embeds"))
    return fn


def build_decode_step(cfg: ModelConfig, axes: AxisSpec, backend: str,
                      seq_shards: int = 1, *, allreduce_algo: str = "paper",
                      topo=None, link=None, embedding=None, tuner=None,
                      profile=None):
    def fn(params, cache, batch):
        comm = Comm(axes, backend, allreduce_algo=allreduce_algo,
                    topo=topo, link=link, embedding=embedding,
                    tuner=tuner, profile=profile)
        return transformer.decode_step(
            comm, cfg, params, cache, batch["tokens"], batch["positions"],
            seq_shards=seq_shards)
    return fn


@jax.named_scope("sample")
def sample_greedy(comm: Comm, logits):
    """Greedy sampling over vocab-sharded logits: local argmax + global
    combine over the model axis.

    Ties break to the LOWEST global index, matching `jnp.argmax` on the
    unsharded vocab: every shard whose local max equals the global max
    contributes its local winner (already the lowest in-shard index),
    losers contribute an off-the-end sentinel, and a min-reduce picks the
    smallest global index among the tied shards."""
    v_local = logits.shape[-1]
    n = comm.axis_size(comm.axes.model)
    base = comm.axis_index(comm.axes.model) * v_local
    loc_max = jnp.max(logits, -1)
    loc_arg = jnp.argmax(logits, -1) + base
    g_max = comm.allreduce(loc_max, comm.axes.model, "max")
    sentinel = jnp.asarray(n * v_local, loc_arg.dtype)
    winner = jnp.where(loc_max >= g_max, loc_arg,
                       jnp.full_like(loc_arg, sentinel))
    return comm.allreduce(winner, comm.axes.model, "min")
