"""Continuous-batching serving engine on the paged symmetric-heap KV
cache (DESIGN.md §15).

Three pieces, separable on purpose:

  * `Scheduler` — the pure-host continuous-batching policy.  Strict-FIFO
    admission into fixed engine slots with worst-case page reservation
    (prompt + max_new tokens) at admission time, per-step join/evict.
    Deterministic and devices-free, so the policy is unit-testable as a
    plain state machine (tests/test_serve_engine.py drives it with a
    synthetic arrival trace).
  * `PagedKV`/`PagePool` (serve/kv.py) — page bookkeeping on the
    symmetric heap.  Heap pressure is admission backpressure: a request
    that doesn't fit simply waits at the queue head (no skipping, so no
    starvation), and no `HeapError` ever escapes the engine.
  * `ServeEngine` — the device half: a paged prefill fast-path (ONE
    forward pass over the smallest bucket of a short ladder that holds
    the prompt, filling the sequence's KV pages; every bucket compiled
    at construction) plus a fixed-shape batched decode step over all
    slots.
    Inactive slots ride along masked (their page-table rows point at the
    reserved null page), so the decode step never recompiles as
    sequences join and leave.  On a TPU the decode's attention reads
    each slot's live pages in place through the paged-decode kernel
    (DESIGN.md §15); its `serve.decode` span names the path and the
    pages one layer read.  Every per-row op is batch-independent, so
    a request's greedy tokens are bit-identical whether it runs alone or
    joins mid-batch — the engine's core correctness invariant.

Model-axis collectives (attention allreduces, the vocab-sharded greedy
sample) run through `Comm`, so a `TunedSelector`/`Profiler` passed to
the engine prices and records every per-step collective (DESIGN.md §13).
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import dataclasses
import time
from typing import Any

import numpy as np

from .kv import PagedKV, PagePool, pages_for
from ..core.fault import PEFailure, fault_event
from ..core.heap import SymmetricHeap


# prefill bucket ladder: the powers of two from 128 and the 1.5x points
# from 768, so a prompt pads at most 2x below 512 and 1.5x above it
PREFILL_LADDER = (128, 256, 512, 768, 1024, 1536, 2048)


def prefill_buckets(prompt_bucket: int, page_size: int) -> tuple[int, ...]:
    """The prompt lengths the engine compiles a prefill for: the rungs of
    `PREFILL_LADDER` below `prompt_bucket` that are whole pages, then
    `prompt_bucket` itself, the longest prompt accepted."""
    return tuple(b for b in PREFILL_LADDER
                 if b < prompt_bucket and b % page_size == 0) + (
        int(prompt_bucket),)


def bucket_for(buckets, n: int) -> int:
    """The smallest of the ascending `buckets` that holds `n` tokens."""
    return buckets[bisect.bisect_left(buckets, n)]


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new: int
    t_submit: float              # scheduler clock at submit


@dataclasses.dataclass
class SlotState:
    rid: int
    prompt: np.ndarray
    max_new: int
    pos: int                     # next position to be written by decode
    t_submit: float              # scheduler clock at submit
    t_admit: float               # scheduler clock at admission
    out: list = dataclasses.field(default_factory=list)
    done: bool = False


class Scheduler:
    """Deterministic continuous-batching policy (pure host code).

    Admission is strict FIFO: free slots are filled in slot-index order
    from the queue head, stopping at the first request whose worst-case
    page reservation does not fit — the head is never skipped, so a big
    request cannot starve behind a stream of small ones.  Eviction scans
    slots in index order each step.  Given the same submission sequence
    and per-slot completion times, the (admit, evict) event order is a
    pure function of the trace.

    `clock` stamps each request at submit and at admission (two reads
    per request), so `t_admit - t_submit` is its queue wait."""

    def __init__(self, kv: PagedKV, page_size: int,
                 clock=time.perf_counter):
        self.kv = kv
        self.page_size = int(page_size)
        self.clock = clock
        self.queue: collections.deque[Request] = collections.deque()
        self.slots: list[SlotState | None] = [None] * kv.max_slots
        self._next_rid = 0
        self.n_admitted = 0
        self.n_evicted = 0

    def pages_needed(self, req: Request) -> int:
        return pages_for(len(req.prompt) + req.max_new, self.page_size)

    def submit(self, prompt, max_new: int) -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        req = Request(self._next_rid, prompt, int(max_new), self.clock())
        if self.pages_needed(req) > self.kv.max_pages:
            raise ValueError(
                f"request needs {self.pages_needed(req)} pages "
                f"> max_pages={self.kv.max_pages}")
        self._next_rid += 1
        self.queue.append(req)
        return req.rid

    def step_evict(self) -> list[tuple[int, SlotState]]:
        """Evict finished sequences (slot-index order), freeing their
        pages back to the pool."""
        out = []
        for i, st in enumerate(self.slots):
            if st is not None and st.done:
                self.kv.evict(i)
                self.slots[i] = None
                self.n_evicted += 1
                out.append((i, st))
        return out

    def step_admit(self) -> list[tuple[int, SlotState]]:
        """Admit queued requests into free slots while pages last."""
        out = []
        for slot, st in enumerate(self.slots):
            if st is not None or not self.queue:
                continue
            req = self.queue[0]
            need = self.pages_needed(req)
            if not self.kv.can_admit(need):
                break           # backpressure: head waits, nobody skips
            self.queue.popleft()
            self.kv.admit(slot, req.rid, need,
                          len(req.prompt) + req.max_new)
            state = SlotState(rid=req.rid, prompt=req.prompt,
                              max_new=req.max_new, pos=len(req.prompt),
                              t_submit=req.t_submit, t_admit=self.clock())
            self.slots[slot] = state
            self.n_admitted += 1
            out.append((slot, state))
        return out

    def active_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slots)
                if s is not None and not s.done]

    def idle(self) -> bool:
        return not self.queue and all(s is None for s in self.slots)


def serve_programs(cfg, mesh, *, page_size: int, backend: str = "shmem",
                   **comm_kw):
    """The engine's jitted (prefill, decode) programs over `mesh` and the
    KV pool's partition specs.  Built from shapes alone, so they also
    lower for a described chip.

    prefill(params, pool, table (1, max_pages), tokens (1, Lb), positions
    (1, Lb), last_idx (1,)) and decode(params, pool, table (B, max_pages),
    tokens (B, 1), positions (B,)) each return (greedy tokens, f32
    logits, pool); decode also returns a tuple of what the stack returns
    beside the pool (the held experts' token rows of a model with MoE
    layers, else nothing).  Both donate the pool: every layer's new
    rows are written into it in place, in one scatter after the layers."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..launch import build
    from ..models import transformer
    from ..parallel.comm import Comm
    from . import step as sstep

    axes = build.axis_spec(mesh)
    _, pspecs = build.abstract_params(cfg, mesh)
    poolspecs = transformer.kv_pool_specs(cfg)

    def prefill_fn(params, pool, table, tokens, positions, last_idx):
        comm = Comm(axes, backend, **comm_kw)
        logits, pool = transformer.prefill_paged(
            comm, cfg, params, pool, table, tokens, positions,
            page_size=page_size)
        lg = jnp.take_along_axis(
            logits, last_idx[:, None, None], axis=1)[:, 0]
        return sstep.sample_greedy(comm, lg), lg, pool

    def decode_fn(params, pool, table, tokens, positions):
        comm = Comm(axes, backend, **comm_kw)
        logits, pool, *extra = transformer.decode_step_paged(
            comm, cfg, params, pool, table, tokens, positions,
            page_size=page_size)
        lg = logits[:, 0]
        return sstep.sample_greedy(comm, lg), lg, pool, tuple(extra)

    outs = (P(), P(None, "model"), poolspecs)
    pjit = jax.jit(build.shard_mapped(
        prefill_fn, mesh, (pspecs, poolspecs, P(), P(), P(), P()), outs),
        donate_argnums=1)
    djit = jax.jit(build.shard_mapped(
        decode_fn, mesh, (pspecs, poolspecs, P(), P(), P()), outs + (P(),)),
        donate_argnums=1)
    return pjit, djit, poolspecs


class ServeEngine:
    """Continuous-batching engine: paged prefill + fixed-shape batched
    decode over `max_slots` sequences, greedy sampling through the
    vocab-sharded `sample_greedy`.

    Each admission prefills at the smallest of `prefill_buckets` that
    holds its prompt (`prefill_buckets(prompt_bucket, page_size)`);
    every bucket's prefill runs once at construction, against the null
    page, so serving never compiles.  `prompt_bucket` is the longest
    prompt accepted.

    The mesh provides tensor parallelism only (data axis must be 1: the
    batch lives in engine slots, not on a mesh axis).  `kv_heap_bytes`
    caps the per-PE symmetric-heap KV region — by default sized to hold
    every slot's worst-case sequence plus the null page."""

    def __init__(self, cfg, mesh, *, params=None, max_slots: int = 4,
                 page_size: int = 8, max_seq: int = 64,
                 prompt_bucket: int = 32, kv_heap_bytes: int | None = None,
                 backend: str = "shmem", tuner=None, profile=None,
                 metrics=None, eos_id: int | None = None,
                 init_key: int = 0, capture_logits: bool = False):
        import dataclasses as dc

        import jax
        import jax.numpy as jnp

        from ..launch import build
        from ..models import layers, transformer

        cfg = dc.replace(cfg, fsdp=False)
        if not transformer.paged_supported(cfg):
            raise ValueError(
                f"paged serving supports {transformer.paged_families()} "
                f"(moe with MLA), not {cfg.family!r} with {cfg.attn!r} "
                f"attention")
        dp, tp, pod = build.mesh_dims(mesh)
        if dp != 1 or pod:
            raise ValueError("ServeEngine batches in engine slots; use a "
                             "(1, tp) mesh (data axis must be 1, no pod)")
        if prompt_bucket > max_seq:
            raise ValueError("prompt_bucket must be <= max_seq")
        self.cfg, self.mesh = cfg, mesh
        self.page_size = int(page_size)
        self.max_seq = int(max_seq)
        self.prompt_bucket = int(prompt_bucket)
        self.prefill_buckets = prefill_buckets(prompt_bucket, page_size)
        self.max_slots = int(max_slots)
        self.eos_id = eos_id
        self.capture_logits = capture_logits
        self._jnp, self._jax = jnp, jax

        max_pages = pages_for(max_seq, page_size)
        pool_shapes = jax.eval_shape(
            lambda: transformer.init_kv_pool(cfg, tp, 1, page_size))
        page_bytes = sum(
            int(np.prod(l.shape)) * l.dtype.itemsize
            for l in jax.tree.leaves(pool_shapes))
        if kv_heap_bytes is None:
            kv_heap_bytes = page_bytes * (max_slots * max_pages + 1)
        self.page_bytes = page_bytes
        self.heap = SymmetricHeap(int(kv_heap_bytes))
        pool = PagePool(self.heap, page_bytes)
        if pool.num_pages < 2:
            raise ValueError(
                f"kv_heap_bytes={kv_heap_bytes} holds {pool.num_pages} "
                f"pages of {page_bytes}B; need >= 2 (null + one live)")
        self.kv = PagedKV(pool, max_slots, max_pages)
        self.scheduler = Scheduler(self.kv, page_size)
        self.results: dict[int, np.ndarray] = {}
        self.logits_trace: dict[int, list] = {}
        self.steps = 0
        # observability (DESIGN.md §16): a ServeMetrics records the
        # request lifecycle; when `profile` is a Tracer, each request
        # additionally becomes an async track with enqueue/admit/
        # first-token instants.  Both default to None == zero cost.
        self.profile = profile
        self.metrics = metrics
        from ..core.trace import Tracer
        self._trace = profile if isinstance(profile, Tracer) else None

        n_dev_pages = pool.num_pages

        with jax.set_mesh(mesh):
            init_fn, _, _ = build.make_init_fn(cfg, mesh, backend)
            if params is None:
                params = jax.jit(init_fn)(jax.random.key(init_key))
            self.params = params
            self._pjit, self._djit, poolspecs = serve_programs(
                cfg, mesh, page_size=page_size, backend=backend,
                tuner=tuner, profile=profile)
            self.pool = jax.jit(build.shard_mapped(
                lambda: transformer.init_kv_pool(cfg, tp, n_dev_pages,
                                                 page_size),
                mesh, (), poolspecs))()
            # compile every bucket now: a free slot's table row is all
            # null page, which no valid read ever sees
            null_row = self.kv.table[:1].copy()
            for Lb in self.prefill_buckets:
                self._prefill(null_row, np.zeros(1, np.int32), Lb)
        jax.block_until_ready(self.pool)
        self.decode_path = ("kernel" if layers.paged_decode_kernel(cfg, tp)
                            else "gather")
        self.kv_kind = "latent" if cfg.attn == "mla" else "gqa"
        # (MoE layers, held experts) token rows of the last decode step
        self.last_expert_rows = None

    # -- observability helpers ------------------------------------------------
    @contextlib.contextmanager
    def _span(self, name: str, **meta):
        """The engine's one instrumentation point.  Always a
        `jax.profiler.TraceAnnotation`, which puts the phase on the
        profiler's clock beside the device's operations and costs about
        a microsecond when no profiler session is open; also a nested
        tracer span or a bare profiler op when one is attached."""
        with self._jax.profiler.TraceAnnotation(name, **meta):
            if self._trace is not None and self._trace.enabled:
                with self._trace.span(name, **meta):
                    yield
            elif self.profile is not None and self.profile.enabled:
                with self.profile.op(name, kind="span"):
                    yield
            else:
                yield

    def _prefill(self, trow, prompt, Lb: int):
        """One prefill of `prompt` at bucket `Lb` into the pages of table
        row `trow` (1, max_pages).  Returns (first token, logits), both
        on the device."""
        jnp = self._jnp
        toks = np.zeros((1, Lb), np.int32)
        toks[0, :len(prompt)] = prompt
        positions = jnp.broadcast_to(
            jnp.arange(Lb, dtype=jnp.int32)[None], (1, Lb))
        last = jnp.asarray([len(prompt) - 1], jnp.int32)
        tok, lg, self.pool = self._pjit(
            self.params, self.pool, jnp.asarray(trow), jnp.asarray(toks),
            positions, last)
        return tok, lg

    def _kv_pages(self, positions) -> int:
        """Pages one attention layer of this decode reads: on the kernel
        path the pages holding each slot's earlier positions (a windowed
        layer reads fewer), on the gather path every slot's whole table."""
        from ..kernels.paged_decode import page_span

        if self.decode_path == "gather":
            return self.kv.table.size
        return int(page_span(positions, self.page_size)[1].sum())

    def program_texts(self) -> dict[str, str]:
        """Compiled HLO text of the prefill and decode programs at this
        engine's shapes, whose metadata carries the model's named scopes
        (kv_update, kv_gather, attend, attn_proj, mlp, lm_head, sample; and
        mla_absorb, moe_route, moe_experts, moe_shared for MoE+MLA)."""
        jax, jnp = self._jax, self._jnp
        table = self.kv.table
        Lb = self.prompt_bucket

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)
        with jax.set_mesh(self.mesh):
            pre = self._pjit.lower(self.params, self.pool,
                                   i32(1, table.shape[1]), i32(1, Lb),
                                   i32(1, Lb), i32(1))
            dec = self._djit.lower(self.params, self.pool, i32(*table.shape),
                                   i32(self.max_slots, 1), i32(self.max_slots))
            return {"prefill_fn": pre.compile().as_text(),
                    "decode_fn": dec.compile().as_text()}

    def _req_event(self, kind: str, rid: int, **args) -> None:
        """Request-lifecycle edge on the tracer's async request track."""
        t = self._trace
        if t is None or not t.enabled:
            return
        if kind == "enqueue":
            t.begin_async("request", rid, f"req {rid}", **args)
        elif kind == "evict":
            t.end_async("request", rid, f"req {rid}", **args)
        else:
            t.instant_async("request", rid, kind, **args)

    # -- client API -----------------------------------------------------------
    def submit(self, prompt, max_new: int) -> int:
        if len(np.asarray(prompt).reshape(-1)) > self.prompt_bucket:
            raise ValueError(
                f"prompt longer than prompt_bucket={self.prompt_bucket}")
        rid = self.scheduler.submit(prompt, max_new)
        if self.metrics is not None:
            self.metrics.on_submit()
        self._req_event("enqueue", rid, prompt_len=len(
            np.asarray(prompt).reshape(-1)), max_new=int(max_new))
        return rid

    def _emit(self, st: SlotState, tok: int, lg=None) -> None:
        st.out.append(int(tok))
        if self.capture_logits:
            self.logits_trace.setdefault(st.rid, []).append(
                np.asarray(lg, np.float32))
        if (len(st.out) >= st.max_new
                or (self.eos_id is not None and int(tok) == self.eos_id)):
            st.done = True

    def step(self) -> dict:
        """One engine iteration: evict -> admit(+prefill) -> batched
        decode.  Returns {"evicted": [...], "admitted": [...],
        "decoded": n_active}.

        A :class:`~repro.core.fault.PEFailure` surfacing from prefill or
        decode (DESIGN.md §17) triggers a graceful drain instead of
        propagating: every live slot's pages are freed and its request
        re-queued at the queue head in slot order, so FIFO order is
        preserved and — because greedy decode is bit-identical batched
        or alone — regenerated results match what the lost step would
        have produced.  The step then returns ``{"faulted": True,
        "requeued": [...], ...}``."""
        try:
            return self._step_inner()
        except PEFailure as exc:
            return self._fault_drain(exc)

    def _fault_drain(self, exc: PEFailure) -> dict:
        """Graceful drain + re-queue on PE loss (DESIGN.md §17)."""
        t0 = time.perf_counter()
        sched = self.scheduler
        requeued = []
        # reversed slot order + appendleft => queue head ends up in slot
        # order, the admission order the lost batch had (FIFO preserved)
        for i in range(len(sched.slots) - 1, -1, -1):
            st = sched.slots[i]
            if st is None:
                continue
            self.kv.evict(i)
            sched.slots[i] = None
            self.logits_trace.pop(st.rid, None)
            sched.queue.appendleft(Request(st.rid, st.prompt, st.max_new,
                                           st.t_submit))
            requeued.append(st.rid)
        requeued.reverse()
        wall = time.perf_counter() - t0
        if self.metrics is not None:
            self.metrics.on_pe_failure(len(requeued), wall)
        prof = self.profile if (self.profile is not None
                                and self.profile.enabled) else None
        fault_event(prof, "fault.serve_drain", pe=exc.pe,
                    n_requeued=len(requeued),
                    recovery_us=int(wall * 1e6))
        self.steps += 1
        if self.metrics is not None:
            self.metrics.sample_engine(self)
        return {"evicted": [], "admitted": [], "decoded": 0,
                "faulted": True, "pe": exc.pe, "requeued": requeued}

    def _step_inner(self) -> dict:
        jnp = self._jnp
        sched = self.scheduler
        metrics = self.metrics
        with self._jax.set_mesh(self.mesh), self._span("serve.step"):
            evicted = []
            with self._span("serve.evict"):
                for slot, st in sched.step_evict():
                    self.results[st.rid] = np.asarray(st.out, np.int32)
                    evicted.append(st.rid)
                    if metrics is not None:
                        metrics.on_evict(st)
                    self._req_event("evict", st.rid, n_tokens=len(st.out))

            with self._span("serve.admit"):
                admits = sched.step_admit()
                if metrics is not None and sched.queue \
                        and any(s is None for s in sched.slots):
                    # free slot + waiting head = page backpressure, the
                    # only reason FIFO admission stalls (DESIGN.md §15)
                    metrics.on_backpressure()
            admitted = []
            for slot, st in admits:
                if metrics is not None:
                    metrics.on_admit(st)
                self._req_event("admit", st.rid, slot=slot)
                Lb = bucket_for(self.prefill_buckets, len(st.prompt))
                with self._span("serve.prefill", bucket=Lb,
                                prompt_len=len(st.prompt)):
                    tok, lg = self._prefill(self.kv.table[slot:slot + 1],
                                            st.prompt, Lb)
                    tok = np.asarray(tok)      # force sync: first token
                self._emit(st, tok[0],
                           np.asarray(lg)[0] if self.capture_logits
                           else None)
                if metrics is not None:
                    metrics.on_first_token(st, Lb)
                self._req_event("first_token", st.rid)
                admitted.append(st.rid)

            active = sched.active_slots()
            if active:
                t0 = time.perf_counter()
                with self._span("serve.decode.prepare"):
                    toks = np.zeros((self.max_slots, 1), np.int32)
                    poss = np.zeros((self.max_slots,), np.int32)
                    for i in active:
                        st = sched.slots[i]
                        toks[i, 0] = st.out[-1]
                        poss[i] = st.pos
                    args = (jnp.asarray(self.kv.table), jnp.asarray(toks),
                            jnp.asarray(poss))
                with self._span("serve.decode", n_pes=len(active),
                                path=self.decode_path,
                                kv_pages=self._kv_pages(poss),
                                kv_kind=self.kv_kind):
                    tok, lg, self.pool, extra = self._djit(
                        self.params, self.pool, *args)
                    # force sync: step complete (the expert rows, when
                    # there are any, come back in the same transfer)
                    tok, extra = self._jax.device_get((tok, extra))
                    if extra:
                        self.last_expert_rows = extra[0]
                if metrics is not None:
                    metrics.on_decode_step(len(active),
                                           time.perf_counter() - t0,
                                           self.decode_path,
                                           self.last_expert_rows)
                with self._span("serve.emit"):
                    lg = np.asarray(lg) if self.capture_logits else None
                    for i in active:
                        st = sched.slots[i]
                        st.pos += 1
                        self._emit(st, tok[i],
                                   lg[i] if self.capture_logits else None)
        self.steps += 1
        if metrics is not None:
            metrics.sample_engine(self)
        return {"evicted": evicted, "admitted": admitted,
                "decoded": len(active)}

    def run(self, max_steps: int = 100_000) -> dict[int, np.ndarray]:
        """Drain queue and slots; returns {rid: generated tokens}."""
        for _ in range(max_steps):
            if self.scheduler.idle():
                break
            self.step()
        # final evict pass so the last finishers land in results
        for slot, st in self.scheduler.step_evict():
            self.results[st.rid] = np.asarray(st.out, np.int32)
            if self.metrics is not None:
                self.metrics.on_evict(st)
            self._req_event("evict", st.rid, n_tokens=len(st.out))
        return self.results
