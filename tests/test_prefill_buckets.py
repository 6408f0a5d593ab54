"""Prefill bucket ladder of the serving engine (serve/engine.py).

The ladder is a pure function of `prompt_bucket` and `page_size`; each
admission prefills at the smallest bucket that holds its prompt.  The
engine tests hold a laddered engine to the single-bucket engine (every
prompt padded to `prompt_bucket`, the path the ladder replaces) on a tiny
GQA model and a tiny MLA+MoE model: same greedy tokens, logits to
rounding, no compile after construction, and `ServeMetrics` counting the
bucket positions the prefills ran."""
import dataclasses
import glob

import jax
import numpy as np
import pytest

from repro.serve import engine as engine_mod
from repro.serve.engine import PREFILL_LADDER, bucket_for, prefill_buckets


# ---------------------------------------------------------------------------
# the ladder: pure host
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("prompt_bucket,page_size",
                         [(16, 8), (32, 4), (100, 4), (128, 16), (128, 128)])
def test_ladder_is_one_bucket_up_to_128(prompt_bucket, page_size):
    assert prefill_buckets(prompt_bucket, page_size) == (prompt_bucket,)


@pytest.mark.parametrize("prompt_bucket,want", [
    (1024, (128, 256, 512, 768, 1024)),
    (2048, (128, 256, 512, 768, 1024, 1536, 2048)),
    (256, (128, 256)),
    (600, (128, 256, 512, 600)),
    (4096, PREFILL_LADDER + (4096,)),
])
def test_ladder_below_prompt_bucket_then_prompt_bucket(prompt_bucket, want):
    assert prefill_buckets(prompt_bucket, 16) == want


@pytest.mark.parametrize("prompt_bucket,page_size,max_seq", [
    (1024, 16, 2048), (2048, 16, 4096), (2048, 256, 2048), (1536, 512, 1536),
    (512, 64, 600)])
def test_ladder_buckets_are_whole_pages_within_max_seq(prompt_bucket,
                                                       page_size, max_seq):
    buckets = prefill_buckets(prompt_bucket, page_size)
    assert list(buckets) == sorted(set(buckets))
    assert buckets[-1] == prompt_bucket
    for b in buckets:
        assert b % page_size == 0 and b <= max_seq
    if page_size > 128:
        assert 128 not in buckets


@pytest.mark.parametrize("n,want", [
    (1, 128), (127, 128), (128, 128), (129, 256), (256, 256), (257, 512),
    (512, 512), (513, 768), (768, 768), (769, 1024), (1023, 1024),
    (1024, 1024), (1025, 1536), (1536, 1536), (1537, 2048), (2048, 2048)])
def test_bucket_for_takes_the_smallest_that_holds_the_prompt(n, want):
    assert bucket_for(prefill_buckets(2048, 16), n) == want


# ---------------------------------------------------------------------------
# the engine: laddered vs single-bucket
# ---------------------------------------------------------------------------

MAX_NEW = 4


def _gqa_engine(params=None, **kw):
    from repro.configs import smoke_config
    from repro.launch.mesh import make_mesh
    from repro.serve.engine import ServeEngine

    return ServeEngine(smoke_config("qwen2-0.5b"), make_mesh(1, 1),
                       params=params, max_slots=3, page_size=16, max_seq=544,
                       prompt_bucket=512, capture_logits=True, **kw)


def _mla_engine(params=None, **kw):
    from repro.configs import smoke_config
    from repro.launch.mesh import make_mesh
    from repro.serve.engine import ServeEngine

    cfg = smoke_config("deepseek-v3-671b")
    cfg = dataclasses.replace(cfg, mtp=False, moe=dataclasses.replace(
        cfg.moe, experts_held=4, experts_offset=2))
    return ServeEngine(cfg, make_mesh(1, 1), params=params, max_slots=3,
                       page_size=4, max_seq=264, prompt_bucket=256,
                       capture_logits=True, **kw)


# prompt lengths landing in every bucket, each bucket's own length among them
LENGTHS = {"gqa": (100, 128, 200, 256, 300, 512),
           "mla": (5, 128, 129, 256)}
MAKE = {"gqa": _gqa_engine, "mla": _mla_engine}


class Compiles:
    """Backend compiles recorded by `jax.monitoring` while open."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0

    def _on(self, event, duration, **kw):
        self.n += event == self.EVENT

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


def _serve(eng, lengths, seed=5):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, 128, size=n).astype(np.int32)
               for n in lengths]
    rids = [eng.submit(p, MAX_NEW) for p in prompts]
    eng.run()
    return rids


@pytest.fixture(scope="module", params=["gqa", "mla"])
def served(request):
    """(kind, laddered engine and rids, single-bucket engine and rids,
    compiles while the laddered engine served, prefill span args)."""
    from jax.profiler import ProfileData
    from repro.serve.metrics import ServeMetrics

    kind = request.param
    make, lengths = MAKE[kind], LENGTHS[kind]
    eng = make(metrics=ServeMetrics())
    assert len(eng.prefill_buckets) >= 2
    # the decode program compiles on its first step, as the benchmark's
    # warm-up request does it; the prefills compiled at construction
    eng.submit(np.ones(1, np.int32), 2)
    eng.run()
    tdir = request.getfixturevalue("tmp_path_factory").mktemp(kind)
    with Compiles() as compiles, jax.profiler.trace(str(tdir)):
        rids = _serve(eng, lengths)
    path, = glob.glob(str(tdir / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    spans = [dict(e.stats)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name == "serve.prefill"]
    mp = pytest.MonkeyPatch()
    mp.setattr(engine_mod, "prefill_buckets", lambda pb, ps: (pb,))
    try:
        ref = make(params=eng.params)
    finally:
        mp.undo()
    assert ref.prefill_buckets == (ref.prompt_bucket,)
    qids = _serve(ref, lengths)
    return kind, (eng, rids), (ref, qids), compiles.n, spans


def test_every_bucket_is_served(served):
    kind, (eng, _), _, _, spans = served
    want = [bucket_for(eng.prefill_buckets, n) for n in LENGTHS[kind]]
    assert sorted(set(want)) == list(eng.prefill_buckets)
    assert [int(s["bucket"]) for s in spans] == want
    assert [int(s["prompt_len"]) for s in spans] == list(LENGTHS[kind])


def test_laddered_tokens_match_single_bucket_engine(served):
    """Rows past the prompt are causally masked for its own rows, so the
    bucket moves the logits by matmul rounding at most."""
    _, (eng, rids), (ref, qids), _, _ = served
    for r, q in zip(rids, qids):
        np.testing.assert_array_equal(eng.results[r], ref.results[q])
        assert len(eng.logits_trace[r]) == MAX_NEW
        for a, b in zip(eng.logits_trace[r], ref.logits_trace[q]):
            np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3)


def test_no_compile_after_construction(served):
    assert served[3] == 0


def test_metrics_count_bucket_positions(served):
    kind, (eng, _), _, _, _ = served
    m = eng.metrics
    lengths = (1,) + LENGTHS[kind]            # the warm-up request first
    assert m.prefill_runs.value == len(lengths)
    assert m.prompt_positions.value == sum(lengths)
    assert m.prefill_positions.value == sum(
        bucket_for(eng.prefill_buckets, n) for n in lengths)
    doc = m.to_json()["metrics"]
    assert doc["serve.prefill_positions"]["value"] == \
        m.prefill_positions.value
