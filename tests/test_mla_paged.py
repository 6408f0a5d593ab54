"""DeepSeek-V3's serving pieces: the published routing (sigmoid scores,
correction bias, group limit, scaled gates), YaRN on the MLA rope lanes,
the paged-decode kernel's latent mode against its gather reference, and
the MoE+MLA model through `ServeEngine` on both decode paths."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.kernels import paged_decode as pd
from repro.models import layers as L
from repro.models import transformer
from repro.models.config import MoEConfig


def test_routing_hand_case_with_group_limit():
    """One token over 8 experts in 4 groups of 2, the best 2 groups kept:
    expert 0 has the highest s + b but its group (0.9 + 0.1) loses to
    groups 2 (0.7 + 0.55) and 1 (0.6 + 0.58), so the top 2 are experts 4
    and 2; gates use s, not s + b, normalised and scaled by 2.5."""
    s = np.array([0.8, 0.3, 0.5, 0.45, 0.4, 0.35, 0.6, 0.1])
    sel = np.array([0.9, 0.1, 0.6, 0.58, 0.7, 0.55, 0.2, 0.2])
    cfg = dataclasses.replace(smoke_config("deepseek-v3-671b"), moe=MoEConfig(
        n_experts=8, top_k=2, d_ff=4, score_func="sigmoid",
        correction_bias=True, n_group=4, topk_group=2, routed_scale=2.5))
    router = np.zeros((8, 8), np.float32)
    router[0] = np.log(s / (1 - s))
    p = {"router": jnp.asarray(router),
         "router_bias": jnp.asarray(sel - s, jnp.float32)}
    x = jnp.asarray(np.eye(8, dtype=np.float32)[:1])
    scores, experts, gates = L.route(cfg, p, x)
    np.testing.assert_allclose(np.asarray(scores[0]), s, rtol=1e-6)
    assert np.asarray(experts[0]).tolist() == [4, 2]
    np.testing.assert_allclose(np.asarray(gates[0]),
                               2.5 * np.array([0.4, 0.5]) / 0.9, rtol=1e-5)
    # without the group limit expert 0 wins
    flat = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                            n_group=1))
    assert np.asarray(L.route(flat, p, x)[1][0]).tolist() == [0, 4]


def test_yarn_frequencies_and_scale_follow_the_formula():
    """DeepSeek-V3's YaRN (factor 40, beta 32/1, 4096 original
    positions, theta 10000) on 64 rope lanes: the ramp runs from lane 10
    to lane 23; softmax scale mscale(40, 1)^2 / sqrt(192)."""
    cfg = get_config("deepseek-v3-671b")
    j = np.arange(32)
    inv_e = 1.0 / 10000.0 ** (2 * j / 64)
    r = np.clip((j - 10) / (23 - 10), 0, 1)
    want = inv_e * (1 - r) + inv_e / 40 * r
    np.testing.assert_allclose(np.asarray(L.mla_rope_freqs(cfg)), want,
                               rtol=1e-6)
    mscale = 0.1 * math.log(40) + 1
    assert abs(mscale ** 2 - 1.8739) < 1e-4
    assert math.isclose(L.mla_softmax_scale(cfg),
                        mscale ** 2 / math.sqrt(192), rel_tol=1e-12)


R, ROPE, H, LAYERS, LAYER = 96, 32, 6, 2, 1      # one 128-lane tile


@pytest.mark.parametrize("page_size,max_seq,pages_per_block",
                         [(16, 64, 2), (8, 48, 4)], ids=["ps16", "ps8"])
def test_latent_kernel_matches_gather_reference(page_size, max_seq,
                                                pages_per_block):
    """The kernel's latent mode (interpret) against `latent_attend_gather`,
    one slot per interesting position on shuffled pages, two empty
    slots."""
    rng = np.random.default_rng(0)
    pos = [0, page_size - 1, page_size, page_size + 3, max_seq - 1]
    active, B = len(pos), len(pos) + 2
    max_pages = max_seq // page_size
    n_pages = 1 + active * max_pages
    table = np.zeros((B, max_pages), np.int32)
    table[:active] = 1 + rng.permutation(n_pages - 1).reshape(active, -1)
    C = R + ROPE
    pool = jnp.asarray(rng.normal(size=(LAYERS, n_pages, page_size, C)),
                       jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(B, H, C)) / math.sqrt(C), jnp.float32)
    new = jnp.asarray(rng.normal(size=(B, C)), jnp.bfloat16)
    positions = jnp.asarray(pos + [0, 0], jnp.int32)
    got = pd.paged_decode_attention(
        q, new[:, None], None, pool, None, jnp.int32(LAYER),
        jnp.asarray(table), positions, page_size=page_size,
        pages_per_block=pages_per_block, interpret=True, v_lanes=R)
    want = L.latent_attend_gather(q, new, pool, jnp.int32(LAYER),
                                  jnp.asarray(table), positions, R)
    assert got.shape == (B, H, R)
    # both f32 over the same bf16 rows; block-wise online softmax vs one
    # pass differ by f32 rounding
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def _engine_cfg():
    cfg = smoke_config("deepseek-v3-671b")
    return dataclasses.replace(cfg, mtp=False, moe=dataclasses.replace(
        cfg.moe, experts_held=4, experts_offset=2))


def _serve(prompts, max_new=10, **kw):
    from repro.launch.mesh import make_mesh
    from repro.serve.engine import ServeEngine

    eng = ServeEngine(_engine_cfg(), make_mesh(1, 1), max_slots=4,
                      page_size=4, max_seq=64, prompt_bucket=32, **kw)
    rids = [eng.submit(p, max_new) for p in prompts]
    res = eng.run()
    return [res[r] for r in rids], eng


@pytest.fixture(scope="module")
def mla_prompts():
    rng = np.random.default_rng(1)
    return [rng.integers(1, 128, size=n).astype(np.int32)
            for n in (5, 17, 9, 30, 2)]


def test_mla_moe_is_a_paged_family():
    cfg = _engine_cfg()
    assert "moe" in transformer.paged_families()
    assert transformer.paged_supported(cfg)
    assert not transformer.paged_supported(smoke_config(
        "granite-moe-3b-a800m"))
    pool = jax.eval_shape(lambda: transformer.init_kv_pool(cfg, 1, 9, 4))
    assert jax.tree.map(lambda a: a.shape, pool) == {
        "latent": (cfg.n_layers, 9, 4, 128)}     # 16 + 8 lanes, one tile


def test_mla_engine_kernel_path_tokens_match_gather_path(mla_prompts,
                                                         on_kernel):
    got, eng = _serve(mla_prompts)
    assert eng.decode_path == "kernel" and eng.kv_kind == "latent"
    rows = eng.last_expert_rows
    assert rows.shape == (2, 4) and rows.dtype == np.int32
    import repro.models.layers as layers
    on_tpu = layers._on_tpu
    layers._on_tpu = lambda: False
    try:
        want, eng2 = _serve(mla_prompts)
    finally:
        layers._on_tpu = on_tpu
    assert eng2.decode_path == "gather"
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_mla_engine_batched_equals_alone(mla_prompts):
    """A request's greedy tokens do not depend on its batch mates: every
    op, the expert share's grouped matmuls included, is per row."""
    batched, _ = _serve(mla_prompts)
    for p, b in zip(mla_prompts, batched):
        alone, _ = _serve([p])
        np.testing.assert_array_equal(alone[0], b)


def test_expert_rows_reach_serve_metrics(mla_prompts):
    from repro.serve.metrics import ServeMetrics

    m = ServeMetrics()
    _, eng = _serve(mla_prompts, metrics=m)
    assert m.expert_rows.count == m.decode_steps.value > 0
    assert m.expert_rows_max.max >= m.expert_rows.mean > 0


def test_launcher_serves_mla_moe_on_the_paged_engine(monkeypatch):
    from repro.launch import serve as launch

    def refuse(*a, **kw):
        raise AssertionError("MoE+MLA fell back to the dense-cache loop")
    monkeypatch.setattr(launch, "_legacy_decode_loop", refuse)
    gen = launch.main(["--arch", "deepseek-v3-671b", "--smoke", "--tokens",
                       "4", "--batch", "2"])
    assert gen.shape == (2, 4)
