"""The plain reference against the program at a small size on the CPU:
the full forward pass, the training loss and gradient, and the serving
engine's tokens and logits.  With the program computing in float32 the
two agree to float32 rounding; in the program's bfloat16 they agree to
bfloat16 rounding."""
import dataclasses

import numpy as np
import pytest

from chipbench import sut, weights
from chipbench.reference import decoder, readings
from chipbench.tests.conftest import tiny_conf


def _variants():
    qwen = tiny_conf()
    # untied head, no QKV bias, a sliding window shorter than the sequence
    llama = dict(tiny_conf(), tie_word_embeddings=False, qkv_bias=False,
                 use_sliding_window=True, sliding_window=8,
                 rope_theta=10000.0)
    return [("qwen2-like", qwen), ("windowed-untied", llama)]


def _program(conf, **kw):
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_mesh

    cfg = dataclasses.replace(sut.model_config(conf, remat="none"), **kw)
    mesh = make_mesh(1, 1)
    with jax.set_mesh(mesh):
        params, _ = sut.make_params(conf, cfg, mesh, 3, norm_std=0.1,
                                    bias_std=0.1)
    return cfg, mesh, params


def _tokens(conf, b=2, s=24, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, conf["vocab_size"], (b, s + 1), dtype=np.int32)


def _ref_weights(conf):
    import jax

    return jax.jit(lambda k: weights.make(conf, k, norm_std=0.1,
                                          bias_std=0.1))(weights.seed_key(3))


@pytest.mark.parametrize("name,conf", _variants())
def test_forward_logits_match_the_program(name, conf):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.launch import build
    from repro.models import layers as L
    from repro.models import transformer
    from repro.parallel.comm import Comm

    cfg, mesh, params = _program(conf, dtype=jnp.float32)
    toks = _tokens(conf)[:, :-1]
    _, _, pspecs = build.make_init_fn(cfg, mesh)

    def fwd(p, t):
        comm = Comm(build.axis_spec(mesh), "shmem")
        h, _ = transformer.forward(comm, cfg, p, t)
        return L.lm_logits(comm, cfg, p["embed"], h)

    with jax.set_mesh(mesh), jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(build.shard_mapped(
            fwd, mesh, (pspecs, P()), P(None, None, "model")))(params, toks))
    with jax.default_matmul_precision("highest"):
        w = _ref_weights(conf)
        want = np.asarray(decoder.logits(conf, w, decoder.hidden(conf, w,
                                                                 toks)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("name,conf", _variants())
def test_train_loss_and_gradient_match_the_program(name, conf):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.launch import build
    from repro.models import transformer
    from repro.parallel.comm import Comm

    cfg, mesh, params = _program(conf, dtype=jnp.float32)
    rows = _tokens(conf)
    batch = {"tokens": rows[:, :-1], "targets": rows[:, 1:]}
    _, _, pspecs = build.make_init_fn(cfg, mesh)

    def loss(p, b):
        return transformer.train_loss(Comm(build.axis_spec(mesh), "shmem"),
                                      cfg, p, b)

    with jax.set_mesh(mesh), jax.default_matmul_precision("highest"):
        lo, g = jax.jit(build.shard_mapped(
            jax.value_and_grad(loss), mesh, (pspecs, P()),
            (P(), pspecs)))(params, batch)
    g = sut.reference_names(g)
    with jax.default_matmul_precision("highest"):
        w = _ref_weights(conf)
        rlo, rg = jax.value_and_grad(decoder.loss, argnums=1)(
            conf, w, batch["tokens"], batch["targets"])
    assert float(lo) == pytest.approx(float(rlo), rel=1e-5)
    assert set(g) == set(rg)
    for k in rg:
        np.testing.assert_allclose(np.asarray(g[k]), np.asarray(rg[k]),
                                   rtol=2e-3, atol=2e-6, err_msg=k)


def test_engine_tokens_and_logits_match_the_reference():
    """The engine decodes in bfloat16 through its paged cache; its served
    tokens are the reference's best or within bfloat16 rounding of it,
    and its logits are within a few percent of the reference's scale."""
    import jax
    from repro.launch.mesh import make_mesh
    from repro.serve.engine import ServeEngine

    conf = tiny_conf()
    cfg, mesh, params = _program(conf)
    s = conf["serve"]
    eng = ServeEngine(cfg, mesh, params=params, max_slots=s["max_slots"],
                      page_size=s["page_size"], max_seq=s["max_seq"],
                      prompt_bucket=s["prompt_bucket"], capture_logits=True)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, conf["vocab_size"], n, dtype=np.int32)
               for n in (5, 17, 30)]
    rids = [eng.submit(p, 12) for p in prompts]
    eng.run()
    w = _ref_weights(conf)
    seqs = [(p, eng.results[r]) for p, r in zip(prompts, rids)]
    gaps, _ = readings.served_gaps(conf, w, seqs, pad_to=s["max_seq"])
    assert len(gaps) == 36 and float(np.max(gaps)) < 0.05
    for (p, served), r in zip(seqs, rids):
        seq = np.concatenate([p, served[:-1]])[None]
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(decoder.logits(conf, w, decoder.hidden(
                conf, w, seq)))[0, len(p) - 1:]
        got = np.stack(eng.logits_trace[r])
        assert np.abs(got - ref).max() <= 0.05 * np.abs(ref).max()
