"""chipbench/flops.py against XLA's own count of the program's compiled
train step, on the CPU at a small size."""
import dataclasses

import pytest

from chipbench import flops, harness, sut

SIZE = dict(hidden_size=256, intermediate_size=1024, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, vocab_size=2048)
BATCH, SEQ = 2, 64


def _conf():
    conf = harness.load_json(harness.BENCH_DIR / "configs" /
                             "qwen2-0.5b.json")
    conf.update(SIZE)
    return conf


def _compiled_flops(conf, remat: str) -> float:
    import jax
    import jax.numpy as jnp
    from repro.launch import build
    from repro.launch.mesh import make_mesh

    # probe_unroll unrolls every scan, so cost_analysis counts each layer
    cfg = dataclasses.replace(sut.model_config(conf, remat=remat),
                              probe_unroll=True)
    mesh = make_mesh(1, 1)
    with jax.set_mesh(mesh):
        wrap, (shapes, _), (oshapes, _), _ = build.make_train_step(
            cfg, mesh, "shmem")
        batch = {k: jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32)
                 for k in ("tokens", "targets")}
        return jax.jit(wrap(batch)).lower(shapes, oshapes, batch) \
            .compile().cost_analysis()["flops"]


def test_train_flops_match_the_compiled_step():
    conf = _conf()
    model = flops.train_flops_per_sequence(conf, SEQ) * BATCH
    plain = _compiled_flops(conf, "none")
    # without remat the step computes the model's work plus the masked
    # upper half of each attention square, norms, softmax and AdamW:
    # a few percent at these sizes
    assert model <= plain <= 1.10 * model
    # remat="full" recomputes every layer's forward pass: at most a third
    # of the model's training FLOPs (the head is not recomputed)
    full = _compiled_flops(conf, "full")
    assert plain < full <= plain + model / 3 * 1.10


def test_decode_and_prefill_counts():
    conf = _conf()
    n = flops.matmul_params(conf)
    assert flops.prefill_flops(conf, 1) == 2 * n + flops.attention_flops(
        conf, 1)
    # decoding token t attends over t + 1 keys: the same total as a
    # prefill over the whole sequence
    assert flops.decode_flops(conf, sum(range(1, 9)), 8) == \
        flops.prefill_flops(conf, 8)
    assert flops.kv_bytes_per_token(conf, 2) == 2 * 2 * 2 * 64 * 2
    assert flops.decode_param_bytes(conf, 4) > 4 * n


@pytest.mark.parametrize("name,params", [("qwen2-0.5b", 494.03e6)])
def test_published_parameter_count(name, params):
    """Qwen2-0.5B has 494M parameters (Qwen2 technical report, Table 1)."""
    conf = harness.load_json(harness.BENCH_DIR / "configs" / f"{name}.json")
    total = flops.decode_param_bytes(conf, 1)
    assert total == pytest.approx(params, rel=2e-3)
