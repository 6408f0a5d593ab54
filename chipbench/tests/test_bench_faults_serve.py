"""The serving cell's run, driven on the CPU at a tiny size past the
harness's look for a chip, comes out correct, and comes out not correct
with the timed path broken underneath: a decode step that leaves the KV
pages as they were, and a token altered where it is sampled.  The fp8
control, put in the program's place, fails the same comparison."""
import numpy as np
import pytest

from chipbench import harness


def _run(ctx):
    out = harness.load_module(ctx.cell.driver).run(ctx)
    return {c.name: (c.value, c.ok) for c in out.checks}


def test_sound_run_is_correct(serve_ctx):
    checks = _run(serve_ctx)
    assert all(ok for _, ok in checks.values()), checks


def test_decode_that_keeps_its_state_is_caught(serve_ctx, monkeypatch):
    from repro.models import transformer

    real = transformer.decode_step_paged

    def stale(comm, cfg, params, pool, *a, **kw):
        logits, _ = real(comm, cfg, params, pool, *a, **kw)
        return logits, pool
    monkeypatch.setattr(transformer, "decode_step_paged", stale)
    checks = _run(serve_ctx)
    assert not checks["max_logit_gap"][1], checks


def test_altered_token_is_caught(serve_ctx, monkeypatch):
    from repro.serve import step as sstep

    real = sstep.sample_greedy
    vocab = serve_ctx.cell.config["vocab_size"]
    monkeypatch.setattr(sstep, "sample_greedy",
                        lambda comm, lg: (real(comm, lg) + 1) % vocab)
    checks = _run(serve_ctx)
    assert not checks["max_logit_gap"][1], checks


def test_fp8_control_is_caught():
    from chipbench.tests.conftest import context, tiny_chat, tiny_conf

    # fp8 rounding grows with width and depth: at 64 wide and 2 layers it
    # nears the limit, at 128 and 4 it reads about twice that
    conf = tiny_conf()
    conf.update(hidden_size=128, intermediate_size=256, num_hidden_layers=4,
                vocab_size=2048)
    ctx = context("qwen2-serve-chat", conf, tiny_chat(), 2.0)
    drv = harness.load_module(ctx.cell.driver)
    real, program = drv.reference_gaps, []

    def control_in_place(conf, seed, seqs, control=False):
        gaps, control_gaps = real(conf, seed, seqs, control=True)
        program.append(float(np.max(gaps)))
        return control_gaps, None
    drv.reference_gaps = control_in_place
    checks = {c.name: (c.value, c.ok) for c in drv.run(ctx).checks}
    assert program[0] <= ctx.cell.limits["max_logit_gap"], program
    assert not checks["max_logit_gap"][1], checks


@pytest.mark.parametrize("seed", [0, 5, 2**33 + 1])
def test_traffic_is_the_same_work_for_every_seed(seed):
    from chipbench import generator
    from chipbench.tests.conftest import tiny_chat

    mix = tiny_chat()
    base = generator.chat_requests(mix, 1, 512, 3.0)
    reqs = generator.chat_requests(mix, seed, 512, 3.0)
    for phase in (False, True):
        for size in (lambda r: len(r.prompt), lambda r: r.max_new):
            a = sorted(size(r) for r in base if r.in_window == phase)
            b = sorted(size(r) for r in reqs if r.in_window == phase)
            assert a == b
    assert max(r.arrival_s for r in reqs) == pytest.approx(
        mix["warmup_s"] + 3.0)
    ids = np.concatenate([r.prompt for r in reqs])
    assert ids.min() >= 1 and ids.max() < 512
