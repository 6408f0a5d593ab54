"""Tiny stand-ins for the benchmark's configurations and mixes, so the
drivers run end to end on the CPU in seconds (widths cut only here)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import harness  # noqa: E402

TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, vocab_size=512,
            eos_token_id=3)


def tiny_conf() -> dict:
    conf = harness.load_json(harness.BENCH_DIR / "configs" /
                             "qwen2-0.5b.json")
    conf.update(TINY)
    conf["serve"] = {"mesh": [1, 1], "max_slots": 8, "page_size": 4,
                     "max_seq": 64, "prompt_bucket": 32}
    return conf


def tiny_chat() -> dict:
    mix = harness.load_json(harness.BENCH_DIR / "traffic" / "chat.json")
    mix.update(rate_per_s=10.0, warmup_s=1.0, drain_limit_s=20.0)
    mix["prompt_len"] = {"median": 8, "sigma": 1.0, "min": 2, "max": 32}
    mix["output_len"] = {"median": 6, "sigma": 0.8, "min": 2, "max": 32}
    mix["sample"] = {"random_requests": 2, "min_served_tokens": 20}
    return mix


def context(cell_name: str, conf: dict, mix: dict, seconds: float,
            seed: int = 2**31 + 7, limits: dict | None = None
            ) -> harness.Context:
    """A driver's context for `cell_name` at a tiny size; the cell's own
    limits file unless `limits` is given."""
    import time

    if limits is None:
        limits = harness.load_json(harness.BENCH_DIR / "limits" /
                                   f"{cell_name}.json")
    cell = harness.Cell(name=cell_name, chips=1, config=conf, traffic=mix,
                        driver=harness.BENCH_DIR / "drivers" /
                        f"{mix['driver']}.py", limits=limits,
                        end_to_end=[], per_layer=[], root=harness.ROOT)
    return harness.Context(cell=cell, seed=seed, seconds=seconds,
                           trace=False, t_start=time.perf_counter(),
                           peak={})


@pytest.fixture
def serve_ctx():
    return context("qwen2-serve-chat", tiny_conf(), tiny_chat(), 2.0)
