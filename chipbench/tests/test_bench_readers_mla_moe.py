"""The per-layer readers of the DeepSeek-V3 cell on a made-up reading:
each reads what it should from the counters and the per-program scope
times, and none reads anything from a run that lacks them (a parent
commit's program)."""
import pytest

from chipbench import harness, phase_reduce
from chipbench import flops_mla_moe as F

CELL = "deepseek-v3-serve-chat2k"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _reading(with_scopes=True):
    conf = harness.load_json(harness.BENCH_DIR / "configs" /
                             "deepseek-v3-ep32.json")
    red = phase_reduce.Reduction(
        window_s=8.0, n_devices=1, busy_s=6.0, collective_s=0.0,
        exposed_collective_s=0.0, op_s={},
        module_s={"jit_prefill_fn": 2.0, "jit_decode_fn": 4.0},
        idle_gaps=[],
        module_op_s={("jit_decode_fn", "fusion.1"): 1.0,
                     ("jit_decode_fn", "custom.2"): 0.5,
                     ("jit_decode_fn", "fusion.3"): 2.5,
                     ("jit_prefill_fn", "fusion.1"): 2.0})
    counters = {"prefill_prompt_lens": [700, 900], "decode_calls": 200,
                "decode_rows": 4000, "decode_context": 4_000_000,
                "expert_rows": 3200, "expert_row_steps": 200,
                "expert_rows_shape": (4, 8)}
    if with_scopes:
        counters["op_scopes"] = {"decode_fn": {
            "fusion.1": "moe_experts", "custom.2": "attend",
            "fusion.3": "mlp"}}
    return harness.Reading(red, counters, conf, PEAK, 1)


def _read(name, r):
    cell = harness.resolve(harness.ROOT, CELL)
    assert name in [m["name"] for m in cell.per_layer]
    return harness.reader(cell, name).read(r)


def test_readers_read_counters_and_scopes():
    r = _reading()
    assert _read("expert_rows.serve_mla_moe", r) == 3200 / (200 * 4 * 8)
    assert _read("moe_share.serve_mla_moe", r) == pytest.approx(25.0)
    keys = 4_000_000
    want = max(keys * F.latent_bytes_per_key(r.config) / 819e9,
               keys * F.attention_pair_flops(r.config, True) / 197e12)
    assert _read("latent_attend_roofline.serve_mla_moe", r) == \
        pytest.approx(100 * want / 0.5)
    mfu = _read("mfu.serve_mla_moe", r)
    assert 0 < mfu < 100


def test_readers_fall_silent_without_the_program_counters():
    r = _reading(with_scopes=False)
    for key in ("expert_rows", "expert_row_steps", "expert_rows_shape"):
        del r.counters[key]
    for name in ("moe_share.serve_mla_moe", "expert_rows.serve_mla_moe",
                 "latent_attend_roofline.serve_mla_moe",
                 "mfu.serve_mla_moe"):
        assert _read(name, r) is None, name


def test_device_and_host_loop_readers_read_the_deepseek_cell():
    r = _reading()
    r.counters.update(engine_steps=100, engine_step_s=4.0)
    assert _read("idle_share.serve", r) == pytest.approx(25.0)
    assert _read("engine_step_ms.serve", r) == pytest.approx(40.0)


def test_live_slots_reads_the_saturated_cells_batch():
    """The saturated qwen2 cell's batch: live slots per decode step, from
    the counters the serving driver has always written; none without a
    decode."""
    cell = harness.resolve(harness.ROOT, "qwen2-serve-chat-sat")
    read = harness.reader(cell, "live_slots.serve_sat").read
    r = _reading()
    r.counters.update(decode_calls=400, decode_rows=48000)
    assert read(r) == 120.0
    r.counters.update(decode_calls=0, decode_rows=0)
    assert read(r) is None


def test_flops_count_the_published_widths():
    """Decode at the ridge: an absorbed key costs 2*128*(2*512+64) FLOPs
    a layer against 576 bf16 lanes; ~242 FLOP/B."""
    conf = _reading().config
    per_key = F.attention_pair_flops(conf, True) / 7
    assert per_key == 2 * 128 * (2 * 512 + 64)
    assert F.latent_bytes_per_key(conf) / 7 == 1152
    assert 8.0e9 < F.decode_weight_bytes(conf) < 9.0e9
