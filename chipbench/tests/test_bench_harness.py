"""The harness finds every part of a cell by name, refuses what it cannot
measure, and BENCHMARK.json keeps to the form the harness reads."""
import hashlib
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *args],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=120)


def test_cpu_run_is_refused():
    p = _run("--workload", "qwen2-serve-chat", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_unknown_workload_is_refused():
    p = _run("--workload", "no-such-cell", "--seed", "1", "--seconds", "1")
    assert p.returncode != 0
    assert p.stdout == ""
    assert "unknown workload" in p.stderr


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("devs,why", [
    ([_Dev("tpu", "TPU v99")] * 4, "not in peaks.json"),
    ([_Dev("tpu", "TPU v5 lite")], "needs 4 chips"),
    ([_Dev("gpu", "H100")], "needs a TPU"),
])
def test_device_check_refuses(monkeypatch, devs, why):
    import jax

    monkeypatch.setattr(jax, "devices", lambda: devs)
    peaks = harness.load_json(harness.BENCH_DIR / "peaks.json")
    with pytest.raises(harness.Refused, match=why):
        harness.device_check(4, peaks)


def test_device_check_accepts_a_v5e_host(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "devices",
                        lambda: [_Dev("tpu", "TPU v5 lite")] * 4)
    peaks = harness.load_json(harness.BENCH_DIR / "peaks.json")
    assert harness.device_check(4, peaks) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}


def _digest(root: pathlib.Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_cell_is_found_by_name_without_editing_a_file(tmp_path):
    """A configuration, a mix, a limits file and a per-layer metric added
    as new files, and entries added to BENCHMARK.json, are all the harness
    needs for a new cell."""
    shutil.copytree(harness.BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = _digest(tmp_path / "chipbench")
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    b = tmp_path / "chipbench"
    conf = harness.load_json(b / "configs" / "qwen2-0.5b.json")
    conf["name"] = "other-model"
    (b / "configs" / "other-model.json").write_text(json.dumps(conf))
    mix = harness.load_json(b / "traffic" / "chat.json")
    mix["rate_per_s"] = 3.0
    (b / "traffic" / "slow-chat.json").write_text(json.dumps(mix))
    (b / "limits" / "other-slow-chat.json").write_text(
        json.dumps({"max_logit_gap": 0.5}))
    (b / "metrics" / "steps_seen.serve.py").write_text(
        "def read(r):\n    return float(r.counters['engine_steps'])\n")
    spec["configs"].append(dict(spec["configs"][0], name="other-model",
                                file="chipbench/configs/other-model.json"))
    spec["workloads"].append({"name": "other-slow-chat",
                              "config": "other-model",
                              "traffic": "slow-chat", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if "workloads" in m and "qwen2-serve-chat" in m["workloads"]:
            m["workloads"].append("other-slow-chat")
    spec["per_layer"].append({"name": "steps_seen.serve", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "engine host loop",
                              "moves": "itl_p95_ms",
                              "workloads": ["other-slow-chat"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.resolve(tmp_path, "other-slow-chat")
    assert cell.config["name"] == "other-model"
    assert cell.traffic["rate_per_s"] == 3.0
    assert cell.limits == {"max_logit_gap": 0.5}
    assert cell.driver == b / "drivers" / "serve.py" and cell.driver.exists()
    assert "steps_seen.serve" in [m["name"] for m in cell.per_layer]
    assert "ttft_p95_ms" in [m["name"] for m in cell.end_to_end]
    reading = harness.Reading(reduction=None, counters={"engine_steps": 7},
                              config=cell.config, peak={}, chips=1)
    assert harness.reader(cell, "steps_seen.serve").read(reading) == 7.0
    after = _digest(tmp_path / "chipbench")
    assert {k: v for k, v in after.items() if k in before} == before


def test_result_line_leaves_out_a_metric_with_nothing_to_read():
    """No program times in the trace: the two readers that need them
    return nothing and their metrics are left out of the line."""
    cell = harness.resolve(ROOT, "qwen2-serve-chat")
    red = type("R", (), dict(busy_s=0.9, window_s=1.0, collective_s=0.0,
                             exposed_collective_s=0.0, op_s={"fusion": 0.9},
                             module_s={}, idle_gaps=[("wait", 0.1)]))()
    out = harness.Outcome(attempted=3, failed=0, checks=[
        harness.Check("max_logit_gap", 0.05, 0.5, True)], end_to_end={},
        memory_peak_bytes=5, reduction=red,
        counters={"engine_steps": 4, "engine_step_s": 0.5,
                  "prefill_prompt_lens": [100], "decode_calls": 4,
                  "decode_rows": 40, "decode_context": 4000,
                  "window_s": 1.0})
    reading = harness.Reading(red, out.counters, cell.config,
                              {"bf16_flops_per_s": 197e12,
                               "hbm_bytes_per_s": 819e9}, 1)
    line = harness.result_line(cell, out, {"platform": "tpu"}, True, reading)
    assert set(line["metrics"]) == {"idle_share.serve",
                                    "engine_step_ms.serve"}
    assert line["metrics"]["engine_step_ms.serve"]["value"] == 125.0
    assert list(line)[-1] == "checks"
    assert line["checks"] == {"max_logit_gap": {"value": 0.05,
                                                "limit": 0.5}}
    assert line["device"]["busy_s"] == 0.9 and line["correct"] is True


def test_benchmark_json_keeps_its_form():
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    b = harness.BENCH_DIR
    used = {w["config"] for w in spec["workloads"]}
    assert used == {c["name"] for c in spec["configs"]}
    for c in spec["configs"]:
        assert (ROOT / c["file"]).exists() and c["file"].startswith(
            "chipbench/")
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 2)
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        cell = harness.resolve(ROOT, w["name"])
        assert cell.driver.exists()
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
    for m in spec["per_layer"]:
        assert (b / "metrics" / f"{m['name']}.py").exists()
        assert set(m["workloads"]) <= {w["name"] for w in spec["workloads"]}
    for name in names:
        assert NAME.match(name)
