"""chip_smoke.py's phases at smoke size on the CPU, and its refusal to
run anywhere but on a TPU."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.configs import smoke_config  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


def test_main_refuses_cpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert "'cpu'" in out.err
    assert out.out == ""


def test_train_phase_smoke():
    res = chip_smoke.train_phase(smoke_config("qwen2-0.5b"),
                                 make_mesh(1, 1), seq_len=32, batch=4,
                                 steps=3, log=lambda _: None)
    assert len(res["losses"]) == 3
    assert np.isfinite(res["losses"]).all()
    assert res["compile_s"] > 0 and res["step_s"] > 0


def test_serve_phase_smoke():
    res = chip_smoke.serve_phase(
        smoke_config("qwen2-0.5b"), make_mesh(1, 1), n_req=4,
        prompt_len=16, new_tokens=6, slots=4, page_size=8, max_seq=64,
        prompt_bucket=16, log=lambda _: None)
    assert res["logit_rel_err"] <= chip_smoke.LOGIT_RTOL
    assert res["argmax_exact"] >= 1


def test_serve_phase_catches_wrong_reference(monkeypatch):
    # logits from another prompt must fail the comparison
    real = chip_smoke.reference_logits
    monkeypatch.setattr(chip_smoke, "reference_logits",
                        lambda cfg, mesh, params, seq: real(
                            cfg, mesh, params, seq[::-1].copy()))
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.serve_phase(
            smoke_config("qwen2-0.5b"), make_mesh(1, 1), n_req=1,
            prompt_len=16, new_tokens=4, slots=1, page_size=8, max_seq=32,
            prompt_bucket=16, log=lambda _: None)


FOUR = textwrap.dedent("""
    import sys
    sys.path.insert(0, {root!r})
    import chip_smoke
    from repro.configs import smoke_config
    chip_smoke.four_chip_phase(smoke_config("qwen2-0.5b"), seq_len=32,
                               batch=8, steps=3, sizes=(4096, 65536))
    print("FOUR_OK")
""")


def test_four_chip_phase_on_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", FOUR.format(root=ROOT)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "FOUR_OK" in r.stdout
    assert "allgather equal to all_gather" in r.stdout
