"""chip_smoke.py at CPU size.

The script refuses any platform but a TPU, so its serving path is run
here through the function it calls, on the reduced deepseek-7b config.
"""

import importlib.util
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_path_serves_reduced_model_on_cpu():
    smoke = _load_smoke()
    cfg = smoke.smoke_config().reduced()
    report = smoke.run_smoke(cfg, batch=2, prompt_len=32, new_tokens=4,
                             requests=2, log=lambda line: None)
    assert len(report["outs"]) == 2
    assert all(out["logits_finite"] for out in report["outs"])
    assert report["param_bytes"] > 0
    # every catalog kernel of the serve path attached, compiled ahead of
    # time, and its base point passed the gate against its ref.py
    assert sorted(name for name, *_ in report["base_gate"]) == [
        "attention", "decode_attention", "matmul", "rmsnorm"]
    assert all(ok for _, _, ok, _ in report["base_gate"])
    assert {name for name, *_ in report["variants"]} == {
        "attention", "decode_attention", "matmul", "rmsnorm"}
    # the CPU runs Pallas in interpret mode: no Mosaic kernel in the HLO,
    # which is exactly (and only) what the chip check rejects here
    bad = smoke.failures(report, batch=2, new_tokens=4)
    assert bad
    assert all("without tpu_custom_call" in line for line in bad), bad


def test_smoke_refuses_a_host_without_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr
