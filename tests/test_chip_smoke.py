"""chip_smoke.py's phases at tiny sizes on the CPU, kernels interpreted.

The script itself refuses to run without a TPU; here each phase is called
directly with small shapes, so its path (pipeline spec -> run -> engine ->
processor) and its reference checks are exercised on every change. The
check that a compiled program holds a Pallas kernel only means something on
the chip, so here the program is only compiled.
"""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs.registry import get_arch

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = sys.modules["chip_smoke"] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # interpret mode compiles no tpu_custom_call: compile, do not inspect
    mod.assert_kernel_compiled = lambda name, jitted, *args: jitted.lower(*args).compile()
    return mod


def _tiny(smoke):
    return smoke.Sizes(n_angles=24, n_det=48, n=40, frames=3, mlem_iters=2,
                       points_per_msg=500, kmeans_messages=4,
                       arch=get_arch("smollm-135m").reduced(), requests=5,
                       prompt_len=32, gen_tokens=4, page_size=16,
                       train_seq_len=32, train_seqs=8)


@pytest.mark.parametrize("phase", ["lightsource", "kmeans", "serving"])
def test_one_chip_phase_runs_and_meets_its_tolerance(smoke, phase):
    out = getattr(smoke, f"{phase}_phase")(0, _tiny(smoke))
    assert out["phase"] == phase
    json.dumps(out)  # each phase's line is one JSON object
    if phase == "lightsource":
        for name in ("gridrec", "mlem"):
            assert out[name]["rel_l2_vs_ref"] <= smoke.TOMO_RTOL
            assert out[name]["batches"] == 3
    elif phase == "kmeans":
        assert out["messages"] == 4
        assert out["max_centroid_diff_vs_ref"] <= smoke.KMEANS_ATOL
    else:
        assert out["requests"] == 5
        assert out["max_first_decode_logit_rel_diff"] <= smoke.LOGITS_RTOL
        assert out["first_token_agree"] == 5


def test_four_chip_extension_on_virtual_devices(smoke, subproc):
    stdout = subproc(f"""
import json, sys
sys.path.insert(0, {str(SCRIPT.parent)!r})
import jax
import chip_smoke as smoke
from repro.configs.registry import get_arch

sz = smoke.Sizes(arch=get_arch("smollm-135m").reduced(), train_seq_len=32, train_seqs=8)
print(json.dumps(smoke.elastic_training_phase(0, sz, jax.devices()[:4])))
""", n_devices=4)
    out = json.loads(stdout.strip().splitlines()[-1])
    assert out["devices_before"] == [0]
    assert out["devices_after"] == [0, 1, 2, 3]
    assert out["mesh_after"] == {"data": 4, "model": 1}
    assert out["losses"][:3] == out["one_chip_losses"][:3]
    assert out["max_loss_rel_diff"] <= smoke.LOSS_RTOL


def test_refuses_to_run_without_a_tpu():
    res = subprocess.run(
        [sys.executable, str(SCRIPT)], capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=SCRIPT.parent)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert "no TPU" in res.stderr
