"""chip_smoke.py's phases on a CPU: smoke_config widths, tiny traffic, the
Pallas kernels run by the TPU interpreter.  Only the TPU-platform check of
``main`` is bypassed (the phases are called directly); the compiled-HLO
custom-call check needs a real TPU compile and is skipped by the phases
themselves when interpreting."""
import importlib.util
import os
import subprocess
import sys

import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # its dataclass resolves annotations here
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load()


def _traffic(cs):
    return cs.Traffic(requests=2, prompt_len=24, new_tokens=3,
                      prefill_chunk=8)


def test_chip_smoke_phases_interpret(smoke):
    traffic = _traffic(smoke)
    cfg = smoke.model_config("none", "masked", False, smoke=True)
    prompts = smoke.make_prompts(cfg.vocab_size, traffic)
    with pltpu.force_tpu_interpret_mode():
        ref, ref8 = smoke.phase_reference(prompts, traffic, smoke=True,
                                          interpret=True)
        assert ref.shape == ref8.shape == (2, cfg.vocab_size)
        got = smoke.phase_compressed("none", ref, prompts, traffic,
                                     smoke=True, interpret=True)
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)
        smoke.phase_compressed("int8", ref8, prompts, traffic, smoke=True,
                               interpret=True)


def test_chip_smoke_compare_rejects_wrong_logits(smoke):
    ref = np.random.default_rng(0).standard_normal((2, 64)).astype(np.float32)
    assert smoke.compare("same", ref, ref, 1e-6) == 0.0
    with pytest.raises(AssertionError):
        smoke.compare("shuffled", ref[:, ::-1].copy(), ref, 0.05)


def test_chip_smoke_refuses_cpu():
    """Off a TPU the script fails before any phase and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, env=env, timeout=300)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "needs a TPU" in res.stderr
