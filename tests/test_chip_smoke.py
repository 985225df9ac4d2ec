"""CPU rehearsal of ``chip_smoke.py``: its phase functions at llama_tiny size
(interpret-mode kernels, virtual devices) so paths, arguments and control
flow stay guarded, and its refusal to produce a result without a TPU. What
these tests see is never a device result."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture
def tiny_cfg():
    from paddle_tpu.models.llama import llama_tiny
    return llama_tiny(max_position_embeddings=256)


def test_serve_phase_rehearsal(tiny_cfg):
    out = chip_smoke.serve_phase(
        tiny_cfg, prompt_lens=(20, 45), new_tokens=4,
        engine_kwargs=dict(prefill_chunk_tokens=16, token_budget=32,
                           max_len=128))
    # the default scheduler is the ragged one: every engine tick is the
    # q-block kernel, never an XLA tier
    assert set(out["engine"]) == {"ragged q-block (Pallas)"}
    assert out["oracle"].get("paged decode (Pallas)", 0) > 0
    assert out["max_regret"] <= chip_smoke.SERVE_REGRET_TOL


def test_train_phase_rehearsal(tiny_cfg):
    out = chip_smoke.train_phase(tiny_cfg, batch=2, seq=128, steps=3)
    assert len(out["losses"]) == 3 and out["losses"][-1] < out["losses"][0]


def test_multichip_phase_rehearsal(tiny_cfg):
    """sharding 2 x mp 2 (the remaining virtual devices fold into dp)."""
    out = chip_smoke.multichip_phase(tiny_cfg, batch=4, seq=128, steps=3)
    assert out["rel"] <= chip_smoke.MULTICHIP_RTOL


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py")],
                          env=env, cwd=REPO, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr
