"""CPU checks of ``chip_smoke.py``: its serving comparison, the float32
reference it compares against, and its refusal to run without a TPU."""
import dataclasses
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import build  # noqa: E402
from repro.models.reference import reference_logits  # noqa: E402


def _rows(n=4, vocab=1000, seed=0):
    return np.random.default_rng(seed).standard_normal((n, vocab))


def test_compare_served_passes_on_matching_logits():
    ref = _rows()
    served = ref + 1e-3 * _rows(seed=1)
    stats = chip_smoke.compare_served_logits(served, ref,
                                             served.argmax(axis=1))
    assert stats["max_err"] < chip_smoke.LOGIT_TOL
    assert stats["max_gap"] <= chip_smoke.GAP_TOL


def test_compare_served_fails_when_token_far_below_reference_max():
    ref = _rows()
    tokens = ref.argmax(axis=1)
    scale = np.sqrt(np.mean(ref[2] ** 2))
    # Position 2 emits a token whose reference logit sits 0.5 scale units
    # below the reference maximum (GAP_TOL is 0.24).
    low = int(np.argmin(np.abs(ref[2] - (ref[2].max() - 0.5 * scale))))
    tokens[2] = low
    served = ref.copy()
    served[2, low] = ref[2].max() + 1e-3      # the served argmax
    with pytest.raises(AssertionError, match="position 2: reference gap"):
        chip_smoke.compare_served_logits(served, ref, tokens)


def test_compare_served_fails_on_logit_error():
    ref = _rows()
    served = ref.copy()
    served[1, 7] += 0.5 * np.sqrt(np.mean(ref[1] ** 2))
    with pytest.raises(AssertionError, match="position 1: logit error"):
        chip_smoke.compare_served_logits(served, ref, served.argmax(axis=1))


def test_compare_served_rejects_mismatched_shapes():
    with pytest.raises(ValueError):
        chip_smoke.compare_served_logits(_rows(3), _rows(4), np.zeros(4))


def _cfg(arch, dtype, **kw):
    small = dict(n_layers=2, d_model=256, n_heads=4, n_kv_heads=2,
                 head_dim=64, d_ff=1024, vocab_size=512, dtype=dtype)
    small.update(kw)
    return dataclasses.replace(get_config(arch), **small)


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "qwen3-1.7b"])
def test_reference_matches_float32_model(arch):
    cfg = _cfg(arch, "float32")
    model = build(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0,
                                cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        logits, _ = model.forward_logits(params, tokens)
    ref = reference_logits(params, cfg, tokens[0])
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_serving_tolerance_passes_bf16_and_fails_float8_weights():
    """The bound admits the configured bf16 model and refuses the same
    weights rounded to float8."""
    cfg = _cfg("internlm2-1.8b", "bfloat16")
    model = build(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 40), 0,
                                cfg.vocab_size)
    ref = np.asarray(reference_logits(params, cfg, tokens[0]))[-1:]

    def served(p):
        _, logits = model.prefill(p, tokens, 48)
        row = np.asarray(logits.astype(jnp.float32))
        return row, row.argmax(axis=1)

    row, tok = served(params)
    chip_smoke.compare_served_logits(row, ref, tok)
    low = jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), params)
    row, tok = served(low)
    with pytest.raises(AssertionError, match="logit error"):
        chip_smoke.compare_served_logits(row, ref, tok, gap_tol=np.inf)


def _run(script_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=script_dir,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_chip_smoke_refuses_without_tpu():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_chip_smoke_refuses_outside_the_repository(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
