"""chip_smoke.py's phases at reduced sizes on CPU, kernels in interpret mode.

The script itself refuses to run without a TPU; these tests call its phase
functions directly so that its control flow and reference comparisons are
exercised on every change. The TPU-only checks (device, rung,
``tpu_custom_call``) live in the script's ``main``.
"""
import importlib.util
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import xl_rel
from repro.models import attention

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_train_phase_reduced(smoke):
    run, ref = smoke.train_phase(smoke.TRAIN_ARCH, reduced=True, steps=6,
                                 batch=2, seq=32,
                                 impl="pallas_fused_interpret")
    assert len(run.history) == 6 and len(ref.history) == 1
    assert run.state is None and ref.state is None      # device state dropped
    assert run.compiled is not None and run.compile_s > 0


def test_serve_phase_reduced(smoke):
    cfg = smoke.serve_config(reduced=True, impl="pallas_fused_interpret")
    out = smoke.serve_phase(cfg, n_requests=4, prompt_len=(8, 40), max_new=6,
                            max_batch=4, page_size=8, prefill_chunk=16,
                            n_decode_checked=3)
    assert sorted(out["outs"]) == [0, 1, 2, 3]
    assert out["err"].shape == (4, 4)
    # a real comparison: bf16 kernels against ragged_dot never agree exactly,
    # and the padded vocabulary's -1e30 logits must not reach the norms
    assert np.all(np.isfinite(out["err"])) and out["err"].max() > 0
    assert out["counters"]["rebuilds"] > 0
    assert 0.0 <= out["agree"] <= 1.0


XL_REL_SMALL = ((2, 3, 41, 13, 29), (1, 2, 16, 8, 8), (1, 2, 16, 1, 9))


def test_xl_rel_phase_reduced(smoke):
    fwd, vjp = smoke.xl_rel_phase(XL_REL_SMALL, interpret=True)
    assert 0 <= fwd <= smoke.XL_REL_FWD_RTOL
    assert 0 <= vjp <= smoke.XL_REL_VJP_RTOL


def _unshifted(qv, r, interpret=False):
    return jnp.einsum("bqhd,khd->bhqk", qv, r)


@jax.custom_vjp
def _unrolled_backward(qv, r):
    return attention._rel_shift(_unshifted(qv, r))


_unrolled_backward.defvjp(
    lambda qv, r: (_unrolled_backward(qv, r), (qv, r)),
    # the cotangent taken as the unshifted product's: never rolled back
    lambda res, g: jax.vjp(_unshifted, *res)[1](g))


@pytest.mark.parametrize("fault", [
    _unshifted,
    lambda qv, r, interpret=False: _unrolled_backward(qv, r),
], ids=["forward_not_shifted", "backward_not_rolled"])
def test_xl_rel_phase_catches_a_planted_fault(smoke, monkeypatch, fault):
    monkeypatch.setattr(xl_rel, "xl_rel_bd", fault)
    with pytest.raises(smoke.SmokeError):
        smoke.xl_rel_phase(XL_REL_SMALL[:1], interpret=True)


def test_held_share_phase_reduced(smoke):
    n_live, grid, worst, t_live, t_all = smoke.held_share_phase(
        n_tokens=300, d_model=128, expert_size=128, n_router=8, held=2, k=2,
        interpret=True)
    assert 0 < n_live < grid // 2
    assert 0 < worst <= smoke.HELD_SHARE_RTOL


def test_check_raises_on_failure(smoke):
    smoke.check(True, "holds")
    with pytest.raises(smoke.SmokeError):
        smoke.check(False, "does not hold")


def test_smoke_refuses_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, cwd=tmp_path, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout


def test_smoke_alone_fails(tmp_path):
    """Copied into a directory without the repo, the script cannot import
    the system and must not report success."""
    alone = tmp_path / "chip_smoke.py"
    alone.write_text(open(os.path.join(REPO, "chip_smoke.py")).read())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, str(alone)], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "No module named 'repro'" in r.stderr
    assert '"ok"' not in r.stdout
