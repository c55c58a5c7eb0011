"""named_scopes.py, device time under scopes that lie inside a layer, on the
traces test_scopes.py reads. The recorded wt103 steps predate the
``mla_latent`` and ``router`` scopes, so the reading is checked on scopes
they hold (``rel_shift``, a kernel's name) against scopes.py's own parts."""
import os
import types

import pytest

from chipbench import named_scopes, scopes
from chipbench.tests.test_scopes import TINY, TRAIN


def test_reads_what_scopes_py_reads(monkeypatch):
    monkeypatch.setattr(named_scopes, "NAMES", ("rel_shift", "cvmm_fused_w1"))
    got = named_scopes.summarize_file(TRAIN)
    want = scopes.summarize_file(TRAIN)
    assert got["steps"] == want.steps
    for name in named_scopes.NAMES:
        assert got[name] > 0
        assert got[name] == pytest.approx(want.parts[name], rel=1e-12)


def test_scopes_the_program_lacks_read_none(tmp_path):
    got = named_scopes.summarize_file(TRAIN)
    assert got["steps"] > 0
    assert all(got[name] == 0 for name in named_scopes.NAMES)
    run = types.SimpleNamespace(tracer=types.SimpleNamespace(
        directory=str(tmp_path)))
    trace_dir = tmp_path / "plugins" / "profile" / "r"
    trace_dir.mkdir(parents=True)
    (trace_dir / "host.xplane.pb").write_bytes(open(TRAIN, "rb").read())
    outcome = types.SimpleNamespace(facts={})
    for name in named_scopes.NAMES:
        assert named_scopes.scope_ms(run, outcome, name) is None


def test_no_trace_and_no_step_read_none(tmp_path):
    assert named_scopes.summarize_file(TINY) is None
    run = types.SimpleNamespace(tracer=types.SimpleNamespace(
        directory=str(tmp_path)))
    outcome = types.SimpleNamespace(facts={})
    assert named_scopes.scope_ms(run, outcome, "router") is None
