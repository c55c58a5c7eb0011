"""The launcher's host spans: with ``--trace-dir`` the three steps after the
compile step are profiled, each inside a ``train`` step annotation holding
the spans ``train.batch``, ``train.step``, ``train.readback`` and
``train.checkpoint``; without it the profiler never starts."""
from __future__ import annotations

import glob
import os

import jax
import pytest

from repro.launch import train

ARGS = ["--arch", "wt103-47m-moe", "--reduced", "--batch", "2", "--seq", "16",
        "--ckpt-every", "1", "--log-every", "1"]
CHILDREN = ("train.batch", "train.step", "train.readback", "train.checkpoint")


def _host_events(path):
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                            dict(ev.stats) if ev.name == "train" else {})
                           for ev in line.events)
    return out


def test_trace_dir_profiles_three_steps_after_compile(tmp_path):
    trace_dir = tmp_path / "trace"
    train.run(ARGS + ["--steps", "5", "--ckpt-dir", str(tmp_path / "ck"),
                      "--trace-dir", str(trace_dir)])
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(files) == 1
    events = _host_events(files[0])
    steps = [e for e in events if e[0] == "train"]
    assert sorted(e[3]["step_num"] for e in steps) == [1, 2, 3]
    for child in CHILDREN:
        spans = [e for e in events if e[0] == child]
        assert len(spans) == 3, child
        # each child span lies inside one step annotation
        assert all(any(s[1] <= c[1] and c[2] <= s[2] for s in steps)
                   for c in spans), child


def test_no_trace_without_the_option(tmp_path, monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("the profiler started without --trace-dir")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    monkeypatch.chdir(tmp_path)
    train.run(ARGS + ["--steps", "3", "--ckpt-dir", str(tmp_path / "ck")])
    assert sorted(os.listdir(tmp_path)) == ["ck"]


@pytest.mark.parametrize("steps", [1, 2])
def test_trace_stops_when_the_run_ends_first(tmp_path, steps):
    trace_dir = tmp_path / "trace"
    train.run(ARGS + ["--steps", str(steps), "--ckpt-dir",
                      str(tmp_path / "ck"), "--trace-dir", str(trace_dir)])
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    traced = sorted(e[3]["step_num"] for f in files for e in _host_events(f)
                    if e[0] == "train")
    assert traced == list(range(1, steps))
