"""scopes.py, the reduction of a device trace to device time per layer scope,
on two traces recorded on a TPU v5e: ``train_scopes.xplane.pb`` (two steps of
wt103-262m-moe cut to 2 layers, with the program's layer scopes and kernel
names; ``record_train_scopes.py``) and ``tiny.xplane.pb`` (a step that opens
no scope)."""
import os
import shutil
import types

import pytest

from chipbench import harness, scopes, trace

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "testdata")
TRAIN = os.path.join(DATA, "train_scopes.xplane.pb")
TINY = os.path.join(DATA, "tiny.xplane.pb")
METRICS = {"attention_ms.train": "attention", "moe_ms.train": "moe",
           "optimizer_ms.train": "optimizer"}


@pytest.fixture(scope="module")
def train():
    s = scopes.summarize_file(TRAIN)
    assert s is not None
    return s


def test_components_take_transforms_off():
    assert scopes.components(
        "jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
        "moe/cvmm_dw_streamed/pallas_call") == [
        "jit(train_step)", "", "while", "body", "closed_call", "checkpoint",
        "moe", "cvmm_dw_streamed", "pallas_call"]
    assert scopes.components("jit(train_step)/transpose(jvp(embed))/mul:") \
        == ["jit(train_step)", "embed", "mul"]
    assert scopes.components("jit(f)/jit(loss)/add") == [
        "jit(f)", "jit(loss)", "add"]


def test_recorded_trace_is_small():
    assert os.path.getsize(TRAIN) < 1 << 20


def test_each_layer_gets_device_time(train):
    assert set(train.layers) == set(scopes.LAYERS)
    assert all(v > 0 for v in train.layers.values())


def test_per_step_division(train):
    assert train.steps == 2
    for layer, seconds in train.layers.items():
        assert train.layer_ms(layer) == pytest.approx(1e3 * seconds / 2,
                                                      rel=1e-12)
    assert train.layer_ms("no_such_layer") is None


def test_scopes_sum_to_the_module_time_less_uncovered(train):
    assert sum(train.layers.values()) + train.uncovered_s == pytest.approx(
        train.ops_s, rel=1e-12)
    # the leaf ops fill the step's runs, less the device's own gaps in them
    assert 0.95 * train.module_s < train.ops_s <= train.module_s
    # the same leaf op time as trace.py reads through jax's ProfileData,
    # which rounds each event down to whole nanoseconds
    t = trace.summarize_file(TRAIN)
    leaf = sum(op.dur_ns for op in t.ops if "train_step" in op.module) * 1e-9
    assert leaf == pytest.approx(train.ops_s, rel=1e-3)
    assert t.module_time("train_step") == (2, pytest.approx(train.module_s,
                                                             rel=1e-6))


def test_container_ops_are_excluded(train, monkeypatch):
    monkeypatch.setattr(trace, "CONTAINERS", ())
    with_containers = scopes.summarize_file(TRAIN)
    # a while op spans the layers' scan: counting it would count its body
    # twice
    from jax.profiler import ProfileData
    loops = 0.0
    for plane in ProfileData.from_file(TRAIN).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    loops += sum(ev.duration_ns for ev in line.events
                                 if trace.op_kind(ev.name)[0] == "while")
    assert loops > 0
    assert with_containers.ops_s - train.ops_s == pytest.approx(
        loops * 1e-9, rel=1e-3)


def test_every_pallas_call_is_named(train):
    assert {"cvmm_fused_w1", "cvmm_fused_w2", "cvmm_dw_streamed",
            "cvmm_fwd"} <= set(train.parts)
    assert train.unnamed_kernels_s == 0
    assert train.parts["rel_shift"] < train.layers["attention"]
    kernels = sum(train.parts[k] for k in scopes.KERNELS if k in train.parts)
    assert kernels < train.layers["moe"]


def test_a_step_without_scopes_is_all_uncovered():
    s = scopes.summarize_file(TINY, "tiny_step")
    # three runs of the jitted step; 18 leaf ops, their duration_ps summed
    assert s.steps == 3
    assert s.layers == {} and s.parts == {}
    assert s.uncovered_s == pytest.approx(4317266e-12, rel=1e-12)
    assert s.module_s == pytest.approx((1518672 + 1530156 + 1300000)
                                       * 1e-12, rel=1e-12)
    assert s.uncovered_share() == 1.0
    assert s.layer_ms("attention") is None
    assert scopes.summarize_file(TINY, "train_step") is None


def _reader_run(directory):
    run = types.SimpleNamespace(tracer=types.SimpleNamespace(
        directory=str(directory)))
    return run, types.SimpleNamespace(facts={})


def _readers():
    return {name: harness.load_module(os.path.join(
        harness.HERE, "metrics", name + ".py")) for name in METRICS}


def _as_trace_dir(tmp_path, xplane):
    d = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    d.mkdir(parents=True)
    shutil.copy(xplane, d / "host.xplane.pb")
    return tmp_path


def test_readers_read_their_layer_and_parse_once(tmp_path, train,
                                                 monkeypatch):
    calls = []
    parse = scopes.summarize_file
    monkeypatch.setattr(scopes, "summarize_file",
                        lambda *a, **k: calls.append(a) or parse(*a, **k))
    run, outcome = _reader_run(_as_trace_dir(tmp_path, TRAIN))
    for name, reader in _readers().items():
        assert reader.read(run, outcome) == pytest.approx(
            train.layer_ms(METRICS[name]), rel=1e-12)
    assert len(calls) == 1


def test_readers_return_none_without_a_trace(tmp_path):
    run, outcome = _reader_run(tmp_path / "never_traced")
    for reader in _readers().values():
        assert reader.read(run, outcome) is None


def test_readers_return_none_where_no_scope_ran(tmp_path):
    # a trace of a program that opens no scope (and runs no train_step)
    run, outcome = _reader_run(_as_trace_dir(tmp_path, TINY))
    for reader in _readers().values():
        assert reader.read(run, outcome) is None


def test_a_reader_returns_none_when_its_scope_is_absent(tmp_path,
                                                        monkeypatch):
    # as on a program that opens the other scopes but not this one: the
    # reader gives no number rather than 0
    monkeypatch.setattr(scopes, "LAYERS", ("embed", "moe", "block"))
    run, outcome = _reader_run(_as_trace_dir(tmp_path, TRAIN))
    readers = _readers()
    assert readers["attention_ms.train"].read(run, outcome) is None
    assert readers["optimizer_ms.train"].read(run, outcome) is None
    assert readers["moe_ms.train"].read(run, outcome) > 0
