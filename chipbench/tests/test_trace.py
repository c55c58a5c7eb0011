"""trace.py on a small trace recorded on a TPU v5e (testdata/tiny.xplane.pb:
three runs of a jitted step, a matmul fusion and a Pallas call, profiled),
against numbers worked out by hand from that file's events."""
import os

import pytest

from chipbench import readers, trace

TINY = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "testdata", "tiny.xplane.pb")


def test_union_length():
    assert trace.union_length([]) == 0
    assert trace.union_length([(0, 10), (5, 12), (20, 25)]) == 17
    assert trace.union_length([(20, 25), (0, 10), (10, 11)]) == 16
    assert trace.union_length([(0, 100), (10, 20)]) == 100


def test_op_kind():
    assert trace.op_kind("%f.1 = bf16[16,513]{1,0:T(8,128)} fusion(%a), "
                         "kind=kLoop") == ("fusion", "bf16[16,513]")
    assert trace.op_kind("%w = (s32[], f32[2]{0}) while((s32[], f32[2]) %t)"
                         ) == ("while", "(tuple)")


@pytest.fixture(scope="module")
def tiny():
    s = trace.summarize_file(TINY)
    assert s is not None
    return s


def test_busy_time_is_the_union_of_ops(tiny):
    # run 1: 13 + 3 + 2 + 1032 + 3 + 451 ns, disjoint = 1504
    # run 2: 13 + 2 (touching) + 3 + 1043 + 3 + 452 = 1516
    # run 3: 13 + 2 + 3 + 812 + 3 + 453 = 1286
    assert tiny.n_devices == 1
    assert tiny.busy_s == pytest.approx((1504 + 1516 + 1286) * 1e-9,
                                        rel=1e-9)


def test_executable_time(tiny):
    n, sec = tiny.module_time("tiny_step")
    assert n == 3
    assert sec == pytest.approx((1518 + 1530 + 1300) * 1e-9, rel=1e-9)
    assert tiny.module_time("burst") == (0, 0)


def test_ops_carry_their_executable_and_kind(tiny):
    assert len(tiny.ops) == 18
    assert all(op.module.startswith("jit_tiny_step") for op in tiny.ops)
    kernels = [op for op in tiny.ops if readers.kernel_shapes(op.name)]
    assert [op.dur_ns for op in kernels] == [451, 452, 453]
    assert kernels[0].label == "custom-call bf16[256,256]"
    outs, ins = readers.kernel_shapes(kernels[0].name)
    assert outs == [("bf16", (256, 256))]
    assert ins == [("bf16", (256, 256)), ("bf16", (256, 256))]
    top = tiny.breakdown()["device_ops"][0]
    assert top[0] == "jit_tiny_step:fusion bf16[256,256]"
    assert top[1] == pytest.approx((1032 + 1043 + 812) * 1e-9, rel=1e-9)


def test_longest_idle_gap(tiny):
    # end of run 1's last op (44801232 + 451) to run 2's first (45755851)
    length, what = tiny.gaps[0]
    assert length == pytest.approx((45755851 - 44801683) * 1e-9, rel=1e-9)
    assert isinstance(what, str) and what
    assert tiny.gaps[1][0] == pytest.approx((46405644 - 45757373) * 1e-9,
                                            rel=1e-9)
