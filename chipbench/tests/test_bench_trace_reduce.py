"""The trace reduction: interval arithmetic on made-up events, and the
whole reduction on small traces recorded on a v5e chip
(chipbench/tools/record_trace.py) and kept under tests/data."""
import pathlib

import pytest

from chipbench import trace_reduce as tr

DATA = pathlib.Path(__file__).parent / "data"


def test_union_minus_and_total():
    merged = tr.union([(0, 10, "a"), (5, 12, "b"), (20, 30, "c")])
    assert merged == [(0, 12), (20, 30)]
    assert tr.total(merged) == 22
    assert tr.minus(merged, tr.union([(8, 25, "x")])) == 8 + 5


def test_collective_names():
    for name in ("all-reduce.3", "collective-permute-start.1",
                 "collective-permute-done", "all-gather-start.2",
                 "reduce-scatter.7", "all-to-all.1"):
        assert tr.is_collective(name)
    for name in ("fusion.12", "convolution.3", "copy-start", "reduce.4"):
        assert not tr.is_collective(name)


def test_leaves_drop_enclosing_events():
    evs = [(0, 100, "while.1"), (0, 10, "fusion.1"), (20, 30, "fusion.2"),
           (90, 100, "copy.1"), (120, 130, "fusion.3")]
    assert [n for _, _, n in tr.leaves(evs)] == ["fusion.1", "fusion.2",
                                                  "copy.1", "fusion.3"]


def test_op_names():
    assert tr.op_name("%all-reduce-start.3 = f32[8]{0} all-reduce-start("
                      "f32[8]{0} %x), to_apply=%add") == "all-reduce-start.3"
    assert tr.op_name("fusion.1") == "fusion.1"


def test_reduce_two_devices():
    """Device 0: a loop 0-60 whose body computes 0-40 and runs a
    collective 30-60 (exposed 40-60), an async all-gather 60-70 around a
    copy 62-69 (exposed 60-62, 69-70), idle 70-100.  Device 1: compute
    0-100.  Window 0-100 ns from the host's window annotation, which also
    clips an op that runs past it."""
    trace = tr.Trace(
        ops={"/device:TPU:0": [(0, 60, "while.1"), (0, 40, "fusion.1"),
                               (30, 60, "all-reduce.1"),
                               (60, 62, "all-gather-start.1"),
                               (62, 69, "copy.1"),
                               (69, 70, "all-gather-done.1")],
             "/device:TPU:1": [(0, 100, "fusion.1"), (100, 150, "late")]},
        async_ops={"/device:TPU:0": [(60, 70, "all-gather-start.1")]},
        modules={"/device:TPU:0": [(0, 60, "jit_step")]},
        host=[(0, 100, tr.WINDOW_ANNOTATION), (60, 100, "host.wait"),
              (80, 90, "host.inner")])
    red = tr.reduce(trace)
    assert red.n_devices == 2
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s == pytest.approx((70 + 100) / 2 * 1e-9)
    assert red.collective_s == pytest.approx((30 + 10) / 2 * 1e-9)
    assert red.exposed_collective_s == pytest.approx((20 + 3) / 2 * 1e-9)
    assert red.module_s == {"jit_step": pytest.approx(30e-9)}
    assert red.op_s["fusion.1"] == pytest.approx(70e-9)
    assert "late" not in red.op_s and "while.1" not in red.op_s
    assert red.idle_gaps == [("host.inner", pytest.approx(30e-9))]
    assert tr.top_ops(red, 1) == [["fusion.1", pytest.approx(70e-9)]]


def test_reduce_without_device_ops_fails():
    with pytest.raises(ValueError):
        tr.reduce(tr.Trace(ops={}, modules={}, host=[]))


def test_recorded_chip_trace():
    """Three steps of a matmul on one v5e chip inside the window
    annotation (record_trace.py): HLO names, op and program times."""
    trace = tr.load(str(DATA / "tpu_v5e_1chip.xplane.pb"))
    red = tr.reduce(trace)
    assert red.n_devices == 1
    assert 0 < red.busy_s <= red.window_s
    assert 0 < sum(red.op_s.values()) <= red.busy_s * 1.001
    assert set(red.op_s) <= {"copy-start", "copy-done",
                             "convert_reduce_fusion"}
    assert red.collective_s == 0 == red.exposed_collective_s
    assert list(red.module_s) == ["jit_step(11255881560334412391)"]
    assert trace.async_ops["/device:TPU:0"]
