"""``bench/trace.py``'s reduction, on timelines checked by hand and on a
small trace recorded on the chip."""

import pathlib

import pytest

from bench import trace

W = (0, 100)


def _dev(ops):
    return trace.Device("/device:TPU:0", W, ops)


def test_busy_and_idle_by_hand():
    # a loop [10, 40) whose body runs a [15, 25) and b [25, 35), then a again
    d = _dev([(10, 40, "while.1"), (15, 25, "a"), (25, 35, "b"),
              (60, 70, "a")])
    # union [10, 40) + [60, 70) = 40 ns busy of a 100 ns window
    assert d.busy() == [(10, 40), (60, 70)]
    assert d.busy_s() == pytest.approx(40e-9)
    assert d.gaps() == [(0, 10), (40, 60), (70, 100)]
    # self time: the loop keeps 30 - 10 - 10 ns of its own
    assert d.op_seconds() == pytest.approx(
        {"while.1": 10e-9, "a": 20e-9, "b": 10e-9})


def test_exposed_permute_by_hand():
    d = _dev([(0, 100, "while.0"), (0, 50, "fusion.1"),
              (50, 60, "collective-permute-done.2"),
              (70, 80, "collective-permute-start.3"), (80, 90, "fusion.4")])
    # the loop contains everything and is no compute of its own; the two
    # permute ops run while no other innermost op does: 10 + 10 ns
    assert d.exposed_s("collective-permute") == pytest.approx(20e-9)


def test_gap_labels_take_the_innermost_host_span():
    red = trace.Reduced([_dev([(0, 10, "a"), (50, 100, "b")])],
                        host=[(0, 100, "engine/step"),
                              (20, 45, "engine/decode")])
    # the one gap [10, 50) has its middle (30) inside engine/decode
    bd = red.breakdown()
    assert bd["idle_gaps"] == [["engine/decode", pytest.approx(40e-9)]]
    assert bd["device_ops"][0][0] == "b"
    assert red.idle_shares() == [pytest.approx(0.4)]


FIXTURE = pathlib.Path(__file__).parent / "data"


def test_recorded_chip_trace():
    """A 19 KB trace recorded on a v5e, against sums made by hand from
    its 18 device operations (``data/expected.json``)."""
    import json

    want = json.loads((FIXTURE / "expected.json").read_text())
    red = trace.reduce_file(FIXTURE / "chip_mini.xplane.pb",
                            want["devices"])
    d = red.devices[0]
    assert d.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert d.busy_s() == pytest.approx(want["busy_s"], rel=1e-9)
    ops = d.op_seconds()
    for name, secs in want["ops"].items():
        assert ops[name] == pytest.approx(secs, rel=1e-9)
    assert red.breakdown()["idle_gaps"][0][0] == want["top_gap_label"]
