"""The reduction from a trace's normalised events to busy time, idle gaps
named by host span, and device time inside spans."""
import gzip
import json
from pathlib import Path

import pytest

from chipbench import trace as T

DATA = Path(__file__).parent / "data"


def _hand_trace():
    # host: a server step holding an admission step, a wait, a server
    # step holding a decode step; device: ops inside the engine steps
    host = [["bench.step", 0, 100], ["engine.step#0", 10, 90],
            ["bench.wait", 100, 150],
            ["bench.step", 150, 260], ["engine.step#1", 160, 250]]
    dev = [[20, 40, "fusion.1"], [50, 60, "fusion.2"],
           [170, 230, "fusion.3"], [232, 240, "argmax"]]
    mods = [[20, 60, "jit_prefill"], [170, 230, "jit_decode"],
            [232, 240, "jit_argmax"]]
    return {"host": host, "devices": {"/device:TPU:0": dev},
            "modules": {"/device:TPU:0": mods}}


def test_short_op_names():
    assert T._short("%fusion.12 = bf16[8] fusion(%a), kind=kLoop") == \
        "fusion.12"
    assert T.is_chip("/device:TPU:3")
    assert not T.is_chip("/device:CUSTOM:Megascale Trace")
    assert not T.is_chip("/host:CPU")


def test_union_busy_and_gaps():
    ivs = [[0, 10], [5, 20], [30, 40]]
    assert T.union(ivs, 0, 100) == [(0, 20), (30, 40)]
    assert T.busy(ivs, 10, 35) == 10 + 5
    assert T.gaps(ivs, 0, 50) == [(20, 30), (40, 50)]


def test_reduce_by_hand():
    kinds = {0: "engine.admit", 1: "engine.decode"}
    red = T.reduce(_hand_trace(), kinds)
    # the window starts at the first device event, ends with the host's
    assert red["window_ns"] == 260 - 20
    assert red["busy_ns"]["/device:TPU:0"] == 20 + 10 + 60 + 8
    assert red["device_ops"] == [["jit_decode", pytest.approx(60e-9)],
                                 ["jit_prefill", pytest.approx(40e-9)],
                                 ["jit_argmax", pytest.approx(8e-9)]]
    names = {n for n, _ in red["idle_gaps"]}
    # the longest gap is the wait; the others fall in the admission's
    # and the decode's spans and in the server step around them
    assert red["idle_gaps"][0] == ["bench.wait", pytest.approx(110e-9)]
    assert {"engine.admit", "engine.decode", "bench.route"} <= names
    assert red["steps"] == {0: (10, 90), 1: (160, 250)}
    assert T.spans_device_ns(_hand_trace(), "/device:TPU:0",
                             [(10, 90), (160, 250)]) == [30, 68]


def test_host_label_innermost():
    host = sorted(_hand_trace()["host"], key=lambda h: h[1])
    assert T.host_label(host, 50, {0: "engine.admit"}) == "engine.admit"
    assert T.host_label(host, 5) == "bench.route"
    assert T.host_label(host, 120) == "bench.wait"
    assert T.host_label(host, 400) == "bench.other"


RECORDED = DATA / "trace-yi6b-chat.json.gz"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_chip_trace():
    """A short slice of a traced yi6b-chat window on one v5e, kept as
    recorded (normalised): the reduction reads a busy share between 0 and
    1, device time inside every engine step, and names every gap."""
    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)
    kinds = {int(k): v for k, v in rec["kinds"].items()}
    tr = rec["trace"]
    red = T.reduce(tr, kinds)
    planes = list(tr["devices"])
    assert planes == ["/device:TPU:0"]
    assert {"engine.admit", "engine.decode"} <= set(kinds.values())
    frac = red["busy_ns"][planes[0]] / red["window_ns"]
    assert 0.0 < frac < 1.0
    assert rec["expect"]["busy_frac"] == pytest.approx(frac, rel=1e-9)
    spans = list(red["steps"].values())
    dev = T.spans_device_ns(tr, planes[0], spans)
    assert all(d > 0 for d in dev)
    assert sum(dev) <= red["busy_ns"][planes[0]] + 1
    assert {n for n, _ in red["idle_gaps"]} <= {
        "engine.admit", "engine.decode", "bench.route", "bench.wait",
        "bench.stamp", "bench.other"}
    # the decode program is the anonymous jitted lambda, and it takes
    # most of the slice's device time
    assert red["device_ops"][0][0] == "jit__lambda"
