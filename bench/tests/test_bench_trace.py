"""Trace reduction: on synthetic events, and on a trace recorded on a TPU
v5e (two cycles of two replica-exchange members, one training step each,
under a host span ``probe.cycles``; ``data/train_trace.xplane.pb.gz``)."""
import gzip
from pathlib import Path

import pytest

from lib import trace

DATA = Path(__file__).resolve().parent / "data" / "train_trace.xplane.pb.gz"


def test_union_and_clip():
    assert trace.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [[0, 2.5],
                                                               [3, 4]]
    assert trace.clip([(0, 5), (6, 7)], 1, 6.5) == [(1, 5), (6, 6.5)]


def test_self_time_of_nested_ops():
    ev = [(0.0, 10.0, "while"), (1.0, 3.0, "a"), (4.0, 9.0, "b"),
          (5.0, 6.0, "c"), (11.0, 12.0, "d")]
    assert trace.self_times(ev) == [3.0, 2.0, 4.0, 1.0, 1.0]


def test_opcode_and_name():
    t = ("%fusion.263 = (f32[30689,2304]{1,0:T(8,128)}, f32[2]{0}) "
         "fusion(f32[30689,2304]{1,0:T(8,128)} %p), kind=kLoop")
    assert trace.opcode(t) == "fusion"
    assert trace.op_name(t) == "fusion:f32[30689,2304]"
    t = ("%custom-call.3 = bf16[144,1,512,64]{3,2,1,0} custom-call("
         "bf16[144,1,512,64]{3,2,1,0} %a), custom_call_target=\"tpu\"")
    assert trace.opcode(t) == "custom-call"
    assert trace.program("jit_train_step(1803761)") == "jit_train_step"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(gzip.decompress(DATA.read_bytes()))
    return trace.Trace(str(path))


def test_recorded_trace_busy_idle(recorded):
    window = recorded.span("probe.cycles")
    assert window is not None
    r = trace.reduce(recorded, window, 1,
                     lambda n: n.startswith("PjitFunction("))
    # busy time by hand: union of the XLA Ops intervals inside the window
    ops = recorded.devices["/device:TPU:0"]["ops"]
    w0, w1 = window
    cuts = sorted((max(s, w0), min(e, w1)) for s, e, _ in ops
                  if e > w0 and s < w1)
    busy, end = 0.0, w0
    for s, e in cuts:
        if e > end:
            busy += e - max(s, end)
            end = e
    assert r["busy_s"] == pytest.approx(busy, rel=1e-9)
    assert r["window_s"] == pytest.approx(w1 - w0)
    assert 0.0 < r["idle_share"] < 0.5
    assert r["busy_s"] + sum(v for _, v in r["idle_gaps"]) == \
        pytest.approx(r["window_s"], rel=1e-9)
    # four training steps ran, each a whole run of jit_train_step
    assert r["program_runs"] == {"jit_train_step": 4}
    self_total = sum(v for _, v in r["device_ops"])
    assert 0.9 * busy < self_total <= busy * (1 + 1e-9) or \
        len(r["device_ops"]) == 10
    assert all(name.startswith("jit_train_step/")
               for name, _ in r["device_ops"])


def test_too_few_chips_in_trace(recorded):
    with pytest.raises(ValueError, match="TPU planes"):
        trace.reduce(recorded, recorded.span("probe.cycles"), 4,
                     lambda n: False)
