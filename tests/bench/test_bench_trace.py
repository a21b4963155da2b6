"""The trace reducer: busy time, idle gaps by host span, top device ops."""

import json
from pathlib import Path

import benchutil  # noqa: F401  (puts the benchmark on sys.path)
import pytest
from chipbench import devtrace

DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def ev(plane, name, start, dur, line=None, long_name=None):
    e = {"plane": plane, "line": line or ("XLA Ops" if "device" in plane
                                          else "python"),
         "name": name, "start_ns": float(start), "dur_ns": float(dur)}
    if long_name:
        e["long_name"] = long_name
    return e


def test_busy_union_gaps_and_ops():
    events = [
        ev(HOST, devtrace.WINDOW, 100, 1000),
        ev(HOST, "loss_and_grads", 100, 600),
        ev(HOST, "optim_update", 700, 300),
        # overlapping ops merge; the part before the window is clipped
        ev(DEV0, "fusion.3", 50, 150,
           long_name="%fusion.3 = f32[1024,512,4,4]{3,2,1,0} fusion(...)"),
        ev(DEV0, "fusion.7", 180, 120,
           long_name="%fusion.7 = f32[1024,512,4,4]{3,2,1,0} fusion(...)"),
        ev(DEV0, "copy.1", 800, 100),
    ]
    s = devtrace.reduce_events(events)
    # busy [100, 300) and [800, 900) inside the window [100, 1100)
    assert s.busy_s == pytest.approx(300e-9)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.idle_share == pytest.approx(0.7)
    assert s.devices == 1
    gaps = dict(s.idle_gaps)
    # gap [300, 800): 400 in loss_and_grads, 100 in optim_update;
    # gap [900, 1100): 100 in optim_update, 100 outside any span
    assert gaps["loss_and_grads"] == pytest.approx(400e-9)
    assert gaps["optim_update"] == pytest.approx(200e-9)
    assert gaps[devtrace.OUTSIDE] == pytest.approx(100e-9)
    ops = dict(s.device_ops)
    assert ops["fusion_f32_1024_512_4_4"] == pytest.approx(220e-9)
    assert ops["copy"] == pytest.approx(100e-9)
    assert [k for k, _ in s.device_ops] == ["fusion_f32_1024_512_4_4",
                                            "copy"]


def test_busy_is_averaged_over_devices():
    events = [ev(HOST, devtrace.WINDOW, 0, 100),
              ev(DEV0, "a", 0, 100), ev(DEV1, "a", 0, 50)]
    s = devtrace.reduce_events(events)
    assert s.devices == 2
    assert s.busy_s == pytest.approx(75e-9)
    assert dict(s.idle_gaps)[devtrace.OUTSIDE] == pytest.approx(25e-9)


@pytest.mark.parametrize("events", [
    [ev(DEV0, "a", 0, 10)],                                 # no window
    [ev(HOST, devtrace.WINDOW, 0, 100)],                    # no device op
    [ev(HOST, devtrace.WINDOW, 0, 100), ev(DEV0, "a", 200, 10)],
])
def test_nothing_to_read_gives_none(events):
    assert devtrace.reduce_events(events) is None


def test_recorded_trace():
    """A slice of a traced vgg16.train_swap window recorded on a TPU v5e:
    the reducer's totals agree with a direct count of the same events."""
    path = Path(__file__).with_name("data") / "trace_small.json"
    events = json.loads(path.read_text())
    s = devtrace.reduce_events(events)
    assert s is not None and s.devices == 1
    w = next(e for e in events if e["name"] == devtrace.WINDOW)
    w0, w1 = w["start_ns"], w["start_ns"] + w["dur_ns"]
    dev = sorted((max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"],
                                                w1))
                 for e in events if e["plane"].startswith("/device:"))
    busy, end = 0.0, w0
    for a, b in dev:
        if b > end:
            busy += b - max(a, end)
            end = b
    assert s.busy_s == pytest.approx(busy / 1e9)
    assert 0.0 < s.busy_s <= s.window_s
    idle = sum(v for _, v in s.idle_gaps)
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-9)
