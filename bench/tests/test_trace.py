"""The reduction from a profiler trace to device busy time, device time by
kernel and idle gaps by host span."""
import pathlib

import pytest

from benchlib import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"

# Times in ps from each line's timestamp_ns; names as the TPU writes them.
SYNTHETIC = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 6000000000 }
    events { metadata_id: 2 offset_ps: 1000000000 duration_ps: 2000000000 }
    events { metadata_id: 3 offset_ps: 4000000000 duration_ps: 3000000000 }
    events { metadata_id: 4 offset_ps: 9000000000 duration_ps: 1000000000 }
    events { metadata_id: 2 offset_ps: 14000000000 duration_ps: 1000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while.20 = (f32[2]) while(f32[2] %a), body=%b" } }
  event_metadata { key: 2 value { id: 2 name: "%matmul.19 = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} %p), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 3 value { id: 3 name: "%dynamic_update_slice.15 = f32[4]{0} dynamic-update-slice(f32[4]{0} %x)" } }
  event_metadata { key: 4 value { id: 4 name: "%copy.52.remat = f32[4]{0} copy(f32[4]{0} %y)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 2 name: "python3" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 11500000000 }
    events { metadata_id: 2 offset_ps: 7500000000 duration_ps: 1000000000 }
    events { metadata_id: 1 offset_ps: 13000000000 duration_ps: 3000000000 }
  }
  lines { id: 3 name: "other" timestamp_ns: 1000000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 20000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.step" } }
  event_metadata { key: 2 value { id: 2 name: "PjitFunction(counted)" } }
  event_metadata { key: 3 value { id: 3 name: "unrelated" } }
}
"""


def test_synthetic_trace_reduces_exactly():
    from jax.profiler import ProfileData
    data = ProfileData.from_text_proto(SYNTHETIC)
    # the same trace stands for the device slice (16 ms by the host clock)
    # and for the host slice, which spans from the first bench.step start
    # (1.000 ms) to the last end (1.016 ms)
    r = trace.reduce(data, 16e-3, host=data)
    assert r.window_s == pytest.approx(16e-3)
    # busy: [1,7) + [9,10) + [14,15) ms after the slice start, the while
    # loop's body counted once
    assert r.busy_s == pytest.approx(8e-3)
    assert r.kernels == ("matmul",)
    assert r.kernel_time("matmul") == pytest.approx(3e-3)
    assert r.kernel_time("copy") == 0.0            # not a Pallas kernel
    assert r.op_s == pytest.approx({"matmul": 3e-3,
                                    "dynamic_update_slice": 3e-3,
                                    "copy": 1e-3})
    assert "while" not in r.op_s
    # idle: [0,1) bench.step; [7,9) midpoint 8 in the dispatch span;
    # [10,14) midpoint 12 in no span; [15,16) in the second step
    assert r.idle_by_span == pytest.approx({
        "bench.step": 2e-3, "PjitFunction(counted)": 2e-3,
        "(no host span)": 4e-3})
    top = r.breakdown()
    assert [k for k, _ in top["device_ops"]][:1] in (["matmul"],
                                                    ["dynamic_update_slice"])
    assert len(top["idle_gaps"]) <= 10
    # without a host slice: the same device time, no idle gaps filed
    alone = trace.reduce(data, 16e-3)
    assert alone.busy_s == pytest.approx(8e-3)
    assert alone.idle_by_span == {} and alone.breakdown()["idle_gaps"] == []


def test_stem_names():
    assert trace.stem("%attention_decode.8 = (f32[1]) custom-call()") == \
        "attention_decode"
    assert trace.stem("%copy.52.remat = f32[4]{0} copy()") == "copy"
    assert trace.stem("%dynamic-slice_bitcast_fusion.3 = f32[4] fusion()") \
        == "dynamic-slice_bitcast_fusion"


@pytest.mark.skipif(not (DATA / "b1.xplane.pb").exists(),
                    reason="no recorded chip trace")
def test_recorded_chip_trace():
    """A few Darknet-19 bucket-1 steps recorded on a TPU v5 lite."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(DATA / "b1.xplane.pb"))
    busy, devices, op_s, kernels, _ = trace.device_time(data)
    assert devices == 1
    assert busy > 0
    assert "matmul" in kernels and op_s["matmul"] > 0
    assert sum(op_s.values()) <= busy * 1.0001
    idle = trace.idle_gaps(data)
    assert idle.get("bench.step", 0) > 0


class _FakeProfiler:
    def __init__(self):
        self.calls = []

    def start_trace(self, log_dir, profiler_options):
        self.calls.append(("start", log_dir.rsplit("/", 1)[-1],
                           profiler_options.host_tracer_level))

    def stop_trace(self):
        self.calls.append(("stop",))


def test_tracer_slices(monkeypatch):
    """The device slice (host tracer off) from start_s for its seconds,
    then the host slice (level 1); both stop when the window closes."""
    import jax
    from benchlib import harness
    fake = _FakeProfiler()
    monkeypatch.setattr(jax.profiler, "start_trace", fake.start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake.stop_trace)
    t = harness.Tracer(0.0, 2.0, 3.0, 1.0, "/x")
    t.before_step(1.0)
    assert fake.calls == []
    t.before_step(2.5)
    assert fake.calls == [("start", "device", 0)]
    dev_start = t.slices["device"][0]
    t.before_step(dev_start + 3.5)       # the device slice is over
    assert fake.calls[1:] == [("stop",), ("start", "host", 1)]
    t.before_step(float("inf"))
    assert fake.calls[3:] == [("stop",)]
    assert len(t.spans()) == 2
    t.before_step(float("inf"))
    assert len(fake.calls) == 4


def test_idle_check_compares_traced_and_untraced_rates():
    """A steady step rate: the device slice's idle share and the one its
    device time per step gives at the untraced step rate agree, with the
    slices' stopping clipped to the window."""
    import types

    from benchlib import measure
    steps = [types.SimpleNamespace(start=i * 0.01, end=i * 0.01 + 0.005,
                                   work=1) for i in range(1000)]
    w = types.SimpleNamespace(start=0.0, end=10.0, steps=steps)
    tracer = types.SimpleNamespace(
        slices={"device": [2.0, 5.0, 5.0], "host": [5.0, 6.0, 12.0]})
    tracer.spans = lambda: [(a, c) for a, _, c in tracer.slices.values()]
    red = types.SimpleNamespace(busy_s=0.9, window_s=3.0)
    line = measure.idle_check(w, red, tracer)
    assert "traced 70.0" in line and "over 300 steps" in line
    assert "untraced step rate 70.0" in line and "over 200 steps" in line
