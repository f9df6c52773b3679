"""The yolov4.b1 cell: counts against the published numbers, `det_err`
with `scale_x_y`, and, at a tiny YOLOv4-shaped size on the CPU, a served
run that is correct, a control that is not, an altered head value that is
caught, and the `pool_ms` reader.

The tiny size is `repro.configs.darknet_ref.yolov4_cfg`'s cut (widths /
16, one residual block per stage, 2 classes) at a 128x128 input, whose
coarsest grid (4) the SPP's 5-wide window does not cover from its edges,
written out here as the configuration file's layer list.  Its limit is
set from tiny readings on the CPU (program 2.0e-06 to 3.3e-06 over seeds
11-12, the bf16x3 control 4.5e-05 to 4.8e-05); the cell's own limit comes from chip
readings at full size (`bench/workloads/yolov4.b1.json` `limits_from`)."""
import types

import numpy as np
import pytest

from benchlib import harness

CELL = "yolov4.b1"
TINY_HW = 128
TINY_LIMITS = {"det_err": 1e-5}


def _tiny_layers():
    from repro.configs.darknet_ref import yolov4_cfg
    from repro.core.darknet.cfg import parse_cfg
    text = yolov4_cfg(size=TINY_HW, width_div=16, blocks=(1, 1, 1, 1, 1),
                      classes=2)
    keep = ("batch_normalize", "filters", "size", "stride", "pad",
            "activation", "from", "layers", "mask", "classes", "scale_x_y")
    return [dict(type=s.type, **{k: v for k, v in s.options.items()
                                 if k in keep})
            for s in parse_cfg(text)[1:]]


def _run(seed=2**31 + 7, control=False, **extra):
    overrides = {"config": {"height": TINY_HW, "width": TINY_HW,
                            "classes": 2, "layers": _tiny_layers()},
                 "traffic": {"pool": 4},
                 "workload": {"limits": TINY_LIMITS, "check_block": 4}}
    return harness.run_cell(CELL, seed, 1.0, False, require_chip=False,
                            overrides=overrides, control=control,
                            log=lambda m: None, **extra)


def _config():
    mod = harness.load_module(harness.BENCH / "configs" / "yolov4.py", "t_")
    conf = harness.load_json(harness.BENCH / "configs" / "yolov4.json")
    return mod, conf


def test_yolov4_counts():
    # yolov4.cfg at 608: 162 layers, 110 convolutions, 128.39 GFLOP per
    # image by 2*OH*OW*Cout*k*k*Cin (darknet prints 128.459 BFLOPs),
    # 64,429,405 parameters, heads at 76, 38 and 19.
    mod, conf = _config()
    assert len(conf["layers"]) == 162
    assert len(mod.convs(conf)) == 110
    assert sum(p["activation"] == "mish" for p in mod.convs(conf)) == 72
    assert mod.flops_per_image(conf) == pytest.approx(128.389e9, rel=1e-4)
    assert mod.flops_per_image(conf) == pytest.approx(128.459e9, rel=1e-3)
    assert mod.param_count(conf) == 64_429_405
    assert [(h["shape_out"], h["scale_x_y"]) for h in mod.heads(conf)] == \
        [((76, 76, 255), 1.2), ((38, 38, 255), 1.1), ((19, 19, 255), 1.05)]
    assert [p["shape_out"] for p in mod.plan(conf)
            if p["type"] == "maxpool"] == [(19, 19, 512)] * 3

    class Step:
        obs = {1: 2}     # two bucket-1 dispatches
    calls = mod.System.op_calls(type("S", (), {"conf": conf})(), [Step()])
    assert len(calls["conv2d"]) == 110
    assert sum(f * n for f, _, n in calls["conv2d"]) == pytest.approx(
        2 * 128.389e9, rel=1e-4)


def test_det_err_undoes_scale_x_y():
    """x and y compare as the logits of their σ after the affine is
    undone; w and h raw; a reference p outside [p_min, p_max] is left
    out."""
    mod, _ = _config()
    s, shift = mod.xy_affine(1.2)
    assert shift == np.float32(-0.5) * (np.float32(1.2) - np.float32(1))
    want = np.full((1, 1, 1, 7), 0.5, np.float32)   # one anchor, 2 classes
    want[..., 2:4] = 2.0
    want[..., :2] = 0.5 * s + shift                  # σ = 0.5 -> 0.5
    got = want.copy()
    got[..., 0] = 1 / (1 + np.exp(-1e-3)) * s + shift  # logit 0 -> 1e-3
    assert mod.det_err([got], [want], 2, 1e-6, 0.9375, [1.2]) == \
        pytest.approx(1e-3 / 2.0, rel=1e-3)
    # Without undoing the affine the same values would read as another
    # logit error.
    assert mod.det_err([got], [want], 2, 1e-6, 0.9375, [1.0]) != \
        pytest.approx(1e-3 / 2.0, rel=1e-3)
    want[..., 0] = 0.99 * s + shift                   # cut: left out
    got[..., 0] = 0.999 * s + shift
    assert mod.det_err([got], [want], 2, 1e-6, 0.9375, [1.2]) == 0.0


def test_reference_follows_darknet_pool_and_head():
    """The reference's max-pool pads pad // 2 low and only as far as a
    window reads high; its head scales x, y about 0.5."""
    mod, _ = _config()
    assert mod.pool_padding(19, 5, 1, 4) == (2, 2)
    assert mod.pool_padding(19, 13, 1, 12) == (6, 6)
    assert mod.pool_padding(224, 2, 2, 1) == (0, 0)
    assert mod.pool_padding(13, 2, 2, 1) == (0, 1)
    x = np.linspace(-3, 3, 7, dtype=np.float32).reshape(1, 1, 1, 7)
    y = np.asarray(mod.yolo_head(x, 2, 1.1))
    sig = 1 / (1 + np.exp(-x.astype(np.float64)))
    np.testing.assert_allclose(y[..., :2], sig[..., :2] * 1.1 - 0.05,
                               rtol=0, atol=1e-7)
    np.testing.assert_allclose(y[..., 2:4], x[..., 2:4])
    np.testing.assert_allclose(y[..., 4:], sig[..., 4:], rtol=1e-6)


def test_program_passes_and_control_fails():
    r = _run(seed=11, control=True)
    assert r["correct"], r["checks"]
    assert r["checks"]["unanswered"]["value"] == 0
    assert r["control"]["det_err"] > TINY_LIMITS["det_err"], r["control"]


def test_altered_head_value_is_caught(monkeypatch):
    from repro.core.darknet.network import CompileCache
    run = CompileCache.run

    def altered(self, x):
        heads = run(self, x)     # x of the first anchor, first cell
        return (heads[0].at[0, 0, 0, 0].add(1e-3),) + heads[1:]

    monkeypatch.setattr(CompileCache, "run", altered)
    r = _run()
    assert not r["correct"], r["checks"]


def test_pool_ms_reads_reduce_window_time():
    """The `reduce_window*` operations' device time over the images of the
    steps wholly inside the device slice; nothing without them."""
    from benchlib import drive
    reader = harness.reader("pool_ms")
    steps = [drive.Step(start=1.0, end=1.01, work=1),
             drive.Step(start=1.5, end=1.51, work=1),
             drive.Step(start=3.5, end=3.51, work=1)]    # after the slice
    trace = types.SimpleNamespace(op_s={"matmul": 1.0,
                                        "reduce_window_max": 0.004,
                                        "reduce_window_max_fusion": 0.002})
    ctx = types.SimpleNamespace(
        trace=trace,
        tracer=types.SimpleNamespace(slices={"device": [0.5, 3.0, 3.1]}),
        window=drive.Window(start=0.0, end=5.0, records=[], steps=steps,
                            compiles=0, failed=0, unanswered=0))
    assert reader.read("pool_ms.yolov4_b1", ctx) == pytest.approx(3.0)
    trace.op_s.pop("reduce_window_max")
    trace.op_s.pop("reduce_window_max_fusion")
    assert reader.read("pool_ms.yolov4_b1", ctx) is None
