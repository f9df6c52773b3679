"""The yolov3.b1 cell: counts against the published numbers, and, at a tiny
YOLO-shaped size on the CPU, a served run that is correct, a control that
is not, the faults the check must catch, and the `fetch_gb_s` reader.

The tiny size is `repro.configs.darknet_ref.yolov3_cfg`'s test cut (64x64,
widths / 16, one residual block per stage, 2 classes), written out here as
the configuration file's layer list.  Its limit is set from tiny readings
on the CPU (program at most 1.4e-06 over seeds 11-12, control at least
3.5e-05); the cell's own limit comes from chip readings at full size
(`bench/workloads/yolov3.b1.json` `limits_from`)."""
import numpy as np
import pytest

from benchlib import harness

CELL = "yolov3.b1"
TINY_LIMITS = {"det_err": 5e-6}


def _tiny_layers():
    from repro.configs.darknet_ref import yolov3_cfg
    from repro.core.darknet.cfg import parse_cfg
    text = yolov3_cfg(size=64, width_div=16, blocks=(1, 1, 1, 1, 1),
                      classes=2)
    return [dict(type=s.type, **{k: v for k, v in s.options.items()
                                 if k in ("batch_normalize", "filters", "size",
                                          "stride", "pad", "activation",
                                          "from", "layers", "mask", "classes")})
            for s in parse_cfg(text)[1:]]


def _run(seed=2**31 + 7, control=False, **extra):
    overrides = {"config": {"height": 64, "width": 64, "classes": 2,
                            "layers": _tiny_layers()},
                 "traffic": {"pool": 8},
                 "workload": {"limits": TINY_LIMITS}}
    return harness.run_cell(CELL, seed, 2.0, False, require_chip=False,
                            overrides=overrides, control=control,
                            log=lambda m: None, **extra)


def _config():
    mod = harness.load_module(harness.BENCH / "configs" / "yolov3.py", "t_")
    conf = harness.load_json(harness.BENCH / "configs" / "yolov3.json")
    return mod, conf


def test_yolov3_counts():
    # YOLOv3, Table 3: YOLOv3-416 takes 65.86 Bn FLOPs; 62,001,757
    # parameters (75 convolutions, batch-norm's 4 per channel on 72).
    mod, conf = _config()
    assert mod.flops_per_image(conf) == pytest.approx(65.86e9, rel=1e-3)
    assert mod.param_count(conf) == 62_001_757
    assert len(mod.convs(conf)) == 75
    assert [h["shape_out"] for h in mod.heads(conf)] == \
        [(13, 13, 255), (26, 26, 255), (52, 52, 255)]

    class Step:
        obs = {1: 2}     # two bucket-1 dispatches
    calls = mod.System.op_calls(type("S", (), {"conf": conf})(), [Step()])
    assert len(calls["conv2d"]) == 75
    assert all(n == 2 for _, _, n in calls["conv2d"])
    assert sum(f * n for f, _, n in calls["conv2d"]) == pytest.approx(
        2 * 65.86e9, rel=1e-3)


def test_program_passes_and_control_fails():
    r = _run(seed=11, control=True)
    assert r["correct"], r["checks"]
    assert r["checks"]["unanswered"]["value"] == 0
    assert r["control"]["det_err"] > TINY_LIMITS["det_err"], r["control"]


def test_altered_head_value_is_caught(monkeypatch):
    from repro.core.darknet.network import CompileCache
    run = CompileCache.run

    def altered(self, x):
        heads = run(self, x)     # w of the first anchor, first cell
        return (heads[0].at[0, 0, 0, 2].add(1e-3),) + heads[1:]

    monkeypatch.setattr(CompileCache, "run", altered)
    r = _run()
    assert not r["correct"], r["checks"]


def test_lost_request_is_caught(monkeypatch):
    from repro.serve import frontend
    step = frontend.CNNServingEngine.step

    def losing(self):
        if (not getattr(self, "_lost", False) and self.pending
                and self.pending[0].rid >= 0):
            self.pending.popleft()
            self._lost = True
        return step(self)

    monkeypatch.setattr(frontend.CNNServingEngine, "step", losing)
    r = _run()
    assert r["checks"]["unanswered"]["value"] == 1, r["checks"]
    assert not r["correct"]


def test_det_err_logits_and_cut():
    """Logistic entries compare as logits, w and h raw; a reference p
    outside [p_min, p_max] leaves its entry out."""
    mod, _ = _config()
    want = np.full((1, 1, 1, 7), 0.5, np.float32)   # one anchor, 2 classes
    want[..., 2:4] = 2.0
    got = want.copy()
    got[..., 0] = 1 / (1 + np.exp(-1e-3))          # logit 0 -> 1e-3
    assert mod.det_err([got], [want], 2, 1e-6, 0.9375) == \
        pytest.approx(1e-3 / 2.0, rel=1e-3)
    want[..., 0] = got[..., 0] = 0.99               # cut: left out
    got[..., 0] = 0.999
    assert mod.det_err([got], [want], 2, 1e-6, 0.9375) == 0.0


def test_fetch_gb_s_reads_the_outputs_counter():
    """Bytes per image from `CompileCache.stats()["outputs"]` times the
    images of the untraced working steps, over their `cnn.fetch` time;
    nothing where the program reports no `outputs`."""
    import types
    from benchlib import drive
    reader = harness.reader("fetch_gb_s")
    ns = 1_000_000
    rec = {"stages": ("cnn.batch", "cnn.put", "cnn.dispatch", "cnn.wait",
                      "cnn.fetch"),
           "perf_ns": np.array([[0, 1, 2, 3, 4, 4 + 2 * ns],
                                [10 * ns, 10 * ns + 1, 10 * ns + 2,
                                 10 * ns + 3, 10 * ns + 4, 14 * ns + 4]])}
    steps = [drive.Step(start=0.0, end=0.003, work=1),
             drive.Step(start=0.010, end=0.015, work=1)]
    stats = {"outputs": {"arrays": 3, "bytes_per_item": 3_619_980}}
    server = types.SimpleNamespace(step_records=lambda: rec,
                                   cache=types.SimpleNamespace(
                                       stats=lambda: stats))
    ctx = types.SimpleNamespace(
        system=types.SimpleNamespace(server=server), tracer=None,
        window=drive.Window(start=0.0, end=1.0, records=[], steps=steps,
                            compiles=0, failed=0, unanswered=0))
    # 2 images of 3,619,980 B over 2 ms + 4 ms of fetch
    assert reader.read("fetch_gb_s.yolov3_b1", ctx) == pytest.approx(
        2 * 3_619_980 / 6e-3 / 1e9)
    stats.pop("outputs")
    assert reader.read("fetch_gb_s.yolov3_b1", ctx) is None

