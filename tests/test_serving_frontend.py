"""One serving surface for CNN and LM traffic: the `ServingFrontend`
protocol, the micro-batching `CNNServingEngine`, and the shared stats
schema both engines emit."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch, reduced
from repro.core import make_engine
from repro.core.darknet.network import Network
from repro.models import transformer as tfm
from repro.serve import frontend as fe
from repro.serve.engine import Request as LMRequest
from repro.serve.engine import ServingEngine

ENGINE = make_engine("xla", "fp32_strict")

CFG = """
[net]
height=12
width=12
channels=3

[convolutional]
batch_normalize=1
filters=8
size=3
stride=1
pad=1
activation=leaky

[convolutional]
filters=4
size=3
stride=2
pad=1
activation=leaky
"""


def _cnn_engine(buckets=(1, 2, 4)):
    net = Network(CFG, ENGINE)
    params = net.init(jax.random.PRNGKey(0))
    return net, params, fe.CNNServingEngine(
        net.compile_cache(params, buckets=buckets))


def _images(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((12, 12, 3)).astype(np.float32)
            for _ in range(n)]


def test_cnn_engine_serves_ragged_traffic_correctly():
    net, params, eng = _cnn_engine()
    imgs = _images(7)
    reqs = [fe.ImageRequest(rid=i, image=im) for i, im in enumerate(imgs)]
    eng.run(reqs)
    assert all(r.done for r in reqs)
    # per-request results match a direct exact-batch compiled call
    want = np.asarray(net.compile(params, batch_size=7)(jnp.stack(imgs)))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(r.result, want[i])
        assert r.latency_s >= 0.0
    st = eng.stats()
    assert st["requests"]["completed"] == 7
    assert st["images"] == 7
    assert st["throughput"] > 0
    # 7 requests on top bucket 4 -> two micro-batch steps (4 then 3-padded)
    assert st["steps"] == 2
    assert st["cache"]["traces"] == len(st["cache"]["compiled"])


def test_cnn_engine_rejects_wrong_image_shape():
    _, _, eng = _cnn_engine()
    with pytest.raises(ValueError, match="image shape"):
        eng.submit(fe.ImageRequest(rid=0, image=np.zeros((8, 8, 3),
                                                         np.float32)))
    assert eng.stats()["requests"]["rejected"] == 1


def test_cnn_engine_step_returns_zero_when_idle():
    _, _, eng = _cnn_engine()
    assert eng.step() == 0


def test_run_serves_past_a_rejected_request():
    """One inadmissible request must not strand the rest of the batch."""
    _, _, eng = _cnn_engine()
    good = [fe.ImageRequest(rid=i, image=im)
            for i, im in enumerate(_images(2))]
    bad = fe.ImageRequest(rid=9, image=np.zeros((8, 8, 3), np.float32))
    eng.run([good[0], bad, good[1]])
    assert all(r.done for r in good)
    assert not bad.done
    st = eng.stats()
    assert st["requests"]["rejected"] == 1
    assert st["requests"]["completed"] == 2


def test_stats_latency_stays_finite_with_rejections_in_the_batch():
    """Regression: latency aggregates cover COMPLETED requests only.  A
    rejected (or still in-flight) request has NaN timestamps — one NaN
    sample in the running aggregate would poison avg/max for the server's
    whole lifetime."""
    _, _, eng = _cnn_engine()
    good = [fe.ImageRequest(rid=i, image=im)
            for i, im in enumerate(_images(3))]
    bad = fe.ImageRequest(rid=9, image=np.zeros((8, 8, 3), np.float32))
    eng.run([bad, *good])
    assert np.isnan(bad.latency_s)          # rejected: NaN - NaN
    st = eng.stats()
    assert np.isfinite(st["latency_s"]["avg"])
    assert np.isfinite(st["latency_s"]["max"])
    assert st["latency_s"]["max"] >= st["latency_s"]["avg"] > 0.0
    assert eng._latency.count == 3          # the completed requests only


def test_latency_agg_refuses_nonfinite_samples():
    """The aggregate guards itself: feeding it an incomplete request's NaN
    latency is a programming error, not a sample."""
    agg = fe.LatencyAgg()
    agg.add(0.25)
    with pytest.raises(ValueError, match="COMPLETED"):
        agg.add(float("nan"))
    with pytest.raises(ValueError, match="COMPLETED"):
        agg.add(fe.Request(rid=0).latency_s)   # never submitted/completed
    assert (agg.count, agg.sum, agg.max) == (1, 0.25, 0.25)


def test_latency_percentiles_nearest_rank():
    """p50/p95/p99 use nearest-rank over the reservoir — exact while the
    sample count fits in it, deterministic always."""
    agg = fe.LatencyAgg()
    for v in range(1, 101):                 # 1..100 ms
        agg.add(v / 1000.0)
    s = agg.summary()
    assert set(fe.LATENCY_KEYS) == set(s)
    assert s["p50"] == pytest.approx(0.050)
    assert s["p95"] == pytest.approx(0.095)
    assert s["p99"] == pytest.approx(0.099)
    assert s["p99"] <= s["max"] == pytest.approx(0.100)
    # empty aggregate reports zeros, not NaNs
    assert fe.LatencyAgg().summary() == {
        "avg": 0.0, "max": 0.0, "p50": 0.0, "p95": 0.0, "p99": 0.0}


def test_latency_reservoir_bounds_memory_and_stays_deterministic():
    """Past capacity the reservoir downsamples (memory stays bounded) and
    two identically-fed aggregates agree bit-for-bit (seeded RNG)."""
    a, b = fe.LatencyAgg(reservoir=64), fe.LatencyAgg(reservoir=64)
    for v in range(1000):
        a.add(v / 1000.0)
        b.add(v / 1000.0)
    assert len(a._samples) == 64
    assert a.summary() == b.summary()
    assert a.count == 1000                  # avg/max still exact
    assert a.summary()["max"] == pytest.approx(0.999)
    # percentile ordering holds even on the downsampled reservoir
    s = a.summary()
    assert 0.0 < s["p50"] <= s["p95"] <= s["p99"] <= s["max"]


def test_rejection_is_a_dedicated_exception_type():
    """Admission failures raise RejectedRequest (a ValueError subclass, so
    existing callers keep working) on both engines."""
    _, _, cnn = _cnn_engine()
    with pytest.raises(fe.RejectedRequest, match="image shape"):
        cnn.submit(fe.ImageRequest(rid=0, image=np.zeros((8, 8, 3),
                                                         np.float32)))
    assert issubclass(fe.RejectedRequest, ValueError)

    cfg = reduced(get_arch("qwen2-0.5b"))
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    lm = ServingEngine(cfg, params, engine=ENGINE, slots=1, max_len=8)
    with pytest.raises(fe.RejectedRequest, match="exceeds the KV cache"):
        lm.submit(LMRequest(rid=0, prompt=list(range(9)), max_new=1))


def test_run_does_not_swallow_genuine_programming_errors():
    """`run` catches exactly RejectedRequest: a submit that dies with any
    other ValueError (mis-shaped engine state, a corrupted queue — here: a
    broken override) must propagate, not masquerade as a rejection."""
    _, _, eng = _cnn_engine()

    class Broken(type(eng)):
        def submit(self, req):
            raise ValueError("mis-shaped engine state")

    eng.__class__ = Broken
    with pytest.raises(ValueError, match="mis-shaped engine state"):
        eng.run([fe.ImageRequest(rid=0, image=_images(1)[0])])


def test_request_positional_construction_keeps_payload_slots():
    """Lifecycle fields on the shared base are keyword-only, so positional
    construction binds the payload right after rid (the pre-refactor LM
    Request API)."""
    r = LMRequest(0, [1, 2, 3], 5)
    assert (r.prompt, r.max_new, r.done) == ([1, 2, 3], 5, False)
    img = np.zeros((2, 2, 3), np.float32)
    assert fe.ImageRequest(1, img).image is img


def test_stats_schema_is_shared_across_cnn_and_lm_engines():
    """The acceptance contract: both engines expose submit/step/run/stats
    and emit the same stats schema."""
    _, _, cnn = _cnn_engine()
    cnn.run([fe.ImageRequest(rid=i, image=im)
             for i, im in enumerate(_images(3))])

    cfg = reduced(get_arch("qwen2-0.5b"))
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    lm = ServingEngine(cfg, params, engine=ENGINE, slots=2, max_len=32)
    lm.run([LMRequest(rid=i, prompt=[1, 2, 3], max_new=2)
            for i in range(2)])

    for eng in (cnn, lm):
        assert isinstance(eng, fe.ServingFrontend)
        st = eng.stats()
        assert set(fe.STATS_KEYS) <= set(st)
        assert set(fe.REQUEST_KEYS) == set(st["requests"])
        assert set(fe.LATENCY_KEYS) == set(st["latency_s"])
        assert st["requests"]["completed"] == st["requests"]["submitted"]
        assert st["latency_s"]["max"] >= st["latency_s"]["avg"] >= 0
    assert cnn.stats()["engine"] == "cnn"
    assert lm.stats()["engine"] == "lm"
    # both request types share the frontend Request base (lifecycle+latency)
    assert issubclass(LMRequest, fe.Request)
    assert issubclass(fe.ImageRequest, fe.Request)


# --------------------------------------------- CNNServingEngine step spans ---

def _serve_one_at_a_time(eng, n):
    """n single-image steps; returns each step() call's duration (ns) by a
    clock read outside the engine."""
    outside = []
    for i, im in enumerate(_images(n)):
        eng.submit(fe.ImageRequest(rid=i, image=im))
        a = time.perf_counter_ns()
        assert eng.step() == 1
        outside.append(time.perf_counter_ns() - a)
    return outside


def test_step_stages_are_ordered_and_tile_the_step_span():
    _, _, eng = _cnn_engine()
    eng.run([fe.ImageRequest(rid=i, image=im)
             for i, im in enumerate(_images(7))])
    rec = eng.step_records()
    assert rec["step"].tolist() == [0, 1]
    t = rec["perf_ns"]
    assert t.shape == (2, len(fe.CNN_STAGES) + 1)
    assert (np.diff(t, axis=1) >= 0).all()        # t0 <= t1 <= ... <= t5
    assert t[0, -1] <= t[1, 0]                    # steps do not overlap
    # the stages tile the span: their totals are the spans' durations
    st = eng.stats()["stages"]
    np.testing.assert_array_equal(
        np.diff(t, axis=1).sum(axis=0),
        [st[s]["total_ns"] for s in fe.CNN_STAGES])
    assert eng.stats()["wall_s"] * 1e9 == pytest.approx(
        (t[:, -1] - t[:, 0]).sum(), abs=1.0)
    epoch, perf = eng.anchor_ns
    np.testing.assert_array_equal(rec["epoch_ns"] - rec["perf_ns"],
                                  epoch - perf)
    assert rec["overwritten"] == 0


@pytest.mark.parametrize("kept,served", [(1, 4), (3, 5), (5, 5)])
def test_step_record_ring_is_bounded_and_counts_what_it_overwrote(
        monkeypatch, kept, served):
    monkeypatch.setattr(fe, "STEPS_KEPT", kept)
    _, _, eng = _cnn_engine(buckets=(1,))
    _serve_one_at_a_time(eng, served)
    rec = eng.step_records()
    lost = max(0, served - kept)
    assert rec["step"].tolist() == list(range(lost, served))  # newest, in order
    assert rec["overwritten"] == lost
    assert rec["perf_ns"].shape == (served - lost, len(fe.CNN_STAGES) + 1)
    assert (np.diff(rec["perf_ns"][:, 0]) > 0).all()
    assert eng._ring.shape == (kept, len(fe.CNN_STAGES) + 1)
    _serve_one_at_a_time(eng, 2)
    rec = eng.step_records()
    assert rec["step"].tolist() == list(range(served + 2 - kept, served + 2))
    assert rec["overwritten"] == served + 2 - kept
    assert (np.diff(rec["perf_ns"][:, 0]) > 0).all()
    assert eng.stats()["stages"]["cnn.wait"]["count"] == served + 2


@pytest.mark.parametrize("kept", [
    pytest.param(fe.STEPS_KEPT, id="ring_holds_every_step"),
    pytest.param(2, id="ring_overwrote_steps")])
def test_wall_s_is_the_sum_of_step_spans(monkeypatch, kept):
    monkeypatch.setattr(fe, "STEPS_KEPT", kept)
    _, _, eng = _cnn_engine(buckets=(1,))
    outside = _serve_one_at_a_time(eng, 4)
    st = eng.stats()
    wall_ns = st["wall_s"] * 1e9
    assert wall_ns == pytest.approx(
        sum(s["total_ns"] for s in st["stages"].values()), abs=1.0)
    assert 0 < wall_ns <= sum(outside)
    t = eng.step_records()["perf_ns"]
    spans = t[:, -1] - t[:, 0]
    if kept >= 4:
        assert wall_ns == pytest.approx(spans.sum(), abs=1.0)
    else:                       # the aggregates still count dropped steps
        assert wall_ns > spans.sum()


def test_stats_stages_keys():
    _, _, eng = _cnn_engine()
    assert set(eng.stats()["stages"]) == set(fe.CNN_STAGES)
    eng.run([fe.ImageRequest(rid=i, image=im)
             for i, im in enumerate(_images(5))])
    st = eng.stats()
    for name in fe.CNN_STAGES:
        s = st["stages"][name]
        assert set(s) == {"count", "total_ns", "mean_ns"}
        assert s["count"] == st["steps"] == 2
        assert s["mean_ns"] == s["total_ns"] / 2


def test_step_spans_share_the_profiler_clock(tmp_path):
    """Through the anchor, each step span lies inside the profiler's own
    span of the same call: `profile_start_time` (epoch ns) plus the
    event's start, to its end."""
    from jax.profiler import ProfileData
    _, _, eng = _cnn_engine(buckets=(1,))
    eng.cache.warmup()
    imgs = _images(3)
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for i, im in enumerate(imgs):
            eng.submit(fe.ImageRequest(rid=i, image=im))
            with jax.profiler.TraceAnnotation(f"test.cnn_step.{i}"):
                eng.step()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    data = ProfileData.from_file(str(path))
    start = next(dict(p.stats)["profile_start_time"] for p in data.planes
                 if p.name == "Task Environment")
    spans = {e.name: (start + e.start_ns, start + e.start_ns + e.duration_ns)
             for p in data.planes for line in p.lines for e in line.events
             if e.name.startswith("test.cnn_step.")}
    rec = eng.step_records()
    slack = 50_000                                # ns
    for i in range(len(imgs)):
        lo, hi = spans[f"test.cnn_step.{i}"]
        t0, t5 = rec["epoch_ns"][i, 0], rec["epoch_ns"][i, -1]
        assert lo - slack <= t0 <= t5 <= hi + slack, (i, lo, t0, t5, hi)
