"""The traffic generator: the same schedule for a seed, the same work for
every seed."""
import pytest

from benchlib import harness, traffic


def _spec(name):
    return traffic.load(harness.BENCH / "traffic" / f"{name}.json")


@pytest.mark.parametrize("name", ["b1", "b8"])
def test_pooled_loops_repeat_for_a_seed(name):
    spec = _spec(name)
    a = traffic.schedule(spec, 2**33 + 5)
    assert a == traffic.schedule(spec, 2**33 + 5)
    assert a != traffic.schedule(spec, 2**33 + 6)


@pytest.mark.parametrize("name", ["b1", "b8"])
def test_pooled_loops_permute_the_pool(name):
    spec = _spec(name)
    a = traffic.schedule(spec, 2**31 + 1)
    assert sorted(i.pool_index for i in a) == list(range(spec["pool"]))


def test_unknown_kind_is_refused(tmp_path):
    path = tmp_path / "t.json"
    path.write_text('{"kind": "open", "rate_per_s": 1}')
    with pytest.raises(ValueError, match="kind must be one of"):
        traffic.load(path)
