"""A cell with replicas: one engine per chip behind ``ReplicaCluster``,
driven end to end through the harness on the CPU (where the two
replicas share the one device), its counts summed over the replicas
and its outputs checked after every replica is gone, on a sample that
holds a request of each replica."""
from types import SimpleNamespace

import pytest

import bench_tiny
import harness
import run
from test_bench_harness import _alter_tokens, _state_unchanged

REPLICAS = {"routing": "prefix", "shared_tier": True}


@pytest.fixture
def seen(monkeypatch):
    """The engines the run built, each one's counters at the window's
    start and end, and the context the readers were given."""
    out = {"engines": [], "snaps": [], "ctx": None}
    counts, reader = run.engine_counts, harness.load_reader

    def engine_counts(engines):
        out["snaps"].append([counts([e]) for e in engines])
        return counts(engines)

    def load_reader(name):
        read = reader(name)

        def spy(ctx):
            out["ctx"] = ctx
            return read(ctx)
        return spy
    monkeypatch.setattr(run, "engine_counts", engine_counts)
    monkeypatch.setattr(harness, "load_reader", load_reader)
    return out


def test_two_replicas_end_to_end(seen):
    spec = bench_tiny.make_spec("chat", chips=2, replicas=REPLICAS)
    out = bench_tiny.run_tiny("chat", trace=1, spec=spec,
                              fault=seen["engines"].append)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert len(seen["engines"]) == 2
    assert seen["engines"][0] is not seen["engines"][1]
    start, end = seen["snaps"]
    per = [{k: b[k] - a[k] for k in a} for a, b in zip(start, end)]
    assert seen["ctx"].counts == {k: per[0][k] + per[1][k] for k in per[0]}
    assert all(p["steps"] > 0 for p in per)       # both replicas served
    assert {"decode_batch_mean", "prompt_cached_share"} <= set(
        out["metrics"])
    assert len(out["device"]["memory_peak_bytes_each"]) == 1
    assert out["compared"]["replicas_unchecked"] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged])
def test_two_replicas_broken_timed_path_is_not_correct(fault):
    spec = bench_tiny.make_spec("chat", chips=2, replicas=REPLICAS)
    out = bench_tiny.run_tiny("chat", spec=spec, fault=fault)
    assert out["correct"] is False
    gap = out["compared"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def _only_replica(k, fault):
    """``fault`` planted in the ``k``-th engine the run builds alone."""
    built = []

    def plant(eng):
        if len(built) == k:
            fault(eng)
        built.append(eng)
    return plant


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged])
def test_one_broken_replica_is_not_correct(fault):
    """A fault in the second replica alone, the first one sound."""
    spec = bench_tiny.make_spec("chat", chips=2, replicas=REPLICAS)
    out = bench_tiny.run_tiny("chat", spec=spec,
                              fault=_only_replica(1, fault))
    assert out["correct"] is False
    gap = out["compared"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def _handles(n_tokens):
    return [SimpleNamespace(request=SimpleNamespace(request_id=i),
                            prompt=[0] * 8, tokens=[0] * t)
            for i, t in enumerate(n_tokens)]


def test_sample_holds_a_request_of_each_replica():
    """However the seed draws, every replica that finished a request
    has one in the sample; the longest request and the most shared stay."""
    done = _handles([40] + [10] * 9)
    replica = {i: 0 if i < 8 else (1 if i == 8 else 2) for i in range(10)}
    hits = {3: [5, 0]}
    for seed in range(2**31, 2**31 + 40):
        pick = run.choose_sample(done, hits, seed, 4, replica)
        ids = [h.request.request_id for h in pick]
        assert ids[:2] == [0, 3]
        assert {8, 9} <= set(ids) and len(ids) == len(set(ids)) == 4


def test_sample_of_one_replica_is_drawn_as_without_replicas():
    """One engine: the sample is the one drawn with no replica map."""
    done = _handles([12, 40, 7, 30, 9, 15, 22])
    one = {i: 0 for i in range(7)}
    for seed in (1, 2**31 + 5, 2**32 + 3):
        assert run.choose_sample(done, {}, seed, 4, one) == \
            run.choose_sample(done, {}, seed, 4)


def test_sample_grows_past_n_to_cover_the_replicas():
    done = _handles([40, 10, 10, 10, 10, 10])
    replica = {i: i for i in range(6)}
    pick = run.choose_sample(done, {}, 7, 4, replica)
    assert sorted(h.request.request_id for h in pick) == list(range(6))
