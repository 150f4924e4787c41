"""The warm-up's cap: a server that never comes out quiet (it compiles
in every chunk) bounds set-up and is said aloud, but is not by itself a
wrong answer; answers that are wrong on the way still count."""

import numpy as np

from benchmark import harness


class FakeCell:
    workload = {"warmup": {"bursts": [4, 8], "quiet_chunks": 2,
                           "max_chunks": 5}}


def chunks_of(monkeypatch, compiles, extra=0):
    """Drive `warm_up` over chunks that report `compiles[i]` compiles."""
    seen = []

    def run_chunk(cell, deployment, server, workers, plan, phase, seconds,
                  drain_s):
        seen.append(phase)
        return {"latency_ms": np.array([1.0]), "attempted": 10,
                "compiles": compiles[len(seen) - 1],
                "checks": {"missing": (0, 0), "extra": (extra, 0)}}

    monkeypatch.setattr(harness, "run_chunk", run_chunk)
    monkeypatch.setattr(harness, "chunk_plan", lambda *a, **k: {})
    return seen


def test_a_server_that_never_quiets_reaches_the_cap_and_is_not_wrong(
        monkeypatch, capsys):
    seen = chunks_of(monkeypatch, [1] * 5)
    assert harness.warm_up(FakeCell, None, None, None, 7) == (5, 0)
    assert len(seen) == 5
    assert "still compiles or lags after 5" in capsys.readouterr().out


def test_two_quiet_chunks_after_the_ladder_end_it(monkeypatch):
    chunks_of(monkeypatch, [3, 2, 0, 1, 0, 0])
    # chunks 0-1 carry the bursts; chunk 2 is quiet, 3 compiles, 4 is
    # quiet: the cap (5) comes before a second quiet chunk in a row
    assert harness.warm_up(FakeCell, None, None, None, 7) == (5, 0)
    chunks_of(monkeypatch, [3, 2, 0, 0])
    assert harness.warm_up(FakeCell, None, None, None, 7) == (4, 0)


def test_wrong_answers_on_the_way_count_and_the_cap_can_be_overridden(
        monkeypatch):
    seen = chunks_of(monkeypatch, [1] * 5, extra=2)
    assert harness.warm_up(FakeCell, None, None, None, 7, bursts=[],
                           max_chunks=3) == (3, 6)
    assert len(seen) == 3
