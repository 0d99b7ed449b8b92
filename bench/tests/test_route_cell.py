"""The routing cell end to end on the CPU at a small fleet: the harness
finds it correct, its control is not, and a broken timed path is caught."""
import pytest

import route
from reference import route_ref
from small import env, route_cell, run


@pytest.fixture(scope="module")
def cell():
    return route_cell()


def test_small_run_is_correct(cell):
    out = run(*cell)
    assert out["correct"], out["checks"]
    assert out["checks"]["unchecked_samples"]["value"] == 0
    assert list(out)[-3] == "checks"           # last key of the result line
    assert set(out["metrics"]) == {"decisions_per_s", "setup_s"}


def test_traced_run_reads_the_stage_metrics(cell):
    out = run(*cell, trace=True)
    assert out["correct"]
    for name in ("walk_us.route", "score_us.route", "commit_us.route",
                 "wave_p99_ms.route"):
        assert out["metrics"][name]["value"] > 0
    assert "device_ops" in out["breakdown"]


def test_controls_disagree_with_the_reference(cell):
    _, _, config, mix = cell
    res = route.run(config, mix, 11, 1.0, env(),
                    controls=("first_min", "float32"))
    assert res["checks"]["decision_mismatches"][0] == 0
    assert res["controls"]["first_min"] > 0
    # scores here stay far below 2**24, where float32 is exact
    assert res["controls"]["float32"] == 0


@pytest.mark.parametrize("every", [1, 7])
def test_altered_answers_are_caught(cell, monkeypatch, every):
    """Answers altered as ``route_batch`` returns them, from the window's
    first wave on (every wave, or one in seven).  Altered from the first
    call of the run, a shift of every label would be a relabelling of the
    instances, which no comparison of decisions can tell apart."""
    from repro.core.router import Router
    _, _, config, mix = cell
    before = 1 + mix["warm_requests"] // mix["wave"]   # compile + warm-up
    orig = Router.route_batch
    calls = [0]

    def broken(self, reqs, now):
        out = orig(self, reqs, now)
        calls[0] += 1
        k = calls[0] - before
        if k > 0 and k % every == 0:       # the answer altered as produced
            out = [(i + 1) % self.factory.n for i in out]
        return out

    monkeypatch.setattr(Router, "route_batch", broken)
    out = run(*cell, seconds=3.0)
    assert not out["correct"]
    assert out["checks"]["decision_mismatches"]["value"] > 0


def test_replay_counts_a_wrong_hit():
    reqs = route_trace_small()
    decisions = [0] * len(reqs)
    hits = [0] * len(reqs)
    svc = route.ServiceModel({"service": {"prefill_tokens_per_s": 1e4,
                                          "tpot_s": 0.01}}, 2)
    res = route_ref.replay(reqs, decisions, hits, 2, 64, 10**9, svc, 4,
                           samples=[])
    # the second request repeats the first prompt: its hit is not 0
    assert res["hit_mismatches"] == 1


def route_trace_small():
    from repro.core.types import Request
    b = (1, 2, 3)
    return [Request(rid=0, arrival=0.0, blocks=b, prompt_len=192,
                    output_len=4),
            Request(rid=1, arrival=0.1, blocks=b, prompt_len=192,
                    output_len=4)]
