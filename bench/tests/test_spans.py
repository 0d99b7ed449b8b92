"""The program's spans and counters read from a traced window: the gap
labels, a trace recorded on the CPU, and the small route and served cells
with the program's profiler-clock bundle attached and hidden."""
from types import SimpleNamespace as NS

import jax
import numpy as np
import pytest
from pytest import approx

import devtrace
import spans
from small import PEAKS, route_cell, serve_cell
from test_trace import _ev, _planes


def test_gap_under_a_program_span_takes_its_name():
    """The gap 100-300 lies inside ``bench.engine_step`` and, more
    tightly, inside ``repro.engine.fragment_save``: the program span
    names it.  The gap 400-600 has no program span over it."""
    planes = _planes()
    planes[2].lines.append(NS(name="repro", events=[
        _ev("repro.engine.step", 0, 410),
        _ev("repro.engine.prefill", 0, 100),
        _ev("repro.engine.fragment_save", 100, 250),
        _ev("repro.engine.fragment_save", 300, 20)]))
    s = spans.reduce_planes(planes)
    assert s["gaps"] == [["repro.engine.fragment_save", approx(200e-9)],
                         ["hooks", approx(200e-9)]]
    assert s["spans"] == {"repro.engine.step": [approx(410e-9), 1],
                          "repro.engine.prefill": [approx(100e-9), 1],
                          "repro.engine.fragment_save": [approx(270e-9), 2]}


def test_without_program_spans_the_reduction_is_unchanged():
    want = devtrace.reduce_planes(_planes())
    got = spans.reduce_planes(_planes())
    assert got.pop("spans") == {}
    assert got == want


def test_recorded_cpu_trace_holds_the_program_spans(tmp_path):
    """Spans of a profiler-clock bundle, recorded on the CPU inside a
    benchmark span, come back with their counts, a parent lasting at
    least as long as its children, and the counters' window deltas."""
    from repro.obs import make_obs
    obs = make_obs(metrics=True, trace="profiler")
    obs.registry.inc("pipeline.fallback_requests", 5)
    f = jax.jit(lambda x: (x @ x).sum())
    x = jax.numpy.ones((64, 64))
    f(x).block_until_ready()
    w = spans.ObsWindow(str(tmp_path / "t"), 60.0, obs.registry)
    w.start()
    for k in range(3):
        with devtrace.annotate(True, "route_batch"):
            with obs.tracer.span("wave", args={"wave": k, "k": 2}):
                with obs.tracer.span("commit"):
                    with obs.tracer.span("commit.fallback",
                                         args={"k": 1}):
                        f(x).block_until_ready()
                    obs.registry.inc("pipeline.fallback_requests")
    w.stop()
    s = spans.reduce(w.log_dir, w.window_s)
    sp = s["spans"]
    assert {k: v[1] for k, v in sp.items()} == {
        "repro.wave": 3, "repro.commit": 3, "repro.commit.fallback": 3}
    assert (sp["repro.wave"][0] >= sp["repro.commit"][0]
            >= sp["repro.commit.fallback"][0] > 0)
    assert w.counts["pipeline.fallback_requests"] == 3
    assert not (tmp_path / "t").exists()


def _traced(cell, seed=7, seconds=1.0):
    return spans.run_traced(*cell, seed, seconds, jax.devices(), PEAKS)


def test_route_fallback_share_is_an_outside_count(monkeypatch):
    """With the small fleet's caches full, the share of window requests
    that the commit stage re-routed is above 0 and equals, exactly, a
    count kept from outside the program over the profiler window."""
    import repro.core.router as router_mod
    orig = router_mod.commit_wave_plan
    on = [False]
    seen = {"fallback": 0, "routed": 0}

    def counted(factory, wave, commit, fallback):
        def tail(rest):
            if on[0]:
                seen["fallback"] += len(rest)
            return fallback(rest)
        if on[0]:
            seen["routed"] += len(wave)
        return orig(factory, wave, commit, tail)

    class Window(spans.ObsWindow):
        def start(self):
            super().start()
            on[0] = True

        def stop(self):
            on[0] = False
            super().stop()

    monkeypatch.setattr(router_mod, "commit_wave_plan", counted)
    monkeypatch.setattr(spans, "ObsWindow", Window)
    line, _ = _traced(route_cell(warm=2048))
    assert line["correct"], line["checks"]
    share = line["program"]["fallback_share.route"]
    assert seen["fallback"] > 0
    assert share == 100.0 * seen["fallback"] / seen["routed"]
    assert line["program"]["fallback_us.route"] > 0
    assert line["counts"]["pipeline.fallback_waves"] > 0
    for name in ("walk_us.route", "score_us.route", "commit_us.route"):
        assert line["metrics"][name] > 0


def test_served_fragment_copy_is_whole_slots():
    """Every prefill of the window saves one slot of the cache and every
    store hit injects one: the copied bytes are slots by the cache's
    shapes, and the program's span and counter metrics read."""
    from repro.models import Model
    import serve
    spec, cell, config, mix = serve_cell()
    line, ctx = _traced((spec, cell, config, dict(mix, gap_limit=0.1)),
                        seconds=2.0)
    assert line["correct"], line["checks"]
    model = Model(serve.model_config(config))
    shapes = jax.eval_shape(
        lambda: model.init_cache(1, config["engine"]["max_len"]))
    slot = sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(shapes))
    prefills = sum(1 for s in ctx["steps"]
                   if s["kind"] == "prefill" and s["traced"])
    c = line["counts"]
    assert prefills > 0
    assert c["engine.fragment_d2h_bytes"] == slot * prefills
    injected, rest = divmod(c["engine.fragment_h2d_bytes"], slot)
    assert rest == 0 and 0 < injected <= prefills
    p = line["program"]
    assert p["fragment_copy_mb.serve"] == approx(
        slot * (prefills + injected) / prefills / 1e6)
    assert p["admit_ms.serve"] > 0 and p["fragment_save_ms.serve"] > 0
    assert 0 < p["hit_prediction_share.serve"] <= 100


@pytest.mark.parametrize("which", ["route", "serve"])
def test_without_the_profiler_clock_the_metrics_read_none(which,
                                                          monkeypatch):
    """A program that offers no profiler clock (as before it had one)
    runs the traced window as it was: correct, nothing attached, and
    every program metric ``None``."""
    import repro.obs
    monkeypatch.setattr(repro.obs, "TRACE_CLOCKS", ("virtual",))
    if which == "route":
        cell, seconds = route_cell(), 1.0
    else:
        spec, c, config, mix = serve_cell()
        cell, seconds = (spec, c, config, dict(mix, gap_limit=0.1)), 2.0
    line, _ = _traced(cell, seconds=seconds)
    assert line["correct"], line["checks"]
    assert line["obs"] is False and line["spans"] == {}
    assert line["program"] and all(v is None
                                   for v in line["program"].values())
