"""The profiler window of a ``--trace 1`` run and its reduction to numbers.

``Window`` records a JAX profiler trace over the first ``seconds`` of a
run's measured window (the run calls ``poll`` between units of work, so
the trace stops at a unit boundary).  ``reduce`` reads the ``.xplane.pb``
file with ``jax.profiler.ProfileData`` and returns:

* ``busy_s``: the union of the intervals in which an operation ran on
  each chip, averaged over the chips that ran any;
* ``module_s`` / ``module_n``: device seconds and calls per compiled
  program (``XLA Modules`` events, the name's ``(id)`` suffix dropped);
* ``ops``: the ten device operations that took most time, each named
  ``<program>/<op>``;
* ``gaps``: the ten longest idle gaps on the first chip, each named by the
  benchmark's innermost host span (``jax.profiler.TraceAnnotation``
  named ``bench.*``) that covers the gap's midpoint.
"""
from __future__ import annotations

import bisect
import glob
import os
import shutil
import time
from typing import Dict, List, Optional, Tuple

HOST_PREFIX = "bench."


def _options():
    import jax
    po = jax.profiler.ProfileOptions()
    po.python_tracer_level = 0        # no per-call Python events
    po.host_tracer_level = 2
    return po


class Window:
    """A profiler trace over the first ``seconds`` of a measured window."""

    def __init__(self, log_dir: str, seconds: float):
        self.log_dir = log_dir
        self.seconds = seconds
        self.active = False
        self.window_s = 0.0
        self._t0 = 0.0

    def start(self):
        import jax
        shutil.rmtree(self.log_dir, ignore_errors=True)
        os.makedirs(self.log_dir, exist_ok=True)
        jax.profiler.start_trace(self.log_dir, profiler_options=_options())
        self._t0 = time.perf_counter()
        self.active = True

    def poll(self):
        if self.active and time.perf_counter() - self._t0 >= self.seconds:
            self.stop()

    def stop(self):
        if self.active:
            import jax
            self.window_s = time.perf_counter() - self._t0
            jax.profiler.stop_trace()
            self.active = False


def annotate(enabled: bool, name: str):
    """A host span in the profiler's trace, or nothing."""
    if not enabled:
        return _NULL
    import jax
    return jax.profiler.TraceAnnotation(HOST_PREFIX + name)


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


# ---------------------------------------------------------------------------
def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def _module_name(name: str) -> str:
    return name.split("(")[0]


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name


def _op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ")[0].lstrip("%")


def reduce_planes(planes) -> dict:
    """Reduce profiler planes (objects with ``name`` and ``lines``; lines
    with ``name`` and ``events``; events with ``name``, ``start_ns`` and
    ``duration_ns``) to the numbers in the module docstring.  A device
    operation is named ``<program>/<op>`` after the program it ran in."""
    busy: List[float] = []
    module_s: Dict[str, float] = {}
    module_n: Dict[str, int] = {}
    op_s: Dict[str, float] = {}
    first_busy: Optional[List[Tuple[float, float]]] = None
    host: List[Tuple[float, float, str]] = []
    for plane in planes:
        if _is_device(plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           _module_name(e.name))
                          for e in lines.get("XLA Modules", ()))
            for lo, hi, m in mods:
                module_s[m] = module_s.get(m, 0.0) + (hi - lo) * 1e-9
                module_n[m] = module_n.get(m, 0) + 1
            starts = [lo for lo, _, _ in mods]
            ops = []
            for e in lines.get("XLA Ops", ()):
                ops.append((e.start_ns, e.start_ns + e.duration_ns))
                i = bisect.bisect_right(starts, e.start_ns) - 1
                prog = mods[i][2] if i >= 0 and e.start_ns < mods[i][1] \
                    else "?"
                name = f"{prog}/{_op_name(e.name)}"
                op_s[name] = op_s.get(name, 0.0) + e.duration_ns * 1e-9
            spans = _union(ops or [(lo, hi) for lo, hi, _ in mods])
            if spans:
                busy.append(sum(hi - lo for lo, hi in spans) * 1e-9)
                if first_busy is None:
                    first_busy = spans
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name[len(HOST_PREFIX):]))
    gaps = []
    if first_busy:
        for (_, a), (b, _) in zip(first_busy, first_busy[1:]):
            mid = (a + b) / 2
            inner = [h for h in host if h[0] <= mid <= h[1]]
            label = (min(inner, key=lambda h: h[1] - h[0])[2] if inner
                     else "outside bench spans")
            gaps.append((label, (b - a) * 1e-9))
        gaps.sort(key=lambda g: -g[1])
    ops_top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    return {"busy_s": sum(busy) / len(busy) if busy else 0.0,
            "chips": len(busy), "module_s": module_s, "module_n": module_n,
            "ops": [[k, v] for k, v in ops_top],
            "gaps": [[k, v] for k, v in gaps[:10]]}


def reduce(log_dir: str, window_s: float) -> dict:
    """Read the trace under ``log_dir``, reduce it, and delete it."""
    import jax
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    out = reduce_planes(data.planes)
    out["window_s"] = window_s
    shutil.rmtree(log_dir, ignore_errors=True)
    return out


def module_seconds(summary: dict, needle: str) -> Tuple[float, int]:
    """Device seconds and calls of the programs whose name holds
    ``needle``."""
    s = sum(v for k, v in summary["module_s"].items() if needle in k)
    n = sum(v for k, v in summary["module_n"].items() if needle in k)
    return s, n
