"""Small pieces every part of the benchmark shares: paths, seeds, the
spec and its files, percentiles and peaks."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def rng(seed: int, *salt: int) -> np.random.Generator:
    """A numpy generator from any whole number (negative or past 64 bits
    included) and optional salts."""
    words = []
    s = int(seed)
    for _ in range(3):                       # two's-complement, 96 bits
        words.append(s & 0xFFFFFFFF)
        s >>= 32
    return np.random.default_rng(words + [int(x) & 0xFFFFFFFF for x in salt])


def jax_seed(seed: int, *salt: int) -> int:
    """A 31-bit seed for ``jax.random.key`` derived from ``seed``."""
    return int(rng(seed, *salt).integers(0, 2**31 - 1))


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def find(entries, name: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(name)


def config_for(spec: dict, name: str) -> dict:
    return load_json(ROOT / find(spec["configs"], name)["file"])


def traffic_for(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def load_module(path: Path, name: str):
    """Import a file by path (metric readers are named after metrics,
    which may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def pct(xs, q: float) -> float:
    """``np.percentile`` (linear), as ``repro.cluster.metrics.pct``."""
    return float(np.percentile(np.asarray(xs, dtype=np.float64), q))


def peaks_for(kind: str) -> dict:
    """The chip's published peaks; an unknown device is an error."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[kind]
