"""Length distributions shared by the request generators. A spec is
``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
``{"dist": "uniform", "min", "max"}``."""

from __future__ import annotations

from statistics import NormalDist

import numpy as np


def _inv_cdf(spec: dict, q: np.ndarray) -> np.ndarray:
    if spec["dist"] == "uniform":
        raw = spec["min"] + q * (spec["max"] - spec["min"])
    elif spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        raw = spec["median"] * np.exp(spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def quantile_lengths(n: int, spec: dict) -> np.ndarray:
    """n lengths that ARE the distribution: its evenly spaced quantiles, in a
    fixed order (the caller permutes them by its seed)."""
    return _inv_cdf(spec, (np.arange(n) + 0.5) / n)


def random_lengths(rng, n: int, spec: dict) -> np.ndarray:
    return _inv_cdf(spec, rng.uniform(1e-9, 1 - 1e-9, n))
