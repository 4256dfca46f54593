"""Pareto with scale ``lo`` and shape ``alpha``, truncated at ``hi``: most
requests near ``lo``, a heavy tail up to ``hi``."""


def quantile(dist: dict, u: float) -> float:
    lo, hi, a = float(dist["lo"]), float(dist["hi"]), float(dist["alpha"])
    top = 1.0 - (lo / hi) ** a
    return lo / (1.0 - u * top) ** (1.0 / a)
