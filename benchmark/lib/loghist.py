"""A window's distribution from two samples of a cumulative log2 histogram:
the program reports ``[[bucket, count], ...]`` (``Histogram.bucket_counts``
of ``polyrl_tpu/obs/histogram.py``: bucket ``i`` holds the values in
``[2**(i/SUBDIV), 2**((i+1)/SUBDIV))``, 9% wide, and every count only
grows), so what a bucket gained between the window's first and last sample
is what was observed in between."""

from __future__ import annotations


def gained(obs: dict, key: str) -> dict | None:
    """{bucket: count gained, first to last sample that carries ``key``};
    None when fewer than two do (an older engine)."""
    xs = [s[key] for s in obs.get("server_info", []) if key in s]
    if len(xs) < 2:
        return None
    first = {i: n for i, n in xs[0]}
    diff = {i: n - first.get(i, 0) for i, n in xs[-1]}
    return {i: n for i, n in diff.items() if n > 0}


def _edge(index: float) -> float:
    from polyrl_tpu.obs.histogram import bucket_edge

    return bucket_edge(index)


def upper_edge(bucket: int) -> float:
    return _edge(bucket + 1)


def tail(counts: dict, beyond: int = 10,
         cap: float = 99.0) -> tuple[float, float, int] | None:
    """(percentile, its value, samples): the highest percentile up to
    ``cap`` that has ``beyond`` samples beyond it, as the geometric middle
    of the bucket its rank falls in; None with ``beyond`` samples or
    fewer."""
    n = sum(counts.values())
    if n <= beyond:
        return None
    pct = min(cap, 100.0 * (1.0 - beyond / n))
    rank, seen = pct / 100.0 * n, 0
    for bucket in sorted(counts):
        seen += counts[bucket]
        if seen >= rank:
            break
    return pct, _edge(bucket + 0.5), n
