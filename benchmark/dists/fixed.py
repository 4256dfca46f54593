"""Every length is ``lo``."""


def quantile(dist: dict, u: float) -> float:
    return float(dist["lo"])
