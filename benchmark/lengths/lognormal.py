"""Log-normal lengths: ``median`` and ``sigma`` (of the logarithm)."""

import math
from statistics import NormalDist


def at_quantiles(spec, q):
    """The distribution's value at each quantile of ``q`` (0 < q < 1)."""
    return [float(spec["median"]) * math.exp(
        float(spec["sigma"]) * NormalDist().inv_cdf(float(p))) for p in q]
