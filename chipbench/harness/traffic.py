"""The one traffic generator: coefficient fields and arrival schedules, from
the seed and the numbers in a configuration and a traffic file (a new mix
of rates, bursts or pauses is a new ``traffic/<mix>.json``).

Fields are the FNO Darcy coefficient (Li et al., arXiv:2010.08895 §5.2):
a Gaussian random field with covariance ``(−Δ + τ²I)^(−α)`` under Neumann
conditions, thresholded to ``a_pos`` where it is non-negative and ``a_neg``
elsewhere.  Its Karhunen–Loève series ``Σ_k w_k ξ_k (π²|k|² + τ²)^(−α/2)
cos(πk₁x) cos(πk₂y)`` (one cosine factor per axis in 3D),
``ξ_k ~ N(0, 1)``, ``ξ_0 = 0``, is evaluated exactly at
each element's centroid (the constant factors of the published generator
do not change the sign, so they are left out).
"""

from __future__ import annotations

import numpy as np

_FIELD_STREAM = 1
_ARRIVAL_STREAM = 2
_SAMPLE_STREAM = 3


def rng(seed: int, stream: int, index: int = 0) -> np.random.Generator:
    """An independent generator per (seed, stream, index); any non-negative
    seed, however large."""
    return np.random.default_rng([int(seed), stream, index])


class FieldSampler:
    """Draws coefficient fields at the centroids of one mesh, in as many
    dimensions as its points have."""

    def __init__(self, points: np.ndarray, cells: np.ndarray, spec: dict):
        n = int(spec["modes"])
        centroids = points[cells].mean(axis=1)
        self.dim = points.shape[1]
        k = np.arange(n)
        weight = np.where(k == 0, np.sqrt(0.5), 1.0)
        self._basis = []
        self._index = []
        for axis in range(self.dim):
            xs, inverse = np.unique(np.round(centroids[:, axis], 12),
                                    return_inverse=True)
            self._basis.append(np.cos(np.pi * xs[:, None] * k) * weight)
            self._index.append(inverse.ravel())
        k2 = sum(g ** 2 for g in np.meshgrid(*[k] * self.dim, indexing="ij"))
        self._scale = (np.pi ** 2 * k2 + float(spec["tau"]) ** 2) ** (
            -float(spec["alpha"]) / 2)
        self._scale[(0,) * self.dim] = 0.0
        self._values = (float(spec["a_pos"]), float(spec["a_neg"]))
        self.modes = n

    def field(self, seed: int, index: int) -> np.ndarray:
        """The ``index``-th field of ``seed``: ``(E,)`` float64."""
        grid = self._scale * rng(seed, _FIELD_STREAM, index).standard_normal(
            (self.modes,) * self.dim)
        # the series on the tensor grid of the centroids' coordinates, one
        # axis at a time
        for axis, basis in enumerate(self._basis):
            grid = np.moveaxis(np.tensordot(basis, grid, axes=(1, axis)),
                               0, axis)
        u = grid[tuple(self._index)]
        return np.where(u >= 0.0, *self._values)

    def pool(self, seed: int, size: int) -> list[np.ndarray]:
        return [self.field(seed, i) for i in range(size)]


def arrivals(seed: int, phases: list, seconds: float) -> np.ndarray:
    """Open-loop due times in ``[0, seconds)``.  ``phases`` is a list of
    ``{"rate_per_s": r, "seconds": s}``, taken in turn and repeated until
    the window is full; a phase without ``seconds`` lasts the whole window
    (a constant rate), ``rate_per_s`` 0 makes a pause (bursts).  Each
    stretch of a phase is a Poisson process of its rate conditioned on its
    count, ``round(r · s)`` sorted uniform times: every seed offers the same
    number of requests at the same rates, so the seed changes when they
    come and not how many there are."""
    out, t, j = [], 0.0, 0
    while t < seconds:
        for phase in phases:
            length = min(float(phase.get("seconds", seconds)), seconds - t)
            if length <= 0:
                raise ValueError(f"a phase of no length: {phase}")
            count = int(round(float(phase["rate_per_s"]) * length))
            out.append(rng(seed, _ARRIVAL_STREAM, j).uniform(t, t + length,
                                                             count))
            j += 1
            t += length
            if t >= seconds:
                break
    due = np.sort(np.concatenate(out))
    if due.size == 0:
        raise ValueError("the arrivals offer no request in the window")
    return due


def sample(seed: int, population: int, size: int) -> np.ndarray:
    """``size`` distinct indices of ``range(population)`` drawn from the
    seed (all of them when ``size >= population``)."""
    if size >= population:
        return np.arange(population)
    return np.sort(rng(seed, _SAMPLE_STREAM).choice(population, size,
                                                    replace=False))
