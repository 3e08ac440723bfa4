"""Diurnal background traffic: peak/off-peak external load.

The paper evaluates under peak and off-peak hours (XSEDE: generic diurnal WAN
load; DIDCLAB: university LAN peaking 11am-3pm).  External load is the fraction
of link capacity consumed by unlogged traffic, i.e. the quantity the paper's
load-intensity heuristic I_s = (bw - th_out)/bw estimates.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

DAY_S = 24 * 3600.0
#: AR(1) coefficient of a link load's walk, DiurnalTraffic's 0.98
WALK_AR = 0.98
#: entropy word of a link load's walk, beside its seed
LOAD_STREAM = 0x10AD
#: Grid step of a link load's walk (simulated s): the mean spacing of one
#: session's chunks on DIDCLAB under Poisson arrivals from 08:00 with a
#: walk per tenant, 137 s, rounded, so the shared walk keeps about the
#: pace each tenant's own walk had.
WALK_STEP_S = 140.0


@dataclasses.dataclass
class DiurnalTraffic:
    """Sinusoidal-plus-noise diurnal load pattern in [0, 1)."""
    base_load: float = 0.10          # off-peak floor
    peak_load: float = 0.55          # added at the busiest hour
    peak_hour: float = 13.0          # center of the busy period
    peak_width_h: float = 4.0        # gaussian width of the busy period
    jitter: float = 0.04             # slow random walk amplitude
    seed: int = 0

    def __post_init__(self):
        # Built on the first draw, so a traffic without jitter never builds one.
        self._rng: np.random.Generator | None = None
        self._walk = 0.0

    @property
    def is_constant(self) -> bool:
        """No jitter and no peak: the load is ``base_load`` at every instant."""
        return self.jitter == 0.0 and self.peak_load == 0.0

    def load_at(self, t_s: float) -> float:
        if self.jitter == 0.0:
            # normal(0, 0) is exactly +0.0, so there is nothing to draw
            self._walk = 0.98 * self._walk + 0.0
        else:
            if self._rng is None:
                self._rng = np.random.default_rng(self.seed)
            self._walk = 0.98 * self._walk + self._rng.normal(0.0, self.jitter)
        if self.peak_load == 0.0 and self._walk == 0.0:
            # the diurnal term is 0.0 and the walk +0.0: the sum is base_load's
            return float(min(max(self.base_load + self._walk, 0.0), 0.95))
        hour = (t_s % DAY_S) / 3600.0
        # circular distance to the peak hour
        d = min(abs(hour - self.peak_hour), 24.0 - abs(hour - self.peak_hour))
        diurnal = self.peak_load * math.exp(-0.5 * (d / self.peak_width_h) ** 2)
        load = self.base_load + diurnal + self._walk
        return float(min(max(load, 0.0), 0.95))

    def is_peak(self, t_s: float) -> bool:
        hour = (t_s % DAY_S) / 3600.0
        d = min(abs(hour - self.peak_hour), 24.0 - abs(hour - self.peak_hour))
        return d <= self.peak_width_h

    @staticmethod
    def constant(load: float) -> "DiurnalTraffic":
        return DiurnalTraffic(base_load=load, peak_load=0.0, jitter=0.0)


@dataclasses.dataclass(frozen=True)
class DiurnalLinkLoad:
    """One link's external load: the diurnal term of :class:`DiurnalTraffic`
    plus its AR(1) walk, as a pure function of simulated time.

    The walk is laid on a fixed grid of ``WALK_STEP_S`` simulated seconds:
    ``walk[0] = 0`` and ``walk[k] = 0.98 * walk[k - 1] + e_k``, the ``e_k``
    drawn in order from ``numpy.random.default_rng([seed, LOAD_STREAM])``
    with sd ``jitter``.  The load at ``t`` is::

        clip(base_load + diurnal(t) + walk[max(floor(t / WALK_STEP_S), 0)],
             0, 0.95)

    so it is piecewise constant in the walk and continuous in the diurnal
    term.  The walk is extended forward in blocks and memoised: a reading
    does not depend on the order or number of readings before it, so one
    instance serves every tenant of a link, and two instances of one seed
    agree.  ``DiurnalTraffic``, whose walk steps once per reading, stays as
    it was for the single-environment history.
    """
    base_load: float = 0.10
    peak_load: float = 0.55
    peak_hour: float = 13.0
    peak_width_h: float = 4.0
    jitter: float = 0.04
    seed: int = 0

    _BLOCK = 256  # innovations drawn per extension of the walk

    def __post_init__(self):
        # the memo of the walk is state of the instance, not a field
        object.__setattr__(self, "_walk", [0.0])
        object.__setattr__(self, "_rng", None)

    def _extend(self, k: int) -> None:
        """Extend the walk through grid index ``k``."""
        walk = self._walk
        if self._rng is None:
            object.__setattr__(self, "_rng", np.random.default_rng(
                [self.seed, LOAD_STREAM]))
        w = walk[-1]
        while len(walk) <= k:
            for e in self._rng.normal(0.0, self.jitter,
                                      size=self._BLOCK).tolist():
                w = WALK_AR * w + e
                walk.append(w)

    def walk_at(self, t_s: float) -> float:
        """The walk's value over the grid step that holds ``t_s``."""
        k = max(math.floor(t_s / WALK_STEP_S), 0)
        if k >= len(self._walk):
            self._extend(k)
        return self._walk[k]

    def load_at(self, t_s: float) -> float:
        # the arithmetic of DiurnalTraffic.load_at, with comparisons in
        # place of min/max: a reading per chunk is on the fleet's hot path
        hour = (t_s % DAY_S) / 3600.0
        d = abs(hour - self.peak_hour)
        if d > 12.0:  # circular distance to the peak hour
            d = 24.0 - d
        k = math.floor(t_s / WALK_STEP_S)
        if k < 0:
            k = 0
        walk = self._walk
        if k >= len(walk):
            self._extend(k)
        load = (self.base_load
                + self.peak_load * math.exp(-0.5 * (d / self.peak_width_h) ** 2)
                + walk[k])
        return 0.0 if load < 0.0 else (0.95 if load > 0.95 else load)


@dataclasses.dataclass
class StepTraffic:
    """Piecewise-constant external load: ``steps`` is [(start_s, load), ...].

    The load at time t is the value of the last step whose start is <= t
    (``initial`` before the first step).  Deterministic — fleet tests use it
    to script harsh load changes that hit every tenant at the same instant,
    where DiurnalTraffic's per-instance random walk would decorrelate them.
    """
    steps: list[tuple[float, float]]
    initial: float = 0.0

    def __post_init__(self):
        self.steps = sorted(self.steps)

    def load_at(self, t_s: float) -> float:
        load = self.initial
        for start, level in self.steps:
            if t_s < start:
                break
            load = level
        return float(min(max(load, 0.0), 0.95))

    def is_peak(self, t_s: float) -> bool:
        return self.load_at(t_s) >= 0.5


@dataclasses.dataclass(frozen=True)
class RegimeShiftTraffic:
    """Abrupt mean-load regime change at ``shift_s`` — the paper's "harsh
    network change" at fleet scale.  External load sits at ``before`` until
    the shift instant, then jumps to ``after`` and stays there; an optional
    sinusoidal ripple adds bounded variation around either level.

    Deterministic and stateless (load is a pure function of t): one frozen
    instance can be shared across fleet tenants, hashed into benchmark
    caches, and replayed bit-for-bit — which is why the ripple is a sinusoid
    rather than DiurnalTraffic's stateful random walk.
    """
    shift_s: float
    before: float = 0.10
    after: float = 0.60
    ripple: float = 0.0              # peak amplitude of the sinusoidal ripple
    ripple_period_s: float = 900.0

    def load_at(self, t_s: float) -> float:
        base = self.before if t_s < self.shift_s else self.after
        wave = self.ripple * math.sin(2.0 * math.pi * t_s / self.ripple_period_s)
        return float(min(max(base + wave, 0.0), 0.95))

    def is_peak(self, t_s: float) -> bool:
        return self.load_at(t_s) >= 0.5
