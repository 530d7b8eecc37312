"""FLIGHTDELAY relation generator of the benchmark (its own copy).

Copied from ``src/repro/data/flightgen.py`` (the paper's §5 substrate:
U.S. DOT flights joined to hourly weather, with planted causal effects) and
kept here so that no change to the program can move the data a benchmark
cell measures. It differs from the original in three ways:

* an airport-popularity law taken from the configuration: flightgen's own
  ``uniform(low, high)`` weights drawn from the seed, or a Zipf law over
  airport rank with a fixed exponent, so that every seed gives the hubs
  the same share of flights;
* flights are emitted in event-time (hour) order, as a deployment
  receives them: the cell of every flight is drawn by inverse CDF from
  sorted uniforms, so no sort is needed;
* only the engine's columns are materialised, already joined to the
  weather of their (airport, hour), with ``dep_delay`` rounded to whole
  minutes as the DOT on-time table reports it.

Everything is drawn from one ``numpy`` generator seeded by ``--seed``:
the same seed gives the same relation on every machine.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

#: planted effects on the uncensored delay (minutes), as in flightgen
TRUE_EFFECTS = {"thunder": 30.0, "lowvis": 25.0, "highwind": 15.0,
                "snow": 40.0}


def popularity(law: Dict, n_airports: int, rng) -> np.ndarray:
    """Relative flight rate of each airport."""
    if law["law"] == "uniform":
        return rng.uniform(law["low"], law["high"], n_airports)
    if law["law"] == "zipf":
        # Zipf-Mandelbrot: (rank + offset) ** -exponent
        rank = np.arange(1, n_airports + 1, dtype=np.float64)
        return (rank + float(law.get("offset", 0))) ** -float(law["exponent"])
    raise ValueError(f"unknown airport popularity law {law['law']!r}")


def _weather(rng, n_airports: int, n_hours: int) -> Dict[str, np.ndarray]:
    """Hourly weather per airport, shape (n_airports, n_hours): the
    formulas of flightgen's ``_weather`` for the columns the engine and
    the planted delays read."""
    shape = (n_airports, n_hours)
    day = np.arange(n_hours) / 24.0
    season = np.broadcast_to(
        0.5 - 0.5 * np.cos(2 * np.pi * (day % 365.25) / 365.25), shape)
    apt_temp = rng.uniform(-5, 15, size=(n_airports, 1))
    storm = np.clip(rng.beta(0.6, 4.0, size=shape) * (0.5 + 1.5 * season),
                    0, 1)
    fog = np.clip(rng.beta(0.7, 6.0, size=shape) * (1.5 - season), 0, 1)
    tempm = apt_temp + 18 * season + rng.normal(0, 4, shape)
    thunder = (rng.random(shape) < 0.01 + 0.25 * storm * season)
    wspdm = np.clip(8 + 45 * storm + rng.normal(0, 6, shape), 0, None)
    precipm = np.clip(storm * rng.gamma(1.5, 0.6, shape) - 0.1, 0, None)
    visim = np.clip(10 - 8.5 * fog - 4 * storm + rng.normal(0, 1.2, shape),
                    0.05, 10)
    return dict(season=season, tempm=tempm, thunder=thunder, wspdm=wspdm,
                precipm=precipm, visim=visim)


def generate(cfg: Dict, seed: int) -> Dict[str, np.ndarray]:
    """The joined fact relation of configuration ``cfg``, in hour order.

    Returns host columns: ``airport``, ``carrier`` (int32), ``traffic``,
    ``w_season``, ``w_precipm``, ``w_wspdm``, ``w_tempm`` (float32), the
    treatments ``thunder``, ``snow``, ``highwind`` (int32) and
    ``dep_delay`` (float32 whole minutes), plus ``delay_revision`` (int32,
    the correction the BTS table may later apply to each delay)."""
    n = int(cfg["n_flights"])
    n_air, n_car = int(cfg["n_airports"]), int(cfg["n_carriers"])
    n_hours = 24 * int(cfg["n_days"])
    rng = np.random.default_rng(seed)
    w = _weather(rng, n_air, n_hours)

    # flight rate per (hour, airport) cell: diurnal, seasonal, popularity
    hours = np.arange(n_hours)
    diurnal = np.clip(np.sin(np.pi * (hours % 24 - 5) / 18.0), 0.02, None)
    season_1d = 0.5 - 0.5 * np.cos(2 * np.pi * ((hours / 24.0) % 365.25)
                                   / 365.25)
    pop = popularity(cfg["airport_popularity"], n_air, rng)
    rate = (diurnal * (1.0 + 1.2 * season_1d))[:, None] * pop[None, :]
    cdf = np.cumsum(rate.reshape(-1))
    cdf /= cdf[-1]
    # sorted uniforms (normalised exponential spacings): cells come out in
    # (hour, airport) order, so the stream is in event-time order
    gaps = rng.exponential(1.0, n + 1)
    u = np.cumsum(gaps[:-1]) / gaps.sum()
    cell = np.minimum(np.searchsorted(cdf, u, side="right"),
                      cdf.size - 1)
    f_hour = (cell // n_air).astype(np.int32)
    f_apt = (cell % n_air).astype(np.int32)
    f_car = rng.integers(0, n_car, n).astype(np.int32)

    # traffic = flights at the same (airport, hour) (paper's AirportTraffic)
    f_traffic = np.bincount(cell, minlength=cdf.size)[cell].astype(np.float32)
    car_cell = f_hour.astype(np.int64) * n_car + f_car
    f_car_traffic = np.bincount(car_cell, minlength=n_hours * n_car)[
        car_cell].astype(np.float32)

    gv = lambda name: w[name][f_apt, f_hour]
    thunder = gv("thunder").astype(np.int32)
    wspdm, precipm, tempm = gv("wspdm"), gv("precipm"), gv("tempm")
    highwind = (wspdm > 40).astype(np.int32)
    snow = ((precipm > 0.3) & (tempm < 0)).astype(np.int32)
    lowvis = (gv("visim") < 1).astype(np.int32)

    base = (6.0 + 0.9 * (f_traffic - f_traffic.mean())
            + 0.15 * (f_car_traffic - f_car_traffic.mean())
            + rng.normal(0, 3, n_car)[f_car] + rng.normal(0, 3, n_air)[f_apt]
            + rng.normal(0, 10, n))
    delay = (base + TRUE_EFFECTS["thunder"] * thunder
             + TRUE_EFFECTS["lowvis"] * lowvis
             + TRUE_EFFECTS["highwind"] * highwind
             + TRUE_EFFECTS["snow"] * snow)
    dep_delay = np.round(np.clip(delay, 0, None)).astype(np.float32)
    revision = rng.integers(-int(cfg["max_revision_min"]),
                            int(cfg["max_revision_min"]) + 1, n
                            ).astype(np.int32)
    f32 = lambda a: np.ascontiguousarray(a, dtype=np.float32)
    return dict(
        airport=f_apt, carrier=f_car, traffic=f_traffic,
        w_season=f32(gv("season")), w_precipm=f32(precipm),
        w_wspdm=f32(wspdm), w_tempm=f32(tempm),
        thunder=thunder, snow=snow, highwind=highwind,
        dep_delay=dep_delay, delay_revision=revision)
