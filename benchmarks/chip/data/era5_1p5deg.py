"""ERA5 on pressure levels at 1.5 degrees (WeatherBench 2's 240 x 121
equiangular grid with poles, 13 levels), flattened to one row per (time,
level, lat, lon) and made from a seed with vectorized numpy.

Each 6-hourly step is one table of 377,520 rows, level-major.  Values are
per-level climatological profiles with a latitude term plus seeded Gaussian
noise: temperature (K), u and v wind (m/s), specific humidity (kg/kg,
lognormal), geopotential (m^2/s^2) and vertical velocity (Pa/s, ascent at
the equator and subsidence in the subtropics).  They hold no NaN,
infinity, subnormal or -0.0.
"""

from __future__ import annotations

import numpy as np

# per-level means, 50 ... 1000 hPa
_T_K = [210.0, 205.0, 211.0, 218.0, 223.0, 229.0, 242.0, 253.0, 262.0, 270.0, 279.0, 283.0, 287.0]
_JET = [0.45, 0.75, 0.95, 1.0, 1.0, 0.9, 0.7, 0.5, 0.4, 0.3, 0.2, 0.15, 0.1]
_Q = [2.5e-6, 2.8e-6, 8e-6, 3e-5, 8e-5, 2e-4, 6e-4, 1.4e-3, 2.5e-3, 3.8e-3, 6.8e-3, 9e-3, 1.2e-2]
_Z_KM = [20.6, 16.2, 13.6, 11.8, 10.4, 9.2, 7.2, 5.6, 4.2, 3.0, 1.46, 0.76, 0.11]
_OMEGA = [0.02, 0.03, 0.05, 0.07, 0.09, 0.11, 0.13, 0.14, 0.14, 0.13, 0.11, 0.09, 0.06]  # Pa/s, noise per level
_G = 9.80665


def _grid(config: dict):
    nlat, nlon = int(config["lat"]), int(config["lon"])
    lat = np.linspace(90.0, -90.0, nlat, dtype=np.float64)
    lon = np.arange(nlon, dtype=np.float64) * (360.0 / nlon)
    return lat, lon


def _base(config: dict):
    """(levels, lat*lon) float64 means of the six variables, and their noise
    scales (one per variable, or one per level)."""
    lat, lon = _grid(config)
    nlev = len(config["levels"])
    assert nlev == len(_T_K), "profiles are given for the 13 WeatherBench 2 levels"
    phi = np.deg2rad(np.repeat(lat, lon.size))[None, :]
    lev = np.arange(nlev)[:, None]
    t = np.asarray(_T_K)[:, None] - 30.0 * np.sin(phi) ** 2 * (lev / (nlev - 1) + 0.3)
    u = 5.0 + 25.0 * np.asarray(_JET)[:, None] * np.cos(2.0 * phi)
    v = np.zeros_like(u)
    q = np.asarray(_Q)[:, None] * (0.3 + 0.7 * np.cos(phi) ** 2)
    z = _G * 1000.0 * np.asarray(_Z_KM)[:, None] * (1.0 - 0.03 * np.sin(phi) ** 2)
    omega = np.asarray(_OMEGA)[:, None]
    lat_deg = np.rad2deg(phi)
    w = omega * (0.2 * np.exp(-(((np.abs(lat_deg) - 25.0) / 10.0) ** 2)) - 0.4 * np.exp(-((lat_deg / 10.0) ** 2)))
    means = np.stack(np.broadcast_arrays(t, u, v, q, z, w))
    return means, [3.0, 8.0, 6.0, 0.4, 0.005, omega]


def make(config: dict, seed: int) -> dict:
    lat, lon = _grid(config)
    levels = np.asarray(config["levels"], np.int32)
    per_level = lat.size * lon.size
    rows = levels.size * per_level
    level_col = np.repeat(levels, per_level)
    lat_col = np.tile(np.repeat(lat, lon.size), levels.size).astype(np.float32)
    lon_col = np.tile(lon, lat.size * levels.size).astype(np.float32)
    means, scales = _base(config)
    first_hour = int(np.datetime64(config["first_step"], "h").astype(np.int64))
    names = list(config["variables"])
    zero = np.float32(0.0)

    tables = {}
    for step in range(int(config["time_steps"])):
        noise = np.random.default_rng([seed, 0xE5, step]).standard_normal((len(names), rows), dtype=np.float32)
        noise = noise.reshape(len(names), levels.size, per_level)
        cols = {
            "time": np.full(rows, first_hour + step * int(config["step_hours"]), np.int32),
            "level": level_col,
            "lat": lat_col,
            "lon": lon_col,
        }
        for j, name in enumerate(names):
            if name == "specific_humidity":
                vals = means[j] * np.exp(scales[j] * noise[j] - 0.08)
            elif name == "geopotential":
                vals = means[j] * (1.0 + scales[j] * noise[j])
            else:
                vals = means[j] + scales[j] * noise[j]
            cols[name] = vals.astype(np.float32).reshape(rows) + zero  # + 0.0 turns any -0.0 into 0.0
        tables[f"t{step:03d}"] = {"columns": cols, "parts": [rows]}
    return tables
