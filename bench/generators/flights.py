"""Synthetic FLIGHTS rows made on the device from a seed (paper §5.1, Table 3).

The distributions are those of the program's host generator
(``repro.data.flights``), written again with ``jax.random`` so that a
run makes its table on the chip instead of in numpy:

* ``origin``: Zipf(``airport_zipf``) over ``n_airports`` airports;
* ``airline``: categorical over ``n_airlines`` with Dirichlet(3) shares
  (both drawn by Walker's alias method: one uniform code and one
  uniform coin per row);
* ``dep_time``: Beta(2.2, 1.6) x 1440 minutes;
* ``day_of_week``: uniform over 1..7;
* ``dep_delay``: airport + airline location, an airline-dependent slope
  in ``dep_time``, N(0, 9) noise, a lognormal tail on 6% of rows, rare
  outliers near the top of the catalog range and a weekend shift, clipped
  to [-60, 1800].

The per-airport and per-airline parameters (shares, locations, slopes)
are drawn once from the configuration's ``param_seed``: they define the
deployment's data, the same for every run. ``--seed`` draws the rows.
Rows are i.i.d., so they are already in uniformly random order and are
blocked straight into the engine's ``Scramble`` layout, padding rows
zero and invalid.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CHUNK_ROWS = 1 << 24       # rows made per device call
DELAY_RANGE = (-60.0, 1800.0)
DEP_TIME_RANGE = (0.0, 1440.0)
N_DAYS = 7
COLUMNS = {"origin": np.int32, "airline": np.int32, "dep_delay": np.float32,
           "dep_time": np.float32, "day_of_week": np.int32}


def alias_table(p: np.ndarray):
    """Walker's alias table ``(accept, alias)`` of the distribution ``p``:
    draw ``i`` uniformly, keep it with probability ``accept[i]``, else
    take ``alias[i]``."""
    n = len(p)
    scaled = np.asarray(p, np.float64) * n
    accept = np.ones(n)
    alias = np.arange(n)
    small = [i for i in range(n) if scaled[i] < 1.0]
    large = [i for i in range(n) if scaled[i] >= 1.0]
    while small and large:
        s, g = small.pop(), large.pop()
        accept[s], alias[s] = scaled[s], g
        scaled[g] -= 1.0 - scaled[s]
        (small if scaled[g] < 1.0 else large).append(g)
    return accept, alias


def params(cfg: dict) -> dict:
    """The deployment's per-airport and per-airline parameters."""
    rng = np.random.default_rng(cfg["param_seed"])
    n_ap, n_al = cfg["n_airports"], cfg["n_airlines"]
    ranks = np.arange(1, n_ap + 1, dtype=np.float64)
    p_airport = 1.0 / ranks ** cfg["airport_zipf"]
    p_airport /= p_airport.sum()
    p_airline = rng.dirichlet(np.full(n_al, 3.0))
    airport_mu = rng.normal(8.0, 4.0, size=n_ap)
    sparse_half = np.arange(n_ap // 2, n_ap)
    neg = sparse_half[::5]
    airport_mu[neg] = rng.normal(-4.0, 1.0, size=neg.shape)
    hot = sparse_half[3::11]
    airport_mu[hot] = rng.normal(55.0, 2.0, size=hot.shape)
    airline_mu = np.linspace(0.0, 14.0, n_al)
    rng.shuffle(airline_mu)
    airline_slope = rng.uniform(0.0, 12.0, size=n_al)
    f32 = lambda x: np.asarray(x, np.float32)
    ap_accept, ap_alias = alias_table(p_airport)
    al_accept, al_alias = alias_table(p_airline)
    return dict(p_airport=p_airport, p_airline=p_airline,
                airport_accept=f32(ap_accept),
                airport_alias=ap_alias.astype(np.int32),
                airline_accept=f32(al_accept),
                airline_alias=al_alias.astype(np.int32),
                airport_mu=f32(airport_mu), airline_mu=f32(airline_mu),
                airline_slope=f32(airline_slope))


def _take(table, idx):
    """``table[idx]`` for a small table, as a masked sum over its entries
    (exact: every term but one is zero), which the chip runs far faster
    than a gather."""
    hit = idx[:, None] == jnp.arange(table.shape[0], dtype=idx.dtype)
    return jnp.sum(jnp.where(hit, table[None, :], jnp.zeros((), table.dtype)),
                   axis=1, dtype=table.dtype)


def _categorical(key, accept, alias, n):
    kc, ku = jax.random.split(key)
    code = jax.random.randint(kc, (n,), 0, accept.shape[0], jnp.int32)
    coin = jax.random.uniform(ku, (n,), jnp.float32)
    return jnp.where(coin < _take(accept, code), code, _take(alias, code))


@functools.partial(jax.jit, static_argnames=("n",))
def _chunk(key, p, n: int):
    ks = jax.random.split(key, 10)
    f32 = jnp.float32
    origin = _categorical(ks[0], p["airport_accept"], p["airport_alias"], n)
    airline = _categorical(ks[1], p["airline_accept"], p["airline_alias"], n)
    dep_time = jax.random.beta(ks[2], 2.2, 1.6, (n,), f32) * f32(1440.0)
    time_effect = _take(p["airline_slope"], airline) * (dep_time
                                                        / f32(1440.0))
    base = (_take(p["airport_mu"], origin) + _take(p["airline_mu"], airline)
            + time_effect)
    noise = f32(9.0) * jax.random.normal(ks[3], (n,), f32)
    tail = jnp.where(jax.random.uniform(ks[4], (n,), f32) < f32(0.06),
                     jnp.exp(f32(2.2) + f32(1.1)
                             * jax.random.normal(ks[5], (n,), f32)),
                     f32(0.0))
    outlier = jnp.where(jax.random.uniform(ks[6], (n,), f32) < f32(2e-5),
                        jax.random.uniform(ks[7], (n,), f32,
                                           f32(1200.0), f32(DELAY_RANGE[1])),
                        f32(0.0))
    lo, hi = f32(DELAY_RANGE[0]), f32(DELAY_RANGE[1])
    delay = jnp.clip(base + noise + tail + outlier, lo, hi)
    day = jax.random.randint(ks[8], (n,), 1, N_DAYS + 1, jnp.int32)
    delay = jnp.clip(delay + jnp.where(day >= 6, f32(-2.0), f32(1.0)),
                     lo, hi)
    return dict(origin=origin, airline=airline, dep_delay=delay.astype(f32),
                dep_time=dep_time.astype(f32), day_of_week=day)


def _key(seed: int):
    """A key from any non-negative seed below 2**64."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def generate(cfg: dict, seed: int, chunk_rows: int = CHUNK_ROWS):
    """``(columns, valid)``: each column ``(n_blocks, block_rows)`` on the
    host, made on the default device one chunk at a time. The next chunk
    is dispatched before this one is copied to the host, so the chip
    works while the host copies; each chunk's device buffers are freed
    once copied."""
    n, br = cfg["rows"], cfg["block_rows"]
    nb = -(-n // br)
    chunk_rows = min(chunk_rows, nb * br)
    cols = {c: np.zeros(nb * br, dt) for c, dt in COLUMNS.items()}
    p = {k: jnp.asarray(v) for k, v in params(cfg).items()
         if not k.startswith("p_")}
    key = _key(seed)
    starts = list(range(0, n, chunk_rows))
    make = lambda c: _chunk(jax.random.fold_in(key, c), p, chunk_rows)
    pending = make(0)
    for c, lo in enumerate(starts):
        ready, pending = pending, (make(c + 1) if c + 1 < len(starts)
                                   else None)
        out = jax.device_get(ready)
        del ready
        m = min(chunk_rows, n - lo)
        for name, col in cols.items():
            col[lo:lo + m] = out[name][:m]
        del out
    valid = (np.arange(nb * br) < n).reshape(nb, br)
    return {c: v.reshape(nb, br) for c, v in cols.items()}, valid


def categorical(cfg: dict) -> dict:
    """Cardinality of each categorical column (codes run from 0)."""
    return {"origin": cfg["n_airports"], "airline": cfg["n_airlines"],
            "day_of_week": N_DAYS + 1}


def catalog() -> dict:
    """Range of each continuous column (the paper's load-time bounds)."""
    return {"dep_delay": DELAY_RANGE, "dep_time": DEP_TIME_RANGE}
