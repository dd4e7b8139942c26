"""Plain HDRF (Petroni et al., CIKM 2015), the reference for HDRF cells.

The partial-degree variant as published: for each edge (u, v) in arrival
order, first count it into both endpoints' partial degrees, then score
every partition p

    g(x, p)  = 1 + (1 - theta_x)  if x already has a replica on p, else 0,
               theta_u = d_u / (d_u + d_v),  theta_v = 1 - theta_u
    bal(p)   = (maxload - load_p) / (eps + maxload - minload)
    score(p) = g(u, p) + g(v, p) + lambda * bal(p)

and place the edge on the first partition of highest score.  A full load
tie (maxload = minload) gives every partition a zero balance term, which
is what eps gives in exact arithmetic (in float32, eps = 1e-3 vanishes
once loads pass 2**15).  A self-loop counts into the partial degree and is
not placed.

The scores are floating point, so the fold runs on the device in
``jax.numpy``, one edge per step of a ``lax.scan``: XLA:TPU's f32 division
differs from IEEE division, and a host reference would disagree with any
correct program there.  It imports nothing of the program and takes
nothing the program made.  ``dtype="bfloat16"`` scores in bfloat16, the
step below the float32 the configuration states: that is the control.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# The comparison: every edge's partition id, exactly.
LIMITS = {"parts_mismatch": 0}

CAPTURES: dict = {}

EPS = 1e-3
CHUNK = 1 << 16


@partial(jax.jit, static_argnames=("dtype",), donate_argnums=(0,))
def _fold(state, src, dst, real, lam, *, dtype):
    dt = jnp.dtype(dtype)
    lam = lam.astype(dt)

    def step(st, edge):
        load, rep, pd = st
        u, v, ok = edge
        one = ok.astype(jnp.int32)
        pd = pd.at[u].add(one).at[v].add(one)
        du = pd[u].astype(dt)
        dv = pd[v].astype(dt)
        theta_u = du / (du + dv)
        theta_v = 1.0 - theta_u
        g_u = jnp.where(rep[u] > 0, 1.0 + (1.0 - theta_u), 0.0).astype(dt)
        g_v = jnp.where(rep[v] > 0, 1.0 + (1.0 - theta_v), 0.0).astype(dt)
        loadf = load.astype(dt)
        maxl = jnp.max(loadf)
        minl = jnp.min(loadf)
        den = EPS + maxl - minl
        bal = (maxl - loadf) / jnp.where(den > 0, den, 1.0)
        score = g_u + g_v + lam * bal
        pick = jnp.argmax(score).astype(jnp.int32)
        placed = ok & (u != v)
        w = placed.astype(jnp.int32)
        load = load.at[pick].add(w)
        rep = rep.at[u, pick].add(w).at[v, pick].add(w)
        return (load, rep, pd), jnp.where(placed, pick, -1)

    return jax.lax.scan(step, state, (src, dst, real))


def partition(src, dst, n_vertices, k, seed, params, *, dtype="float32"):
    """(parts, internals) for one job on (src, dst) in arrival order."""
    src = np.asarray(src, np.int32)
    dst = np.asarray(dst, np.int32)
    E = src.size
    state = (jnp.zeros((k,), jnp.int32),
             jnp.zeros((n_vertices, k), jnp.int32),
             jnp.zeros((n_vertices,), jnp.int32))
    lam = jnp.float32(params["lam"])
    out = []
    for start in range(0, E, CHUNK):
        s = src[start:start + CHUNK]
        d = dst[start:start + CHUNK]
        n = s.size
        pad = CHUNK - n
        real = np.arange(CHUNK) < n
        state, parts = _fold(state, jnp.asarray(np.pad(s, (0, pad))),
                             jnp.asarray(np.pad(d, (0, pad))),
                             jnp.asarray(real), lam, dtype=dtype)
        out.append(parts[:n])
    parts = np.asarray(jnp.concatenate(out)) if out else np.zeros(0, np.int32)
    return parts, {}
