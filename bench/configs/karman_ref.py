"""Plain reference of the Kármán channel: its state from the seed, one step
of the solver, and the row-per-d-grid layout of a snapshot.

Written from the method's description, on composite (nx, ny) fields in plain
``jax.numpy``, and importing nothing of the program:

- explicit-Euler fractional step (Chorin projection): momentum with a
  5-point Laplacian and first-order upwind advection, then the pressure
  Poisson equation, then the projection u -= dt·∇p;
- the Poisson solve: ``mg_cycles`` V-cycles from p = 0, weighted Jacobi
  with p = 0 on the cell faces of the domain (ghost = −interior), 2×2
  averaging restriction, bilinear (9/3/3/1) prolongation with zero ghosts,
  the sweeps doubled per level below the finest, ``n_coarse`` sweeps on the
  coarsest level;
- boundary conditions: plug inflow on the first column, zero-gradient
  outflow on the last, no slip on the walls and the cylinder.

Difference operators wrap around the domain, as the solver's do; the
boundary conditions overwrite the wrapped values at the walls, the inlet
and the outlet.  ``dtype`` sets the precision of every field and
operation (float32 as the configuration states; bfloat16 is the control).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

FLUID, SOLID, INFLOW, OUTFLOW, WALL = 0, 1, 2, 3, 4
FIELDS = ("u", "v", "p", "T")


def geometry(c: dict) -> dict:
    nx, ny = c["nx"], c["ny"]
    h = 1.0 / nx  # channel height 1
    nu = c["u_in"] * c["diameter"] / c["re"]
    return {
        "h": h,
        "nu": nu,
        "dt": min(c["cfl"] * h / c["u_in"], c["diffusion_number"] * h * h / nu),
        "cx": int(nx * c["cylinder_row_frac"]),
        "cy": int(ny * c["cylinder_col_frac"]),
        # the cylinder holds the cells whose integer squared distance from
        # its centre is at most (d/2)², d = diameter / h
        "r2": int(math.floor((c["diameter"] / h / 2) ** 2)),
    }


def cell_types(c: dict) -> jax.Array:
    g = geometry(c)
    i = jax.lax.broadcasted_iota(jnp.int32, (c["nx"], c["ny"]), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c["nx"], c["ny"]), 1)
    ct = jnp.zeros((c["nx"], c["ny"]), jnp.int8)
    ct = jnp.where((i == 0) | (i == c["nx"] - 1), WALL, ct)
    ct = jnp.where(j == 0, INFLOW, ct)
    ct = jnp.where(j == c["ny"] - 1, OUTFLOW, ct)
    ct = jnp.where((i - g["cx"]) ** 2 + (j - g["cy"]) ** 2 <= g["r2"], SOLID, ct)
    return ct.astype(jnp.int8)


def _initial(c: dict, seed_parts: jax.Array, dtype) -> dict:
    key = jax.random.fold_in(jax.random.PRNGKey(seed_parts[0]), seed_parts[1])
    ku, kv = jax.random.split(key)
    shape = (c["nx"], c["ny"])
    a = c["perturbation"]
    return {
        "u": (c["u_in"] * (1.0 + a * jax.random.normal(ku, shape, jnp.float32))).astype(dtype),
        "v": (c["u_in"] * a * jax.random.normal(kv, shape, jnp.float32)).astype(dtype),
        "p": jnp.zeros(shape, dtype),
        "T": jnp.full(shape, c["room_T"], dtype),
        "T_solid": jnp.full(shape, c["room_T"], dtype),
        "cell_type": cell_types(c),
        "t": jnp.zeros((), dtype),
    }


def initial_state(c: dict, seed: int, dtype=jnp.float32) -> dict:
    """The channel at rest with plug flow, perturbed by 1 % noise drawn from
    the seed; made on the device in one jitted call."""
    parts = jnp.asarray([seed % (1 << 32), (seed >> 32) % (1 << 31)], jnp.uint32)
    return jax.jit(partial(_initial, c, dtype=dtype))(parts)


# -- the solver ----------------------------------------------------------------


def _lap(f, h):
    return (jnp.roll(f, 1, 0) + jnp.roll(f, -1, 0) + jnp.roll(f, 1, 1) + jnp.roll(f, -1, 1) - 4 * f) / (h * h)


def _advect(f, u, v, h):
    back_x = (f - jnp.roll(f, 1, 1)) / h
    fwd_x = (jnp.roll(f, -1, 1) - f) / h
    back_y = (f - jnp.roll(f, 1, 0)) / h
    fwd_y = (jnp.roll(f, -1, 0) - f) / h
    return u * jnp.where(u > 0, back_x, fwd_x) + v * jnp.where(v > 0, back_y, fwd_y)


def _bcs(c, u, v, ct):
    u = jnp.where(ct == INFLOW, c["u_in"], u)
    v = jnp.where(ct == INFLOW, 0.0, v)
    u = u.at[:, -1].set(u[:, -2])
    v = v.at[:, -1].set(v[:, -2])
    solid = (ct == SOLID) | (ct == WALL)
    return jnp.where(solid, 0.0, u), jnp.where(solid, 0.0, v)


def _ghosts(p):
    """Pad by one cell with ghost = −(adjacent interior): p = 0 on the faces."""
    p = jnp.concatenate([-p[:1], p, -p[-1:]], axis=0)
    return jnp.concatenate([-p[:, :1], p, -p[:, -1:]], axis=1)


def _jacobi(p, f, h, sweeps, omega):
    h2 = h * h

    def sweep(p, _):
        g = _ghosts(p)
        new = 0.25 * (g[:-2, 1:-1] + g[2:, 1:-1] + g[1:-1, :-2] + g[1:-1, 2:] - h2 * f)
        return (1.0 - omega) * p + omega * new, None

    return jax.lax.scan(sweep, p, None, length=sweeps)[0]


def _residual(p, f, h):
    g = _ghosts(p)
    lap = (g[:-2, 1:-1] + g[2:, 1:-1] + g[1:-1, :-2] + g[1:-1, 2:] - 4.0 * p) / (h * h)
    return f - lap


def _restrict(r):
    H, W = r.shape
    return r.reshape(H // 2, 2, W // 2, 2).mean(axis=(1, 3))


def _prolong(e):
    z = jnp.pad(e, 1)
    c, up, down = z[1:-1, 1:-1], z[:-2, 1:-1], z[2:, 1:-1]
    left, right = z[1:-1, :-2], z[1:-1, 2:]
    f00 = (9 * c + 3 * up + 3 * left + z[:-2, :-2]) / 16.0
    f01 = (9 * c + 3 * up + 3 * right + z[:-2, 2:]) / 16.0
    f10 = (9 * c + 3 * down + 3 * left + z[2:, :-2]) / 16.0
    f11 = (9 * c + 3 * down + 3 * right + z[2:, 2:]) / 16.0
    H, W = e.shape
    top = jnp.stack([f00, f01], axis=-1).reshape(H, 2 * W)
    bottom = jnp.stack([f10, f11], axis=-1).reshape(H, 2 * W)
    return jnp.stack([top, bottom], axis=1).reshape(2 * H, 2 * W)


def _v_cycle(p, f, h, mg, level=0):
    scale = 1 + level if mg["double_coarse_smooth"] else 1
    if min(p.shape) <= mg["coarse_size"]:
        return _jacobi(p, f, h, mg["n_coarse"], mg["omega"])
    p = _jacobi(p, f, h, mg["n_pre"] * scale, mg["omega"])
    r = _restrict(_residual(p, f, h))
    e = _v_cycle(jnp.zeros_like(r), r, 2 * h, mg, level + 1)
    p = p + _prolong(e)
    return _jacobi(p, f, h, mg["n_post"] * scale, mg["omega"])


def _step(c, state):
    g = geometry(c)
    h, dt, nu = g["h"], g["dt"], g["nu"]
    ct = state["cell_type"]
    u, v = _bcs(c, state["u"], state["v"], ct)
    u_s = u + dt * (nu * _lap(u, h) - _advect(u, u, v, h))
    v_s = v + dt * (nu * _lap(v, h) - _advect(v, u, v, h))
    u_s, v_s = _bcs(c, u_s, v_s, ct)
    div = (jnp.roll(u_s, -1, 1) - jnp.roll(u_s, 1, 1)) / (2 * h) + (jnp.roll(v_s, -1, 0) - jnp.roll(v_s, 1, 0)) / (2 * h)
    f = div / dt
    p = jnp.zeros_like(f)
    for _ in range(c["mg_cycles"]):
        p = _v_cycle(p, f, h, c["mg"])
    dpdx = (jnp.roll(p, -1, 1) - jnp.roll(p, 1, 1)) / (2 * h)
    dpdy = (jnp.roll(p, -1, 0) - jnp.roll(p, 1, 0)) / (2 * h)
    u_n, v_n = _bcs(c, u_s - dt * dpdx, v_s - dt * dpdy, ct)
    return {**state, "u": u_n, "v": v_n, "p": p, "t": state["t"] + dt}


def make_step(c: dict):
    """The jitted reference step; the dtype is the state's own."""
    return jax.jit(partial(_step, c))


# -- the snapshot layout ------------------------------------------------------------


def blocked_rows(c: dict, field) -> np.ndarray:
    """(nx, ny) → (G, n²): one row per d-grid of n × n cells, d-grids in
    row-major order, cells row-major inside a d-grid."""
    n = c["n_block"]
    gx, gy = c["nx"] // n, c["ny"] // n
    a = np.asarray(field)
    return a.reshape(gx, n, gy, n).transpose(0, 2, 1, 3).reshape(gx * gy, n * n)


def field_of_rows(c: dict, rows) -> np.ndarray:
    """(G, n²) → (nx, ny): the inverse of :func:`blocked_rows`."""
    n = c["n_block"]
    gx, gy = c["nx"] // n, c["ny"] // n
    return np.asarray(rows).reshape(gx, gy, n, n).transpose(0, 2, 1, 3).reshape(gx * n, gy * n)


def fields_from_snapshot(c: dict, snap: dict) -> dict:
    """The fields a lossless snapshot holds, laid out as the state's
    (nx, ny) fields: u, v, p, T from ``current_cell_data``, ``cell_type``
    and the clock ``t`` in the state's float32."""
    cells = np.asarray(snap["current_cell_data"])
    out = {f: field_of_rows(c, cells[:, :, i]) for i, f in enumerate(FIELDS)}
    out["cell_type"] = field_of_rows(c, snap["cell_type"])
    out["t"] = np.float32(snap["t"])
    return out
