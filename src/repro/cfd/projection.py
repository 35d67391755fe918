"""Incompressible Navier–Stokes via Chorin projection (paper §2.1).

Explicit-Euler fractional step on a collocated grid:

    u* = u + dt·(ν ∇²u − (u·∇)u + b)         (momentum, upwind advection)
    ∇²p = ∇·u* / dt                           (pressure Poisson, multigrid)
    u  = u* − dt·∇p                           (projection → ∇·u = 0)

Thermal coupling (operation-theatre scenario) replaces b with the
Boussinesq buoyancy term ρ∞·β·(T−T∞)·g and advances the energy equation
(3) with the same upwind/diffusion operators.  Obstacles are immersed
boundaries: cell_type masks force u=v=0 (and Dirichlet T) inside solids.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .multigrid import MGConfig, solve_poisson

FLUID, SOLID, INFLOW, OUTFLOW, WALL = 0, 1, 2, 3, 4


@dataclass(frozen=True)
class FluidConfig:
    nx: int  # rows (y direction held in axis 0)
    ny: int  # cols (x / streamwise direction in axis 1)
    h: float
    dt: float
    nu: float = 1e-3  # kinematic viscosity
    u_in: float = 1.0  # inflow velocity (streamwise, axis-1)
    thermal: bool = False
    alpha: float = 1.4e-4  # heat diffusivity
    beta: float = 3.4e-3  # thermal expansion
    T_ref: float = 293.0
    gravity: float = 9.81
    mg: MGConfig = MGConfig()
    mg_cycles: int = 4


def _lap(f: jax.Array, h: float) -> jax.Array:
    return (
        jnp.roll(f, 1, 0) + jnp.roll(f, -1, 0) + jnp.roll(f, 1, 1) + jnp.roll(f, -1, 1) - 4 * f
    ) / (h * h)


def _upwind_adv(f: jax.Array, u: jax.Array, v: jax.Array, h: float) -> jax.Array:
    """(u·∇)f with first-order upwinding.  u = axis-1 velocity, v = axis-0."""
    dfdx_m = (f - jnp.roll(f, 1, 1)) / h
    dfdx_p = (jnp.roll(f, -1, 1) - f) / h
    dfdy_m = (f - jnp.roll(f, 1, 0)) / h
    dfdy_p = (jnp.roll(f, -1, 0) - f) / h
    return u * jnp.where(u > 0, dfdx_m, dfdx_p) + v * jnp.where(v > 0, dfdy_m, dfdy_p)


def _grad(p: jax.Array, h: float) -> tuple[jax.Array, jax.Array]:
    dpdx = (jnp.roll(p, -1, 1) - jnp.roll(p, 1, 1)) / (2 * h)
    dpdy = (jnp.roll(p, -1, 0) - jnp.roll(p, 1, 0)) / (2 * h)
    return dpdx, dpdy


def divergence(u: jax.Array, v: jax.Array, h: float) -> jax.Array:
    return (jnp.roll(u, -1, 1) - jnp.roll(u, 1, 1)) / (2 * h) + (
        jnp.roll(v, -1, 0) - jnp.roll(v, 1, 0)
    ) / (2 * h)


def apply_velocity_bcs(cfg: FluidConfig, u, v, cell_type):
    # inflow column (left edge): plug flow
    u = jnp.where(cell_type == INFLOW, cfg.u_in, u)
    v = jnp.where(cell_type == INFLOW, 0.0, v)
    # outflow (right edge): zero-gradient
    u = u.at[:, -1].set(u[:, -2])
    v = v.at[:, -1].set(v[:, -2])
    # solid walls + obstacle: no slip
    solid = (cell_type == SOLID) | (cell_type == WALL)
    u = jnp.where(solid, 0.0, u)
    v = jnp.where(solid, 0.0, v)
    return u, v


def step(cfg: FluidConfig, state: dict) -> dict:
    """One fractional-step update.  state: u, v, p, T, cell_type, t."""
    u, v, p, T, cell_type = state["u"], state["v"], state["p"], state["T"], state["cell_type"]
    dt, h = cfg.dt, cfg.h
    u, v = apply_velocity_bcs(cfg, u, v, cell_type)

    bx = jnp.zeros_like(u)
    by = jnp.zeros_like(v)
    if cfg.thermal:
        by = by - cfg.gravity * cfg.beta * (T - cfg.T_ref)  # Boussinesq

    u_star = u + dt * (cfg.nu * _lap(u, h) - _upwind_adv(u, u, v, h) + bx)
    v_star = v + dt * (cfg.nu * _lap(v, h) - _upwind_adv(v, u, v, h) + by)
    u_star, v_star = apply_velocity_bcs(cfg, u_star, v_star, cell_type)

    rhs = divergence(u_star, v_star, h) / dt
    p = solve_poisson(rhs, h, cfg.mg, cycles=cfg.mg_cycles)

    dpdx, dpdy = _grad(p, h)
    u_new = u_star - dt * dpdx
    v_new = v_star - dt * dpdy
    u_new, v_new = apply_velocity_bcs(cfg, u_new, v_new, cell_type)

    if cfg.thermal:
        T = T + dt * (cfg.alpha * _lap(T, h) - _upwind_adv(T, u_new, v_new, h))
        T = jnp.where(cell_type == SOLID, state["T_solid"], T)
        T = jnp.where(cell_type == INFLOW, cfg.T_ref, T)

    return {
        **state,
        "u": u_new,
        "v": v_new,
        "p": p,
        "T": T,
        "t": state["t"] + dt,
    }


from functools import lru_cache


@lru_cache(maxsize=32)
def make_step(cfg: FluidConfig):
    """jit-compiled step, cached per config — TRS branches with an unchanged
    FluidConfig reuse the compiled executable (reload stays metadata-cheap).
    A named closure, so traces call the executable ``jit_fluid_step``."""

    def fluid_step(state: dict) -> dict:
        return step(cfg, state)

    return jax.jit(fluid_step)
