"""The paper's two TRS scenarios (§4).

1. **Kármán vortex street** — Schäfer–Turek channel benchmark: 2-D channel,
   cylinder obstacle near the inlet, Re = 100 → unsteady vortex shedding.
   TRS use: simulate, roll back to t₁, move the obstacle / add a second
   one, continue as branches.

2. **Operation theatre (thermally coupled)** — simplified 2-D room: inflow
   along one full wall, slightly open "door" outlet on the opposite wall,
   heated bodies (lamps T=324.66 K, humans 299.50 K, equipment 290.16 K).
   TRS use: converge, roll back, raise the lamp temperature by 50 K,
   continue — at ~1/3 the cost of a full rerun.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .multigrid import MGConfig
from .projection import FLUID, INFLOW, OUTFLOW, SOLID, WALL, FluidConfig

LAMP_T = 324.66
HUMAN_T = 299.50
OBJECT_T = 290.16
ROOM_T = 290.16


def karman_vortex(nx: int = 64, ny: int = 256, re: float = 100.0) -> tuple[FluidConfig, dict]:
    """Channel with a cylinder at ~1/4 length; Re = u·D/ν = 100."""
    h = 1.0 / nx  # channel height 1
    D = 0.25  # cylinder diameter (in channel heights)
    u_in = 1.0
    nu = u_in * D / re
    cfg = FluidConfig(
        nx=nx,
        ny=ny,
        h=h,
        # explicit Euler: advective CFL 0.2, and ν·dt/h² ≤ 0.2 < 1/4 (2-D
        # diffusion), which binds from nx ≈ 400 on
        dt=min(0.2 * h / u_in, 0.2 * h * h / nu),
        nu=nu,
        u_in=u_in,
        mg=MGConfig(n_pre=2, n_post=2),
        mg_cycles=4,
    )
    cell_type = np.zeros((nx, ny), np.int8)
    cell_type[0, :] = WALL
    cell_type[-1, :] = WALL
    cell_type[:, 0] = INFLOW
    cell_type[:, -1] = OUTFLOW
    state = {
        "u": jnp.full((nx, ny), u_in, jnp.float32),
        "v": jnp.zeros((nx, ny), jnp.float32),
        "p": jnp.zeros((nx, ny), jnp.float32),
        "T": jnp.full((nx, ny), ROOM_T, jnp.float32),
        "T_solid": jnp.full((nx, ny), ROOM_T, jnp.float32),
        "cell_type": jnp.asarray(add_cylinder(cell_type, nx, ny, cx=nx // 2, cy=ny // 4, d=D / h)),
        "t": jnp.zeros((), jnp.float32),
    }
    return cfg, state


def add_cylinder(cell_type: np.ndarray, nx: int, ny: int, cx: int, cy: int, d: float) -> np.ndarray:
    """Immersed cylinder obstacle (the TRS 'move the obstacle' knob)."""
    ct = np.array(cell_type, copy=True)
    ii, jj = np.meshgrid(np.arange(nx), np.arange(ny), indexing="ij")
    mask = (ii - cx) ** 2 + (jj - cy) ** 2 <= (d / 2) ** 2
    ct[mask] = SOLID
    return ct


def operation_theatre(nx: int = 64, ny: int = 64, lamp_T: float = LAMP_T) -> tuple[FluidConfig, dict]:
    """Thermally coupled room: full-wall inflow (left), door outlet (right),
    lamp + two 'humans' + table as heated solids."""
    h = 4.0 / nx  # 4 m room
    u_in = 0.2
    cfg = FluidConfig(
        nx=nx,
        ny=ny,
        h=h,
        dt=0.1 * h / u_in,
        nu=1.5e-3,
        u_in=u_in,
        thermal=True,
        alpha=2.0e-3,
        beta=3.4e-3,
        T_ref=ROOM_T,
        mg=MGConfig(),
        mg_cycles=4,
    )
    ct = np.zeros((nx, ny), np.int8)
    Ts = np.full((nx, ny), ROOM_T, np.float32)
    ct[0, :] = WALL
    ct[-1, :] = WALL
    ct[:, 0] = INFLOW
    # door: lower quarter of the right wall open
    ct[:, -1] = WALL
    ct[3 * nx // 4 :, -1] = OUTFLOW
    # lamp near the ceiling centre
    lamp = (slice(nx // 8, nx // 8 + 3), slice(ny // 2 - 4, ny // 2 + 4))
    ct[lamp] = SOLID
    Ts[lamp] = lamp_T
    # operating table + patient (centre)
    table = (slice(nx // 2, nx // 2 + 4), slice(ny // 2 - 8, ny // 2 + 8))
    ct[table] = SOLID
    Ts[table] = HUMAN_T
    # two assistants
    for off in (-12, 12):
        body = (slice(nx // 2 - 6, nx // 2 + 8), slice(ny // 2 + off - 2, ny // 2 + off))
        ct[body] = SOLID
        Ts[body] = HUMAN_T
    state = {
        "u": jnp.full((nx, ny), u_in, jnp.float32),
        "v": jnp.zeros((nx, ny), jnp.float32),
        "p": jnp.zeros((nx, ny), jnp.float32),
        "T": jnp.full((nx, ny), ROOM_T, jnp.float32),
        "T_solid": jnp.asarray(Ts),
        "cell_type": jnp.asarray(ct),
        "t": jnp.zeros((), jnp.float32),
    }
    return cfg, state
