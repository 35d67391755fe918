"""The fluid solver's device path: the snapshot loop and its steering,
through ``repro.cfd.sim.Simulation`` and a ``CheckpointManager``.

Loops (a traffic mix names one in ``loop``):

- ``snapshot``: a closed loop of ``Simulation.snapshot()`` then
  ``Simulation.run(every)``, the calls ``run(..., snapshot_every)`` makes,
  with ``every`` the steps in which the inflow carries the flow across
  ``advected_cells_per_snapshot`` cells.  One period is a run and the
  snapshot after it.
- ``steer``: two snapshots in set-up, then restores of them in turn, each
  followed by ``run(steps_after_restore)``: the paper's steering and
  time-reversible rollback.  One period is a restore and its steps.

The check compares what the timed path produced with the plain reference
beside the configuration.  The loop keeps no device state for it: after
each snapshot or restore it keeps a fingerprint of the device fields
(:func:`fingerprint`, a few words).  Once the window has closed, every
snapshot is read back from the file, laid out as fields by the reference's
own code and fingerprinted again, and every restore's fingerprint is held
against its snapshot's (the configuration's snapshots are lossless).  The
fields read back, and the last state of a steering run, are compared with
the reference solver run from the same seed, within the limit the
configuration states.

A configuration's ``control`` key, set only when a limit is calibrated,
puts a control in the program's place: ``bf16_reference`` steps with the
reference computed in bfloat16, the precision below the configuration's
float32.  ``codec`` switches on the program's own lossy snapshot path.
"""

from __future__ import annotations

import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import Check, Outcome, Restore, Save, span

FINGERPRINTED = ("u", "v", "p", "T", "t", "cell_type")
SOLVED = ("u", "v", "p")


class FsyncClock:
    """``os.fsync`` timed: the seconds spent in it so far, in this process."""

    def __init__(self, fsync):
        self.fsync, self.s = fsync, 0.0

    def __call__(self, fd):
        t = time.perf_counter()
        try:
            return self.fsync(fd)
        finally:
            self.s += time.perf_counter() - t


def fsync_clock() -> FsyncClock:
    if not isinstance(os.fsync, FsyncClock):
        os.fsync = FsyncClock(os.fsync)
    return os.fsync


def simulation(c: dict, ref, seed: int, path: str):
    """The program under test: ``Simulation`` on the reference's initial
    state, writing through a manager that records what each call did."""
    from repro.cfd.multigrid import MGConfig
    from repro.cfd.projection import FluidConfig
    from repro.cfd.sim import Simulation
    from repro.core.checkpoint import CheckpointManager, CodecPolicy

    fsync = fsync_clock()

    class Recording(CheckpointManager):
        """Keeps each save's ``SaveResult`` and time in fsync, and each
        restore's read time."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.results, self.fsyncs, self.reads = [], [], []

        def save(self, step, state, **kw):
            f0 = fsync.s
            with span("save"):
                result = super().save(step, state, **kw)
            self.results.append(result)
            self.fsyncs.append(fsync.s - f0)
            return result

        def restore(self, step=None, verify=True):
            t = time.perf_counter()
            with span("read"):
                out = super().restore(step, verify=verify)
            self.reads.append(time.perf_counter() - t)
            return out

    g = ref.geometry(c)
    cfg = FluidConfig(
        nx=c["nx"], ny=c["ny"], h=g["h"], dt=g["dt"], nu=g["nu"], u_in=c["u_in"],
        mg=MGConfig(**c["mg"]), mg_cycles=c["mg_cycles"],
    )
    policy = CodecPolicy(default=c["codec"]) if c.get("codec", "none") != "none" else None
    manager = Recording(path, common={"scenario": c["name"], "nx": c["nx"], "ny": c["ny"]}, codec_policy=policy)
    state = ref.initial_state(c, seed)
    sim = Simulation(cfg, state, manager, n_block=c["n_block"], n_ranks=c["n_ranks"])
    if c.get("control"):
        sim._step_fn = CONTROLS[c["control"]](c, ref)
    return sim


def bf16_reference(c: dict, ref):
    """The control step: the reference in bfloat16 (the clock in float32),
    its fields handed back in the program's float32."""
    step = ref.make_step(c)
    low = ("u", "v", "p", "T", "T_solid")

    @jax.jit
    def run(state):
        out = step({k: v.astype(jnp.bfloat16) if k in low else v for k, v in state.items()})
        return {k: v.astype(state[k].dtype) for k, v in out.items()}

    return run


CONTROLS = {"bf16_reference": bf16_reference}


@jax.jit
def fingerprint(fields: dict) -> jax.Array:
    """Two position-weighted sums, mod 2**32, of each field's bits: one
    element changed, or moved, changes the first (its weights are odd)."""
    out = []
    for f in FINGERPRINTED:
        x = fields[f]
        bits = (jax.lax.bitcast_convert_type(x, jnp.uint32) if x.dtype.itemsize == 4 else x.astype(jnp.uint32)).ravel()
        i = jax.lax.iota(jnp.uint32, bits.size)
        w1, w2 = 2 * i + 1, i * jnp.uint32(0x9E3779B1) + jnp.uint32(0x7F4A7C15)
        out.append(jnp.stack([jnp.sum(bits * w1, dtype=jnp.uint32), jnp.sum((bits ^ (bits >> 13)) * w2, dtype=jnp.uint32)]))
    return jnp.stack(out)


def fingerprint_of(state: dict) -> jax.Array:
    return fingerprint({f: state[f] for f in FINGERPRINTED})


def fields_differ(a: jax.Array, b: jax.Array) -> int:
    """How many of the fingerprinted fields differ between two fingerprints."""
    return int(np.count_nonzero(np.any(np.asarray(a) != np.asarray(b), axis=-1)))


def _same_bits(a, b) -> bool:
    a, b = np.ascontiguousarray(a), np.ascontiguousarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and np.array_equal(a.view(np.uint8), b.view(np.uint8))


def field_errors(state: dict, want: dict) -> dict[str, float]:
    """Each solved field's largest gap, against the reference field's
    largest magnitude."""
    out = {}
    for f in SOLVED:
        a, b = jnp.asarray(state[f], jnp.float32), want[f].astype(jnp.float32)
        out[f] = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
    return out


def _worst(acc: dict[str, float], new: dict[str, float]) -> dict[str, float]:
    return {f: max(acc.get(f, 0.0), v) for f, v in new.items()}


def reference_at(c: dict, ref, seed: int, steps):
    """Yield ``(n, state)`` of the reference run from ``seed`` at each step
    count ``n`` in ``steps``, in increasing order."""
    step = ref.make_step(c)
    s = ref.initial_state(c, seed)
    last = max(steps)
    for n in range(last + 1):
        if n in steps:
            yield n, s
        if n < last:
            s = step(s)


def snapshot_every(c: dict, ref, cells: float) -> int:
    """Steps in which the inflow carries the flow across ``cells`` cells."""
    g = ref.geometry(c)
    return max(1, math.ceil(cells * g["h"] / (c["u_in"] * g["dt"]) - 1e-9))


def _outcome(c: dict, checks: dict, errors: dict, steps, attempted: int, failed: int) -> Outcome:
    limits = c["limits"]
    checks = {k: Check(v, limits[k]) for k, v in checks.items()}
    checks["solver_err"] = Check(max(errors.values()), limits["solver_err"])
    return Outcome(checks, attempted, failed, detail={"solver_err_by_field": errors, "steps": sorted(steps)})


def snapshot_loop(rec, ref):
    c = rec.config
    every = snapshot_every(c, ref, float(rec.traffic["advected_cells_per_snapshot"]))
    sim = simulation(c, ref, rec.seed, f"{rec.workdir}/run.th5")
    rec.mark("state")
    # set-up: one step builds the solver, one snapshot warms the staging, the
    # writer and the fingerprint; the window opens when they return
    sim.run(1)
    rec.mark("first_step")
    n = 1
    kept = [(n, sim.snapshot(), fingerprint_of(sim.state))]  # (steps run, step written, fingerprint)
    jax.block_until_ready(kept[0][2])
    with rec.window():
        while True:
            with span("step"):
                sim.run(every)
            n += every
            rec.steps += every
            t = rec.clock()
            with span("snapshot"):
                written = sim.snapshot()
            m = sim.manager
            rec.saves.append(Save(rec.clock() - t, m.results[-1].wall_s, m.fsyncs[-1]))
            kept.append((n, written, fingerprint_of(sim.state)))
            if rec.boundary():
                break

    def check() -> Outcome:
        sim.state = None
        steps = {n for n, _, _ in kept}
        mismatched, failed, errors, prev = 0, 0, {}, None
        for i, ((n, written, fp), (_, want)) in enumerate(zip(kept, reference_at(c, ref, rec.seed, steps))):
            bad = int(written != n)
            if written in sim.manager.steps():
                _, got = sim.manager.restore(written)
                fields = {k: jnp.asarray(v) for k, v in ref.fields_from_snapshot(c, got).items()}
                bad += fields_differ(fingerprint(fields), fp)
                cells = np.asarray(got["current_cell_data"])
                bad += int(not _same_bits(got["previous_cell_data"], cells if prev is None else prev))
                prev = cells
                errors = _worst(errors, field_errors(fields, want))
            else:
                bad += len(FINGERPRINTED) + 1
                errors = _worst(errors, dict.fromkeys(SOLVED, math.inf))
            mismatched += bad
            failed += int(bad > 0 and i > 0)
        sim.manager.close()
        return _outcome(c, {"file_mismatch": mismatched}, errors, steps, len(rec.saves), failed)

    return check


def steer_loop(rec, ref):
    c, tr = rec.config, rec.traffic
    after = int(tr["steps_after_restore"])
    sim = simulation(c, ref, rec.seed, f"{rec.workdir}/run.th5")
    rec.mark("state")
    sim.run(1)
    rec.mark("first_step")
    a, fp_a = sim.snapshot(), fingerprint_of(sim.state)
    sim.run(int(tr["steps_between_snapshots"]))
    b, fp_b = sim.snapshot(), fingerprint_of(sim.state)
    rec.mark("snapshots")
    prints = {a: fp_a, b: fp_b}
    steps_of = {a: 1, b: 1 + int(tr["steps_between_snapshots"])}
    # warm the restore path and the step after it
    sim.restore(a)
    sim.run(after)
    restored = []  # (step restored, fingerprint of the fields on the device)
    with rec.window():
        while True:
            target = (b, a)[len(restored) % 2]
            t = rec.clock()
            with span("restore"):
                sim.restore(target)
                jax.block_until_ready([sim.state[f] for f in FINGERPRINTED])
            rec.restores.append(Restore(rec.clock() - t, sim.manager.reads[-1]))
            restored.append((target, fingerprint_of(sim.state)))
            with span("step"):
                sim.run(after)
            rec.steps += after
            if rec.boundary():
                break

    def check() -> Outcome:
        mismatched, failed = 0, 0
        for target, fp in restored:
            bad = fields_differ(fp, prints[target])
            mismatched += bad
            failed += int(bad > 0)
        n = steps_of[restored[-1][0]] + after
        last = {f: sim.state[f] for f in SOLVED}
        sim.state = None
        sim.manager.close()
        errors = {}
        for _, want in reference_at(c, ref, rec.seed, {n}):
            errors = field_errors(last, want)
        return _outcome(c, {"restore_mismatch": mismatched}, errors, {n}, len(rec.restores), failed)

    return check


LOOPS = {"snapshot": snapshot_loop, "steer": steer_loop}
