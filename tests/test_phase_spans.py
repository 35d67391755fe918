"""The phase spans of the snapshot / save / restore / load path, as a
``jax.profiler`` trace records them on the CPU and as the tracer's ring
holds them: every phase of one snapshot and one restore appears, nested in
its parent on one host line, the save's phases do not overlap, and the
``bytes`` tags are the datasets' sizes."""

import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.cfd.scenarios import karman_vortex
from repro.cfd.sim import FIELDS, Simulation
from repro.core.checkpoint import CheckpointManager
from repro.obs import TRACER
from repro.obs.trace import PHASE_SPANS

# each phase and the phase it nests in (None: outermost)
PARENT = {
    "sim.snapshot": None,
    "sim.fetch": "sim.snapshot",
    "sim.topology": "sim.snapshot",
    "ckpt.save": "sim.snapshot",
    "ckpt.plan": "ckpt.save",
    "ckpt.write": "ckpt.save",
    "ckpt.seal": "ckpt.save",
    "ckpt.commit": "ckpt.save",
    "ckpt.fsync": "ckpt.commit",
    "ckpt.restore": None,
    "th5.read": "ckpt.restore",
    "th5.verify": "th5.read",
    "sim.load": None,
    "sim.layout": "sim.load",
}
# AsyncCheckpointer's own phases, which a snapshot does not open
# (tests/test_async_stage_spans.py, tests/test_async_stage_gather.py)
ASYNC_SAVER = {"ckpt.stage", "ckpt.wait", "ckpt.fetch", "ckpt.assemble"}


@pytest.fixture()
def traced(tmp_path):
    """One snapshot and one restore of the 32 x 128 channel under a CPU
    profiler trace, with the ring on too."""
    cfg, state = karman_vortex(nx=32, ny=128)
    mgr = CheckpointManager(str(tmp_path / "run.th5"), common={"scenario": "karman"})
    sim = Simulation(cfg, state, mgr)
    sim.run(2)
    sim.snapshot()  # warm: the eager copies compile outside the trace
    sim.run(1)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    TRACER.configure(enabled=True, sample_every=1)
    TRACER.reset()
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        step = sim.snapshot()
        sim.restore(step)
    finally:
        jax.profiler.stop_trace()
        ring = TRACER.drain()
        TRACER.configure(enabled=False)
        TRACER.reset()
    (path,) = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"), recursive=True)
    lines = [
        [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)) for e in line.events if e.name in PARENT]
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
    ]
    events = [line for line in lines if line]
    assert len(events) == 1, "the phases lie on more than one host line"
    group = f"/simulation/step_{step:08d}/state/"
    sizes = {p: mgr.file.meta(group + p).nbytes for p in ("current_cell_data", "previous_cell_data", "cell_type", "t")}
    yield step, sim, mgr, events[0], ring, sizes
    mgr.close()


def _innermost(events, i):
    """Name of the shortest other event that contains event ``i``."""
    _, s, e, _ = events[i]
    around = [(b - a, n) for j, (n, a, b, _) in enumerate(events) if j != i and a <= s and e <= b]
    return min(around)[1] if around else None


def test_every_phase_nests_in_its_parent_on_one_host_line(traced):
    _, _, _, events, ring, _ = traced
    assert set(PARENT) | ASYNC_SAVER == set(PHASE_SPANS)
    assert {n for n, *_ in events} == set(PARENT)
    for i, (name, *_) in enumerate(events):
        assert _innermost(events, i) == PARENT[name], name
    # the ring holds the same trees, parent by id
    by_id = {s.span_id: s for s in ring}
    assert {s.name for s in ring} == set(PARENT)
    for s in ring:
        parent = by_id[s.parent_id].name if s.parent_id else None
        assert parent == PARENT[s.name], s.name


def test_plan_write_seal_and_commit_are_disjoint_inside_the_save(traced):
    _, _, _, events, _, _ = traced
    (save,) = [(a, b) for n, a, b, _ in events if n == "ckpt.save"]
    order = ["ckpt.plan", "ckpt.write", "ckpt.seal", "ckpt.commit"]
    parts = sorted((a, b, n) for n, a, b, _ in events if n in order)
    assert [n for *_, n in parts] == order
    assert save[0] <= parts[0][0] and parts[-1][1] <= save[1]
    for (_, end, _), (start, _, _) in zip(parts, parts[1:]):
        assert end <= start
    assert sum(1 for n, *_ in events if n == "ckpt.fsync") == 2


def test_bytes_tags_are_the_datasets_sizes(traced):
    step, sim, mgr, events, _, sizes = traced
    tags = lambda name: [t for n, _, _, t in events if n == name]  # noqa: E731
    state_bytes = sum(sizes.values())
    for name in ("ckpt.save", "ckpt.write", "ckpt.seal"):
        assert tags(name) == [{**({"step": step} if name == "ckpt.save" else {}), "bytes": state_bytes}]
    # the snapshot stages every leaf C-ordered: planning copies nothing
    assert tags("ckpt.plan") == [{"bytes": state_bytes, "copy_bytes": 0}]
    # the step counter, the four fields, the cell types
    assert sorted(t["bytes"] for t in tags("sim.fetch")) == sorted(
        [sim.state["t"].nbytes, sizes["current_cell_data"], sizes["cell_type"]]
    )
    assert tags("sim.topology") == [{"grids": sim.layout.G}]
    assert tags("ckpt.commit") == [{"generation": mgr.file.generation}]
    assert tags("ckpt.restore") == [{"step": step}]
    assert sorted(t["bytes"] for t in tags("th5.read")) == sorted(sizes.values())
    assert sorted(t["bytes"] for t in tags("th5.verify")) == sorted(sizes.values())
    layout = [t["bytes"] for t in tags("sim.layout")]
    assert len(layout) == len(FIELDS) and sum(layout) == sizes["current_cell_data"]
    assert np.all(np.asarray(layout) == layout[0])
