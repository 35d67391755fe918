"""The checkpointed training cell at a small size on the CPU: the
``train_ckpt`` loop on a 2 x 2 mesh of forced host devices, driven through
the harness's ``Run`` on a fake clock; a byte flipped in what a save wrote
makes the run not correct; its five per-layer readers; the model FLOPs that
``train_step_mfu`` counts."""

import json
import math
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402
from bench.harness import Run, Save  # noqa: E402

CELL = "train_qwen3_4l_v4_2x2_ckpt"
# the smoke widths of qwen3 (repro.configs.qwen3_8b.smoke_config)
SMALL = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
         "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 128}
SMALL_TRAFFIC = {"batch": 8, "seq_len": 32, "save_every_steps": 2}  # two blocks of 4: a half batch
SEED = 2**33 + 17  # wider than 32 bits, as a run's seed may be

LOOP = r"""
import json, os, sys, tempfile
sys.path[:0] = [os.getcwd(), os.path.join(os.getcwd(), "src")]
from bench.harness import Run
from bench.spec import Benchmark
from repro.core import checkpoint, tree_ser
from repro.obs import TRACER

fault = sys.argv[1]
cell = Benchmark(os.getcwd()).cell(%(cell)r)
cell.config.update(%(small)r)
cell.traffic.update(%(traffic)r)
if fault == "control":
    cell.config["control"] = "float8_reference"


class Tick:
    "A clock that moves one second each time it is read."
    t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


save = checkpoint.CheckpointManager.save


def faulty_save(self, step, state, **kw):
    "After set-up's save: one byte flipped in what the save writes, or in the file once it is committed."
    params = state["train_state"]["params"]
    if fault == "staged" and step > 2:
        params["embed"] = params["embed"].copy()
        params["embed"].view("uint8")[5] ^= 1
    result = save(self, step, state, **kw)
    if fault == "file" and step > 2:
        group = checkpoint._step_group(step)
        (embed,) = [p for p in tree_ser.leaf_paths(self.file.group_attrs(group)["skeleton"])
                    if p.endswith("params.embed")]
        offset = self.file.meta(f"{group}/state/{embed}").offset
        fd = os.open(self.path, os.O_RDWR)
        try:
            os.pwrite(fd, bytes([os.pread(fd, 1, offset + 5)[0] ^ 1]), offset + 5)
        finally:
            os.close(fd)
    return result


checkpoint.CheckpointManager.save = faulty_save

import dataclasses
from repro.train import optim

update = optim.adamw_update


def faulty_update(grads, state, params, cfg, lr=None):
    "Every step: lr doubled, the state left as it was, or the working parameters not recast."
    if fault == "lr":
        cfg = dataclasses.replace(cfg, lr=2 * cfg.lr)
    new_params, new_state, metrics = update(grads, state, params, cfg, lr)
    if fault == "frozen":
        new_params, new_state = params, dict(new_state, master=state["master"])
    if fault == "stale_cast":
        new_params = params
    return new_params, new_state, metrics


optim.adamw_update = faulty_update
TRACER.configure(enabled=True, sample_every=1)
with tempfile.TemporaryDirectory() as workdir:
    rec = Run(config=cell.config, traffic=cell.traffic, seed=%(seed)d, seconds=1.0, workdir=workdir,
              t_process=0.0, clock=Tick())
    check = cell.system.LOOPS[cell.traffic["loop"]](rec, cell.reference)
    spans = [[s.name, s.tags or {}] for s in sorted(TRACER.drain(), key=lambda s: s.t0)
             if s.name in ("ckpt.stage", "ckpt.wait")]
    TRACER.configure(enabled=False)
    outcome = check()
print(json.dumps({
    "correct": outcome.correct, "attempted": outcome.attempted, "failed": outcome.failed,
    "checks": {k: [c.value, c.limit] for k, c in outcome.checks.items()},
    "detail": outcome.detail, "steps": rec.steps, "saves": [s.__dict__ for s in rec.saves],
    "compiles_in_window": rec.compiles_in_window, "marks": rec.marks, "spans": spans,
}))
"""


def _loop(fault: str) -> dict:
    code = LOOP % {"cell": CELL, "small": SMALL, "traffic": SMALL_TRAFFIC, "seed": SEED}
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code, fault], capture_output=True, text=True, env=env,
                          cwd=ROOT, timeout=280)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def sound():
    return _loop("none")


LEAVES = {"embed", "final_norm", "lm_head", "ln1", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "ln2", "w_gate", "w_up",
          "w_down"}


def test_sound_run_is_correct(sound):
    r = sound
    assert r["correct"] and r["failed"] == 0 and r["attempted"] == len(r["saves"]) == 1
    checks = r["checks"]
    assert checks["file_mismatch"][0] == 0 and checks["resume_mismatch"][0] == 0 and checks["cast_mismatch"][0] == 0
    for k in ("loss_err", "grad_err", "update_err"):
        assert 0 < checks[k][0] <= checks[k][1]
    d = r["detail"]
    # one period: set-up stepped to 2, the window 2 more and saved step 4,
    # and the resumed trainer started there
    assert r["steps"] == SMALL_TRAFFIC["save_every_steps"]
    assert d["saved_step"] == d["resumed_step"] == 4
    assert r["compiles_in_window"] == 0
    assert list(r["marks"]) == ["state", "first_steps", "first_save"]
    (save,) = r["saves"]
    assert 0 < save["write_s"] < math.inf and d["write_s"] == [save["write_s"]]
    assert d["host_peak_rss_bytes"] > 0 and d["free_disk_bytes"] > 2 * d["state_bytes"]
    assert list(d["host_peak_rss_bytes_after"]) == ["set_up", "window", "write", "resume", "step", "reference"]
    assert d["judged"] == "program" and set(d["readings"]) == {"program", "half_batch"}
    program = d["readings"]["program"]
    assert set(program["grad_err_by_leaf"]) == set(program["update_err_by_leaf"]) == LEAVES
    assert [program[k] for k in ("loss_err", "grad_err", "update_err")] == [checks[k][0] for k in
                                                                            ("loss_err", "grad_err", "update_err")]


def test_a_step_over_half_the_batch_reads_above_the_limits(sound):
    """What the check reads of a step whose gradient is the first half of
    the batch's: it fails the gradient and update limits."""
    half, checks = sound["detail"]["readings"]["half_batch"], sound["checks"]
    assert half["grad_err"] > 2 * checks["grad_err"][1] and half["update_err"] > 2 * checks["update_err"][1]


def test_float8_control_fails_the_gradient_limit():
    """The reference with its product inputs in float8_e4m3fn in the
    program's place (the configuration's ``control``): not correct, on the
    gradient and update limits; the program's own readings, in the same run,
    pass them."""
    r = _loop("control")
    assert not r["correct"] and r["failed"] == 0
    d, checks = r["detail"], r["checks"]
    assert d["judged"] == "float8_reference" and set(d["readings"]) == {"program", "half_batch", "float8_reference"}
    assert checks["file_mismatch"][0] == checks["resume_mismatch"][0] == 0
    for k in ("grad_err", "update_err"):
        assert checks[k][0] == d["readings"]["float8_reference"][k] > checks[k][1] > d["readings"]["program"][k]


@pytest.mark.parametrize("fault", ["frozen", "lr", "stale_cast"])
def test_a_step_that_updates_wrongly_is_not_correct(fault):
    """Every step of the program with its update at fault: ``frozen``
    leaves the parameters and master weights as they were, ``lr`` doubles
    the learning rate, ``stale_cast`` keeps the working parameters and
    updates only the master weights.  The gradient is right in each."""
    r = _loop(fault)
    checks = r["checks"]
    assert not r["correct"] and r["failed"] == 0
    assert checks["grad_err"][0] <= checks["grad_err"][1]
    if fault == "stale_cast":
        assert checks["cast_mismatch"][0] == len(LEAVES)
    else:
        assert checks["update_err"][0] == pytest.approx(1.0, abs=0.01)


def test_the_loop_opens_the_async_savers_phases(sound):
    """Set-up's save and the window's each stage the whole state and join
    the write before: ``ckpt.stage`` then ``ckpt.wait``; set-up's own
    ``wait`` for its save to land is one more."""
    spans = sound["spans"]
    assert [name for name, _ in spans] == ["ckpt.stage", "ckpt.wait", "ckpt.wait", "ckpt.stage", "ckpt.wait"]
    state_bytes = sound["detail"]["state_bytes"]
    for name, tags in spans:
        if name == "ckpt.stage":
            # the data pipeline's two integers ride with the train state
            assert tags["bytes"] >= state_bytes and tags["gathered_bytes"] == state_bytes


@pytest.mark.parametrize("fault", ["staged", "file"])
def test_a_flipped_byte_in_the_saved_file_is_not_correct(fault):
    """``staged``: the flip is sealed with the save, so only the fingerprint
    sees it, in one leaf.  ``file``: the CRC sees it, the resume falls back
    to set-up's save, and every leaf and the step differ."""
    r = _loop(fault)
    assert not r["correct"] and r["failed"] == 1
    file_bad, resume_bad = r["checks"]["file_mismatch"][0], r["checks"]["resume_mismatch"][0]
    assert file_bad >= 1 and resume_bad >= 1
    if fault == "staged":
        assert file_bad == resume_bad == 1
    else:
        assert r["detail"]["resumed_step"] == 2
        assert r["checks"]["loss_err"][0] == math.inf


# -- the cell's entries and configuration ---------------------------------------------

DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW_METRICS = {  # name: (source, layer, moves)
    "train_stage_s": ("program_span", "async staging", "save_stall_s"),
    "train_wait_s": ("program_span", "async staging", "save_stall_s"),
    "step_s.train": ("device_trace", "train step", "loop_steps_per_s"),
    "device_idle.train": ("device_trace", "device", "loop_steps_per_s"),
    "train_step_mfu": ("device_trace", "train step", "loop_steps_per_s"),
}


def test_the_cell_and_its_metrics_are_declared():
    (w,) = [w for w in DOC["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == ("qwen3_8b_4l_v4_2x2", "train_ckpt10", 4)
    (c,) = [c for c in DOC["configs"] if c["name"] == w["config"]]
    assert c["reduced"] == ["num_hidden_layers", "vocab_size"]
    per_layer = {m["name"]: m for m in DOC["per_layer"]}
    for name, (source, layer, moves) in NEW_METRICS.items():
        m = per_layer[name]
        assert (m["source"], m["layer"], m["moves"], m["workloads"]) == (source, layer, moves, [CELL])
    cell = spec.Benchmark(str(ROOT)).cell(CELL)
    assert {m.name for m in cell.end_to_end} == {"setup_s", "loop_steps_per_s", "save_stall_s"}
    assert {m.name for m in cell.per_layer} == set(NEW_METRICS)


def test_the_configuration_is_the_published_one_cut_in_depth_and_vocabulary():
    from repro.configs import get_config, qwen3_8b

    cell = spec.Benchmark(str(ROOT)).cell(CELL)
    c = cell.config
    full = get_config("qwen3-8b")
    published = {theirs: getattr(full, ours) for theirs, ours in cell.system.WIDTHS.items()}
    cut = {k: v for k, v in published.items() if c[k] != v}
    assert cut == {"num_hidden_layers": 36, "vocab_size": 151936} == c["published"]
    assert (c["num_hidden_layers"], c["vocab_size"]) == (4, 151936 // 4)
    assert set(c["reduced"]) == set(cut)
    assert cell.system.model_config(c) == qwen3_8b.share(4, 4)


def test_the_stated_state_is_the_programs():
    """The parameters and the bytes of a save that the configuration states
    are those of the program's train state at the cut (shapes only): 14
    bytes a parameter, bfloat16 working copy, float32 master, mu and nu,
    and the two step counters."""
    import jax

    from repro.train.steps import TrainSetup, init_train_state

    cell = spec.Benchmark(str(ROOT)).cell(CELL)
    c = cell.config
    state = jax.eval_shape(lambda: init_train_state(jax.random.PRNGKey(0), cell.system.model_config(c), TrainSetup()))
    n = sum(x.size for x in jax.tree.leaves(state["params"]))
    assert n == c["state"]["parameters"] == 1_082_954_752
    assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state)) == c["state"]["bytes_a_save"] == 14 * n + 8


# -- the readers ------------------------------------------------------------------------


def _readers():
    return {m.name: m.reader for m in spec.Benchmark(str(ROOT)).metrics}


def _recorded_run():
    """One period as the cell records it: 10 steps of 1.2 s, each busy
    1.0 s on every chip, then a save of 27 s: staging 26.5 s, then a join
    of 0.01 s; the chips idle through the save."""
    from bench.trace_reduce import reduce_events

    cell = spec.Benchmark(str(ROOT)).cell(CELL)
    rec = Run(config=cell.config, traffic=cell.traffic, seed=SEED, seconds=20.0, workdir="", t_process=0.0)
    rec.steps = 10
    rec.saves = [Save(27.0, 31.0)]
    spans = [("window", 0.0, 39.0)]
    ops = []
    for i in range(10):
        spans.append(("step", 1.2 * i, 1.2 * i + 1.2))
        ops.append((1.2 * i + 0.1, 1.2 * i + 1.1))
    spans += [("save", 12.0, 39.0), ("ckpt.stage", 12.0, 38.5), ("ckpt.wait", 38.5, 38.51)]
    rec.trace = reduce_events([ops] * 4, [[]] * 4, spans)
    return rec


def test_the_five_readers_on_a_recorded_run(monkeypatch):
    readers = _readers()
    mfu = readers["train_step_mfu"]
    monkeypatch.setattr(mfu, "peak_flops", lambda: 197e12)
    rec = _recorded_run()
    assert readers["train_stage_s"].read(rec) == pytest.approx(26.5)
    assert readers["train_wait_s"].read(rec) == pytest.approx(0.01)
    assert readers["step_s.train"].read(rec) == pytest.approx(1.0)
    assert readers["device_idle.train"].read(rec) == pytest.approx(100 * (1 - 10.0 / 39.0))
    # 3.91e14 model FLOPs a step in 1.0 s on four chips of 197 TFLOP/s
    flops = mfu.step_flops(rec.config, 16, 4096)
    assert flops == pytest.approx(3.91e14, rel=1e-3)
    assert mfu.read(rec) == pytest.approx(100 * flops / (4 * 197e12))
    # an untraced run, or a program without the spans: nothing to read, no error
    rec.trace = None
    for name in NEW_METRICS:
        assert readers[name].read(rec) is None


def test_mfu_has_no_default_peak():
    """A chip not in the table of peaks is an error (the CPU here)."""
    with pytest.raises(KeyError):
        _readers()["train_step_mfu"].peak_flops()


def test_model_flops_at_the_smoke_widths_by_hand():
    """6 N T + 6 L S (H d_head) T at qwen3's smoke widths, with N counted by
    hand and against the program's own parameters."""
    import jax

    from repro.configs import get_smoke
    from repro.models import transformer

    mfu = _readers()["train_step_mfu"]
    d, ff, heads, kv, dh, layers, vocab = 64, 128, 4, 2, 16, 2, 128
    per_layer = d * heads * dh + 2 * d * kv * dh + heads * dh * d + 3 * d * ff  # wq, wk and wv, wo, SwiGLU
    n_hand = layers * per_layer + vocab * d  # and the head
    assert n_hand == 81920
    tokens = 4 * 32
    assert mfu.step_flops(SMALL, 4, 32) == 6 * n_hand * tokens + 6 * layers * 32 * heads * dh * tokens
    # every weight of the program that multiplies: all but the embedding,
    # a lookup, and the norms, which scale
    shapes = jax.eval_shape(lambda: transformer.init_model(jax.random.PRNGKey(0), get_smoke("qwen3-8b")))
    names = {jax.tree_util.keystr(path).split("[")[-1].strip("']"): x.size
             for path, x in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert sum(v for k, v in names.items() if k != "embed" and "norm" not in k and k not in ("ln1", "ln2")) == n_hand
