"""Checkpointed training: ``repro.train.trainer.Trainer`` on a 2 x 2
(data, model) mesh, saving through its ``AsyncCheckpointer`` into a
``CheckpointManager``.

Loops (a traffic mix names one in ``loop``):

- ``train_ckpt``: the trainer's own step (the batch from its
  ``TokenStream``, placed as its batch sharding, then its jitted step),
  and every ``save_every_steps`` steps the save ``Trainer.run`` makes: an
  asynchronous, double-buffered ``AsyncCheckpointer.save``, which returns
  once the state is staged on the host and leaves the write to a
  background thread.  One period is those steps and the save after them.
  Set-up builds the state, runs two steps (the first compiles) and makes
  one save that it waits for, so that the window's save does not queue
  behind it.

The loop keeps no host copy of a saved tree: after each save it keeps a
fingerprint of the device state (:func:`fingerprint`, two words a leaf).
The check waits for the last write, then starts a fresh ``Trainer`` on a
new mesh from the file with ``init_or_resume`` and lets it take one step:

- ``file_mismatch``: the tree its ``restore(verify=True)`` read, uploaded
  as the state is laid out and fingerprinted, against the saved state's
  fingerprint, leaf by leaf (every leaf and one more when the window's save
  is not the step read back);
- ``resume_mismatch``: the resumed device state's fingerprint against the
  same;
- ``cast_mismatch``: the leaves whose bfloat16 working parameters after the
  step are not the cast of its float32 master weights;
- ``loss_err``: the step's loss against the plain reference's on that
  step's batch, at the saved bfloat16 weights, relative;
- ``grad_err``: the worst leaf's relative L2 error of the gradient that
  step applied, recovered from Adam's first moment as
  (mu' - b1 mu) / (1 - b1) and undone from its global-norm clip, against
  the reference's float32 gradient;
- ``update_err``: the worst leaf's relative L2 error of the step's change
  to the master weights against a plain AdamW step (the reference's
  ``adamw_delta``, the configuration's ``optimizer`` constants) from the
  reference's gradient and the saved moments and master weights.  A step
  that leaves the state unchanged reads 1.

The reference runs data-parallel over the four chips, its parameters and
gradients split over them, a sequence a chip at a time.  ``detail`` gives
each reading of the program and of what a step over the first half of the
batch reads (``half_batch``).  A configuration's ``control`` key,
set only when a limit is calibrated, puts a control in the program's place
for the last three numbers: ``float8_reference``, the reference with its
matrix products' inputs in ``float8_e4m3fn``, its gradient applied by the
plain AdamW step; the program's readings stay in ``detail``.
"""

from __future__ import annotations

import math
import resource
import shutil

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from bench.harness import Check, Outcome, Save, span

MESH = ((2, 2), ("data", "model"))
# a configuration's ``control``: the reference, its products' inputs in this
# precision, in the program's place
CONTROLS = {"float8_reference": jnp.float8_e4m3fn}
# the configuration's keys (the published config.json's) and the program's
WIDTHS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "num_hidden_layers": "n_layers",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps",
    "tie_word_embeddings": "tie_embeddings",
}
# the program's parameter tree, leaf by leaf, as the reference names it
LAYER_LEAVES = {
    "ln1": ("ln1",),
    "wq": ("mixer", "wq"),
    "wk": ("mixer", "wk"),
    "wv": ("mixer", "wv"),
    "wo": ("mixer", "wo"),
    "q_norm": ("mixer", "q_norm"),
    "k_norm": ("mixer", "k_norm"),
    "ln2": ("ln2",),
    "w_gate": ("ffn", "w_gate"),
    "w_up": ("ffn", "w_up"),
    "w_down": ("ffn", "w_down"),
}
NORMS = {"ln1", "q_norm", "k_norm", "ln2", "final_norm"}  # stored as offsets from 1


def model_config(c: dict):
    """The program's cut of the published model (``qwen3_8b.share``: its
    layers and a share of its vocabulary) with the widths the configuration
    file states, which are the published ones: a test run shrinks them."""
    from repro.configs import qwen3_8b

    cut = qwen3_8b.share(c["num_hidden_layers"], c["published"]["vocab_size"] // c["vocab_size"])
    return cut.scaled(**{ours: c[theirs] for theirs, ours in WIDTHS.items()})


def seeds(seed: int) -> tuple[int, int]:
    """The weights' and the data's PRNG seeds, drawn from a seed of any size."""
    w, d = np.random.SeedSequence(seed).generate_state(2)
    return int(w) >> 1, int(d) >> 1


def _bits(x: jax.Array) -> jax.Array:
    if x.dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    uint = {2: jnp.uint16, 1: jnp.uint8}[x.dtype.itemsize]
    return jax.lax.bitcast_convert_type(x, uint).astype(jnp.uint32)


def _leaf_print(x: jax.Array) -> jax.Array:
    bits = _bits(x)
    i = jnp.zeros(x.shape, jnp.uint32)  # the element's index in C order
    for d, stride in enumerate(np.cumprod((1,) + x.shape[::-1])[-2::-1] if x.ndim else ()):
        i = i + jax.lax.broadcasted_iota(jnp.uint32, x.shape, d) * jnp.uint32(stride)
    w1, w2 = 2 * i + 1, i * jnp.uint32(0x9E3779B1) + jnp.uint32(0x7F4A7C15)
    return jnp.stack([jnp.sum(bits * w1, dtype=jnp.uint32), jnp.sum((bits ^ (bits >> 13)) * w2, dtype=jnp.uint32)])


@jax.jit
def fingerprint(tree) -> jax.Array:
    """Two position-weighted sums, mod 2**32, of each leaf's bits, in tree
    order: one element changed, or moved, changes the first (its weights
    are odd).  Computed where the leaves lie, with no gather."""
    return jnp.stack([_leaf_print(x) for x in jax.tree.leaves(tree)])


def leaves_differ(a, b) -> int:
    return int(np.count_nonzero(np.any(np.asarray(a) != np.asarray(b), axis=-1)))


def reference_layout(tree: dict) -> dict:
    """A tree laid out as the program's parameters (its one stage of
    identical layers) in the reference's layout."""
    (slot,) = tree["stages"][0]["slots"]

    def leaf(path):
        node = slot
        for k in path:
            node = node[k]
        return node

    return {
        "embed": tree["embed"],
        "layers": {name: leaf(path) for name, path in LAYER_LEAVES.items()},
        "final_norm": tree["final_norm"],
        "lm_head": tree["lm_head"],
    }


def reference_params(params: dict) -> dict:
    """The program's parameters as the reference's float32 ones: its RMSNorm
    weights are stored as offsets from 1."""
    out = reference_layout(params)
    f32 = lambda name, x: (1.0 + x.astype(np.float32)) if name in NORMS else x.astype(np.float32)  # noqa: E731
    out["layers"] = {k: f32(k, v) for k, v in out["layers"].items()}
    return {k: v if k == "layers" else f32(k, v) for k, v in out.items()}


def _split_spec(shape, n: int, axis: str) -> P:
    """Split the largest dimension that ``n`` divides; replicate otherwise."""
    dims = sorted((s, i) for i, s in enumerate(shape) if s % n == 0)
    if not dims:
        return P()
    spec = [None] * len(shape)
    spec[dims[-1][1]] = axis
    return P(*spec)


class Reference:
    """The reference's loss and gradient of a batch, on ``devices``: the
    parameters and gradients split over them, the batch in blocks of one
    sequence a device."""

    def __init__(self, ref, c: dict, devices):
        self.ref, self.c = ref, c
        self.mesh = Mesh(np.asarray(devices), ("r",))
        self.n = len(devices)
        self.fns = {}

    def sharding(self, tree):
        return jax.tree.map(lambda x: NamedSharding(self.mesh, _split_spec(x.shape, self.n, "r")), tree)

    def place(self, params: dict) -> dict:
        return jax.device_put(params, self.sharding(params))

    def __call__(self, params: dict, tokens: np.ndarray, labels: np.ndarray, mm_dtype=jnp.float32, halves=False):
        """The mean token loss and its gradient over the batch; with
        ``halves``, the same over its first half as well (``None`` where the
        blocks do not halve)."""
        key = jnp.dtype(mm_dtype).name
        if key not in self.fns:
            rows = NamedSharding(self.mesh, P("r"))
            grad = jax.value_and_grad(lambda p, t, l: self.ref.nll_sum(p, t, l, self.c, mm_dtype))
            self.fns[key] = jax.jit(grad, in_shardings=(self.sharding(params), rows, rows),
                                    out_shardings=(NamedSharding(self.mesh, P()), self.sharding(params)))
        fn, total, grads, half = self.fns[key], 0.0, None, None
        if len(tokens) % self.n:
            raise ValueError(f"a batch of {len(tokens)} does not split over {self.n} devices")
        mean = lambda nll, g, n: (nll / n, jax.tree.map(lambda x: x / n, g))  # noqa: E731
        for i in range(0, len(tokens), self.n):
            nll, g = fn(params, tokens[i : i + self.n], labels[i : i + self.n])
            total += float(nll)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
            if halves and 2 * (i + self.n) == len(tokens):
                half = mean(total, grads, tokens.size / 2)
        return (*mean(total, grads, tokens.size), half)


def flat(tree: dict) -> dict:
    """A tree in the reference's layout as one leaf a name."""
    return {"embed": tree["embed"], "final_norm": tree["final_norm"], "lm_head": tree["lm_head"], **tree["layers"]}


@jax.jit
def _rel_l2(got, want):
    return jnp.linalg.norm(got - want) / jnp.linalg.norm(want)


def rel_errors(got: dict, want: dict) -> dict[str, float]:
    """Relative L2 error of each leaf of ``got`` (on the device or the host)
    against ``want`` (on the device), both in the reference's layout."""
    got, want = flat(got), flat(want)
    return {k: float(_rel_l2(jax.device_put(got[k], want[k].sharding), want[k])) for k in sorted(want)}


def applied_gradient(mu_new, mu_old, grad_norm: float, opt: dict) -> dict:
    """The gradient an AdamW step applied, from its first moment before and
    after: (mu' - b1 mu) / (1 - b1) is the clipped gradient, and the clip
    scaled it by min(1, clip_norm / grad_norm)."""
    scale = np.float32(min(1.0, opt["clip_norm"] / max(grad_norm, 1e-12)))
    b1, one_minus = np.float32(opt["b1"]), np.float32(1 - opt["b1"])
    return jax.jit(lambda new, old: jax.tree.map(lambda n, o: (n - b1 * o) / one_minus / scale, new, old))(mu_new, mu_old)


def update_errors(ref, opt: dict, count: int, old: dict, want: dict, candidates: dict) -> dict[str, dict[str, float]]:
    """Each candidate's change to the float32 master weights against the
    change a plain AdamW step (``ref.adamw_delta``, with the configuration's
    constants) makes from the reference gradient ``want`` and the moments and
    master weights of the state before the step (``old``, on the host).  A
    candidate gives its change (``"delta"``) or a gradient the same plain
    step applies (``"grad"``).  Leaf by leaf, relative L2, so that a few
    leaves at a time are on the device."""
    consts = {k: opt[k] for k in ("lr", "b1", "b2", "eps", "weight_decay")}
    delta = jax.jit(lambda g, m, v, p, s: ref.adamw_delta(g, m, v, p, count=count, clip_scale=s, **consts))
    scale = {name: ref.clip_scale(cand["grad"], opt["clip_norm"]) for name, cand in candidates.items()
             if "delta" not in cand}
    want_scale = ref.clip_scale(want, opt["clip_norm"])
    old = {k: flat(reference_layout(old[k])) for k in ("mu", "nu", "master")}
    given = {name: flat(cand["delta"] if "delta" in cand else cand["grad"]) for name, cand in candidates.items()}
    errors = {name: {} for name in candidates}
    for leaf, g in flat(want).items():
        m, v, p = (jax.device_put(old[k][leaf], g.sharding) for k in ("mu", "nu", "master"))
        d_want = delta(g, m, v, p, want_scale)
        for name, cand in candidates.items():
            x = jax.device_put(given[name][leaf], g.sharding)
            d = x if "delta" in cand else delta(x, m, v, p, scale[name])
            errors[name][leaf] = float(_rel_l2(d, d_want))
    return errors


def _trainer(c: dict, tr: dict, path: str, data_seed: int, devices):
    from repro.core.checkpoint import CheckpointManager
    from repro.launch.mesh import make_mesh
    from repro.train.data import DataConfig
    from repro.train.optim import AdamWConfig
    from repro.train.steps import TrainSetup
    from repro.train.trainer import Trainer, TrainerConfig

    class Recording(CheckpointManager):
        """Keeps each save's ``SaveResult`` and, of each restore, the
        fingerprint of the tree it read, uploaded as the trainer lays out
        its state, and the host train state that the check needs."""

        trainer = None
        read = None  # (step, fingerprint, host train state)

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.results = []

        def save(self, step, state, **kw):
            result = super().save(step, state, **kw)
            self.results.append(result)
            return result

        def restore(self, step=None, verify=True):
            step, snap = super().restore(step, verify=verify)
            state = snap["train_state"]
            on_device = jax.device_put(state, self.trainer.state_sharding)
            self.read = (step, fingerprint(on_device), state)
            del on_device
            return step, snap

    cfg = model_config(c)
    opt = c["optimizer"]
    trainer = Trainer(
        cfg,
        Recording(path, common={"arch": c["arch"], "config": c["name"]}),
        setup=TrainSetup(adamw=AdamWConfig(**{k: opt[k] for k in ("lr", "b1", "b2", "eps", "weight_decay", "clip_norm")})),
        data=DataConfig(seed=data_seed, batch=int(tr["batch"]), seq_len=int(tr["seq_len"])),
        tcfg=TrainerConfig(checkpoint_every=int(tr["save_every_steps"])),
        mesh=make_mesh(*MESH, devices=devices),
    )
    trainer.manager.trainer = trainer
    return trainer


def _step(trainer, step: int) -> dict:
    """One step as ``Trainer.run`` takes it; returns its metrics on the host."""
    batch = jax.device_put(trainer.stream.batch(step), trainer.batch_sharding)
    trainer.state, metrics = trainer.step_fn(trainer.state, batch)
    return {k: float(v) for k, v in metrics.items()}


def _peak_rss() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


@jax.jit
def _minus(a, b):
    return jax.tree.map(jnp.subtract, a, b)


@jax.jit
def _cast_like(master, params):
    return jax.tree.map(lambda m, p: m.astype(p.dtype), master, params)


def program_step(trainer, old: dict, opt: dict, start: int) -> tuple[dict, dict, int]:
    """The resumed trainer's next step, and what the check reads of it, on
    the host in the reference's layout: the gradient it applied, its change
    to the master weights; and the leaves whose working parameters are not
    the master weights' cast."""
    metrics = _step(trainer, start)
    new = trainer.state
    like = lambda host, dev: jax.device_put(host, jax.tree.map(lambda x: x.sharding, dev))  # noqa: E731
    mu_new, master_new = new["opt"]["mu"], new["opt"]["master"]
    grad = applied_gradient(mu_new, like(old["opt"]["mu"], mu_new), metrics["grad_norm"], opt)
    grad = jax.device_get(reference_layout(grad))
    delta = jax.device_get(reference_layout(_minus(master_new, like(old["opt"]["master"], master_new))))
    cast_bad = leaves_differ(fingerprint(new["params"]), fingerprint(_cast_like(master_new, new["params"])))
    trainer.state = None
    return metrics, {"loss": metrics["loss"], "grad": grad, "delta": delta}, cast_bad


def readings(cand: dict, want_loss: float, want: dict, update: dict[str, float]) -> dict:
    by_leaf = rel_errors(cand["grad"], want)
    return {"loss_err": abs(cand["loss"] - want_loss) / abs(want_loss), "grad_err": max(by_leaf.values()),
            "update_err": max(update.values()), "grad_err_by_leaf": by_leaf, "update_err_by_leaf": update}


def train_ckpt_loop(rec, ref):
    c, tr = rec.config, rec.traffic
    control = c.get("control")
    if control is not None and control not in CONTROLS:
        raise ValueError(f"control {control!r}: the train cell has {sorted(CONTROLS)}")
    every = int(tr["save_every_steps"])
    devices = jax.devices()[: math.prod(MESH[0])]
    path = f"{rec.workdir}/train.th5"
    free_disk = shutil.disk_usage(rec.workdir).free
    w_seed, d_seed = seeds(rec.seed)
    trainer = _trainer(c, tr, path, d_seed, devices)
    trainer.init_or_resume(w_seed)
    rec.mark("state")
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(trainer.state))
    if free_disk < 2 * state_bytes:  # the warm-up save and the window's
        raise OSError(f"{rec.workdir}: {free_disk} bytes free, two saves need {2 * state_bytes}")
    for n in range(2):  # the first compiles
        _step(trainer, n)
    n = 2
    rec.mark("first_steps")
    trainer._checkpoint(n)  # the save Trainer.run makes
    kept = (n, fingerprint(trainer.state))
    trainer.async_ckpt.wait()
    rec.mark("first_save")
    rss = {"set_up": _peak_rss()}
    with rec.window():
        while True:
            for _ in range(every):
                with span("step"):
                    _step(trainer, n)
                n += 1
                rec.steps += 1
            t = rec.clock()
            with span("save"):
                trainer._checkpoint(n)
            rec.saves.append(Save(rec.clock() - t, math.nan))
            kept = (n, fingerprint(trainer.state))
            if rec.boundary():
                break
    rss["window"] = _peak_rss()

    def check() -> Outcome:
        saved_step, saved_print = kept
        memory = devices[0].memory_stats()
        trainer.async_ckpt.wait()
        rss["write"] = _peak_rss()
        for save, result in zip(rec.saves, trainer.manager.results[1:]):  # after the warm-up save
            save.write_s = result.wall_s
        trainer.state = None
        trainer.manager.close()
        n_leaves = len(saved_print)

        resumed = _trainer(c, tr, path, d_seed, devices)
        start = resumed.init_or_resume(w_seed)
        rss["resume"] = _peak_rss()
        read_step, read_print, old = resumed.manager.read
        resumed.manager.read = None
        file_bad = leaves_differ(read_print, saved_print) + (n_leaves + 1) * int(read_step != saved_step)
        resume_bad = leaves_differ(fingerprint(resumed.state), saved_print) + (n_leaves + 1) * int(start != saved_step)
        detail = {"saved_step": saved_step, "resumed_step": start, "state_bytes": state_bytes,
                  "free_disk_bytes": free_disk, "write_s": [s.write_s for s in rec.saves],
                  "device_memory_stats": memory}
        cast_bad = n_leaves
        loss_err = grad_err = update_err = math.inf
        if start == saved_step:
            opt = c["optimizer"]
            batch = resumed.stream.batch(start)
            tokens, labels = np.asarray(batch["tokens"]), np.asarray(batch["labels"])
            metrics, program, cast_bad = program_step(resumed, old, opt, start)
            rss["step"] = _peak_rss()
            reference = Reference(ref, c, devices)
            weights = reference.place(reference_params(old["params"]))
            want_loss, want, half = reference(weights, tokens, labels, halves=True)
            candidates = {"program": program}
            if half is not None:  # the step's fault of reading half its batch
                candidates["half_batch"] = dict(zip(("loss", "grad"), half))
            if control is not None:
                loss, grad, _ = reference(weights, tokens, labels, CONTROLS[control])
                candidates[control] = {"loss": loss, "grad": grad}
            del weights
            update = update_errors(ref, opt, int(old["opt"]["count"]) + 1, old["opt"], want, candidates)
            read = {name: readings(cand, want_loss, want, update[name]) for name, cand in candidates.items()}
            rss["reference"] = _peak_rss()
            judged = read[control or "program"]
            loss_err, grad_err, update_err = (judged[k] for k in ("loss_err", "grad_err", "update_err"))
            detail.update(loss=metrics["loss"], reference_loss=want_loss, grad_norm=metrics["grad_norm"],
                          judged=control or "program", readings=read)
        resumed.manager.close()
        detail["host_peak_rss_bytes"] = _peak_rss()
        detail["host_peak_rss_bytes_after"] = rss
        detail["run_s"] = rec.clock() - rec.t_process
        limits = c["limits"]
        checks = {"file_mismatch": file_bad, "resume_mismatch": resume_bad, "cast_mismatch": cast_bad,
                  "loss_err": loss_err, "grad_err": grad_err, "update_err": update_err}
        failed = int(file_bad > 0 or resume_bad > 0)
        return Outcome({k: Check(v, limits[k]) for k, v in checks.items()}, len(rec.saves), failed, detail)

    return check


LOOPS = {"train_ckpt": train_ckpt_loop}
