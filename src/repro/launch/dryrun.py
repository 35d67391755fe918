import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
).strip()

"""Multi-pod dry-run (deliverable e) + roofline extraction (deliverable g).

For a given (architecture × input shape × mesh) cell this lowers and
compiles the real step function — ``train_step`` / ``prefill_step`` /
``serve_step`` — against ``ShapeDtypeStruct`` inputs (no allocation), then
records ``memory_analysis()``, ``cost_analysis()`` and the HLO collective
traffic into ``results/dryrun/<cell>.json``.

The environment lines above MUST stay the first statements in this
module: jax locks the platform and the device count on first backend
initialisation.  The dry-run is a host-device compile tool, so it pins the
CPU platform (on a TPU host it would otherwise take the chip and fail for
want of 512 devices) and appends the 512-device flag to any ``XLA_FLAGS``
already set.  Nothing else in the repo sets this flag — smoke tests and
benchmarks see one device.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b \
        --shape train_4k --mesh pod [--out results/dryrun] [--opt ...]
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from ..analysis import flops as aflops
from ..analysis import roofline as rf
from ..configs import ARCHS, get_config
from ..configs.shapes import SHAPES, shape_applicable
from ..distributed import sharding
from ..models import transformer
from ..models.common import active_params_per_token, count_params
from ..serve.steps import make_prefill_step, make_serve_step
from ..train.steps import TrainSetup, init_train_state, make_train_step, train_state_specs
from .mesh import HBM_PER_CHIP, make_production_mesh


def input_token_sds(cfg, batch: int, seq: int):
    shape = (batch, seq, cfg.n_codebooks) if cfg.n_codebooks else (batch, seq)
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def build_cell(cfg, shape, mesh, setup: TrainSetup, overrides: dict):
    """Returns (jitted, args_sds) ready to lower."""
    kind = shape.kind
    if kind == "train":
        rule_fn = (
            sharding.train_rules_zero3
            if overrides.get("layout") == "zero3"
            else sharding.train_rules
        )
        rules = rule_fn(mesh, cfg)
        rules.update(overrides.get("rules", {}))
        step_fn, state_specs, bspecs = make_train_step(cfg, mesh, setup, rules=rules)
        key = jax.random.PRNGKey(0)
        state_sds = jax.eval_shape(lambda k: init_train_state(k, cfg, setup), key)
        batch_sds = {
            "tokens": input_token_sds(cfg, shape.global_batch, shape.seq_len),
            "labels": input_token_sds(cfg, shape.global_batch, shape.seq_len),
        }
        state_specs = sharding.fix_specs(mesh, state_specs, state_sds)
        bspecs = sharding.fix_specs(mesh, bspecs, batch_sds)
        in_sh = (sharding.to_named(mesh, state_specs), sharding.to_named(mesh, bspecs))
        jitted = jax.jit(step_fn, in_shardings=in_sh, donate_argnums=0)
        return jitted, (state_sds, batch_sds)

    rule_fn = {
        "prefill": sharding.prefill_rules,
        "decode": sharding.decode_rules,
        "decode_long": sharding.decode_long_rules,
    }[kind]
    rules = rule_fn(mesh, cfg)
    rules.update(overrides.get("rules", {}))
    pspecs = sharding.spec_tree(rules, transformer.param_axes(cfg))
    cache_spec_tree = sharding.spec_tree(rules, transformer.cache_axes(cfg))
    params_sds = jax.eval_shape(
        lambda k: transformer.init_model(k, cfg), jax.random.PRNGKey(0)
    )
    cache_sds = transformer.cache_specs(cfg, shape.global_batch, shape.seq_len)
    pspecs = sharding.fix_specs(mesh, pspecs, params_sds)
    cache_spec_tree = sharding.fix_specs(mesh, cache_spec_tree, cache_sds)
    if kind == "prefill":
        step_fn, *_ = make_prefill_step(cfg, mesh, rules=rules)
        tokens_sds = input_token_sds(cfg, shape.global_batch, shape.seq_len)
    else:
        step_fn, *_ = make_serve_step(cfg, mesh, rules=rules)
        tokens_sds = input_token_sds(cfg, shape.global_batch, 1)
    tok_axes = ("batch", None, None)
    in_sh = (
        sharding.to_named(mesh, pspecs),
        sharding.to_named(mesh, sharding.resolve_spec(tok_axes[: len(tokens_sds.shape)], rules)),
        sharding.to_named(mesh, cache_spec_tree),
    )
    jitted = jax.jit(step_fn, in_shardings=in_sh, donate_argnums=2)
    return jitted, (params_sds, tokens_sds, cache_sds)


def run_cell(arch: str, shape_name: str, mesh_kind: str, setup: TrainSetup, overrides=None):
    overrides = overrides or {}
    cfg = get_config(arch)
    for k, v in overrides.get("model", {}).items():
        cfg = cfg.scaled(**{k: v})
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    n_chips = mesh.devices.size
    t0 = time.time()
    jitted, args = build_cell(cfg, shape, mesh, setup, overrides)
    with mesh:
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    colls = rf.parse_collectives(hlo, n_chips)

    n_params = count_params(cfg)
    n_active = active_params_per_token(cfg)
    model_flops = rf.model_flops_for_cell(cfg, shape, n_active)
    # trip-correct analytic totals (XLA cost_analysis counts while bodies once)
    if shape.kind in ("decode", "decode_long"):
        afl = aflops.cell_flops(cfg, shape.global_batch, 1, shape.kind, cache_len=shape.seq_len)
        ahb = aflops.cell_hbm_bytes(cfg, n_params, shape.global_batch, 1, shape.kind, cache_len=shape.seq_len)
    else:
        afl = aflops.cell_flops(cfg, shape.global_batch, shape.seq_len, shape.kind)
        ahb = aflops.cell_hbm_bytes(cfg, n_params, shape.global_batch, shape.seq_len, shape.kind)
    terms = rf.roofline(
        flops_per_chip=float(afl["total"]) / n_chips,
        hbm_bytes_per_chip=float(ahb["total"]) / n_chips,
        wire_bytes_per_chip=float(colls.wire_bytes_tpu_adjusted),
        n_chips=n_chips,
        model_flops_global=model_flops,
    )
    mem_per_chip = int(ma.argument_size_in_bytes + ma.temp_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "status": "ok",
        "n_chips": n_chips,
        "n_params": n_params,
        "n_active_params": n_active,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_per_chip_bytes": mem_per_chip,
            "fits_hbm": bool(mem_per_chip <= HBM_PER_CHIP),
        },
        "cost": {
            "xla_flops_per_chip_raw": float(ca.get("flops", 0.0)),
            "xla_bytes_per_chip_raw": float(ca.get("bytes accessed", 0.0)),
            "analytic_flops_total": float(afl["total"]),
            "analytic_flops_breakdown": {k: float(v) for k, v in afl.items()},
            "analytic_hbm_bytes_total": float(ahb["total"]),
            "analytic_hbm_breakdown": {k: float(v) for k, v in ahb.items()},
            "note": "XLA cost_analysis counts while bodies once; analytic model is trip-correct",
        },
        "collectives": colls.to_json(),
        "roofline": terms.to_json(),
        "setup": {
            "optimizer": setup.optimizer,
            "microbatch": setup.microbatch,
            "remat": cfg.remat,
            "overrides": {k: v for k, v in overrides.items() if k != "rules"},
        },
    }
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCHS))
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "adafactor"])
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--remat", default=None, choices=[None, "none", "full", "dots"])
    ap.add_argument("--logit-chunk", type=int, default=None)
    ap.add_argument("--print-hlo", action="store_true")
    ap.add_argument("--layout", default=None, choices=[None, "zero3"])
    ap.add_argument(
        "--rule", action="append", default=[],
        help="logical-axis rule override, e.g. --rule cache_seq=model "
             "(value: mesh axis, comma-tuple, or 'none')",
    )
    args = ap.parse_args()

    setup = TrainSetup(optimizer=args.optimizer, microbatch=args.microbatch)
    overrides = {"model": {}, "rules": {}}
    if args.layout:
        overrides["layout"] = args.layout
    for kv in args.rule:
        k, v = kv.split("=", 1)
        if v == "none":
            overrides["rules"][k] = None
        elif "," in v:
            overrides["rules"][k] = tuple(v.split(","))
        else:
            overrides["rules"][k] = v
    if args.remat:
        overrides["model"]["remat"] = args.remat
    if args.logit_chunk:
        overrides["model"]["logit_chunk"] = args.logit_chunk

    os.makedirs(args.out, exist_ok=True)
    name = f"{args.arch}__{args.shape}__{args.mesh}__{args.tag}"
    try:
        result = run_cell(args.arch, args.shape, args.mesh, setup, overrides)
    except Exception as e:
        result = {
            "arch": args.arch, "shape": args.shape, "mesh": args.mesh,
            "status": "error", "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
    path = os.path.join(args.out, name + ".json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    status = result["status"]
    rl = result.get("roofline", {})
    print(
        f"[{status}] {name}  compile={result.get('compile_s', '-')}s "
        f"mem/chip={result.get('memory', {}).get('peak_per_chip_bytes', 0)/2**30:.2f}GiB "
        f"bottleneck={rl.get('bottleneck', '-')}"
    )
    if status == "error":
        print(result["error"])
        print(result["traceback"][-2000:])
        raise SystemExit(1)


if __name__ == "__main__":
    main()
