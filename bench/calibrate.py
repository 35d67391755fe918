"""The readings that a cell's check limits are set from.  The benchmark's
runs never call this; it is run by hand on the chip when a limit is set.

    python3 bench/calibrate.py --workload <cell> --seconds <s> [--seeds 1 2 ...]
        [--control-seeds 3 4 5] [--controls bf16_reference lossy_codec]

In one process, for each of ``--seeds``, one run of the cell as
``bench/run.py`` makes it (set-up, window, check): the sound runs, whose
largest reading is a limit's lower end.  Then, for each of
``--control-seeds``, the same runs with a control in the program's place,
whose smallest reading is the upper end, and which the harness has to judge
not correct:

- ``bf16_reference``: the reference computed in bfloat16 steps the fields
  (the configuration's ``control`` key);
- ``lossy_codec``: the program with its own lossy path switched on
  (``int8-blockq`` snapshots) in place of the lossless one the
  configuration states.

Prints one JSON line per run: its seed, ``correct`` and each number its
check compared.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

CONTROLS = {"bf16_reference": {"control": "bf16_reference"}, "lossy_codec": {"codec": "int8-blockq"}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--controls", nargs="*", choices=sorted(CONTROLS), default=sorted(CONTROLS))
    args = ap.parse_args(argv)

    import jax

    from bench.harness import run_cell
    from bench.spec import Benchmark
    from repro.launch.compile_cache import enable_compile_cache

    cell = Benchmark(ROOT).cell(args.workload)
    device = jax.devices()[0]
    if device.platform == "tpu":  # a CPU rehearsal leaves the chip's cache alone
        enable_compile_cache()

    def one(reading: str, seed: int, c) -> None:
        with tempfile.TemporaryDirectory(prefix="bench_cal_") as workdir:
            r = run_cell(c, seed=seed, seconds=args.seconds, trace=False, workdir=workdir, t_process=time.perf_counter())
        print(json.dumps({"reading": reading, "seed": seed, "device": device.device_kind, "correct": r["correct"],
                          "checks": {k: v["value"] for k, v in r["checks"].items()},
                          "metrics": {k: v["value"] for k, v in r["metrics"].items()}}), flush=True)

    for seed in args.seeds:
        one("program", seed, cell)
    for reading in args.controls:
        control = dataclasses.replace(cell, config=dict(cell.config, **CONTROLS[reading]))
        for seed in args.control_seeds:
            one(reading, seed, control)
    return 0


if __name__ == "__main__":
    sys.exit(main())
