"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the run's counts on an earlier line and, as its last line, one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones), ``device``,
``breakdown`` (traced runs) and ``checks``, each number that decided
``correct`` beside its limit.  Exits non-zero, with no result, where JAX
finds no TPU or fewer chips than the cell asks for.  JAX's compilation cache
lives in ``.jax_cache`` at the root of the checkout; run files live in a
temporary directory removed at exit.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the cache path is part of the cache key: a fixed place in the checkout
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from bench.spec import Benchmark

    cell = Benchmark(ROOT).cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # cache every executable, the small eager ones too, so that only the
    # first run of a cell in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from bench.harness import run_cell

    with tempfile.TemporaryDirectory(prefix="bench_") as workdir:
        result = run_cell(
            cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace), workdir=workdir, t_process=T_PROCESS
        )
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
