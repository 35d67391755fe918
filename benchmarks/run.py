"""Benchmark harness — one module per paper table/figure (deliverable d).

    PYTHONPATH=src python -m benchmarks.run

Prints ``name,us_per_call,derived`` CSV lines per benchmark plus the
per-figure detail lines.  Figure map:
    io_bandwidth     → Fig. 8a/8b (write bandwidth vs ranks, vs VPIC-IO)
    io_ablation      → §5.2 optimisation ablation + async overlap
    ghost_exchange   → Fig. 2a (halo update scaling)
    multigrid_bench  → Fig. 2b/2c (solver scaling / contraction)
    trs_savings      → §4 TRS cost-saving scenario
    lm_checkpoint    → framework integration (train-state snapshots)
    service_load     → §2.3/§4 served: N-client read/steering broker load
    recovery         → fault tolerance: crash-recovery scan + reconnect dip
    streaming        → live subscriptions: push fan-out rate + latency
    query            → predicate pushdown: sparse query vs dense full scan
    observability    → tracing plane: traced-vs-untraced serve overhead
"""

from __future__ import annotations

import functools
import time


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import (
        ghost_exchange,
        io_ablation,
        io_bandwidth,
        lm_checkpoint,
        multigrid_bench,
        observability,
        query,
        recovery,
        service_load,
        streaming,
        trs_savings,
    )

    print("name,us_per_call,derived")
    suites = [
        # fig8 write curves + the run's compression and read sections
        ("io_bandwidth_fig8", io_bandwidth.run,
         lambda res: f"best={max(r['mpfluid_MBps'] for r in res['fig8'])}MB/s,"
                     + io_bandwidth.derived_summary(res)),
        ("io_ablation_s52", io_ablation.run, lambda rows: f"overlap_ratio={rows[-1]['overlap_ratio']:.3f}"),
        ("ghost_exchange_fig2a", ghost_exchange.run, lambda rows: f"us_per_grid={rows[-1]['us_per_grid']:.2f}"),
        ("multigrid_fig2bc", multigrid_bench.run, lambda rows: f"contraction={rows[-1]['contraction_per_cycle']:.3f}"),
        ("trs_savings_s4", trs_savings.run, lambda rows: f"production_ratio={rows[-1]['prod_ratio']:.3f}"),
        ("lm_checkpoint", lm_checkpoint.run, lambda rows: f"write={max(r['write_MBps'] for r in rows):.0f}MB/s"),
        # multi-client broker: aggregate served MB/s scaling with client count
        ("service_load_serve", service_load.run,
         lambda res: f"agg8={res['traffic'][-1]['agg_MBps']:.0f}MB/s,"
                     f"speedup_vs_1client={res['speedup_max_clients_vs_1']:.2f}x,"
                     f"p99={res['traffic'][-1]['p99_ms']:.0f}ms"),
        # the same traffic over the wire protocol (ServiceServer + sockets)
        ("service_load_serve_wire",
         functools.partial(service_load.run, transport="socket"),
         lambda res: f"agg8={res['traffic'][-1]['agg_MBps']:.0f}MB/s,"
                     f"speedup_vs_1client={res['speedup_max_clients_vs_1']:.2f}x,"
                     f"p99={res['traffic'][-1]['p99_ms']:.0f}ms"),
        # fault tolerance: crash-recovery scan rate + reconnect throughput dip
        ("recovery_fault_tolerance", recovery.run,
         lambda res: f"scan={res['scan'][-1]['scan_MBps']:.0f}MB/s,"
                     f"dip={res['reconnect']['dip_ratio']:.2f},"
                     f"reconnects={res['reconnect']['reconnects']}"),
        # predicate pushdown: sparse-query speedup over the dense scan
        ("query_pushdown", query.run,
         lambda res: f"sel={res['selectivity']:.0%},speedup={res['speedup']:.1f}x,"
                     f"pruned={res['pruned_ratio']:.2f}"),
        # tracing overhead: fully-traced serve throughput vs untraced
        ("observability_overhead", observability.run,
         lambda res: f"traced_over_untraced={res['traced_over_untraced']:.3f},"
                     f"spans_per_run={res['spans_per_run']}"),
        # live subscriptions: N-viewer push fan-out over the wire
        ("streaming_push_fanout", streaming.run,
         lambda res: f"fanout{res['fanout'][-1]['subscribers']}="
                     f"{res['fanout'][-1]['fanout_MBps']:.0f}MB/s,"
                     f"p99={res['fanout'][-1]['push_p99_ms']:.1f}ms,"
                     f"writer_ratio={res['fanout'][-1]['writer_ratio']:.2f}"),
    ]
    for name, fn, derive in suites:
        t0 = time.perf_counter()
        rows = fn(out=lambda s: print(f"  {s}"))
        wall = time.perf_counter() - t0
        print(f"{name},{wall * 1e6 / max(len(rows), 1):.0f},{derive(rows)}")


if __name__ == "__main__":
    main()
