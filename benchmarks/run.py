"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``--quick`` shrinks sweeps
(used by CI/tests); full mode is the default for the report in
EXPERIMENTS.md §Benchmarks.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig9]
"""
from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks import (chaos, common, completion_modes, contention,
                        e2e_step, fabric, far_memory, host_device_bw,
                        install_path, kv_capacity, offload_step, overlap,
                        rdma_analogue, serve_slo, vmem_stream)
from repro import obs
from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    ("fig8_vmem_stream", vmem_stream),
    ("fig9_18_host_device_bw", host_device_bw),
    ("fig11_12_contention", contention),
    ("fig13_14_completion_modes", completion_modes),
    ("fig19_20_rdma_analogue", rdma_analogue),
    ("tab1_offload_step", offload_step),
    ("farmem_tier_sweep", far_memory),
    ("serve_overlap", overlap),
    ("fabric_sweep", fabric),
    ("chaos_soak", chaos),
    ("serve_slo", serve_slo),
    ("install_path", install_path),
    ("kv_capacity", kv_capacity),
    ("e2e_and_roofline", e2e_step),
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: quick sweeps + miss-pipeline JSON")
    ap.add_argument("--only", default="")
    ap.add_argument("--json", default="",
                    help="miss-pipeline metrics JSON path (farmem module); "
                         "defaults to BENCH_miss_pipeline.json with --smoke")
    ap.add_argument("--select-json", default="",
                    help="path-selection sweep JSON path (farmem module); "
                         "defaults to BENCH_path_select.json with --smoke")
    ap.add_argument("--fabric-json", default="",
                    help="fabric sweep JSON path (fabric module); "
                         "defaults to BENCH_fabric.json with --smoke")
    ap.add_argument("--chaos-json", default="",
                    help="chaos soak JSON path (chaos module); "
                         "defaults to BENCH_chaos.json with --smoke")
    ap.add_argument("--serve-slo-json", default="",
                    help="serving SLO bench JSON path (serve_slo "
                         "module); defaults to BENCH_serve_slo.json "
                         "with --smoke")
    ap.add_argument("--install-json", default="",
                    help="fused install-path bench JSON path "
                         "(install_path module); defaults to "
                         "BENCH_install_path.json with --smoke")
    ap.add_argument("--kv-capacity-json", default="",
                    help="KV capacity-multipliers bench JSON path "
                         "(kv_capacity module); defaults to "
                         "BENCH_kv_capacity.json with --smoke")
    ap.add_argument("--seed", type=int, default=0,
                    help="RNG seed recorded in every BENCH_*.json "
                         "(all benchmark generators are seeded; the "
                         "artifact names the reproducible run)")
    ap.add_argument("--trace-out", default="", metavar="PATH",
                    help="enable tracing and write a Chrome trace-event "
                         "JSON of the whole run (Perfetto-loadable)")
    ap.add_argument("--metrics", action="store_true",
                    help="enable live metrics (the registry snapshot "
                         "lands in every BENCH_*.json; on by default "
                         "with --smoke)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    quick = args.quick or args.smoke
    common.set_bench_seed(args.seed)
    if args.trace_out:
        obs.trace.enable()
    if args.metrics or args.smoke:
        obs.metrics.enable_live()
    json_out = args.json or ("BENCH_miss_pipeline.json" if args.smoke
                             else "")
    select_out = args.select_json or ("BENCH_path_select.json"
                                      if args.smoke else "")
    fabric_out = args.fabric_json or ("BENCH_fabric.json"
                                      if args.smoke else "")
    chaos_out = args.chaos_json or ("BENCH_chaos.json"
                                    if args.smoke else "")
    serve_slo_out = args.serve_slo_json or ("BENCH_serve_slo.json"
                                            if args.smoke else "")
    install_out = args.install_json or ("BENCH_install_path.json"
                                        if args.smoke else "")
    kv_capacity_out = args.kv_capacity_json or ("BENCH_kv_capacity.json"
                                                if args.smoke else "")

    print("name,us_per_call,derived")
    failed = []
    for name, mod in MODULES:
        if args.only and args.only not in name:
            continue
        print(f"# --- {name} ---", flush=True)
        try:
            if (json_out or select_out) and mod is far_memory:
                mod.run(quick=quick, out=json_out, select_out=select_out)
            elif fabric_out and mod is fabric:
                mod.run(quick=quick, out=fabric_out)
            elif chaos_out and mod is chaos:
                mod.run(quick=quick, out=chaos_out)
            elif serve_slo_out and mod is serve_slo:
                mod.run(quick=quick, out=serve_slo_out)
            elif install_out and mod is install_path:
                mod.run(quick=quick, out=install_out)
            elif kv_capacity_out and mod is kv_capacity:
                mod.run(quick=quick, out=kv_capacity_out)
            else:
                mod.run(quick=quick)
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if args.trace_out:
        n_ev = obs.trace.export(args.trace_out)
        print(f"# wrote {n_ev} trace events to {args.trace_out}",
              flush=True)
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        sys.exit(1)
    print("# all benchmarks complete")


if __name__ == "__main__":
    main()
