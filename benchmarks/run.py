"""Benchmark harness — one function per paper table/figure.

Prints ``name,value,derived`` CSV.  Tables map to the paper:
  table1 — processing-time comparison (sequential vs Courier pipeline)
  table2 — per-module evaluation (HLS report → TPU roofline estimate)
  table3 — resource utilization (BRAM/DSP/LUT → VMEM/MXU budget)
  fig4   — traced function call graph incl. I/O data
  fusion — fused mega-kernels vs unfused chains (beyond-paper)
  roofline — deliverable (g), from the dry-run artifacts when present

Also writes ``BENCH_pipeline.json`` (machine-readable tokens/s +
bottleneck ms incl. the fused path) so the perf trajectory is tracked
across PRs.

``--smoke``: the fast CI entry point — a 2-token pipeline benchmark plus
the fusion smoke comparison only (pair with ``pytest -m "not slow"``, see
``make bench-smoke``).
"""
from __future__ import annotations

import sys
import traceback


def _emit(mod) -> bool:
    """Print ``mod``'s rows; False (after an ERROR row) when it raised."""
    try:
        for name, value, derived in mod.run():
            print(f"{name},{value},{str(derived).replace(',', ';')}")
        return True
    except Exception as e:
        print(f"{mod.__name__}.ERROR,-1,{type(e).__name__}: "
              f"{str(e)[:120]}".replace(",", ";"))
        traceback.print_exc(file=sys.stderr)
        return False


def main() -> None:
    from benchmarks import (analysis, decode, devices, faults,
                            fig4_callgraph, fusion, overload, replan,
                            replicate, roofline, table1_pipeline,
                            table2_modules, table3_resources,
                            trace_pipeline)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    smoke = "--smoke" in sys.argv[1:]
    print("name,value,derived")
    if smoke:
        # 2-token pipeline benchmark + fusion comparison + adaptive-replan
        # smoke, small frames; one measurement feeds both the CSV rows and
        # BENCH_pipeline.json (measured_numbers / *.payload are memoized)
        try:
            m = table1_pipeline.measured_numbers(n_frames=2, size=(64, 96))
            for key in ("sequential_ms", "wavefront_ms", "async_ms"):
                print(f"smoke.{key},{round(m[key], 3)},2-token 64x96 stream")
            f = fusion.payload(smoke=True)["harris_kernel"]
            print(f"smoke.fusion.speedup,{f['speedup']},"
                  f"fused {f['fused_ms']} ms vs chain {f['chain_ms']} ms")
            rep = replan.payload(smoke=True)
            print(f"smoke.replan.recovery,{rep['sim']['recovery']},"
                  f"adaptive {rep['sim']['tps_adaptive']} tps vs static "
                  f"{rep['sim']['tps_static']} tps")
            print(f"smoke.replan.dropped,{rep['hot_swap']['dropped']},"
                  f"{rep['hot_swap']['served']} served; "
                  f"{rep['hot_swap']['recompiles_after_warmup']} recompiles")
            wide = replicate.payload(smoke=True)
            reps = str(wide['sim']['replicas']).replace(",", ";")
            print(f"smoke.replicate.speedup,{wide['sim']['speedup']},"
                  f"replicated {wide['sim']['tps_replicated']} tps vs serial "
                  f"{wide['sim']['tps_serial']} tps; replicas {reps}")
            print(f"smoke.replicate.dropped,{wide['hot_swap']['dropped']},"
                  f"{wide['hot_swap']['served']} served; "
                  f"{wide['hot_swap']['recompiles_after_warmup']} recompiles; "
                  f"{wide['sim']['out_of_order']} out-of-order")
            dev = devices.payload(smoke=True)
            dv = str(dev['sim']['bottleneck_devices']).replace(",", ";")
            print(f"smoke.devices.speedup,{dev['sim']['speedup']},"
                  f"multi-device {dev['sim']['tps_replicated']} tps vs serial "
                  f"{dev['sim']['tps_serial']} tps; devices {dv}")
            print(f"smoke.devices.pinned,{dev['sim']['distinct_devices']},"
                  f"{dev['pinning']['distinct']} distinct committed devices; "
                  f"{dev['hot_swap']['dropped']} dropped across swap")
            flt = faults.payload(smoke=True)   # asserts 0 dropped, >= 0.8x
            print(f"smoke.faults.device_loss,{flt['device_loss']['dropped']},"
                  f"{flt['device_loss']['served']} served; "
                  f"{flt['device_loss']['quarantined']} quarantined; "
                  f"{flt['device_loss']['out_of_order']} out-of-order")
            print(f"smoke.faults.recovery,{flt['device_loss']['recovery']},"
                  f"post-loss {flt['device_loss']['tps_after']} tps vs "
                  f"survivors-only {flt['device_loss']['tps_survivor']} tps")
            print(f"smoke.faults.transient,{flt['transient']['dropped']},"
                  f"{flt['transient']['retries']} retries absorbed "
                  f"{flt['transient']['errors_injected']} injected faults")
            ver = analysis.payload(smoke=True)["verify"]   # asserts < 5%
            print(f"smoke.verify.overhead,{ver['ratio']},"
                  f"verify {ver['verify_ms']} ms vs build {ver['build_ms']} "
                  f"ms over {ver['n_nodes']} nodes")
            trc = trace_pipeline.payload(smoke=True)  # asserts >= 1.5x + parity
            t = trc["transformer"]
            fused = ";".join(t["fused_nodes"]) or "none"
            print(f"smoke.trace.speedup,{t['speedup']},"
                  f"traced transformer async {t['tps_async']} tps vs "
                  f"sequential {t['tps_sequential']} tps; fused {fused}")
            print(f"smoke.trace.results_match,{int(t['results_match'])},"
                  f"{t['captured_inputs']} captured weights; recurrent "
                  f"{int(trc['recurrent']['results_match'])}; serving "
                  f"{int(trc['serving']['results_match'])}")
            ovl = overload.payload(smoke=True)  # asserts goodput + accounting
            hot, ch = ovl["sweep"]["2x"], ovl["chaos"]
            print(f"smoke.overload.goodput,"
                  f"{hot['interactive']['goodput']},"
                  f"interactive {hot['interactive']['served']}/"
                  f"{hot['interactive']['submitted']} at 2x capacity; p99 "
                  f"{hot['interactive']['p99_ms']} ms vs "
                  f"{ovl['deadline_ms']['interactive']} ms deadline")
            print(f"smoke.overload.chaos,"
                  f"{int(not ch['accounted'])},"
                  f"{ch['served']} served; {ch['shed']} shed; "
                  f"{ch['expired']} expired; {ch['failed']} failed of "
                  f"{ch['submitted']}; {ch['out_of_order']} out-of-order; "
                  f"{ch['errors_injected']} faults")
            dec = decode.payload(smoke=True)  # asserts >= 1.5x TTFT + parity
            db, dc = dec["boundary"], dec["continuous"]
            print(f"smoke.decode.ttft,{dec['p50_ttft_improvement']},"
                  f"continuous {dc['p50_ttft_ms']} ms vs boundary "
                  f"{db['p50_ttft_ms']} ms p50 at {dec['load']}x capacity; "
                  f"{dc['seam_joins']} seam joins")
            print(f"smoke.decode.dropped,{db['dropped'] + dc['dropped']},"
                  f"results_match {int(dec['results_match'])}; "
                  f"{db['out_of_order'] + dc['out_of_order']} out-of-order; "
                  f"{db['recompiles_steady'] + dc['recompiles_steady']} "
                  f"recompiles")
            path = table1_pipeline.write_bench_json(smoke=True)
            print(f"smoke.bench_json,0,{path}")
        except Exception as e:
            print(f"smoke.ERROR,-1,{type(e).__name__}: "
                  f"{str(e)[:120]}".replace(",", ";"))
            traceback.print_exc(file=sys.stderr)
            sys.exit(1)
        return
    # replan/replicate/devices/faults/overload last: their thread pools,
    # serving loops, and open-loop load generators are the noisiest
    # neighbors for the wall-clock benchmarks that precede them
    ok = [_emit(mod) for mod in (
        table1_pipeline, table2_modules, table3_resources, fig4_callgraph,
        fusion, roofline, analysis, trace_pipeline, replan, replicate,
        devices, faults, overload, decode)]
    try:
        path = table1_pipeline.write_bench_json()
        print(f"bench_json,0,{path}")
    except Exception as e:
        print(f"bench_json.ERROR,-1,{type(e).__name__}: "
              f"{str(e)[:120]}".replace(",", ";"))
        traceback.print_exc(file=sys.stderr)
        ok.append(False)
    if not all(ok):
        sys.exit(1)


if __name__ == "__main__":
    main()
