"""Paper Table I — processing-time comparison (sequential vs Courier pipeline).

Three parts:
1. *Reproduction*: feed the paper's own measured/estimated per-function
   times (Zynq) to our Pipeline Generator and verify it reproduces the
   4-stage plan and the ≈15x speedup the paper measured.
2. *This system*: trace the actual jnp Harris app on this host, build the
   mixed pipeline (Pallas "hw" modules + jnp "sw" normalize) and measure
   sequential vs synchronous-wavefront vs async-executor wall time over a
   multi-frame token stream (with and without per-stage micro-batching).
3. *Serving*: run the same pipeline behind the dynamic-batching
   request-queue server and report per-request latency percentiles.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp

from repro.configs.harris import config as HARRIS
from repro.core import (courier_offload, linear_ir, partition_optimal,
                        partition_paper)
from repro.models.harris import corner_harris_demo, make_harris_db
from repro.core.tracer import Library

PAPER_FNS = ["cvtColor", "cornerHarris", "normalize", "convertScaleAbs"]


def paper_replay() -> list[tuple[str, float, str]]:
    rows = []
    offl = [HARRIS.paper_times_offl[f] for f in PAPER_FNS]
    ir = linear_ir("harris-paper", PAPER_FNS, offl)
    plan = partition_paper(ir, n_threads=3)
    pred_period = plan.bottleneck_ms
    pred_speedup = HARRIS.paper_total_orig_ms / pred_period
    rows.append(("table1.paper.n_stages", plan.n_stages,
                 "paper built 4"))
    rows.append(("table1.paper.pipeline_period_ms", pred_period,
                 f"paper measured {HARRIS.paper_total_offl_ms}"))
    rows.append(("table1.paper.predicted_speedup", round(pred_speedup, 2),
                 f"paper measured {HARRIS.paper_speedup}x"))
    opt = partition_optimal(ir)
    rows.append(("table1.optimal_dp.bottleneck_ms", opt.bottleneck_ms,
                 f"{opt.n_stages} stages (beyond-paper)"))
    return rows


def measured_run(n_frames: int = 12, hw: bool = True,
                 size: tuple[int, int] = (270, 480)) -> list[tuple[str, float, str]]:
    """Trace + offload + run the real app; wall-clock seq vs pipelined."""
    m = measured_numbers(n_frames=n_frames, hw=hw, size=size)
    H, W = size
    return [
        ("table1.this_host.sequential_ms_per_frame", m["sequential_ms"],
         f"{H}x{W}, {n_frames} frames, unmodified eager app"),
        ("table1.this_host.staged_nopipe_ms_per_frame", m["staged_ms"],
         "compiled stages, no token overlap"),
        ("table1.this_host.pipelined_ms_per_frame", m["wavefront_ms"],
         f"{m['n_stages']} stages, synchronous wavefront run()"),
        ("table1.this_host.async_ms_per_frame", m["async_ms"],
         f"PipelineExecutor, mean occupancy {m['occupancy']:.1f} tokens"),
        ("table1.this_host.async_microbatch_ms_per_frame", m["microbatch_ms"],
         f"PipelineExecutor, microbatch={m['microbatch']}"),
        ("table1.this_host.async_throughput_fps", m["async_tps"],
         "async executor frames/s"),
        ("table1.this_host.speedup_total",
         round(m["sequential_ms"] / max(m["wavefront_ms"], 1e-9), 3),
         "vs unmodified app (paper's headline comparison)"),
        ("table1.this_host.speedup_pipelining",
         round(m["staged_ms"] / max(m["wavefront_ms"], 1e-9), 3),
         "token overlap only; 1-core container limits true parallelism"),
        ("table1.this_host.speedup_async_vs_wavefront",
         round(m["wavefront_ms"] / max(m["async_ms"], 1e-9), 3),
         "async executor vs synchronous wavefront run()"),
        ("table1.this_host.speedup_async_vs_sequential",
         round(m["sequential_ms"] / max(m["async_ms"], 1e-9), 3),
         "async executor vs unmodified sequential app"),
    ]


_numbers_cache: dict = {}


def measured_numbers(n_frames: int = 12, hw: bool = True,
                     size: tuple[int, int] = (270, 480)) -> dict:
    """Machine-readable core of the Table-1 measurement (per-frame ms and
    tokens/s for every execution mode); consumed by ``bench_payload``.
    Memoized per (n_frames, hw, size) so the CSV rows and the JSON artifact
    share one measurement instead of running the benchmark twice."""
    cache_key = (n_frames, hw, tuple(size))
    if cache_key in _numbers_cache:
        return _numbers_cache[cache_key]
    db = make_harris_db(with_hw=hw)
    lib = Library(db)
    app = corner_harris_demo(lib)
    H, W = size
    key = jax.random.PRNGKey(0)
    frames = [jax.random.uniform(jax.random.PRNGKey(i), (H, W, 3)) * 255
              for i in range(n_frames)]
    off = courier_offload(app, frames[0], db=db, prefer_hw=False)

    # warmup both paths
    jax.block_until_ready(off.pipeline(frames[0]))
    jax.block_until_ready(app(frames[0]))

    def best_ms(f, reps: int = 3) -> float:
        """min-of-reps wall time (single-shot timings are noisy on a
        shared 1-2 core container)."""
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f())
            best = min(best, (time.perf_counter() - t0) * 1e3)
        return best

    def run_eager():
        return [app(f) for f in frames]

    def run_staged():
        # same compiled stages, no token overlap (isolates the pipelining
        # gain from the stage-compilation gain, like Table I's two columns)
        return [off.pipeline(f) for f in frames]

    t_seq = best_ms(run_eager)
    t_seqjit = best_ms(run_staged)

    # async executor (eager issue, bounded pool).  The pool is sized like
    # the wavefront's (~2x stages), NOT to the whole frame stream: on a
    # small host the live working set (pool x frame + intermediates) is
    # what dominates per-frame wall time, and an n_frames pool measurably
    # loses to the wavefront on big frames purely through allocator/cache
    # pressure.  Interleave the wavefront/async reps so both sample the
    # same background noise (shared-container swings dominate single runs).
    S = off.pipeline.plan.n_stages
    ex = off.pipeline.executor(max_in_flight=2 * S + 1)
    jax.block_until_ready(ex.run(frames[:2]))
    ex.reset_stats()
    t_pipe = t_async = float("inf")
    for _ in range(5):
        t_pipe = min(t_pipe, best_ms(lambda: off.map(frames), reps=1))
        t_async = min(t_async, best_ms(lambda: ex.run(frames), reps=1))
    occ = ex.stats().mean_occupancy

    # async executor + per-stage micro-batching (stacked token groups)
    mb = 4
    exb = off.pipeline.executor(max_in_flight=max(2 * S + 1, 2 * mb),
                                microbatch=mb)
    jax.block_until_ready(exb.run(frames[:mb]))
    t_batched = best_ms(lambda: exb.run(frames))

    _numbers_cache[cache_key] = {
        "shape": [H, W], "n_frames": n_frames,
        "sequential_ms": t_seq / n_frames,
        "staged_ms": t_seqjit / n_frames,
        "wavefront_ms": t_pipe / n_frames,
        "async_ms": t_async / n_frames,
        "microbatch_ms": t_batched / n_frames,
        "microbatch": mb,
        "occupancy": occ,
        "n_stages": off.pipeline.plan.n_stages,
        "bottleneck_ms": off.pipeline.plan.bottleneck_ms,
        "sequential_tps": round(n_frames / max(t_seq / 1e3, 1e-9), 2),
        "wavefront_tps": round(n_frames / max(t_pipe / 1e3, 1e-9), 2),
        "async_tps": round(n_frames / max(t_async / 1e3, 1e-9), 2),
        "compile_count": off.pipeline.compile_count(),
    }
    return _numbers_cache[cache_key]


# --------------------------------------------------------------------------- #
# Machine-readable benchmark artifact (BENCH_pipeline.json)
# --------------------------------------------------------------------------- #
def bench_payload(smoke: bool = False) -> dict:
    """sequential / wavefront / async / fused tokens-per-sec + bottleneck ms,
    plus the fusion, adaptive-replan, and stage-replication benchmarks —
    the perf trajectory tracked across PRs."""
    from benchmarks import (decode, devices, faults, fusion, overload,
                            replan, replicate, trace_pipeline)

    n_frames = 2 if smoke else 12
    size = (64, 96) if smoke else (270, 480)
    # fusion comparison first: it is the finest-grained measurement and the
    # most sensitive to allocator/background state left by the big-frame
    # run; the replan/replicate benchmarks LAST — their thread pools and
    # serving loops are the noisiest neighbors of all
    fus = fusion.payload(smoke=smoke)
    m = measured_numbers(n_frames=n_frames, hw=True, size=size)
    trc = trace_pipeline.payload(smoke=smoke)
    rep = replan.payload(smoke=smoke)
    wide = replicate.payload(smoke=smoke)
    dev = devices.payload(smoke=smoke)
    flt = faults.payload(smoke=smoke)    # fault churn + serving loops
    ovl = overload.payload(smoke=smoke)  # open-loop load saturation
    dec = decode.payload(smoke=smoke)    # last: open-loop decode sessions
    return {
        "bench": "table1_pipeline", "smoke": bool(smoke),
        "shape": m["shape"], "n_frames": m["n_frames"],
        "tokens_per_sec": {
            "sequential": m["sequential_tps"],
            "wavefront": m["wavefront_tps"],
            "async": m["async_tps"],
            "fused": fus["pipeline"]["fused"]["tokens_per_sec"],
        },
        "bottleneck_ms": {
            "pipeline": round(m["bottleneck_ms"], 6),
            "fused_pipeline": fus["pipeline"]["fused"]["bottleneck_ms"],
            "unfused_pipeline": fus["pipeline"]["unfused"]["bottleneck_ms"],
        },
        "per_frame_ms": {k: round(m[k], 4) for k in
                         ("sequential_ms", "staged_ms", "wavefront_ms",
                          "async_ms", "microbatch_ms")},
        "compile_count_steady": m["compile_count"],
        "fusion": fus,
        "trace": trc,
        "replan": rep,
        "replicate": wide,
        "devices": dev,
        "faults": flt,
        "overload": ovl,
        "decode": dec,
    }


def write_bench_json(path: str | None = None, smoke: bool = False) -> str:
    path = path or os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_pipeline.json")
    with open(path, "w") as f:
        json.dump(bench_payload(smoke=smoke), f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def serving_run(n_requests: int = 24, max_batch: int = 4) -> list[tuple[str, float, str]]:
    """Dynamic-batching serving loop over the Harris pipeline (latency)."""
    from repro.launch.serve import serve_pipeline_demo

    stats = serve_pipeline_demo(n_requests=n_requests, max_batch=max_batch,
                                max_wait_ms=4.0, size=(64, 96)).stats
    lat = stats["latency_ms"]
    return [
        ("table1.serving.requests", stats["requests_served"],
         f"{stats['batches']} dynamic batches, "
         f"mean size {stats['mean_batch_size']:.1f}"),
        ("table1.serving.latency_p50_ms", round(lat["p50"], 2),
         "per-request (queue + execute)"),
        ("table1.serving.latency_p95_ms", round(lat["p95"], 2),
         "per-request (queue + execute)"),
        ("table1.serving.throughput_rps", round(stats["throughput_rps"], 2),
         "requests/s, first submit → last completion"),
    ]


def run() -> list[tuple[str, float, str]]:
    return paper_replay() + measured_run() + serving_run()


if __name__ == "__main__":
    for r in run():
        print(",".join(str(x) for x in r))
    print("wrote", write_bench_json())
