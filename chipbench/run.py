"""One run of one benchmark cell on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell names a configuration (``configs/<config>.json``: the app's
sizes, batching, chips) and a traffic mix (``traffic/<traffic>.json``,
whose ``kind`` names its generator ``loadgen/<kind>.py``), both found
through ``BENCHMARK.json``.  The configuration's ``app`` names
``apps/<app>.py``, which provides

- ``REQUIRED``: the configuration keys it reads;
- ``inputs(config, seed) -> Source``: the seeded requests.  A source has
  ``device_pool(n)`` and ``host_pool(n)`` (items may differ in shape),
  ``warm_items()`` (one item of every shape the pools can hold) and
  ``size(item)`` (the item's work, in the app's ``UNIT``);
- ``build(config, source, devices) -> record.Served``: the served system,
  warmed on every item of ``warm_items()``;
- ``check(outputs, items, source) -> {name: {"value", "limit"}}``: the
  served outputs against the benchmark's own reference of their inputs
  (and of whatever else the source drew from the seed, such as weights);
- ``control(items, source)``: that reference one precision lower, put in
  the served path's place; ``control.py`` judges it by ``check``, and a
  run never calls it.

The run builds the app, then drives its ``RequestQueueServer`` with the
mix for ``--seconds``.  Afterwards it checks a sample of the served
outputs, drawn from the seed.  A "frame" here, in ``frames_per_s`` and in
``record.FrameRecord``, is one served request of the app.

The last stdout line is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``, each read by
``metrics/<name>.py``), ``device``, with ``--trace 1`` a ``breakdown``,
and last ``checks``, each number compared beside its limit.  Without a TPU,
or with fewer chips than the cell asks for, it exits 1 and prints no
result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from chipbench import trace_reduce  # noqa: E402
from chipbench.record import FrameRecord, RunData, percentile  # noqa: E402

WAIT_PAST_CLOSE_S = 60.0    # how long a frame due in the window may take
MAX_KEPT = 128              # served frames held on the device for the check
AUTOTUNE_DIR = os.path.join(ROOT, ".autotune-cache")


class NoChip(RuntimeError):
    pass


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    app: object       # the module apps/<app>.py that the config names


def _json(path: str):
    with open(path) as f:
        return json.load(f)


def _module(kind: str, name: str):
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def app_of(config: dict):
    """The app module that ``config`` names; there is no default."""
    if "app" not in config:
        raise KeyError(f"configuration {config.get('name')!r} names no app")
    return _module("apps", config["app"])


def resolve(name: str, bench: dict | None = None) -> Cell:
    """The cell ``name`` with its configuration, app, traffic and
    metrics."""
    bench = bench or _json(os.path.join(ROOT, "BENCHMARK.json"))
    (w,) = [w for w in bench["workloads"] if w["name"] == name] or [None]
    if w is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    (c,) = [c for c in bench["configs"] if c["name"] == w["config"]]

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    config = _json(os.path.join(ROOT, c["file"]))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=_json(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json")),
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)],
                app=app_of(config))


def chip_devices(chips: int) -> list:
    """The first ``chips`` TPU devices; :class:`NoChip` without them."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


# --------------------------------------------------------------------------- #
# host spans (traced runs only)
# --------------------------------------------------------------------------- #
def span_factory(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def wrap_in_span(obj, attr: str, name: str, spans) -> None:
    """Record a host span around every call of ``obj.attr``."""
    fn = getattr(obj, attr)

    def wrapped(*a, **k):
        with spans(name):
            return fn(*a, **k)

    setattr(obj, attr, wrapped)


# --------------------------------------------------------------------------- #
# the measured window
# --------------------------------------------------------------------------- #
def drive(load, size, srv, t0: float, seconds: float, spans, keep):
    """Send the mix from ``t0`` for ``seconds``; wait for every frame sent.

    Returns the frame records and the served frames kept for the check
    (index -> device array).  A producer thread sends, a client thread
    waits for each result in order and holds it ready; the client keeps
    the result of each frame whose index ``keep`` accepts (at most
    ``MAX_KEPT``) and drops every other, and the request's frame with it.
    ``size(item)`` gives each frame's record its size.
    """
    import jax

    t1 = t0 + seconds
    sent: queue.SimpleQueue = queue.SimpleQueue()
    records: list[FrameRecord] = []
    kept: dict[int, object] = {}
    errors: list[BaseException] = []

    def producer():
        try:
            for i, (due, p) in enumerate(load.schedule(seconds)):
                if due is None:
                    if time.perf_counter() >= t1:
                        break
                    t_due = None
                else:
                    t_due = t0 + due
                    delay = t_due - time.perf_counter()
                    if delay > 0:
                        time.sleep(delay)
                t_submit = time.perf_counter()
                with (spans("upload") if load.on_host
                      else contextlib.nullcontext()):
                    x = load.frame(p)
                with spans("submit"):
                    r = srv.submit(x)
                sent.put((FrameRecord(i, p, t_due, t_submit, size(x)), r))
        except BaseException as e:
            errors.append(e)
        finally:
            sent.put(None)

    def client():
        while True:
            item = sent.get()
            if item is None:
                return
            rec, r = item
            try:
                with spans("client_wait"):
                    out = r.wait(timeout=max(
                        t1 + WAIT_PAST_CLOSE_S - time.perf_counter(), 1e-3))
                    jax.block_until_ready(out)
                rec.t_ready = time.perf_counter()
                rec.queue_ms = r.queue_ms
                if keep(rec.index) and len(kept) < MAX_KEPT:
                    kept[rec.index] = out
            except Exception as e:    # a frame that never came
                rec.error = f"{type(e).__name__}: {e}"
            finally:
                r.result, r.args = None, ()
            records.append(rec)

    threads = [threading.Thread(target=producer, name="bench-producer"),
               threading.Thread(target=client, name="bench-client")]
    for t in threads:
        t.start()
    return threads, records, kept, errors


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices: list, peak: dict, t_start: float = T_PROCESS,
             log=print) -> dict:
    """Set up, measure and check one run; returns the result line's
    object.  ``devices`` are the chips the cell holds, ``peak`` their
    kind's entry of ``peaks.json``."""
    from jax import monitoring

    compiles: list[float] = []
    full_gc: list[tuple[float, float]] = []   # (start, seconds)
    gc_started: list[float] = []

    def on_event(event, duration_secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(time.perf_counter())

    def on_gc(phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            gc_started.append(time.perf_counter())
        elif gc_started:
            t = gc_started.pop()
            full_gc.append((t, time.perf_counter() - t))

    monitoring.register_event_duration_secs_listener(on_event)
    gc.callbacks.append(on_gc)
    try:
        return _run_cell(cell, seed, seconds, trace, devices, peak, t_start,
                         log, compiles, full_gc)
    finally:
        gc.callbacks.remove(on_gc)
        gc.unfreeze()
        monitoring.unregister_event_duration_listener(on_event)


def _run_cell(cell, seed, seconds, trace, devices, peak, t_start, log,
              compiles, full_gc) -> dict:
    import jax

    cfg, app = cell.config, cell.app
    source = app.inputs(cfg, seed)
    load = _module("loadgen", cell.traffic["kind"]).Load(
        cell.traffic, source, seed)
    # one frame in check_every, from an offset drawn from the seed: every
    # seed keeps as many frames, spread over the window
    every = int(cell.traffic["check_every"])
    offset = int(np.random.default_rng([seed, 2]).integers(every))
    served = app.build(cfg, source, devices)
    for line in served.plan_lines:
        log(line)
    srv, ex = served.server, served.executor
    spans = span_factory(trace)
    if trace:
        wrap_in_span(srv, "_collect_batch", "batcher_wait", spans)
        wrap_in_span(ex, "submit_many", "dispatch", spans)
        wrap_in_span(ex, "_finalize", "retire", spans)
        log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(log_dir)
    # set-up leaves some 10^5 long-lived objects (the traced app, plans,
    # programs); a full collection that scans them holds every thread of
    # the server for tens of ms at a random instant of the window
    gc.collect()
    gc.freeze()
    srv.start()
    stage_compiles = ex.compile_count()
    ex.reset_stats()
    with spans("window"):
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        threads, records, kept, errors = drive(
            load, source.size, srv, t0, seconds, spans,
            lambda i: (i + offset) % every == 0)
        time.sleep(max(t0 + seconds - time.perf_counter(), 0.0))
        t1 = time.perf_counter()
    ex_stats = ex.stats().as_dict()
    summary = None
    if trace:
        jax.profiler.stop_trace()
    for t in threads:
        t.join()
    srv.stop()
    ex.close()
    if errors:
        raise errors[0]
    in_window = sum(t0 <= c <= t1 for c in compiles)
    gc_window = [d for t, d in full_gc if t0 <= t <= t1]
    stage_compiles = ex.compile_count() - stage_compiles
    # the CPU backend that the tests drive reports no memory statistics
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)

    run = RunData(config=cfg, peak=peak,
                  seconds=float(seconds), t0=t0, t1=t1, setup_s=setup_s,
                  frames=sorted(records, key=lambda f: f.index),
                  executor=ex_stats)
    if trace:
        summary = trace_reduce.reduce(trace_reduce.load(
            trace_reduce.find_xplane(log_dir)))
        shutil.rmtree(log_dir, ignore_errors=True)
        run.trace = summary
    late = [(f.t_submit - f.t_due) * 1e3 for f in run.attempted()
            if f.t_due is not None]
    log(f"window: {seconds:g} s; frames attempted {len(run.attempted())}, "
        f"ready in the window {len(run.ready_in_window())}; compiles in the "
        f"window {in_window} (stage programs {stage_compiles})")
    log(f"sizes: {sum(f.size for f in run.attempted())} {app.UNIT} over "
        f"the frames attempted")
    log(f"gc: full collections in the window {len(gc_window)}, longest "
        f"{max(gc_window, default=0.0) * 1e3:.3f} ms")
    if late:
        log(f"generator lateness: p99 {percentile(late, 99):.3f} ms, "
            f"max {max(late):.3f} ms")
        lat = run.latencies_ms()
        log(f"latency over {len(lat)} frames due: " + ", ".join(
            f"p{q} {percentile(lat, q):.3f}" for q in (50, 90, 95, 99))
            + f", max {max(lat):.3f} ms")
    else:
        log("generator lateness: closed loop, no schedule")
    if summary is not None:
        for dev, busy in summary.busy_s.items():
            log(f"trace: {dev} busy {busy:.6f} s of "
                f"{summary.window_s:.6f} s")

    # the check, off the window: served frames against the app's reference
    host_out = _host(kept)
    kept.clear()
    pool_of = {f.index: f.pool_index for f in run.frames}
    keys = sorted(host_out)
    pool = {p: load.pool_frame(p) for p in sorted({pool_of[i] for i in keys})}
    load.release()
    del served, srv, ex
    checks = app.check([host_out[i] for i in keys],
                       [pool[pool_of[i]] for i in keys], source)
    attempted = run.attempted()
    failed = sum(f.error is not None for f in attempted)
    log(f"check: {len(keys)} served frames compared, from "
        f"{len(pool)} distinct pool frames")
    checks["frames_lost"] = {"value": failed, "limit": 0}
    correct = bool(keys) and all(c["value"] is not None
                                 and c["value"] <= c["limit"]
                                 for c in checks.values())

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = _module("metrics", m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    out = {"correct": bool(correct), "attempted": len(attempted),
           "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.mean_busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.device_ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps]}
    out["checks"] = checks
    return out


def _host(kept: dict) -> dict:
    """The kept device arrays, copied to the host in one transfer."""
    import jax

    keys = sorted(kept)
    return dict(zip(keys, jax.device_get([kept[k] for k in keys])))


def cell_peak(devices: list) -> dict:
    """The published peaks of the cell's device kind; an unknown kind is an
    error, not a default."""
    peaks = _json(os.path.join(BENCH, "peaks.json"))["kinds"]
    kind = devices[0].device_kind
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = resolve(args.workload)
    os.environ["REPRO_AUTOTUNE_CACHE"] = AUTOTUNE_DIR
    # libtpu otherwise logs to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        devices = chip_devices(cell.chips)
    except NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                   cell_peak(devices))
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
