"""Bring-up check: the Harris pipeline served end to end on a TPU.

Traces the paper's unmodified cornerHarris_Demo app on 1920x1080 float32
frames drawn from ``--seed``, builds the pipeline twice (unfused, and with
cvtColor+cornerHarris fused into one kernel) and serves ``REQUESTS``
frames through each pipeline's RequestQueueServer
(``repro.launch.serve.serve_pipeline_demo``).  For each pipeline it fails
unless:

* cvtColor, cornerHarris and convertScaleAbs (or the fused pair) are placed
  on the chip, and every stage holding one runs a Mosaic kernel
  (``tpu_custom_call``) of that name, in its per-token and its batched
  executable;
* the Off-load Switcher logged no fallback and nothing compiled while the
  requests were served;
* every served frame, and the Harris response the served pipeline's own
  stage programs produce for it, agree with a host numpy float32 reference
  (``repro.models.harris.numpy_reference``) within ``IMAGE_ATOL`` gray
  levels and ``RESPONSE_RTOL`` of the response range.

``--chips 4`` runs only the multi-chip path: the same frames served through
a plan whose bottleneck stage is widened onto four chips, and through the
one-chip pipeline; outputs must be identical and the widened stage must
have run on at least two chips.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Without a
TPU the script exits non-zero and prints no such line.

    python chip_smoke.py [--seed 0]
    python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SIZE = (1080, 1920)               # the paper's frame, H x W
REQUESTS = 16                     # frames served through each pipeline
MAX_BATCH = 4                     # the one-chip server's batch size
IMAGE_ATOL = 1e-2                 # gray levels, on the final 0-255 image
RESPONSE_RTOL = 1e-5              # of max |response|, on the raw response
# the Pallas kernel each hardware node must run (its pallas_call name)
KERNEL_OF = {"cvtColor": "cvt_color", "cornerHarris": "corner_harris",
             "convertScaleAbs": "convert_scale_abs",
             "cvtColor+cornerHarris": "harris_fused_pair"}
HW_NODES = {False: {"cvtColor", "cornerHarris", "convertScaleAbs"},
            True: {"cvtColor+cornerHarris", "convertScaleAbs"}}


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def stage_kernels(run) -> list[tuple[set[str], set[str]]]:
    """Kernels in each stage's per-token and batched executables."""
    import jax.numpy as jnp

    from repro.kernels.backend import compiled_kernels

    pipe = run.offloaded.pipeline
    batch = run.executor.microbatch
    env = dict(zip(pipe.graph_inputs, run.frames[:1]))
    out = []
    for fn, bfn in zip(pipe.stage_fns, pipe.batched_stage_fns()):
        stacked = {k: jnp.stack([v] * batch) for k, v in env.items()}
        out.append((compiled_kernels(fn.lower(env).compile().as_text()),
                    compiled_kernels(bfn.lower(stacked).compile().as_text())))
        env = fn(env)
    return out


def served_responses(run, node_key: str) -> list:
    """The output of node ``node_key`` for every served frame, taken from
    the batched stage programs the executor ran, in batches of its
    microbatch (the last one padded with its own final frame)."""
    import jax.numpy as jnp

    pipe = run.offloaded.pipeline
    batch = run.executor.microbatch
    (value,) = [v for n in pipe.ir.nodes if n.fn_key == node_key
                for v in n.outputs]
    out = []
    for i in range(0, len(run.frames), batch):
        chunk = run.frames[i:i + batch]
        env = dict(zip(pipe.graph_inputs, [jnp.stack(
            chunk + [chunk[-1]] * (batch - len(chunk)))]))
        for bfn in pipe.batched_stage_fns():
            env = bfn(env)
            if value in env:
                break
        check(value in env, f"{node_key} output {value} never crossed a "
              "stage boundary")
        out.extend(env[value][:len(chunk)])
    return out


def smoke_pipeline(fuse: bool, args, refs) -> None:
    import numpy as np

    from repro.launch.serve import serve_pipeline_demo

    name = "fused" if fuse else "unfused"
    t0 = time.perf_counter()
    run = serve_pipeline_demo(n_requests=REQUESTS, max_batch=MAX_BATCH,
                              size=SIZE, seed=args.seed, fuse=fuse)
    served_s = time.perf_counter() - t0
    pipe = run.offloaded.pipeline
    kernels = stage_kernels(run)
    print(f"[{name}] {pipe.plan.n_stages} stages, microbatch "
          f"{run.executor.microbatch}")
    hw = set()
    for k, (stage, (one, many)) in enumerate(zip(pipe.plan.stages, kernels)):
        nodes = [pipe.ir.node(n) for n in stage.node_names]
        print(f"[{name}]   stage {k}: "
              + ", ".join(f"{n.fn_key}={n.placement.kind}" for n in nodes)
              + f"; kernels {sorted(one)}, batched {sorted(many)}")
        want = {KERNEL_OF[n.fn_key] for n in nodes if n.placement.is_hw}
        hw |= {n.fn_key for n in nodes if n.placement.is_hw}
        check(want <= one and want <= many,
              f"{name} stage {k} should run kernels {sorted(want)}; its "
              f"executables hold {sorted(one)} / {sorted(many)}")
    check(hw == HW_NODES[fuse], f"{name}: hardware nodes {sorted(hw)}, "
          f"want {sorted(HW_NODES[fuse])}")
    fallbacks = run.offloaded.plan.fallback_log + run.offloaded.fallbacks
    served = run.stats["requests_served"]
    print(f"[{name}] requests served {served}/{REQUESTS}, compiles in "
          f"the served window {run.compiles_in_window}, fallbacks "
          f"{len(fallbacks)}; build+warmup+serve {served_s:.1f} s")
    check(not fallbacks, f"{name}: fallbacks {fallbacks}")
    check(run.compiles_in_window == 0,
          f"{name}: {run.compiles_in_window} compiles while serving")
    check(served == REQUESTS and len(run.results) == REQUESTS,
          f"{name}: served {served} of {REQUESTS} requests")

    responses = served_responses(
        run, "cvtColor+cornerHarris" if fuse else "cornerHarris")
    img_err = resp_err = 0.0
    for got, resp, (ref_resp, ref_img) in zip(run.results, responses, refs):
        got = np.asarray(got)
        check(got.shape == SIZE and bool(np.isfinite(got).all()),
              f"{name}: served frame of shape {got.shape} or not finite")
        img_err = max(img_err, float(np.max(np.abs(got - ref_img))))
        resp = np.asarray(resp)
        resp_err = max(resp_err, float(np.max(np.abs(resp - ref_resp)))
                       / float(np.max(np.abs(ref_resp))))
    print(f"[{name}] max |served - ref| {img_err:.3g} gray levels "
          f"(limit {IMAGE_ATOL}); max |response - ref| / max |ref| "
          f"{resp_err:.3g} (limit {RESPONSE_RTOL})")
    check(img_err <= IMAGE_ATOL, f"{name}: served frames off by {img_err}")
    check(resp_err <= RESPONSE_RTOL, f"{name}: response off by {resp_err}")


def smoke_four_chips(args) -> None:
    import numpy as np

    from repro.launch.serve import serve_pipeline_demo

    # batch 1 here, not MAX_BATCH: the widened plan grows its batch by up
    # to 4x (replication_aware_batching), and REQUESTS frames must still
    # form enough groups to reach every replica of the widened stage
    kw = dict(n_requests=REQUESTS, max_batch=1, size=SIZE, seed=args.seed)
    one = serve_pipeline_demo(**kw)
    # three workers beyond one per stage: the planner widens the
    # bottleneck stage and pins its replicas to distinct chips
    wide = serve_pipeline_demo(
        devices=4, worker_budget=one.offloaded.pipeline.plan.n_stages + 3,
        **kw)
    per_stage = wide.executor.stats().per_stage
    for k, c in enumerate(per_stage):
        print(f"[4 chips] stage {k}: replicas {c.replicas}, pinned to "
              f"{c.devices}, groups run per device id {dict(c.ran_on)}")
    widened = [k for k, c in enumerate(per_stage) if c.replicas > 1]
    same = sum(bool(np.array_equal(np.asarray(a), np.asarray(b)))
               for a, b in zip(one.results, wide.results))
    print(f"[4 chips] served {wide.stats['requests_served']}/{REQUESTS}; "
          f"{same}/{REQUESTS} identical to the one-chip pipeline; compiles "
          f"in the served window {wide.compiles_in_window}; widened stages "
          f"{widened}")
    check(len(wide.results) == len(one.results) == REQUESTS,
          "4 chips: not every request was served")
    check(same == REQUESTS, "4 chips: outputs differ from one chip")
    check(bool(widened), "4 chips: the planner widened no stage")
    for k in widened:
        ran_on = sorted(per_stage[k].ran_on)
        check(len(ran_on) >= 2,
              f"4 chips: widened stage {k} ran only on device ids {ran_on}")
    check(wide.compiles_in_window == 0,
          f"4 chips: {wide.compiles_in_window} compiles while serving")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax
    import numpy as np

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"FAIL: no TPU; JAX found {dev.platform} devices",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"FAIL: --chips {args.chips} but JAX found {len(devs)}",
              file=sys.stderr)
        return 1
    print(f"device: {dev.device_kind} ({dev.platform}), {len(devs)} "
          f"visible, jax {jax.__version__}")
    try:
        if args.chips == 4:
            smoke_four_chips(args)
        else:
            from repro.launch.serve import demo_frames
            from repro.models.harris import numpy_reference

            refs = [numpy_reference(np.asarray(f))
                    for f in demo_frames(REQUESTS, SIZE, args.seed)]
            for fuse in (False, True):
                smoke_pipeline(fuse, args, refs)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
