"""Multi-pod dry-run — prove the distribution config is coherent (task §e).

For every (architecture × input shape) cell, on the single-pod 16×16 mesh
and the 2×16×16 multi-pod mesh:

    lowered  = jax.jit(step, ...).lower(**input_specs(arch))
    compiled = lowered.compile()
    print(compiled.memory_analysis())    # proves it fits
    print(compiled.cost_analysis())      # FLOPs/bytes for §Roofline

plus a collective-bytes pass over the post-SPMD HLO (cost_analysis doesn't
report collectives).  Results land in artifacts/dryrun/*.json for
benchmarks/roofline.py and EXPERIMENTS.md §Dry-run.

Usage:
    python -m repro.launch.dryrun --arch gemma3-12b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod|--both]
"""
import argparse
import json
import math
import os
import re
import time
import traceback

import jax

from repro.configs import ARCH_IDS, SHAPES, get_config, supports_shape
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (batch_structs, make_decode_step,
                                make_prefill_step, make_train_step,
                                serve_structs, train_state_structs)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
}

_COLL_RE = re.compile(
    r"=\s+(?P<types>[^=]*?)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?P<suffix>-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def collective_bytes(hlo: str) -> dict[str, float]:
    """Sum result bytes of every collective op in post-SPMD HLO text."""
    out: dict[str, float] = {}
    for line in hlo.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        if f"{m.group('op')}-done(" in line:
            continue
        byts = 0.0
        for dt, dims in _SHAPE_RE.findall(m.group("types")):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            byts += n * _DTYPE_BYTES[dt]
        key = m.group("op")
        out[key] = out.get(key, 0.0) + byts
        out[f"{key}_count"] = out.get(f"{key}_count", 0.0) + 1
    return out


def _mem_dict(mem) -> dict:
    keys = ["argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes"]
    d = {}
    for k in keys:
        try:
            d[k] = int(getattr(mem, k))
        except Exception:  # lint: allow-swallow(best-effort memory_analysis probe; absent fields are expected per backend)
            pass
    return d


_COST_KEYS = ("flops", "bytes accessed", "transcendentals", "optimal_seconds")


def _cost_dict(cost) -> dict:
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return {k: float(cost[k]) for k in _COST_KEYS if k in cost}


def default_scan_chunks(n_layers: int) -> int:
    """Largest divisor of L not exceeding ~sqrt(L) (nested-remat chunk)."""
    best = 1
    for c in range(1, int(math.isqrt(n_layers)) + 2):
        if n_layers % c == 0:
            best = c
    return best


# --------------------------------------------------------------------------- #
# Probes: XLA's cost model counts a while-loop body ONCE (trip count is
# ignored), so the big scanned model under-reports FLOPs/bytes/collectives.
# We therefore compile two tiny *unrolled* variants (k1, k2 layers) on the
# same mesh/shardings and extrapolate linearly in L:
#     total(L) = C(k1) + (C(k2) - C(k1)) / (k2 - k1) * (L - k1)
# Time-recurrence inner scans (rwkv/ssm) remain under-counted and get an
# analytic correction in benchmarks/roofline.py (documented there).
# --------------------------------------------------------------------------- #
def probe_layer_counts(cfg) -> tuple[int, int]:
    if cfg.cross_attn_every:
        return cfg.cross_attn_every, 2 * cfg.cross_attn_every
    if cfg.global_every:
        return cfg.global_every, 2 * cfg.global_every
    return 1, 2


def _probe_one(cfg, shape, mesh, k: int, seq_parallel: bool) -> dict:
    from dataclasses import replace
    ck = replace(cfg, n_layers=k)
    if shape.kind == "train":
        _, step = make_train_step(ck, mesh, scan_chunks=0,
                                  seq_parallel=seq_parallel, unroll=True,
                                  loss_chunk=shape.seq_len)
        state, shardings = train_state_structs(ck, mesh)
        batch = batch_structs(ck, shape, mesh)
        jitted = jax.jit(step, out_shardings=(shardings, None),
                         donate_argnums=(0,))
        with mesh:
            compiled = jitted.lower(state, batch).compile()
    elif shape.kind == "prefill":
        _, step = make_prefill_step(ck, mesh, unroll=True)
        sv = serve_structs(ck, shape, mesh)
        batch = batch_structs(ck, shape, mesh)
        with mesh:
            compiled = jax.jit(step).lower(sv["params"], batch).compile()
    else:
        _, step = make_decode_step(ck, mesh, unroll=True)
        sv = serve_structs(ck, shape, mesh)
        batch = batch_structs(ck, shape, mesh)
        jitted = jax.jit(step, out_shardings=(None, sv["cache_shardings"]),
                         donate_argnums=(1,))
        with mesh:
            compiled = jitted.lower(sv["params"], sv["cache"], batch).compile()
    return {"k": k, "cost": _cost_dict(compiled.cost_analysis()),
            "collectives": collective_bytes(compiled.as_text())}


def probe_extrapolate(p1: dict, p2: dict, n_layers: int) -> dict:
    k1, k2 = p1["k"], p2["k"]
    out = {"flops": 0.0, "bytes": 0.0, "collectives": {}}

    def lerp(a, b):
        return a + (b - a) / (k2 - k1) * (n_layers - k1)

    out["flops"] = lerp(p1["cost"].get("flops", 0.0), p2["cost"].get("flops", 0.0))
    out["bytes"] = lerp(p1["cost"].get("bytes accessed", 0.0),
                        p2["cost"].get("bytes accessed", 0.0))
    keys = set(p1["collectives"]) | set(p2["collectives"])
    for key in keys:
        out["collectives"][key] = lerp(p1["collectives"].get(key, 0.0),
                                       p2["collectives"].get(key, 0.0))
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str | None = "artifacts/dryrun",
             seq_parallel: bool = True, scan_chunks: int | None = None,
             probe: bool = True, serving_layout: bool = False,
             verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "kind": shape.kind,
                 "n_params": cfg.n_params, "n_params_active": cfg.n_params_active,
                 "seq_len": shape.seq_len, "global_batch": shape.global_batch}

    ok, why = supports_shape(cfg, shape)
    if not ok:
        rec.update(status="skip", reason=why)
        _write(rec, out_dir)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    rec["chips"] = int(n_chips)
    t0 = time.perf_counter()
    try:
        if shape.kind == "train":
            chunks = (default_scan_chunks(cfg.n_layers)
                      if scan_chunks is None else scan_chunks)
            rec["scan_chunks"] = chunks
            _, step = make_train_step(cfg, mesh, scan_chunks=chunks,
                                      seq_parallel=seq_parallel)
            state, shardings = train_state_structs(cfg, mesh)
            batch = batch_structs(cfg, shape, mesh)
            jitted = jax.jit(step, out_shardings=(shardings, None),
                             donate_argnums=(0,))
            with mesh:
                lowered = jitted.lower(state, batch)
        elif shape.kind == "prefill":
            _, step = make_prefill_step(cfg, mesh)
            sv = serve_structs(cfg, shape, mesh, serving_layout=serving_layout)
            batch = batch_structs(cfg, shape, mesh)
            jitted = jax.jit(step)
            with mesh:
                lowered = jitted.lower(sv["params"], batch)
        else:  # decode
            _, step = make_decode_step(cfg, mesh)
            sv = serve_structs(cfg, shape, mesh, serving_layout=serving_layout)
            batch = batch_structs(cfg, shape, mesh)
            jitted = jax.jit(step, out_shardings=(None, sv["cache_shardings"]),
                             donate_argnums=(1,))
            with mesh:
                lowered = jitted.lower(sv["params"], sv["cache"], batch)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        rec.update(
            status="ok", lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            memory=_mem_dict(mem), cost=_cost_dict(cost), collectives=coll,
            hlo_bytes=len(hlo))
        if probe:
            try:
                k1, k2 = probe_layer_counts(cfg)
                p1 = _probe_one(cfg, shape, mesh, k1, seq_parallel)
                p2 = _probe_one(cfg, shape, mesh, k2, seq_parallel)
                rec["probe"] = {"p1": p1, "p2": p2,
                                "extrapolated": probe_extrapolate(
                                    p1, p2, cfg.n_layers)}
            except Exception as e:
                rec["probe"] = {"error": f"{type(e).__name__}: {e}"}
        if verbose:
            print(f"[{arch} × {shape_name} × {mesh_name}] OK "
                  f"lower={t_lower:.1f}s compile={t_compile:.1f}s")
            print("  memory_analysis:", rec["memory"])
            c = rec["cost"]
            print(f"  cost: flops={c.get('flops', 0):.3e} "
                  f"bytes={c.get('bytes accessed', 0):.3e}")
            print("  collectives:", {k: f"{v:.3e}" for k, v in coll.items()
                                     if not k.endswith("_count")})
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        if verbose:
            print(f"[{arch} × {shape_name} × {mesh_name}] FAIL: {rec['error']}")
    _write(rec, out_dir)
    return rec


def _write(rec: dict, out_dir: str | None) -> None:
    if out_dir is None:
        return
    os.makedirs(out_dir, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1)


def main() -> None:
    # 512 virtual host devices for the pod meshes; must precede the first
    # backend query (importing jax does not initialize one)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both", action="store_true",
                    help="run single-pod and multi-pod meshes")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--no-seq-parallel", action="store_true")
    ap.add_argument("--scan-chunks", type=int, default=None)
    ap.add_argument("--no-probe", action="store_true")
    ap.add_argument("--serving-layout", action="store_true",
                    help="TP-only weights for prefill/decode (no FSDP "
                         "re-gather; see EXPERIMENTS.md §Perf B1')")
    args = ap.parse_args()

    meshes = [False, True] if args.both else [args.multi_pod]
    cells = ([(a, s) for a in ARCH_IDS for s in SHAPES]
             if args.all else [(args.arch, args.shape)])
    n_fail = 0
    for arch, shape in cells:
        for mp in meshes:
            rec = run_cell(arch, shape, mp, out_dir=args.out,
                           seq_parallel=not args.no_seq_parallel,
                           scan_chunks=args.scan_chunks,
                           probe=not args.no_probe,
                           serving_layout=args.serving_layout)
            n_fail += rec["status"] == "error"
    if n_fail:
        raise SystemExit(f"{n_fail} cells failed")


if __name__ == "__main__":
    main()
