"""Quickstart — the paper's Fig. 1 flow on its own case study, end to end.

An *unmodified* Harris corner-detection app is traced while it runs
(Frontend, Steps 1-3), the call graph incl. I/O data is rendered (Fig. 4),
the Backend looks up Pallas "hardware modules" in the database and the
Pipeline Generator builds a balanced mixed sw/hw pipeline (Step 8), which
the Function Off-loader deploys as a drop-in replacement (Step 9).

    PYTHONPATH=src python examples/quickstart.py
"""
import time

import jax
import numpy as np

from repro.core import courier_offload
from repro.core.tracer import Library
from repro.launch.compile_cache import enable_compile_cache
from repro.models.harris import corner_harris_demo, make_harris_db


def main():
    enable_compile_cache()
    # The "running binary": user code over a library namespace, never edited.
    db = make_harris_db(with_hw=True)
    lib = Library(db)
    app = corner_harris_demo(lib)

    frames = [jax.random.uniform(jax.random.PRNGKey(i), (270, 480, 3)) * 255
              for i in range(8)]

    # Steps 1-9 in one call: trace -> DB lookup -> balanced partition ->
    # token pipeline -> deployable wrapper.
    off = courier_offload(app, frames[0], db=db, n_threads=3)

    print("=== Fig.4: traced call graph (I/O data + profile) ===")
    print(off.ir.render())
    print("\n=== Step 8: generated pipeline ===")
    print(off.describe())

    # Deployed run: same semantics, pipelined execution.
    ref = app(frames[0])
    got = off(frames[0])
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)
    print("\nsemantics preserved: pipeline(f) == original(f)")

    for name, fn in [("original (unmodified app)",
                      lambda: [jax.block_until_ready(app(f)) for f in frames]),
                     ("Courier pipeline (token stream)",
                      lambda: jax.block_until_ready(off.map(frames)))]:
        fn()                      # warmup
        t0 = time.perf_counter()
        fn()
        print(f"{name:34s}: {(time.perf_counter() - t0) * 1e3 / len(frames):7.2f} ms/frame")


if __name__ == "__main__":
    main()
