"""Profile one score_batch dispatch: cost analysis + component ablation.

Usage: python tools/profile_kernel.py [--hlo] [--ablate]
Writes nothing; prints findings.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_NODES = int(os.environ.get("BENCH_NODES", "10000"))
BATCH = int(os.environ.get("BENCH_BATCH", "256"))


def main() -> None:
    import jax

    import bench
    from nomad_tpu.ops.kernels import score_batch
    from nomad_tpu.parallel import build_batch_inputs

    m, shapes = bench.build_cluster()
    arrays = m.sync()
    inp = build_batch_inputs(m, [shapes[i % len(shapes)] for i in range(BATCH)])
    args = (
        arrays, arrays.used, inp["tg_counts"], inp["spread_counts"],
        inp["penalties"], inp["reqs"], inp["class_eligs"], inp["host_masks"],
    )

    lowered = jax.jit(score_batch).lower(*args)
    compiled = lowered.compile()
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        print("== cost_analysis ==")
        for k in sorted(ca):
            v = ca[k]
            if isinstance(v, float) and v > 1e6:
                print(f"  {k}: {v:.3e}")
    except Exception as e:  # noqa: BLE001
        print("cost_analysis failed:", e)

    # Timed dispatch
    out = score_batch(*args)
    out.rows.block_until_ready()
    ts = []
    for _ in range(10):
        t = time.time()
        score_batch(*args).rows.block_until_ready()
        ts.append(time.time() - t)
    print(f"dispatch median: {np.median(ts)*1000:.2f} ms  "
          f"({BATCH/np.median(ts):.0f} evals/s)")

    if "--hlo" in sys.argv:
        txt = compiled.as_text()
        path = "/tmp/score_batch_hlo.txt"
        with open(path, "w") as f:
            f.write(txt)
        print("HLO written to", path, f"({len(txt)} bytes)")


if __name__ == "__main__":
    main()
