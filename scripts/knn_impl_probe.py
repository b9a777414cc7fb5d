"""Time the three GEOMESA_KNN_IMPL variants on the current backend.

One process per impl (the knob is read at trace time and steps are memoized),
child mode timing a single impl, parent mode printing one JSON line:

    python scripts/knn_impl_probe.py            # all impls, JSON summary
    python scripts/knn_impl_probe.py map        # child: one impl

Purpose: pick the config-3 KNN default for the real chip with measured
evidence (the map impl's single 10⁸-length ``lax.top_k`` per query is the
suspected dominant cost — see parallel/query.py ``_local_knn_heaps``).
Scale via GEOMESA_BENCH_N (default 8M rows), Q (default 64), K (default 10).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

N = int(os.environ.get("GEOMESA_BENCH_N", 8_000_000))
Q = int(os.environ.get("GEOMESA_BENCH_Q", 64))
K = int(os.environ.get("GEOMESA_BENCH_K", 10))


def child(impl: str) -> None:
    os.environ["GEOMESA_KNN_IMPL"] = impl
    import numpy as np

    import geomesa_tpu  # noqa: F401  (x64 on)
    import jax
    import jax.numpy as jnp

    from geomesa_tpu.parallel.mesh import make_mesh, shard_columns
    from geomesa_tpu.parallel.query import make_batched_knn_step

    rng = np.random.default_rng(7)
    lon = rng.uniform(-180, 180, N)
    lat = rng.uniform(-90, 90, N)
    xi = ((lon + 180.0) / 360.0 * 2**31).astype(np.int32)
    yi = ((lat + 90.0) / 180.0 * 2**31).astype(np.int32)
    mesh = make_mesh()
    cols, _, _ = shard_columns(mesh, {"x": xi, "y": yi})
    qx = jnp.asarray(rng.uniform(-150, 150, Q).astype(np.float32))
    qy = jnp.asarray(rng.uniform(-60, 60, Q).astype(np.float32))
    step = make_batched_knn_step(mesh, K)

    def run():
        d, r = step(cols["x"], cols["y"], jnp.int32(N), qx, qy)
        return np.asarray(d), np.asarray(r)

    t0 = time.perf_counter()
    d, _ = run()  # compile + warmup
    compile_s = time.perf_counter() - t0
    lat_ms = []
    for _ in range(5):
        s = time.perf_counter()
        run()
        lat_ms.append((time.perf_counter() - s) * 1e3)
    print(json.dumps({
        "impl": impl, "backend": jax.default_backend(),
        "n": N, "q": Q, "k": K,
        "batch_p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
        "ms_per_point": round(float(np.percentile(lat_ms, 50)) / Q, 4),
        "compile_s": round(compile_s, 1),
        "checksum": round(float(np.asarray(d).sum()), 3),
    }))


def main() -> None:
    results = []
    for impl in ("map", "scan", "blocked"):
        try:
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__), impl],
                capture_output=True, text=True, cwd=ROOT,
                timeout=int(os.environ.get(
                    "GEOMESA_KNN_PROBE_CHILD_TIMEOUT", 1200)),
            )
            line = out.stdout.strip().splitlines()[-1] if out.stdout.strip() else ""
            results.append(json.loads(line) if line.startswith("{") else
                           {"impl": impl, "error": out.stderr[-300:]})
        except (subprocess.TimeoutExpired, json.JSONDecodeError) as e:
            results.append({"impl": impl, "error": str(e)[:200]})
    ok = [r for r in results if "batch_p50_ms" in r]
    winner = min(ok, key=lambda r: r["batch_p50_ms"])["impl"] if ok else None
    # identical distance multisets -> checksums agree within f32 noise.
    # With fewer than two survivors there IS no cross-check — report False
    # so a lone fast impl can never pass the downstream verification gate.
    sums = [r["checksum"] for r in ok]
    agree = (len(sums) >= 2 and
             max(sums) - min(sums) <= max(abs(s) for s in sums) * 1e-5 + 1e-3)
    print(json.dumps({"results": results, "winner": winner,
                      "checksums_agree": agree}))


if __name__ == "__main__":
    if len(sys.argv) > 1:
        child(sys.argv[1])
    else:
        main()
