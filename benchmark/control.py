"""The control: the plain reference computed in float32, judged in the
program's place. It has to come out not correct.

    python3 benchmark/control.py --workload <cell> --seed <n> --requests <k> [--rehearse]

Same data and the same window requests as ``run.py`` makes from the seed
(the window's stream, first ``k`` requests), answered by the reference in
float32 (coordinates, box edges and epoch milliseconds rounded to f32:
the nearest precision below the configuration's f64) and judged by the
same comparison and limits as a run. The system under test is not built,
so no chip is needed; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
if str(HERE.parent) not in sys.path:
    sys.path.insert(0, str(HERE.parent))

from benchmark import reference, traffic  # noqa: E402
from benchmark.run import judge, load_cell  # noqa: E402


def control(workload: str, seed: int, n_requests: int, rehearse: bool) -> dict:
    _, _, cfg, mix = load_cell(workload)
    if rehearse:
        cfg = {**cfg, **cfg["rehearse"]}
    call = importlib.import_module(f"benchmark.calls.{mix['call']}")
    gen = importlib.import_module(f"benchmark.datagen.{cfg['generator']}")
    data = gen.generate(cfg, seed)
    reqs = traffic.Traffic(mix, cfg, data, seed, traffic.STREAM_WINDOW)
    requests = [reqs.request(k) for k in range(n_requests)]
    ref32 = reference.Referee(data, dtype="float32")
    kept = [call.control(ref32, r) for r in requests]
    checks = judge(call, reference.Referee(data), requests, kept)
    correct = all(checks[k] <= call.LIMITS[k] for k in call.LIMITS)
    return {"workload": workload, "seed": seed, "requests": n_requests,
            "correct": correct,
            "checks": {k: {"value": checks[k], "limit": call.LIMITS[k]}
                       for k in call.LIMITS}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    print(json.dumps(control(args.workload, args.seed, args.requests,
                             args.rehearse)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
