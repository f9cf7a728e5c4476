"""Find the highest query rate a service cell sustains: one set-up, many rates.

    python3 bench/sweep.py --workload svc-s23p10.mixed-zipf-ie --seed 7 \\
        --rates 100,200,400,800 --seconds 10

Sets the cell up once (as ``run.py`` does), then for each rate drives one
open-loop window with the cell's traffic at that rate, ingest running
where the mix ingests. Prints one JSON line per rate: latency
percentiles, how late the generator ran, the answered rate, the ingest
rate, and the median latency of the window's first and last thirds (a
last third far slower than the first is a growing backlog). A rate is
sustained when the backlog does not grow and the generator is not late.
Not part of a benchmark run: the cell's rate is fixed in its traffic
file from one such sweep.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(1, os.path.join(_ROOT, "src"))

import numpy as np  # noqa: E402

from bench import harness, loadgen  # noqa: E402


def main(argv=None) -> int:
    """Entry point; see the module docstring."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    info = harness.cell(harness.load_spec(), args.workload)
    harness._configure_jax(harness.ROOT)
    devices = harness._devices(1, allow_cpu=False)
    ctx = harness.Context(info, args.seed, args.seconds, False, False,
                          T_START, devices, None)
    driver = harness.load_module(info["driver"], "bench_driver")
    st = driver.Setup(ctx)
    print(json.dumps({"setup": st.timings}), flush=True)
    ctx.settle()
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        offsets, payloads = driver._schedule(ctx, st.n, st.base, rate,
                                             args.seconds, args.seed + k)
        start = time.perf_counter() + 0.05
        out, ing = st.window(ctx, offsets, payloads, start, args.seconds)
        s = loadgen.latency_summary(out)
        third = args.seconds / 3
        rel = out.due - start
        lat = out.latency
        first = lat[rel < third]
        last = lat[rel >= 2 * third]
        answered = out.done[out.ok]
        print(json.dumps({
            "rate": rate, **s,
            "answered_per_s": float(np.sum(answered <= start + args.seconds)
                                    / args.seconds),
            "first_third_p50_ms": float(np.median(first) * 1e3),
            "last_third_p50_ms": float(np.median(last) * 1e3),
            "ingest_edges_per_s": driver.acked_edges_per_s(
                st, ing, start + args.seconds, args.seconds),
        }), flush=True)
    st.server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
