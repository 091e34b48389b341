"""Find a serving cell's knee: the highest offered rate the engine
sustains without a growing backlog.

    python3 chipbench/knee.py --workload <serving cell> --seed 7 \
        --seconds 40 --rates 1.5,2,2.5,3

One process builds and warms the cell once, then offers the cell's mix
at each rate for ``--seconds`` and prints one line per rate: requests,
TTFT median and p90, token-gap p99, output tokens per second, the mean
queue wait of the last quarter of requests beside the first quarter's
(a backlog that grows shows as a later quarter that waits longer), and
how long the window's open requests took to drain.  The benchmark's
runs never call this; its table goes into ``PERF.md`` and the rate
into the mix file.
"""
import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, openloop  # noqa: E402
from chipbench.run import use_compile_cache  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import numpy as np

    cell = harness.find_cell(args.workload)
    use_compile_cache()
    harness.require_chips(cell.chips)
    drv = cell.driver
    _, eng = drv.build(cell.config, args.seed)
    drv.warm(eng, cell.config, np.random.default_rng(args.seed + 1))
    for rate in map(float, args.rates.split(",")):
        mix = dict(cell.traffic, rate_per_s=rate)
        reqs = openloop.requests(mix, args.seed, args.seconds,
                                 cell.config["vocab_size"])
        end = drv.serve(eng, reqs, args.seconds, harness.Spans())
        ttft = [(r.times[0] if r.times else end) - r.due for r in reqs]
        gaps = [b - a for r in reqs for a, b in zip(r.times, r.times[1:])
                if b <= args.seconds]
        q = max(1, len(reqs) // 4)
        row = {"rate_per_s": rate, "requests": len(reqs),
               "ttft_p50_ms": 1e3 * openloop.percentile(ttft, 0.5),
               "ttft_p80_ms": 1e3 * openloop.percentile(ttft, 0.8),
               "ttft_p90_ms": 1e3 * openloop.percentile(ttft, 0.9),
               "itl_p99_ms": 1e3 * openloop.percentile(gaps, 0.99),
               "out_tok_s": sum(t <= args.seconds for r in reqs
                                for t in r.times) / args.seconds,
               "first_quarter_ttft_ms": 1e3 * float(np.mean(ttft[:q])),
               "last_quarter_ttft_ms": 1e3 * float(np.mean(ttft[-q:])),
               "drain_s": end - args.seconds,
               "unfinished": sum(r.served is None for r in reqs)}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
