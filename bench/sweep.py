"""Find a cell's knee: the highest offered rate with no growing backlog.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 1,2,3

One process, one server: for each rate, in the order given, the window's
prompt lengths are warmed up, the traffic is offered open-loop for
``--seconds`` and drained as in a benchmark run. Each rate prints one
JSON line: tokens/s, the TTFT and token-gap tails, and the backlog test.
The backlog grows when the requests due in the window's last third wait
more than twice as long for their first token as those due in its first
third, or when requests are still unfinished at the window's end beyond
what the arrivals of the last ten seconds explain. The sweep ends at the
first rate that leaves requests unfinished after the drain. The knee goes
into the cell's file by hand, with the sweep recorded in ``PERF.md``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    sys.path[0] = str(_ROOT)
if str(_ROOT / "src") not in sys.path:
    sys.path.insert(1, str(_ROOT / "src"))

from bench import stats  # noqa: E402
from bench.client_loop import run_window  # noqa: E402
from bench.run import build_server, device_info, end_to_end, log, use_compile_cache, warm_up  # noqa: E402
from bench.spec import load_cell  # noqa: E402
from bench.traffic import generate, warmup_lengths  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    use_compile_cache()
    log(f"device: {device_info(cell.chips, True)}")
    w = cell.arch.widths(cell.config)
    server = build_server(cell, args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        arrivals = generate(cell.mix, rate, args.seconds, args.seed, w.vocab)
        warm_up(server, warmup_lengths(cell.mix, rate, args.seconds), w.vocab, args.seed)
        clients, t0, end, _ = run_window(server, arrivals, seconds=args.seconds,
                                      drain_s=float(cell.load["drain_s"]),
                                      events=cell.mix.events)
        m = end_to_end(clients, t0, end, args.seconds, 0.0)
        third = args.seconds / 3
        first = [c.token_times[0] - c.due for c in clients
                 if c.token_times and c.due < t0 + third]
        last = [c.token_times[0] - c.due for c in clients
                if c.token_times and c.due >= end - third]
        open_at_end = sum(1 for c in clients if not c.token_times or c.token_times[-1] > end)
        recent = sum(1 for c in clients if c.due >= end - 10.0)
        ratio = (stats.percentile(last, 50) / stats.percentile(first, 50)) if first and last else None
        print(json.dumps({
            "rate_per_s": rate, "offered": len(clients),
            "failed": sum(c.failed for c in clients),
            "tokens_per_s": m["tokens_per_s"], "ttft_p90_s": m.get("ttft_p90_s"),
            "itl_p95_ms": m.get("itl_p95_ms"),
            "ttft_median_last_over_first": ratio,
            "open_at_end": open_at_end, "due_last_10s": recent,
            "growing": bool((ratio is not None and ratio > 2.0) or open_at_end > recent),
            "at": time.time(),
        }), flush=True)
        if any(c.req is not None and not (c.req.done or c.req.dropped) for c in clients):
            log(f"requests still in the server after the drain at {rate}/s: sweep ends")
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
