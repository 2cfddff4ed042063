"""Readings that set a cell's limit on the logit gap, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 11,12,13

For each seed, in one process: a benchmark run of the cell at its own
load for ``--seconds`` (the drain finishes the longest requests), judged
twice on the same sample of served streams by the run's own verdict
(``bench.run.verdict``): once with the served tokens (the program's
reading, which has to be correct) and once with the tokens that the
float8 control (the architecture's ``reference_logits`` with
``control=True``) puts first in their place (the control's, which has
to be not correct). One JSON line per seed. The limit lies above the
largest program reading over a dozen seeds and below the smallest
control reading (``PERF.md``).
Benchmark runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    sys.path[0] = str(_ROOT)
if str(_ROOT / "src") not in sys.path:
    sys.path.insert(1, str(_ROOT / "src"))

from bench.run import run_cell, use_compile_cache  # noqa: E402
from bench.spec import load_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    use_compile_cache()
    for seed in [int(s) for s in args.seeds.split(",")]:
        res = run_cell(cell, seed, args.seconds, False, control=True)
        prog = res["program"]
        print(json.dumps({
            "seed": seed, "attempted": res["attempted"], "failed": res["failed"],
            "program_correct": prog["correct"],
            "program": prog["checks"]["max_logit_gap"]["value"],
            "control_correct": res["correct"],
            "control": res["checks"]["max_logit_gap"]["value"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
