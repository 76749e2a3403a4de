"""One traced run of a cell, with the program's own spans reduced too.

  python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py --trace 1`` does and prints the same
result line, with ``info.program_spans`` (``benchlib/spans.reduce`` of
the same trace: the device's idle time split among the engine's
``engine.*`` spans) and ``info.tick_idle_ms`` (the idle time inside the
decode-tick spans per tick) added, and the spans' table in the log.  On a
program without such spans both are empty.
"""

from __future__ import annotations

import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run  # noqa: E402
from benchlib import spans  # noqa: E402
from benchlib import trace as trace_lib  # noqa: E402


def traced_run(spec: dict, seed: int, seconds: float, jax, devs,
               log=print, bench=run) -> dict:
    """``bench.run_cell`` with the trace on; its result with the program
    spans added.  ``bench`` is the harness module that runs the cell."""
    from jax.profiler import ProfileData
    found = {}
    reduce_dir = trace_lib.reduce_dir

    def both(log_dir: str, top: int = 10) -> dict:
        # run_cell deletes the trace once it is reduced: read it here too
        prof = ProfileData.from_file(trace_lib.find_xplane(log_dir))
        found["spans"] = spans.reduce(prof)
        return trace_lib.reduce(prof, top)

    trace_lib.reduce_dir = both
    try:
        out = bench.run_cell(spec, seed, seconds, True, jax, devs, log)
    finally:
        trace_lib.reduce_dir = reduce_dir
    log("program spans:\n" + spans.table(found["spans"]))
    out["info"]["program_spans"] = found["spans"]
    out["info"]["tick_idle_ms"] = spans.tick_idle_ms(found["spans"])
    return out


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    sys.stdout.reconfigure(line_buffering=True)
    spec = run.load_cell(args.workload)
    try:
        jax, devs = run.setup_jax(spec["cell"]["chips"])
    except run.Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr, flush=True)
        sys.exit(2)
    run.report(traced_run(spec, args.seed, args.seconds, jax, devs))


if __name__ == "__main__":
    main()
