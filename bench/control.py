"""Readings that set a cell's limit on ``correct``: the program's widest
logit gap, and the control's, on several seeds in one process.

  python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed it serves the cell's traffic through the timed path (an open
loop's whole schedule, served ungated: the same handlers in the same order
as the window; resident sessions: set-up, then ``--seconds`` of decode
ticks), and compares a sample of what was served with the plain float32
reference.  The control is that reference computed with float8 inputs and
weights to every linear layer, one precision step below the bf16 the
configuration states; its reading is the float32 reference's gap for the
token that the float8 reference puts first, at the same positions.  Each
reading goes through the harness's own decision against the
configuration's limit: ``correct`` for the program, ``control_correct``
for the control, which has to come out false.  One JSON line per seed.
The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

from benchlib import serving  # noqa: E402


def load_run():
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return run


def readings(run, spec: dict, seed: int, seconds: float, jax,
             log=print) -> dict:
    p = run.prepare(spec, seed, seconds, jax, log)
    if p.resident:
        eng = run.resident_setup(p, seconds, log)
        rec = serving.Records()
        eng.rec = rec
        rec.t0 = serving.clock()
        serving.drive(eng, rec, gated=False, deadline=rec.t0 + seconds)
    else:
        eng = run.serve_ungated(p, log)
    served = run.served_tokens(p, eng)
    del eng
    gc.collect()
    res = run.compare(p, served, control=True)
    limit = p.conf["check"]["logit_gap_limit"]
    return {"seed": seed, "gap": res["gap"],
            "control_gap": res["control_gap"], "limit": limit,
            "correct": run.decide(res, limit),
            "control_correct": run.decide(res, limit, "control_gap"),
            "tokens": res["tokens"], "argmax_served": res["argmax_served"],
            "requests": res["requests"]}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    sys.stdout.reconfigure(line_buffering=True)
    run = load_run()
    spec = run.load_cell(args.workload)
    try:
        jax, _ = run.setup_jax(spec["cell"]["chips"])
    except run.Refused as e:
        print(f"control: refused: {e}", file=sys.stderr, flush=True)
        sys.exit(2)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(run, spec, seed, args.seconds, jax)))
        gc.collect()


if __name__ == "__main__":
    main()
