"""Chip benchmark of the Tetris/CDSP serving path, one cell per run.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json`` and the plain reference it names) under a
traffic mix (``bench/traffic/<mix>.json``, read by the one generator in
``benchlib/traffic.py``).  The run builds the weights from ``--seed`` on the
device, warms up every shape the window will use, measures for
``--seconds`` on the wall clock, checks what the window served against the
plain reference, and prints one JSON object as its last line of standard
output.  With ``--trace 1`` it traces the window and reports the cell's
per-layer metrics, each read by ``bench/metrics/<metric>.py``.

Two kinds of traffic:

* an open loop (``arrival.process`` ``poisson``): requests arrive at their
  due wall times through ``ServingEngine.submit``/``serve``'s handlers;
  set-up serves the same schedule once, ungated, on a fresh engine;
* resident sessions (``resident``): set-up prefills the sessions and
  compiles the decode step for every block-table width the window can
  reach; the window decodes.

It exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when the kernels are not the Pallas
ones.  The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``.jax_cache`` at the root of the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
WORK = os.path.join(ROOT, ".bench_work")
sys.path.insert(0, BENCH)

from benchlib import check, serving, traffic  # noqa: E402
from benchlib import trace as trace_lib  # noqa: E402
from benchlib.compiles import CompileCounter  # noqa: E402
from benchlib.peaks import peaks  # noqa: E402


class Refused(Exception):
    """The run cannot measure: no chip, too few chips, no Pallas kernels,
    or no program to measure."""


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: str = ROOT) -> dict:
    """Everything a cell names, found by name under ``bench/``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; cells: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, config["file"])) as f:
        conf = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)

    def mine(m):
        return m.get("workloads") is None or workload in m["workloads"]
    return {"cell": cell, "conf": conf, "traffic": mix,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def setup_jax(chips: int):
    """Import the program and JAX (after placing the compile cache);
    refuse anything but the Pallas kernels on enough TPU chips."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise Refused(f"the program is missing: no {src}/repro")
    sys.path.insert(0, src)
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    # every program, however quick to compile, is kept: a warm run then
    # compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found {len(devs)} {devs[0].platform} "
                      "device(s); this benchmark measures the chip and never "
                      "falls back")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} TPU chips, JAX found "
                      f"{len(devs)}")
    from repro.kernels import ops
    if ops.default_impl() != "pallas":
        raise Refused(f"kernel impl is {ops.default_impl()!r} "
                      f"(REPRO_KERNEL_IMPL="
                      f"{os.environ.get('REPRO_KERNEL_IMPL')!r}); the served "
                      "path must run the Pallas kernels")
    return jax, devs[:chips]


def program_config(conf: dict):
    """The program's ModelConfig for this configuration, checked against
    the sizes the configuration file states."""
    import dataclasses
    from repro.configs.registry import get_config
    m = conf["model"]
    cfg = dataclasses.replace(get_config(conf["registry"]),
                              n_layers=m["num_hidden_layers"],
                              dtype=conf["dtype"])
    have = {"hidden_size": cfg.d_model,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim_, "intermediate_size": cfg.d_ff,
            "vocab_size": cfg.padded_vocab,
            "qkv_bias": cfg.qkv_bias,
            "partial_rotary_factor": (cfg.partial_rotary_factor
                                      if cfg.rope_type == "partial" else 1.0),
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps}
    want = {k: m.get(k) for k in have}
    if have != want:
        raise SystemExit(f"the program's {conf['registry']} differs from "
                         f"{conf['name']}: {have} vs {want}")
    return cfg


def check_layout(cfg, weights) -> None:
    import jax
    from repro.models.params import param_shapes
    want = jax.tree.map(tuple, param_shapes(cfg),
                        is_leaf=lambda x: isinstance(x, tuple))
    have = jax.tree.map(lambda a: tuple(a.shape), weights)
    if want != have:
        raise SystemExit("the reference's weight layout is not the "
                         "program's parameter tree")


def pct(vals, q: float) -> float:
    """The q-th percentile (0-100), linear between closest ranks."""
    v = sorted(vals)
    if not v:
        return float("nan")
    k = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


# ------------------------------------------------------------- the run
def prepare(spec: dict, seed: int, seconds: float, jax, log=print,
            fault=None):
    """The weights, the schedule and its prompts, and the engine class of
    one run."""
    import types
    conf, mix = spec["conf"], spec["traffic"]
    ref = load_module(os.path.join(BENCH, "configs",
                                   conf["reference"] + ".py"),
                      "bench_reference")
    counter = CompileCounter(jax)
    cfg = program_config(conf)
    t = serving.clock()
    params = ref.weights(conf["model"], seed, conf["dtype"])
    jax.block_until_ready(params)
    check_layout(cfg, params)
    wbytes = sum(a.nbytes for a in jax.tree.leaves(params))
    log(f"weights: {wbytes} bytes from seed {seed}, "
        f"{serving.clock() - t:.3f} s")
    reqs = traffic.schedule(mix, seconds)
    prompts = traffic.prompt_tokens(reqs, conf["model"]["vocab_size"], seed)
    cls = serving.make_engine_class()
    if fault is not None:
        cls = fault(cls)
    return types.SimpleNamespace(
        conf=conf, ref=ref, cfg=cfg, params=params, reqs=reqs,
        prompts=prompts, cls=cls, counter=counter, seed=seed,
        resident=mix["arrival"]["process"] == "resident")


def new_engine(p):
    eng = serving.build_engine(p.cls, p.cfg, p.params, p.conf["engine"])
    serving.submit(eng, p.reqs, p.prompts)
    return eng


def resident_setup(p, seconds: float, log=print):
    """Prefill the sessions, then compile the decode step for every
    block-table width that ``seconds`` of ticks can reach."""
    eng = new_engine(p)
    pre = serving.Records()
    eng.rec = pre
    B = len(p.reqs)
    d = eng.dstates[0]
    serving.drive(eng, pre, gated=False, deadline=None,
                  until=lambda: len(d.meta) == B and len(pre.ticks) >= 1)
    n_setup = len(pre.ticks)
    serving.drive(eng, pre, gated=False, deadline=None,
                  until=lambda: len(pre.ticks) >= n_setup + 16)
    tick_s = statistics.median(x["t1"] - x["t0"] for x in pre.ticks[-16:])
    reach = math.ceil(1.5 * seconds / tick_s) + 64
    longest = max(m.cache_len for m in d.meta.values())
    w0 = serving.decode_width(eng)
    w1 = -(-(longest + reach + 1) // d.block_size) + 1
    for w in range(w0 + 1, w1 + 1):
        serving.warm_decode_width(eng, 0, w)
    free = d.blocks.total_blocks - sum(len(m.blocks)
                                       for m in d.meta.values())
    log(f"set-up: {B} sessions resident after {len(pre.ticks)} ticks, "
        f"tick {tick_s * 1e3:.2f} ms, widths {w0}..{w1} pages warmed for "
        f"up to {reach} ticks; {free} free pages, the window needs at most "
        f"{B * -(-reach // d.block_size)}")
    return eng


def serve_ungated(p, log=print):
    """Serve the whole schedule once, ungated, on a fresh engine: the
    shapes the window can reach, compiled (or loaded from the cache)."""
    t = serving.clock()
    eng = new_engine(p)
    n0 = p.counter.n
    serving.drive(eng, serving.Records(), gated=False, deadline=None)
    log(f"warm-up: the schedule ({len(p.reqs)} requests, "
        f"{sum(r['prompt_len'] for r in p.reqs)} prompt tokens) served "
        f"ungated in {serving.clock() - t:.3f} s with "
        f"{p.counter.n - n0} compiles")
    return eng


def served_tokens(p, eng) -> dict:
    """Every finished request's tokens; a resident session's so far."""
    return {r: list(v) for r, v in eng.outputs.items()
            if p.resident or len(v) >= eng.reqs[r].output_len}


def compare(p, served: dict, control: bool = False) -> dict:
    plen = {r["rid"]: r["prompt_len"] for r in p.reqs}
    rids = check.sample(served, plen, p.seed)
    res = check.gaps(p.ref, p.params, p.conf["model"], p.prompts, served,
                     rids, control=control)
    res["requests"] = len(rids)
    res["rids"] = rids
    return res


def decide(res: dict, limit: float, key: str = "gap") -> bool:
    """``correct``: tokens were compared and the widest gap is within the
    configuration's limit."""
    return res["tokens"] > 0 and res[key] <= limit


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             jax, devs, log=print, fault=None) -> dict:
    """One run of one cell.  Returns the result object (the last line)."""
    cell, conf = spec["cell"], spec["conf"]
    p = prepare(spec, seed, seconds, jax, log, fault)
    reqs, resident, counter = p.reqs, p.resident, p.counter
    rec = serving.Records()
    if resident:
        eng = resident_setup(p, seconds, log)
    else:
        warm = serve_ungated(p, log)
        del warm
        gc.collect()
        eng = new_engine(p)
    pb = serving.pool_bytes(eng)
    log(f"pools: decode {pb['decode']} bytes, prefill {pb['prefill']} "
        f"bytes")
    eng.rec = rec

    trace_dir = os.path.join(WORK, "trace-" + cell["name"])
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    n_before = counter.n
    rec.t0 = serving.clock()
    setup_s = rec.t0 - T_START
    rec.deadline = rec.t0 + seconds
    with (jax.profiler.TraceAnnotation(trace_lib.WINDOW) if trace
          else contextlib.nullcontext()):
        serving.drive(eng, rec, gated=not resident, deadline=rec.deadline,
                      annotate=trace, finish=not resident)
        jax.block_until_ready([eng.pkv.pools, eng.dstates[0].kv.pools])
    rec.end = serving.clock()
    window_compiles = counter.n - n_before
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        reduced = trace_lib.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    stats = [dv.memory_stats() or {} for dv in devs]
    peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats)
    multi = sum(len(eng.reqs[r].chunk_plan or ()) > 1 for r in eng.reqs)
    log(f"window: {seconds} s, compiles in the window: {window_compiles}, "
        f"loop ran {rec.end - rec.t0:.3f} s; {len(rec.chunks)} chunks "
        f"({sum(c['hist'] > 0 for c in rec.chunks)} over paged history), "
        f"{multi} multi-chunk requests, {len(rec.ticks)} ticks; "
        f"preemptions {len(eng.preempt_log)}; longest handler "
        f"{rec.longest[1]} {rec.longest[0]:.4f} s at "
        f"{rec.longest[2] - rec.t0:.3f} s")

    # ---- end-to-end metrics
    e2e: dict = {"setup_s": setup_s}
    if resident:
        toks = sum(1 for s in rec.stamps.values() for x in s
                   if x <= rec.deadline)
        e2e["decode_tokens_per_s"] = toks / seconds
        attempted, failed = len(reqs), 0
        log(f"decode: {toks} tokens in the window, {len(rec.ticks)} ticks")
    else:
        attempted = len(reqs)
        ttft, missing = [], 0
        for r in reqs:
            rid = r["rid"]
            due = rec.due.get(rid, rec.t0 + r["arrival"])
            st = rec.stamps.get(rid)
            if st:
                ttft.append(st[0] - due)
            else:
                missing += 1
                ttft.append(rec.end - due)
        failed = missing
        gaps = [b - a for st in rec.stamps.values()
                for a, b in zip(st, st[1:]) if b <= rec.deadline]
        late = [(rec.due[r] - rec.t0, rec.gate[r] - rec.due[r])
                for r in rec.gate] or [(0.0, 0.0)]
        q = seconds / 4
        late1 = [x for t, x in late if t < q]
        late4 = [x for t, x in late if t >= 3 * q]
        e2e["ttft_p90_s"] = pct(ttft, 90)
        e2e["tbt_p99_ms"] = pct(gaps, 99) * 1e3
        log(f"requests: {attempted} due in the window, {missing} without "
            f"a first token; TTFT p50 {pct(ttft, 50):.4f} s p90 "
            f"{pct(ttft, 90):.4f} s max {max(ttft):.4f} s; {len(gaps)} "
            f"gaps, TBT p50 {pct(gaps, 50) * 1e3:.3f} ms p99 "
            f"{pct(gaps, 99) * 1e3:.3f} ms")
        log(f"gate lateness: mean {statistics.fmean(x for _, x in late):.4f}"
            f" s; first quarter {statistics.fmean(late1) if late1 else 0:.4f}"
            f" s, last quarter {statistics.fmean(late4) if late4 else 0:.4f}"
            f" s; max {max(x for _, x in late):.4f} s")

    # ---- per-layer metrics
    layer: dict = {}
    if trace:
        run = {"rec": rec, "trace": reduced, "model": conf["model"],
               "peak": peaks(devs[0].device_kind), "seconds": seconds}
        for m in spec["per_layer"]:
            reader = load_module(os.path.join(BENCH, "metrics",
                                              m["name"] + ".py"),
                                 "metric_" + m["name"].replace(".", "_"))
            v = reader.read(run)
            if v is not None:
                layer[m["name"]] = {"value": v, "unit": m["unit"]}
        log(f"trace: busy {reduced['busy_s']:.4f} s of "
            f"{reduced['window_s']:.4f} s; kernels {reduced['kernels']}; "
            f"steps {reduced['step_seconds']}")

    # ---- correctness: the window's served tokens against the reference
    served = served_tokens(p, eng)
    del eng
    gc.collect()
    t = serving.clock()
    res = compare(p, served)
    limit = conf["check"]["logit_gap_limit"]
    ok = decide(res, limit)
    log(f"reference: {res['requests']} requests, {res['tokens']} served "
        f"tokens, {res['argmax_served']} at the reference's argmax, "
        f"{serving.clock() - t:.3f} s")
    checks = {"logit_gap": {"value": res["gap"], "limit": limit},
              "tokens_compared": {"value": res["tokens"], "limit": 1}}

    metrics = {}
    for m in spec["end_to_end"]:
        metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    d0 = devs[0]
    out = {"correct": bool(ok), "attempted": attempted, "failed": failed,
           "metrics": layer if trace else metrics,
           "device": {"platform": d0.platform, "kind": d0.device_kind,
                      "count": len(devs), "memory_peak_bytes": peak_bytes}}
    if trace:
        out["device"]["busy_s"] = reduced["busy_s"]
        out["device"]["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["info"] = {"window_compiles": window_compiles,
                   "compiles": counter.n, "compile_s": counter.secs,
                   "chunks_over_history": sum(c["hist"] > 0
                                              for c in rec.chunks),
                   "multi_chunk_requests": multi,
                   "compared": res["rids"], "e2e": e2e}
    out["checks"] = checks
    return out


def report(out: dict) -> None:
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    args = ap.parse_args()
    sys.stdout.reconfigure(line_buffering=True)
    spec = load_cell(args.workload)
    try:
        jax, devs = setup_jax(spec["cell"]["chips"])
    except Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr, flush=True)
        sys.exit(2)
    d = devs[0]
    print(f"device: {d.device_kind} x{len(devs)} (platform {d.platform}); "
          f"kernel impl pallas")
    report(run_cell(spec, args.seed, args.seconds, bool(args.trace), jax,
                    devs))


if __name__ == "__main__":
    main()
