"""Bring-up smoke of the Tetris serving path on TPU at full yi-9b width.

Serves a few requests through ``ServingEngine.submit``/``serve`` with the
``tetris`` policy from ``make_policy``, on yi-9b at its published widths
(d_model 4096, 32 heads, 4 KV heads, head_dim 128, d_ff 11008, vocab 64000,
bf16) cut to 24 of its 48 layers, so the weights (8.71 GiB) and the KV
pools fit one 16 GB TPU v5e.  Weights are random, drawn from ``--seed``.

  python chip_smoke.py             one chip: the served path against a plain
                                   forward of each prompt
  python chip_smoke.py --chips 4   four chips: the same requests on the
                                   sequence-parallel mesh (SP ring prefill,
                                   striped pools, split-KV decode) against
                                   the one-chip served run in this process
  python chip_smoke.py --plant-fault
                                   one chip with a planted decode fault
                                   (history pages masked out of decode
                                   attention): the checks must refuse it,
                                   so this run must exit non-zero

Checks, with the tolerance LOGIT_TOL written below:
  * each request's next-token logits at its last prompt position against
    the comparison run (max abs error over the vocabulary);
  * every decoded token: the plain reference, teacher-forced on the served
    tokens, must rate the served token within LOGIT_TOL of its own best
    logit at that step.  A wrong decode path serves tokens the reference
    rates lower; a near-tie within rounding may go either way.

It exits non-zero, printing no result, when JAX finds no TPU, when the
kernels are not the Pallas ones (``REPRO_KERNEL_IMPL``), or when the repo's
``src/repro`` is not next to this script.  Every phase runs in this one
process: a chip belongs to one process at a time.  The compile cache is
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``.  The
last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

LAYERS = 24                 # of yi-9b's 48: 48 would be 16.45 GiB of bf16
OUTPUT_LEN = 16
# (arrival seconds on the event clock, prompt tokens).  Under the planner's
# Eq. (1) model the first three prompts keep the four prefill instances
# busy, so tetris splits the last one into two chunks (2334 then 5668 tokens
# on a wider group): the second chunk attends over paged history.  Their
# decodes overlap, so decode batches hold several requests.
TRAFFIC = ((0.0, 2500), (0.0, 6000), (0.01, 4096), (0.2, 8002))
N_PREFILL, SP_CANDIDATES = 4, (1, 2, 4)
BLOCK = 64                  # tokens per KV page
MAX_BATCH = 4
MAX_SEQ = 8064              # 126 pages: the longest request (8002 + 16)
PREFILL_POOL_BLOCKS = 336   # every prompt resident at once (326 pages)
HOST_POOL_BLOCKS = 64       # host tier: swap / second-tier prefix cache
# Max |logit error| allowed against the comparison run, and the most a
# served token may fall short of the reference's best logit.  The logits
# leave the model in bf16 (8 significant bits); with these random weights
# their magnitude is under 2 (the run prints the largest, about 1.1), where
# bf16 spacing is 2**-7 = 0.0078.  The runs round the same bf16 activations
# and K/V in a different order through 24 layers; on a v5e their logits
# differ by 0.017-0.019, about 2.3 spacings.  LOGIT_TOL is 8 spacings: over
# three times that error, and an eighth of the smallest shortfall the
# planted decode fault (--plant-fault) produced there, 0.52.
LOGIT_TOL = 0.0625


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def gib(n: float) -> str:
    return f"{int(n)} bytes ({n / 2**30:.2f} GiB)"


class CompileCounter:
    """Counts XLA backend compiles and their seconds via jax.monitoring."""

    def __init__(self, jax):
        self.n, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs


def setup(chips: int):
    """Import JAX (after placing the compile cache) and refuse anything but
    the Pallas kernels on TPU."""
    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"the repro package is missing: no {src}/repro next to this "
             "script")
    sys.path.insert(0, src)
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO, ".jax_cache"))
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX found {len(devs)} {devs[0].platform} device(s) "
             f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); this "
             "smoke test measures the chip and never falls back")
    if len(devs) < chips:
        fail(f"--chips {chips} needs {chips} TPU devices, found {len(devs)}")
    from repro.kernels import ops
    impl = ops.default_impl()
    if impl != "pallas":
        fail(f"kernel impl is {impl!r} (REPRO_KERNEL_IMPL="
             f"{os.environ.get('REPRO_KERNEL_IMPL')!r}); on the chip the "
             "served path must run the Pallas kernels")
    d = devs[0]
    print(f"device: {d.device_kind} x{len(devs)} (platform {d.platform})")
    print(f"kernel impl: {impl}")
    return jax, devs


def build_engine(cfg, params, ctx):
    from repro.core.latency_model import table1_model
    from repro.serving.engine import ServingEngine
    from repro.serving.simulator import ClusterSpec, make_policy

    class Engine(ServingEngine):
        """Keeps each request's last-prompt logits: admission to decode
        drops the prefill state that holds them."""
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            self.prompt_logits = {}

        def _on_transfer_done(self, now, rid):
            if rid in self._prefill:
                self.prompt_logits[rid] = self._prefill[rid].logits
            super()._on_transfer_done(now, rid)

    spec = ClusterSpec(n_prefill=N_PREFILL, n_decode=1,
                       sp_candidates=SP_CANDIDATES)
    return Engine(cfg, params, spec,
                  make_policy("tetris", table1_model(), spec),
                  ctx=ctx, max_batch=MAX_BATCH, max_seq=MAX_SEQ,
                  block_size=BLOCK, prefill_pool_blocks=PREFILL_POOL_BLOCKS,
                  host_pool_blocks=HOST_POOL_BLOCKS)


def pool_bytes(eng) -> dict:
    import jax
    dec = sum(a.nbytes for d in eng.dstates
              for a in jax.tree.leaves(d.kv.pools))
    pre = sum(a.nbytes for a in jax.tree.leaves(eng.pkv.pools))
    host = (sum(a.nbytes for a in jax.tree.leaves(eng.host.pools))
            if eng.host is not None else 0)
    return {"decode": dec, "prefill": pre, "host": host}


def serve(jax, cfg, params, ctx, prompts, counter, label):
    """One served run: returns (engine, outputs, host logits, seconds,
    compiles during the run)."""
    import numpy as np
    from repro.serving.request import Request
    eng = build_engine(cfg, params, ctx)
    for rid, ((t, plen), toks) in enumerate(zip(TRAFFIC, prompts)):
        eng.submit(Request(rid=rid, arrival=t, prompt_len=plen,
                           output_len=OUTPUT_LEN), toks)
    n0 = counter.n
    t0 = time.perf_counter()
    outs = eng.serve()
    jax.block_until_ready([eng.pkv.pools, eng.dstates[0].kv.pools])
    secs = time.perf_counter() - t0
    logits = {rid: np.asarray(eng.prompt_logits[rid][0, 0, :cfg.vocab_size],
                              np.float32) for rid in outs}
    print(f"serve [{label}]: {secs:.3f} s wall (block_until_ready), "
          f"{counter.n - n0} compiles during the run")
    return eng, outs, logits, secs, counter.n - n0


def describe_run(eng, outs) -> None:
    from repro.serving.simulator import summarize
    for rid in sorted(outs):
        r = eng.reqs[rid]
        print(f"  req {rid}: prompt {r.prompt_len} plan {r.chunk_plan} "
              f"tokens {len(outs[rid])}")
    multi = sum(len(eng.reqs[r].chunk_plan) >= 2 for r in outs)
    batch = max((len(e.args.get("rids", ())) for e in eng.tracer.events
                 if e.kind == "tick"), default=0)
    s = summarize(eng.reqs)
    print(f"  multi-chunk requests: {multi}; max decode batch: {batch}")
    print(f"  event clock (planner's Eq. (1) model, not a device time): "
          f"TTFT p50 {s['ttft_p50']:.4f} s, TBT p50 "
          f"{s['tbt_p50'] * 1e3:.2f} ms")
    if multi < 1:
        fail("no request was split into two or more chunks")
    if batch < 2:
        fail("decode never batched two requests")
    for rid, toks in outs.items():
        if len(toks) < OUTPUT_LEN:
            fail(f"request {rid} emitted {len(toks)} of {OUTPUT_LEN} tokens")


def plant_fault() -> None:
    """Mask history pages out of decode attention: every decode step sees
    only its last BLOCK tokens.  Prefill is untouched, so only the decode
    check can catch it."""
    from repro.kernels import ops
    real = ops.paged_decode_attention

    def faulty(*args, window=None, **kw):
        return real(*args, window=BLOCK, **kw)
    ops.paged_decode_attention = faulty
    print(f"planted fault: decode attends to its last {BLOCK} tokens only")


def reference(jax, cfg, params, prompts, runs):
    """Plain forward of each prompt (reference attention, f32 softmax),
    then a dense-cache decode teacher-forced on each served run's tokens.

    ``runs`` maps a label to that run's {rid: tokens}.  Returns the
    last-prompt logits per request, and per label and request each step's
    shortfall: the reference's best logit minus its logit for the token
    the run served."""
    import jax.numpy as jnp
    import numpy as np
    from repro.models.sharding import ExecContext
    from repro.models.transformer import forward
    ctx = ExecContext(impl="ref_blocked")   # the dense score matrix of an
    # 8002-token prompt would be 8 GiB; ref_blocked is the same math tiled
    prefill = jax.jit(lambda p, t, pos: forward(p, cfg, ctx, t, pos,
                                                "prefill"))
    decode = jax.jit(lambda p, t, pos, c, n: forward(
        p, cfg, ctx, t, pos, "decode", caches=c, cache_len=n),
        donate_argnums=(3,))

    def vec(lg):
        return np.asarray(lg[0, 0, :cfg.vocab_size], np.float32)

    first, short = {}, {label: {} for label in runs}
    for rid, toks in enumerate(prompts):
        S = len(toks)
        lg, _, cache0 = prefill(params, jnp.asarray(toks)[None],
                                jnp.arange(S, dtype=jnp.int32)[None])
        cache0 = jax.tree.map(
            lambda a: jnp.pad(a, [(0, 0), (0, 0), (0, MAX_SEQ - S)]
                              + [(0, 0)] * (a.ndim - 3)), cache0)
        first[rid] = vec(lg)
        done = {}                        # served tokens -> their shortfalls
        for label, outs in runs.items():
            served = tuple(outs[rid])
            if served not in done:
                caches = jax.tree.map(jnp.copy, cache0)   # decode donates
                v, sf = first[rid], []
                for i, tok in enumerate(served):
                    sf.append(float(v.max() - v[tok]))
                    if i + 1 < len(served):
                        n = jnp.asarray([S + i], jnp.int32)
                        lg, _, caches = decode(
                            params, jnp.asarray([[tok]], jnp.int32),
                            n[:, None], caches, n)
                        v = vec(lg)
                done[served] = sf
                del caches
            short[label][rid] = done[served]
        del cache0
    return first, short


def check(label, logits, want_logits, shortfalls) -> bool:
    """The tolerance rule: prompt logits within LOGIT_TOL of the
    comparison's, and every served token within LOGIT_TOL of the
    reference's best logit at its step."""
    import numpy as np
    ok = True
    for rid in sorted(logits):
        err = float(np.max(np.abs(logits[rid] - want_logits[rid])))
        sf = shortfalls[rid]
        worst = max(sf)
        print(f"  {label} req {rid}: max |logit err| {err:.6f}, max "
              f"|logit| {float(np.max(np.abs(logits[rid]))):.4f}; decode: "
              f"{len(sf)} served tokens checked, worst shortfall from the "
              f"reference's best {worst:.6f}, reference argmax served at "
              f"{sum(x == 0.0 for x in sf)}/{len(sf)} (tolerance "
              f"{LOGIT_TOL})")
        ok &= err <= LOGIT_TOL and worst <= LOGIT_TOL
    return ok


def replicate(jax, leaves, sharding):
    """Move a list of arrays onto ``sharding`` one by one, dropping each
    source as its copy lands: the device that held the weights never keeps
    two copies of them."""
    for i in range(len(leaves)):
        leaves[i] = jax.device_put(leaves[i], sharding)
        leaves[i].block_until_ready()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--plant-fault", action="store_true",
                    help="mask history out of decode; the run must fail")
    args = ap.parse_args()
    if args.plant_fault and args.chips != 1:
        ap.error("--plant-fault runs on one chip")
    sys.stdout.reconfigure(line_buffering=True)   # progress survives a kill
    jax, devs = setup(args.chips)
    if args.plant_fault:
        plant_fault()
    import numpy as np
    from repro.configs.registry import get_config
    from repro.models.params import init_params
    from repro.models.sharding import ExecContext

    counter = CompileCounter(jax)
    full = get_config("yi-9b")
    cfg = dataclasses.replace(full, n_layers=LAYERS)
    print(f"model: {cfg.name} at published widths, {LAYERS} of "
          f"{full.n_layers} layers (d_model {cfg.d_model}, heads "
          f"{cfg.n_heads}/{cfg.n_kv_heads} kv, head_dim {cfg.head_dim_}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype})")
    t0 = time.perf_counter()
    params = init_params(cfg, jax.random.PRNGKey(args.seed), dtype=cfg.dtype)
    jax.block_until_ready(params)
    wbytes = sum(a.nbytes for a in jax.tree.leaves(params))
    print(f"weights: {gib(wbytes)}, random from seed {args.seed}, init "
          f"{time.perf_counter() - t0:.3f} s")
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg.vocab_size, L).astype(np.int32)
               for _, L in TRAFFIC]

    one_ctx = ExecContext()
    eng, outs, logits, cold, n_cold = serve(jax, cfg, params, one_ctx,
                                            prompts, counter, "1 chip, cold")
    pb = pool_bytes(eng)
    print(f"pools: decode K+V {gib(pb['decode'])}, prefill K+V "
          f"{gib(pb['prefill'])}, host tier {gib(pb['host'])} (host RAM)")
    describe_run(eng, outs)
    del eng
    gc.collect()

    def run_reference(runs):
        t0 = time.perf_counter()
        out = reference(jax, cfg, params, prompts, runs)
        print(f"reference: plain forward + dense-cache decode teacher-"
              f"forced on the served tokens, {time.perf_counter() - t0:.3f}"
              f" s (compiles included)")
        return out

    if args.chips == 1:
        ref_logits, short = run_reference({"1 chip": outs})
        eng, outs_w, _, warm, n_warm = serve(jax, cfg, params, one_ctx,
                                             prompts, counter, "1 chip, warm")
        del eng
        gc.collect()
        if outs_w != outs:
            fail("the warm run emitted other tokens than the cold run")
        print(f"compile: {counter.n} backend compiles, {counter.secs:.3f} s "
              f"summed; cold serve {cold:.3f} s with {n_cold} compiles, "
              f"warm serve {warm:.3f} s with {n_warm}")
        ok = check("1-chip vs reference", logits, ref_logits,
                   short["1 chip"])
    else:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.compat import make_mesh
        from repro.launch.mesh import make_context
        mesh = make_mesh((4, 1), ("data", "model"))
        leaves, treedef = jax.tree.flatten(params)
        del params
        replicate(jax, leaves, NamedSharding(mesh, P()))
        params = jax.tree.unflatten(treedef, leaves)
        del leaves
        eng4, outs4, logits4, secs4, n4 = serve(
            jax, cfg, params, make_context(mesh, "serve_paged"), prompts,
            counter, "4 chips, serve_paged")
        for name, pools in (("prefill", eng4.pkv.pools),
                            ("decode", eng4.dstates[0].kv.pools)):
            leaf = jax.tree.leaves(pools)[0]
            held = sorted(s.device.id for s in leaf.addressable_shards)
            per = leaf.addressable_shards[0].data.nbytes
            print(f"  {name} pool layer stack {tuple(leaf.shape)} on devices "
                  f"{held}, {per} bytes per device")
            if len(held) != 4 or per * 4 != leaf.nbytes:
                fail(f"the {name} pool is not striped over four chips")
        chunks = [c["len"] for r in eng4.chunk_log.values() for c in r]
        print(f"  chunks: {len(chunks)}, padded to the 4-way ring: "
              f"{sum(L % 4 != 0 for L in chunks)} (the sharded-pool gather "
              f"oracles raise under the Pallas impl)")
        describe_run(eng4, outs4)
        del eng4
        gc.collect()
        print(f"compile: {counter.n} backend compiles, {counter.secs:.3f} s "
              f"summed; 1-chip cold serve {cold:.3f} s, 4-chip serve "
              f"{secs4:.3f} s with {n4} compiles")
        ref_logits, short = run_reference({"1 chip": outs,
                                           "4 chips": outs4})
        ok = check("1-chip vs reference", logits, ref_logits,
                   short["1 chip"])
        ok &= check("4-chip vs 1-chip", logits4, logits, short["4 chips"])
    stats = devs[0].memory_stats() or {}
    print(f"peak_bytes_in_use (device 0): "
          f"{gib(stats.get('peak_bytes_in_use', 0))}")
    if not ok:
        fail(f"served results outside the tolerance {LOGIT_TOL}")
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devs)}}))


if __name__ == "__main__":
    main()
