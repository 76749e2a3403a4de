"""A tiny configuration and mixes for driving whole runs on the CPU."""

import dataclasses
import importlib.util
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MODEL = {"hidden_size": 256, "intermediate_size": 512,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
         "num_hidden_layers": 2, "vocab_size": 512, "rope_theta": 10000.0,
         "rms_norm_eps": 1e-5, "qkv_bias": True,
         "partial_rotary_factor": 0.5}
ENGINE = {"n_prefill": 4, "sp_candidates": [1, 2, 4], "max_batch": 4,
          "max_seq": 1024, "block_size": 64, "prefill_pool_blocks": 64,
          "host_pool_blocks": 64}
LIMIT = 0.04
MIXES = {
    "mixed": {"schedule_seed": 1,
              "arrival": {"process": "poisson", "rate_per_s": 4.0},
              "prompt": {"dist": "loguniform", "min": 64, "max": 300},
              "output": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                         "min": 4, "max": 40}},
    # one fan-out batch of eight at 5.3 s whose last prompt (12,749
    # tokens) the planner splits, so its second chunk runs over paged
    # history
    "fanout": {"schedule_seed": 1016,
               "arrival": {"process": "poisson", "rate_per_s": 0.25,
                           "batch": {"size": 8, "spread_s": 0.2}},
               "prompt": {"stratified": True, "mixture": [
                   {"weight": 0.8, "dist": "loguniform", "min": 512,
                    "max": 2048},
                   {"weight": 0.2, "dist": "lognormal", "median": 12000,
                    "sigma": 0.35, "min": 8192, "max": 24576,
                    "bounds": "truncate"}]},
               "output": {"dist": "lognormal", "median": 64, "sigma": 0.7,
                          "min": 16, "max": 256, "bounds": "clip"}},
    "decode": {"schedule_seed": 1,
               "arrival": {"process": "resident", "sessions": 3},
               "prompt": {"dist": "loguniform", "min": 100, "max": 300},
               "output": {"dist": "fixed", "value": 3000}},
}


def load_run():
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)

    def program_config(conf):
        # the program's chatglm3-6b block at the tiny widths above
        from repro.configs.registry import get_config
        return dataclasses.replace(
            get_config("chatglm3-6b"), d_model=256, n_heads=4, n_kv_heads=2,
            head_dim=64, d_ff=512, vocab_size=512, n_layers=2,
            dtype="bfloat16")
    run.program_config = program_config
    return run


def spec(kind: str) -> dict:
    engine = ENGINE
    if kind == "fanout":
        # pools that hold the batch's prompts at once
        engine = dict(ENGINE, max_batch=8, max_seq=4096,
                      prefill_pool_blocks=448)
    conf = {"name": "tiny", "registry": "chatglm3-6b",
            "reference": "dense_gqa", "dtype": "bfloat16", "model": MODEL,
            "engine": engine, "check": {"logit_gap_limit": LIMIT}}
    if kind == "decode":
        e2e = [{"name": "decode_tokens_per_s", "unit": "tokens/s"}]
    else:
        e2e = [{"name": "ttft_p90_s", "unit": "s"}]
    return {"cell": {"name": "tiny." + kind, "chips": 1}, "conf": conf,
            "traffic": MIXES[kind],
            "end_to_end": e2e + [{"name": "setup_s", "unit": "s"}],
            "per_layer": []}
