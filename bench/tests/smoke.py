"""Smoke-size cells for the CPU tests: the registry's models shrunk to a
few layers and widths, served through the same harness."""

from __future__ import annotations

import copy

import harness

SMOKE_MODELS = {
    "dense": ("starcoder2-3b", {
        "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "d_ff": 192, "vocab": 256, "rope_theta": 100000.0,
        "norm_eps": 1e-05, "gated_mlp": False, "qkv_bias": False,
        "tie_embeddings": False, "param_dtype": "bfloat16",
        "activation_dtype": "bfloat16", "kv_cache_dtype": "bfloat16"}),
    "moe": ("olmoe-1b-7b", {
        "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
        "head_dim": 16, "d_ff_expert": 32, "n_experts": 8, "top_k": 2,
        "vocab": 256, "rope_theta": 10000.0, "norm_eps": 1e-05,
        "capacity_factor": 4.0, "qkv_bias": False,
        "tie_embeddings": False, "param_dtype": "bfloat16",
        "activation_dtype": "bfloat16", "kv_cache_dtype": "bfloat16"}),
}

MIX = {
    "arrivals": "poisson",
    "prompt": {"dist": "fixed", "tokens": 40},
    "output": {"dist": "fixed", "tokens": 6},
    "token_ids": "uniform", "eos": False, "shape_seed": 5,
}

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# sound smoke runs read a widest gap near 7e-3 (dense) and a mean gap
# near 1.5e-2 (moe, whose widest gap swings with router near-ties); the
# float8 control reads 0.24 and 0.12
LIMITS = {"dense": {"max_logit_gap": 0.05}, "moe": {"mean_logit_gap": 0.05}}


def spec(family: str = "dense", rate: float = 20.0) -> dict:
    """A cell spec as `harness.cell_spec` returns one, at smoke size."""
    registry, model = SMOKE_MODELS[family]
    overrides = {k: v for k, v in model.items()
                 if k not in ("head_dim", "n_kv_heads")}
    overrides["n_kv_heads"] = model["n_kv_heads"]
    overrides["head_dim"] = model["head_dim"]
    bench = harness.read_json(harness.ROOT, "BENCHMARK.json")
    return {
        "workload": {"name": f"smoke-{family}", "config": "smoke",
                     "traffic": "smoke", "chips": 1},
        "config_name": "smoke",
        "config": {"registry": registry, "family": family,
                   "overrides": overrides, "model": copy.deepcopy(model),
                   "engine": {"max_batch": 2, "max_len": 256,
                              "chunk_tokens": 16, "admission": "chunked",
                              "kv_layout": "dense"},
                   "tuner_seed": 0, "chips": 1},
        "traffic_name": "smoke",
        "traffic": MIX,
        "cell": {"rate_per_s": rate, "limits": LIMITS[family]},
        "end_to_end": bench["end_to_end"],
        "per_layer": bench["per_layer"],
    }
