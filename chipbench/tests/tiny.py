"""A cell small enough for the CPU: the Qwen2 code path at toy widths,
built as the resolved data ``run.run`` takes, for the tests."""
import copy

CONFIG = {
    "source": "toy", "reference": "qwen2", "model_type": "qwen2",
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "tie_word_embeddings": True, "torch_dtype": "bfloat16",
    "use_sliding_window": False, "vocab_size": 512, "engine": {},
}

TRAFFIC = {
    "arrivals": "poisson_stratified",
    "lengths": "lognormal_stratified_balanced",
    "prompt_len": {"median": 100, "sigma": 0.8, "min": 16, "max": 128,
                   "round_up": 64},
    "output_len": {"median": 8, "sigma": 0.8, "min": 2, "max": 24,
                   "round_up": 1},
    "token_ids": "uniform",
}

CELL = {
    "config": "toy", "traffic": "toy", "slots": 4,
    "max_len": 512, "rate_rps": 8.0, "ramp_s": 0.3,
    "check": {"max_logit_gap": 0.01},
}

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
         "hbm_bytes": 16e9}


def resolved(paged: bool, per_layer=(), end_to_end=(), config=None):
    cfg = copy.deepcopy(config or CONFIG)
    if paged:
        cfg["engine"] = {"access_path": "xdma", "fused_install": False}
    name = "toy.paged" if paged else "toy.resident"
    mk = lambda n: {"name": n, "unit": "x"}
    return {"name": name,
            "entry": {"name": name, "config": "toy", "traffic": "toy",
                      "chips": 1},
            "cell": copy.deepcopy(CELL), "config": cfg,
            "traffic": copy.deepcopy(TRAFFIC), "peaks": dict(PEAKS),
            "end_to_end": [mk(n) for n in end_to_end],
            "per_layer": [mk(n) for n in per_layer]}
