"""A tiny copy of a cell for CPU tests: the same harness, files and
program path, at widths a test can hold."""
from __future__ import annotations

import json
import pathlib

TINY = {
    "name": "tiny", "source": "test", "arch": "tiny-gqa",
    "reference": "dense_gqa", "num_hidden_layers": 2, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 128, "vocab_size": 512, "hidden_act": "silu",
    "rms_norm_eps": 1e-6, "norm_type": "rmsnorm", "rope_theta": 10000.0,
    "tie_word_embeddings": True,
    "qkv_bias": True, "torch_dtype": "bfloat16",
    "serving": {"engines": 2, "mesh": None, "max_lanes": 4, "num_pages": 96,
                "page_size": 16, "prefill_chunk": 16, "max_len": 128,
                "prefix_cache": True, "scheduler": "jsq"},
    "check": {"sample_tokens": 40, "max_sequences": 6,
              "max_logit_gap": 0.025},
}

TRAFFIC = {
    "kind": "sessions", "loop": "open", "request_rate_per_s": 6.0,
    "lead_in_s": 0.5, "drain_s": 30.0, "structure_seed": 0,
    "system_prompts": {"count": 2, "min": 16, "max": 32, "zipf": 1.0,
                       "align": 16},
    "turns": {"dist": "geometric", "mean": 2, "min": 1, "max": 3},
    "think_s": {"dist": "exponential", "mean": 0.5},
    "user": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 4,
             "max": 20},
    "reply": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 2,
              "max": 10},
    "output": {"dist": "lognormal", "median": 4, "sigma": 0.5, "min": 2,
               "max": 8},
    "prompt_cap": 112,
}

# the four-chip cell's shape: one engine sharded (1, 4), KV heads split
# four ways, non-gated MLP, untied head; a closed loop of clients
TINY_TP4 = dict(
    TINY, name="tiny-tp4", arch="tiny-tp4", num_attention_heads=8,
    num_key_value_heads=4, hidden_act="gelu_pytorch_tanh",
    tie_word_embeddings=False, qkv_bias=False,
    serving={"engines": 1, "mesh": [1, 4], "max_lanes": 4, "kv_slots": 12,
             "num_pages": 97, "page_size": 16, "prefill_chunk": 16,
             "max_len": 128, "prefix_cache": True, "scheduler": "jsq"})

TRAFFIC_CLOSED = {
    "kind": "sessions", "loop": "closed", "clients": 8,
    "sessions_per_client": 4, "lead_in_s": 0.5, "structure_seed": 0,
    "system_prompts": {"count": 0},
    "turns": {"dist": "fixed", "value": 1},
    "think_s": {"dist": "fixed", "value": 0.0},
    "user": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8,
             "max": 60},
    "output": {"dist": "lognormal", "median": 5, "sigma": 0.5, "min": 2,
               "max": 8},
    "prompt_cap": 60,
}

REPO = pathlib.Path(__file__).resolve().parents[2]


def model_config(c: dict):
    """The program's ModelConfig for a tiny configuration file."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=c["name"], num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        qkv_bias=c.get("qkv_bias", False),
        gated_ffn=c["hidden_act"] == "silu",
        tie_embeddings=c["tie_word_embeddings"], dtype=c["torch_dtype"],
        norm_eps=c.get("rms_norm_eps", c.get("norm_epsilon")),
        rope_theta=c["rope_theta"])


def make_root(tmp: pathlib.Path, config: dict = None, traffic: dict = None,
              workload: str = "tiny.mix") -> pathlib.Path:
    """A checkout-like root holding a one-cell BENCHMARK.json and its
    data files (the code is read from the real ``bench/``)."""
    config = TINY if config is None else config
    traffic = TRAFFIC if traffic is None else traffic
    mix = workload.split(".", 1)[1]
    for sub in ("configs", "traffic"):
        (tmp / "bench" / sub).mkdir(parents=True, exist_ok=True)
    (tmp / "bench" / "configs" / "tiny.json").write_text(json.dumps(config))
    (tmp / "bench" / "traffic" / f"{mix}.json").write_text(
        json.dumps(traffic))
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    bench = {"configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
             "workloads": [{"name": workload, "config": "tiny",
                            "traffic": mix, "chips": 1}],
             "end_to_end": [dict(m, workloads=[workload])
                            for m in real["end_to_end"]],
             "per_layer": [dict(m, workloads=[workload])
                           for m in real["per_layer"]]}
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def patch_program(monkeypatch, config: dict = None) -> None:
    """Make the program serve the tiny configuration under its arch id,
    and let the harness run on the CPU: its look for a chip, the peak
    table and the device memory reading are skipped."""
    import jax

    from bench import harness
    from repro.serving import builders

    monkeypatch.setattr(harness, "devices", lambda chips: jax.devices())
    monkeypatch.setattr(harness.peaks, "peaks", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    monkeypatch.setattr(harness, "peak_bytes", lambda: 0)

    config = TINY if config is None else config
    cfg = model_config(config)
    real = builders.get_config
    monkeypatch.setattr(builders, "get_config",
                        lambda arch: cfg if arch == config["arch"]
                        else real(arch))
