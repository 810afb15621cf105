"""Assigned-architecture configs must match the assignment table exactly."""
import pytest

from repro.configs import ARCH_IDS, all_configs, get_config, reduced

# (layers, d_model, heads, kv, d_ff, vocab)
EXPECTED = {
    "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
    "starcoder2-3b": (30, 3072, 24, 2, 12288, 49152),
    "musicgen-large": (48, 2048, 32, 32, 8192, 2048),
    "minitron-8b": (32, 4096, 32, 8, 16384, 256000),
    "starcoder2-7b": (32, 4608, 36, 4, 18432, 49152),
    "mixtral-8x22b": (56, 6144, 48, 8, 16384, 32768),
    "xlstm-350m": (24, 1024, 4, 4, 0, 50304),
    "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
    "llava-next-mistral-7b": (32, 4096, 32, 8, 14336, 32000),
    "qwen2-1.5b": (28, 1536, 12, 2, 8960, 151936),
    "mellum2-12b-a2.5b": (28, 2304, 32, 4, 7168, 98304),
    "mellum2-12b-a2.5b-d12": (12, 2304, 32, 4, 7168, 98304),
}

FAMILY = {
    "dbrx-132b": "moe", "starcoder2-3b": "dense", "musicgen-large": "audio",
    "minitron-8b": "dense", "starcoder2-7b": "dense",
    "mixtral-8x22b": "moe", "xlstm-350m": "ssm",
    "recurrentgemma-9b": "hybrid", "llava-next-mistral-7b": "vlm",
    "qwen2-1.5b": "dense", "mellum2-12b-a2.5b": "moe",
    "mellum2-12b-a2.5b-d12": "moe",
}


def test_registry_complete():
    assert sorted(ARCH_IDS) == sorted(EXPECTED)
    assert len(all_configs()) == 12


@pytest.mark.parametrize("arch", sorted(EXPECTED))
def test_dims(arch):
    cfg = get_config(arch)
    L, d, H, KV, F, V = EXPECTED[arch]
    assert cfg.num_layers == L
    assert cfg.d_model == d
    assert cfg.num_heads == H
    assert cfg.num_kv_heads == KV
    assert cfg.d_ff == F
    assert cfg.vocab_size == V
    assert cfg.family == FAMILY[arch]
    assert cfg.citation


def test_family_traits():
    assert get_config("dbrx-132b").moe.num_experts == 16
    assert get_config("dbrx-132b").moe.top_k == 4
    assert get_config("mixtral-8x22b").moe.num_experts == 8
    assert get_config("mixtral-8x22b").moe.top_k == 2
    assert get_config("mixtral-8x22b").sliding_window == 4096
    assert get_config("qwen2-1.5b").qkv_bias
    assert get_config("musicgen-large").num_codebooks == 4
    assert not get_config("musicgen-large").use_rope
    assert get_config("llava-next-mistral-7b").vision_patches == 576
    assert get_config("recurrentgemma-9b").local_window == 2048
    mellum = get_config("mellum2-12b-a2.5b")
    assert (mellum.moe.num_experts, mellum.moe.top_k) == (64, 8)
    assert mellum.d_expert == 896 and mellum.d_ff == 7168
    assert mellum.yarn.factor == 16.0


def test_patterns_expand():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        pat = cfg.layer_pattern()
        assert len(pat) == cfg.num_layers
    # xLSTM mixes block kinds (sLSTM + mLSTM)
    mixers = {b.mixer for b in get_config("xlstm-350m").layer_pattern()}
    assert mixers == {"mlstm", "slstm"}
    # RecurrentGemma: 1 local-attn per 2 recurrent
    rg = get_config("recurrentgemma-9b").layer_pattern()
    attn = [b for b in rg if b.mixer == "attn"]
    rec = [b for b in rg if b.mixer == "rglru"]
    assert len(rec) > len(attn)
    assert all(b.window == 2048 for b in attn)
    # Mellum2: (sliding, sliding, sliding, full); YaRN on the full layers
    pat = get_config("mellum2-12b-a2.5b-d12").layer_pattern()
    assert [b.window for b in pat] == [1024, 1024, 1024, None] * 3
    assert [b.yarn for b in pat] == [False, False, False, True] * 3
    assert all(b.ffn == "moe" for b in pat)


def test_subquadratic_flags():
    assert get_config("xlstm-350m").is_subquadratic()
    assert get_config("recurrentgemma-9b").is_subquadratic()
    assert get_config("mixtral-8x22b").is_subquadratic()   # native SWA
    # dense archs only via the beyond-paper long-context variant
    cfg = get_config("minitron-8b")
    assert cfg.long_context_window is not None


@pytest.mark.parametrize("arch", sorted(EXPECTED))
def test_reduced_is_small_and_same_family(arch):
    cfg = reduced(get_config(arch))
    assert cfg.num_layers == 2
    assert cfg.d_model <= 512
    assert cfg.family == get_config(arch).family
    if cfg.moe:
        assert cfg.moe.num_experts <= 4


def test_param_counts_plausible():
    # names encode scale: sanity-check the analytic count within 2x
    approx = {"dbrx-132b": 132e9, "mixtral-8x22b": 141e9,
              "qwen2-1.5b": 1.5e9, "starcoder2-3b": 3e9,
              "starcoder2-7b": 7e9, "minitron-8b": 8e9,
              "recurrentgemma-9b": 9e9, "xlstm-350m": 350e6,
              "mellum2-12b-a2.5b": 12e9}
    for arch, n in approx.items():
        got = get_config(arch).param_count()
        assert 0.5 * n < got < 2.2 * n, (arch, got, n)


def test_active_params_less_for_moe():
    for arch in ("dbrx-132b", "mixtral-8x22b"):
        cfg = get_config(arch)
        assert cfg.active_param_count() < cfg.param_count()
    cfg = get_config("mellum2-12b-a2.5b")      # 12B total, 2.5B active
    assert 2.2e9 < cfg.active_param_count() < 2.8e9
    cfg = get_config("qwen2-1.5b")
    assert cfg.active_param_count() == cfg.param_count()
