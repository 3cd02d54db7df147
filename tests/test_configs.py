"""Config registry: every assigned arch present, exact hyperparameters,
reduced variants respect the smoke-test contract."""
import pytest

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, REGISTRY, get_config, shapes_for

EXPECTED = {
    "gemma-2b": dict(num_layers=18, d_model=2048, num_heads=8, num_kv_heads=1,
                     d_ff=16384, vocab_size=256_000, head_dim=256),
    "phi3.5-moe-42b-a6.6b": dict(num_layers=32, d_model=4096, num_heads=32,
                                 num_kv_heads=8, d_ff=6400, vocab_size=32_064),
    "smollm-360m": dict(num_layers=32, d_model=960, num_heads=15,
                        num_kv_heads=5, d_ff=2560, vocab_size=49_152),
    "qwen2-vl-2b": dict(num_layers=28, d_model=1536, num_heads=12,
                        num_kv_heads=2, d_ff=8960, vocab_size=151_936),
    "hubert-xlarge": dict(num_layers=48, d_model=1280, num_heads=16,
                          num_kv_heads=16, d_ff=5120, vocab_size=504),
    "starcoder2-3b": dict(num_layers=30, d_model=3072, num_heads=24,
                          num_kv_heads=2, d_ff=12288, vocab_size=49_152),
    "zamba2-2.7b": dict(num_layers=54, d_model=2560, num_heads=32,
                        num_kv_heads=32, d_ff=10240, vocab_size=32_000),
    "qwen1.5-110b": dict(num_layers=80, d_model=8192, num_heads=64,
                         num_kv_heads=8, d_ff=49152, vocab_size=152_064),
    "mamba2-1.3b": dict(num_layers=48, d_model=2048, vocab_size=50_280),
    "mixtral-8x7b": dict(num_layers=32, d_model=4096, num_heads=32,
                         num_kv_heads=8, d_ff=14336, vocab_size=32_000),
}


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_assigned_arch_exact(arch):
    cfg = get_config(arch)
    for k, v in EXPECTED[arch].items():
        assert getattr(cfg, k) == v, f"{arch}.{k}: {getattr(cfg, k)} != {v}"
    assert cfg.source, f"{arch} missing source citation"


def test_special_fields():
    assert get_config("mixtral-8x7b").sliding_window == 4096
    assert get_config("mixtral-8x7b").moe.num_experts == 8
    assert get_config("phi3.5-moe-42b-a6.6b").moe.num_experts == 16
    assert get_config("mamba2-1.3b").ssm.state_dim == 128
    assert get_config("zamba2-2.7b").ssm.state_dim == 64
    assert get_config("qwen1.5-110b").qkv_bias
    assert get_config("qwen2-vl-2b").positional == "mrope"
    assert get_config("hubert-xlarge").is_encoder
    assert get_config("gemma-2b").mlp_activation == "geglu"


@pytest.mark.parametrize("arch", sorted(REGISTRY))
def test_reduced_contract(arch):
    r = REGISTRY[arch].reduced()
    assert r.num_layers <= 2
    assert r.d_model <= 512
    assert r.vocab_size <= 512
    if r.moe is not None:
        assert r.moe.num_experts <= 4
    assert r.family == REGISTRY[arch].family


def test_input_shapes():
    assert INPUT_SHAPES["train_4k"].seq_len == 4096
    assert INPUT_SHAPES["train_4k"].global_batch == 256
    assert INPUT_SHAPES["prefill_32k"].global_batch == 32
    assert INPUT_SHAPES["decode_32k"].global_batch == 128
    assert INPUT_SHAPES["long_500k"].seq_len == 524_288


def test_encoder_skips_decode():
    assert "decode_32k" not in shapes_for(get_config("hubert-xlarge"))
    assert "decode_32k" in shapes_for(get_config("gemma-2b"))


def test_param_counts_match_published():
    # within 15% of the published sizes
    approx = {"gemma-2b": 2.5e9, "smollm-360m": 0.36e9, "starcoder2-3b": 3.0e9,
              "mixtral-8x7b": 46.7e9, "mamba2-1.3b": 1.3e9,
              "qwen1.5-110b": 111e9}
    for arch, n in approx.items():
        got = get_config(arch).param_count()
        assert abs(got - n) / n < 0.15, (arch, got, n)
    # MoE active params
    assert get_config("mixtral-8x7b").active_param_count() < 14e9


def test_moonlight_is_one_chips_share():
    """Moonlight-16B-A3B's published widths, with the cut stated beside
    them: 9 of 27 layers, 8 of 64 routed experts held (8 chips share each
    expert layer)."""
    cfg = get_config("moonlight-16b-a3b-ep8")
    assert (cfg.d_model, cfg.num_heads, cfg.vocab_size, cfg.d_ff) == \
        (2048, 16, 163_840, 11_264)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (512, 128, 64, 128)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.expert_d_ff,
            cfg.num_shared_experts) == (64, 6, 1408, 2)
    assert cfg.routed_scaling_factor == 2.446
    assert (cfg.num_layers, cfg.first_k_dense) == (9, 1)
    assert cfg.num_experts // cfg.experts_held == 8
    assert cfg.mla_latent_dim == 576 and not cfg.tie_embeddings
    # 1.56 G parameters held here: 3.1 GB in bfloat16
    assert 1.5e9 < cfg.param_count() < 1.6e9
