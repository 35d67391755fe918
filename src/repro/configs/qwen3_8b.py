"""qwen3-8b — 36L d4096 32H (GQA kv=8) d_ff=12288, vocab 151936, qk_norm.
[hf:Qwen/Qwen3-8B]"""

from ..models.common import LayerSpec, ModelConfig, uniform_stages


def config() -> ModelConfig:
    return ModelConfig(
        name="qwen3-8b",
        family="dense",
        d_model=4096,
        n_layers=36,
        vocab_size=151936,
        n_heads=32,
        n_kv_heads=8,
        head_dim=128,
        d_ff=12288,
        qk_norm=True,
        stages=uniform_stages(36, LayerSpec("attn", "mlp")),
        tie_embeddings=False,
        rope_theta=1_000_000.0,
    )


def share(n_layers: int, vocab_div: int) -> ModelConfig:
    """The published widths, cut to ``n_layers`` (one pipeline stage of the
    uniform pattern) and to 1/``vocab_div`` of the vocabulary (one chip's
    slice of a vocabulary-parallel head)."""
    full = config()
    return full.scaled(
        n_layers=n_layers,
        stages=uniform_stages(n_layers, LayerSpec("attn", "mlp")),
        vocab_size=full.vocab_size // vocab_div,
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="qwen3-smoke",
        family="dense",
        d_model=64,
        n_layers=2,
        vocab_size=128,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        d_ff=128,
        qk_norm=True,
        stages=uniform_stages(2, LayerSpec("attn", "mlp")),
        tie_embeddings=False,
    )
