"""The paper's own evaluation pool (§5 Models): Llama-family variants with a
shared tokenizer — llama-68m, tinyllama-1.1b, llama-2-7b(-chat) — plus
scaled-down "demo" versions trainable on this CPU host for the end-to-end
SpecRouter serving examples and Table-2 benchmark.

The *demo* pool keeps the paper's capability ORDERING and rough size ratios
while being small enough to train a few hundred steps on CPU so that model
distributions genuinely correlate (random-init models have ~0 acceptance,
which would make speculation trivially useless)."""
import jax.numpy as jnp

from ..models.config import ModelConfig

ARCH_ID = "llama-pool"


def full_pool():
    """Paper-scale configs at their published widths, as in each model's
    Hugging Face ``config.json`` (all three have untied output heads)."""
    base = dict(arch_type="dense", rope_theta=10_000.0, dtype=jnp.bfloat16,
                vocab_size=32000, tie_embeddings=False)
    return [
        ModelConfig(name="llama-68m", num_layers=2, d_model=768,
                    num_heads=12, num_kv_heads=12, d_ff=3072,
                    rms_eps=1e-6, max_position=2048,
                    source="[paper §5 Models; hf:JackFram/llama-68m "
                           "config.json]", **base),
        ModelConfig(name="tinyllama-1.1b", num_layers=22, d_model=2048,
                    num_heads=32, num_kv_heads=4, d_ff=5632,
                    rms_eps=1e-5, max_position=2048,
                    source="[paper §5 Models; hf:TinyLlama/TinyLlama-1.1B-"
                           "Chat-v1.0 config.json]", **base),
        ModelConfig(name="llama-2-7b", num_layers=32, d_model=4096,
                    num_heads=32, num_kv_heads=32, d_ff=11008,
                    rms_eps=1e-5, max_position=4096,
                    source="[paper §5 Models; hf:meta-llama/Llama-2-7b-hf "
                           "config.json]", **base),
        ModelConfig(name="llama-2-13b", num_layers=40, d_model=5120,
                    num_heads=40, num_kv_heads=40, d_ff=13824,
                    rms_eps=1e-5, max_position=4096,
                    source="[hf:meta-llama/Llama-2-13b-hf config.json]",
                    **base),
    ]


def demo_pool(vocab_size: int = 512):
    """CPU-trainable pool with the same capability ordering as the paper's
    68m : 1.1b : 7b roles.  The wall-clock cost ratio c = T_draft/T_target
    must be genuinely small for speculation to pay off (paper §2.2), so the
    target is sized ~60× the draft in FLOPs — on this CPU that yields
    c ≈ 0.1, comparable to the paper's llama-68m : llama-2-7b pairing."""
    base = dict(arch_type="dense", rope_theta=10_000.0, dtype=jnp.float32,
                max_position=2048, source="[paper §5, demo-scaled]")
    return [
        ModelConfig(name="demo-68m", num_layers=2, d_model=64,
                    num_heads=4, num_kv_heads=4, d_ff=256,
                    vocab_size=vocab_size, **base),
        ModelConfig(name="demo-1b", num_layers=5, d_model=160,
                    num_heads=4, num_kv_heads=4, d_ff=640,
                    vocab_size=vocab_size, **base),
        ModelConfig(name="demo-7b", num_layers=12, d_model=384,
                    num_heads=8, num_kv_heads=8, d_ff=1536,
                    vocab_size=vocab_size, **base),
    ]


def config() -> ModelConfig:
    return full_pool()[2]   # llama-2-7b: the paper's target model


def smoke_config() -> ModelConfig:
    return demo_pool()[0]
