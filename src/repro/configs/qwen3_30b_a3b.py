"""Qwen3-30B-A3B — the paper's MoE evaluation model.

48L d_model=2048 32H (GQA kv=4) of head_dim 128 (q is 4096 wide, wider
than d_model), 128 experts top-8, moe_d_ff=768, vocab=151936.  Every
layer normalizes each head's q and k with an RMSNorm of width head_dim
(eps 1e-6) after the projection and before RoPE.  Published Amber-P skip
list: q_proj/gate_proj skipped in layers 41, 46, 47 → 56.9% coverage.
Robust-Norm scoring disabled inside routed experts (paper: dynamic
routing → per-expert stats unstable).

Source: https://huggingface.co/Qwen/Qwen3-30B-A3B/blob/main/config.json
"""
import dataclasses

from repro.configs.base import ModelConfig


@dataclasses.dataclass(frozen=True)
class Qwen3Config(ModelConfig):
    """``ModelConfig`` with the per-head q/k RMSNorm switch, read by the
    model as ``getattr(cfg, "qk_norm", False)``."""
    qk_norm: bool = True


CONFIG = Qwen3Config(
    name="qwen3-30b-a3b",
    family="moe",
    n_layers=48,
    d_model=2048,
    n_heads=32,
    n_kv_heads=4,
    d_ff=6144,
    vocab_size=151936,
    head_dim=128,
    n_experts=128,
    top_k=8,
    moe_d_ff=768,
    rope_theta=1e6,
    qgate_skip_layers=(41, 46, 47),
    sub_quadratic=False,
)


def smoke() -> ModelConfig:
    # head_dim twice d_model / n_heads, as in the published model
    return Qwen3Config(
        name="qwen3-moe-smoke",
        family="moe",
        n_layers=2,
        d_model=64,
        n_heads=4,
        n_kv_heads=2,
        d_ff=128,
        vocab_size=256,
        head_dim=32,
        n_experts=8,
        top_k=2,
        moe_d_ff=32,
        qgate_skip_layers=(1,),
        attn_chunk=8,
    )
