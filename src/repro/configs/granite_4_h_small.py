"""Granite-4.0-H-Small [hybrid] — 40L d_model=4096, Mamba-2 (128 heads x
64, d_state 128, 1 group, conv 4, chunk 256) in 9 of every 10 layers and
NoPE GQA attention (32H, kv=8, d_head 128) at layer 5 of each period;
MoE in every layer: 72 experts of width 768, top-10, plus one shared
SwiGLU of width 1536; vocab 100352, tied; Granite's four multipliers.
32.2B parameters (32B-A9B).
[hf:ibm-granite/granite-4.0-h-small config.json; the expert width is
read from ``intermediate_size``]

The benchmark serves one chip's share of a stated deployment
(``bench/configs/granite-4-h-small.json``): one pipeline stage of 10
layers, 9 of the 72 experts of each layer (``router_width`` keeps 72)."""
import dataclasses

from .base import ArchConfig, TrainSettings

PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ArchConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    d_head=128,
    d_ff=0,
    vocab=100352,
    tie_embeddings=True,
    norm_eps=1e-5,
    n_experts=72,
    router_width=72,
    top_k=10,
    d_expert_ff=768,
    d_shared_ff=1536,
    ssm_state=128,
    ssm_conv=4,
    ssm_expand=2,
    ssm_heads=128,
    ssm_head_dim=64,
    ssm_groups=1,
    ssm_chunk=256,
    layer_types=PERIOD,
    embedding_multiplier=12.0,
    attention_multiplier=0.0078125,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    use_rope=False,
    train=TrainSettings(microbatches=8, sharding="fsdp_tp",
                        opt_dtype="bfloat16"),
)


def reduced() -> ArchConfig:
    """Every mechanism at toy widths: one whole period, 3 of 12 experts
    held, chunk 8 so that prompts span chunks."""
    return dataclasses.replace(
        CONFIG, n_layers=len(PERIOD), d_model=64, n_heads=4, n_kv_heads=2,
        d_head=16, vocab=512, n_experts=3, router_width=12, expert_offset=3,
        top_k=4, d_expert_ff=32, d_shared_ff=48, ssm_state=8, ssm_heads=8,
        ssm_head_dim=16, ssm_chunk=8, train=TrainSettings())
