"""moonlight-16b-a3b [moe] — 27L d_model=2048, latent attention (MLA, 16
heads, kv rank 512, q/k 128+64, v 128), layer 0 dense (d_ff 11264), then
26 MoE layers of 64 routed experts (width 1408, top-6) + 2 shared, vocab
163840, untied. [hf:moonshotai/Moonlight-16B-A3B, model_type deepseek_v3]

Routing is DeepSeek-V3's ``noaux_tc`` with one group: sigmoid scores,
top-6 chosen on score + a correction bias that gradients do not train,
weights the chosen scores normalised and scaled by 2.446, and the
per-sequence balance loss. Published but not in the catalog, and so
assumed: the balance weight alpha 1e-4 and bias update speed gamma 1e-3
(DeepSeek-V3 technical report, section 4.2).
"""

from repro.configs.base import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b",
        family="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        d_ff=11264,
        vocab_size=163840,
        n_experts=64,
        top_k=6,
        moe_d_ff=1408,
        shared_d_ff=2 * 1408,
        router_score="sigmoid",
        routed_scale=2.446,
        router_bias_rate=1e-3,
        balance_loss="seq",
        balance_weight=1e-4,
        n_dense_layers=1,
        kv_lora_rank=512,
        qk_nope_dim=128,
        qk_rope_dim=64,
        v_head_dim=128,
        rope_theta=50000.0,
        rms_eps=1e-5,
    )


def tiny() -> ModelConfig:
    return config().replace(
        name="moonlight-tiny",
        n_layers=3,
        d_model=64,
        n_heads=4,
        n_kv_heads=4,
        d_ff=128,
        vocab_size=256,
        n_experts=8,
        e_local=4,
        top_k=3,
        moe_d_ff=32,
        shared_d_ff=64,
        kv_lora_rank=32,
        qk_nope_dim=16,
        qk_rope_dim=8,
        v_head_dim=16,
        attn_chunk=64,
    )
