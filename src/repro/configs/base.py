"""Model & shape configuration.

One ``ModelConfig`` per assigned architecture lives in
``src/repro/configs/<arch_id>.py`` (exact numbers from the assignment) plus a
``tiny()`` reduced variant of the same family for CPU smoke tests. The
registry resolves ``--arch <id>`` lookups for the launcher, dry-run and
benchmarks.
"""

from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field
from typing import Optional


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | audio | vlm | hybrid
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 → d_model // n_heads
    # MoE: routing is dropless. The router spans all ``n_experts``; this
    # program holds ``e_local`` of them (0 → all), from expert 0: a chip's
    # share under expert parallelism.
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    e_local: int = 0
    shared_d_ff: int = 0  # shared experts, as one always-on MLP (0 → none)
    router_score: str = "softmax"  # softmax | sigmoid
    routed_scale: float = 1.0  # on the normalised top-k weights
    # sigmoid routing's selection bias, not trained by gradients: after
    # each step bias_i += rate * sign(mean load - load_i) (0 → no bias)
    router_bias_rate: float = 0.0
    balance_loss: str = "switch"  # switch | seq (per-sequence, DeepSeek-V3)
    balance_weight: float = 0.01
    n_dense_layers: int = 0  # leading layers with a dense MLP of d_ff
    # multi-head latent attention (kv_lora_rank > 0; no q compression):
    # per head q/k of qk_nope_dim + qk_rope_dim, v of v_head_dim
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # attention details
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    use_rope: bool = True
    local_window: int = 0  # 0 → global attention
    attn_chunk: int = 512  # flash block size
    # layer pattern, cycled: entries in {attn, mlstm, slstm, rglru}
    block_pattern: tuple = ("attn",)
    # encoder-decoder (whisper)
    is_encoder_decoder: bool = False
    n_enc_layers: int = 0
    enc_seq: int = 0  # precomputed frame embeddings fed by the stub frontend
    # recurrent dims
    lru_width: int = 0
    conv_width: int = 4
    # misc
    act: str = "silu"
    rms_norm: bool = True
    rms_eps: float = 1e-6
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    # training-time policy knobs (overridable per run / hillclimb)
    remat: str = "full"  # none | dots | full
    scan_layers: bool = True

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def experts_held(self) -> int:
        return self.e_local or self.n_experts

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers if self.is_moe else 0

    @property
    def sub_quadratic(self) -> bool:
        """True if attention cost doesn't grow quadratically without bound
        (pure-recurrent or bounded local window) → runs long_500k."""
        kinds = set(self.block_pattern)
        if "attn" not in kinds:
            return True
        return self.local_window > 0

    def pattern_for_layers(self) -> tuple:
        p = self.block_pattern
        return tuple(p[i % len(p)] for i in range(self.n_layers))

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def param_count(self) -> int:
        """Approximate total parameter count (embedding + blocks)."""
        d, hd = self.d_model, self.hd
        n = self.vocab_size * d  # embed (+ tied unembed)
        if not self.tie_embeddings:
            n += self.vocab_size * d
        attn = d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d
        if self.is_mla:
            qk = self.qk_nope_dim + self.qk_rope_dim
            r = self.kv_lora_rank
            attn = (d * self.n_heads * qk + d * (r + self.qk_rope_dim) + r
                    + r * self.n_heads * (self.qk_nope_dim + self.v_head_dim)
                    + self.n_heads * self.v_head_dim * d)
        dense_mlp = 3 * d * self.d_ff if self.act == "silu" else 2 * d * self.d_ff
        moe_mlp = (self.experts_held * 3 * d * self.moe_d_ff
                   + d * self.n_experts + 3 * d * self.shared_d_ff)
        for i, kind in enumerate(self.pattern_for_layers()):
            if kind == "attn":
                n += attn
                moe = self.is_moe and i >= self.n_dense_layers
                n += moe_mlp if moe else dense_mlp
            elif kind == "rglru":
                w = self.lru_width or d
                n += 2 * d * w + w * d + 3 * w * (w // max(self.n_heads, 1)) + self.conv_width * w
                n += dense_mlp
            elif kind == "mlstm":
                di = 2 * d
                n += d * 2 * di + 3 * di * di + 2 * di + di * d + self.conv_width * di
            elif kind == "slstm":
                dh = d
                n += 4 * d * dh + 4 * dh * (dh // max(self.n_heads, 1))
                n += 2 * d * int(d * 4 / 3)
        if self.is_encoder_decoder:
            # encoder blocks: self-attn + mlp; decoder adds cross-attn
            enc = (attn + dense_mlp) * self.n_enc_layers
            cross = (4 * d * self.n_heads * hd) * self.n_layers
            n += enc + cross
        return n

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top_k experts instead of all)."""
        if not self.is_moe:
            return self.param_count()
        d = self.d_model
        full = self.param_count()
        moe_total = self.n_moe_layers * self.experts_held * 3 * d * self.moe_d_ff
        moe_active = self.n_moe_layers * self.top_k * 3 * d * self.moe_d_ff
        return full - moe_total + moe_active


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


# The four assigned LM shape cells.
SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}

ARCH_IDS = [
    "qwen3-moe-235b-a22b",
    "granite-moe-3b-a800m",
    "xlstm-125m",
    "whisper-tiny",
    "smollm-360m",
    "deepseek-coder-33b",
    "llama3-8b",
    "qwen2.5-3b",
    "chameleon-34b",
    "recurrentgemma-2b",
    "moonlight-16b-a3b",
]


def _module_for(arch_id: str):
    mod = arch_id.replace("-", "_").replace(".", "_")
    return importlib.import_module(f"repro.configs.{mod}")


def get_config(arch_id: str) -> ModelConfig:
    return _module_for(arch_id).config()


def get_tiny_config(arch_id: str) -> ModelConfig:
    return _module_for(arch_id).tiny()


def cells(arch_id: str) -> list[tuple[str, str]]:
    """All runnable (arch, shape) cells for an arch; decode/long rules from
    DESIGN.md §7 (long_500k only for sub-quadratic archs)."""
    cfg = get_config(arch_id)
    out = []
    for shape in SHAPES.values():
        if shape.name == "long_500k" and not cfg.sub_quadratic:
            continue  # noted skip: quadratic full attention at 500k
        out.append((arch_id, shape.name))
    return out


def all_cells() -> list[tuple[str, str]]:
    out = []
    for a in ARCH_IDS:
        out.extend(cells(a))
    return out
