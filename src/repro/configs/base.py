"""Model configuration dataclass covering every assigned architecture family.

A single ``ModelConfig`` describes dense / MoE / SSM / hybrid / enc-dec / VLM /
audio backbones.  Architecture configs live one-per-file in this package and
are looked up through :func:`repro.configs.get_config`.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

# Architecture families ------------------------------------------------------
DENSE = "dense"
MOE = "moe"
SSM = "ssm"
HYBRID = "hybrid"
ENC_DEC = "enc_dec"  # seq2seq (audio backbone)
VLM = "vlm"          # decoder-only with vision-patch frontend stub
AUDIO = "audio"      # enc-dec with audio-frame frontend stub


@dataclass(frozen=True)
class ModelConfig:
    # identity -----------------------------------------------------------
    name: str
    arch_type: str                     # dense | moe | ssm | hybrid | vlm | audio
    citation: str = ""

    # transformer core -----------------------------------------------------
    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 1024
    vocab_size: int = 1024
    head_dim: Optional[int] = None     # default d_model // n_heads
    qkv_bias: bool = False
    norm: str = "rmsnorm"              # rmsnorm | layernorm
    activation: str = "swiglu"         # swiglu | gelu
    rope_theta: float = 10_000.0
    max_seq_len: int = 8192
    tie_embeddings: bool = False
    sliding_window: Optional[int] = None   # SWA window (tokens); None = full attn

    # MoE ------------------------------------------------------------------
    n_experts: int = 0                 # routed experts held (0 = dense MLP)
    top_k: int = 0
    n_shared_experts: int = 0          # always-on experts (qwen2-moe style)
    moe_d_ff: int = 0                  # per-expert hidden dim
    router_aux_coef: float = 0.01
    # expert parallelism: the router scores ``n_router_experts`` (0 = the
    # ``n_experts`` held) and this layer holds experts
    # [expert_offset, expert_offset + n_experts) of them
    n_router_experts: int = 0
    expert_offset: int = 0
    # softmax (qwen/olmoe) | sigmoid (DeepSeek-V3: a selection-only bias,
    # chosen scores normalised and times ``routed_scaling``)
    router_score: str = "softmax"
    routed_scaling: float = 1.0
    first_dense_layers: int = 0        # leading dense-MLP layers (MoE stacks)

    # latent attention (MLA, DeepSeek-V2/V3): kv_lora_rank > 0 ------------------
    kv_lora_rank: int = 0              # latent width c; the cache holds [c | k_pe]
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # SSM / Mamba2 (SSD) -----------------------------------------------------
    ssm_state: int = 0                 # N — state size per head
    ssm_expand: int = 2                # d_inner = expand * d_model
    ssm_head_dim: int = 64             # P — SSD head dim
    ssm_conv: int = 4                  # depthwise conv width
    ssm_chunk: int = 128               # SSD chunk length

    # hybrid (zamba2-style): shared attention block applied every k SSM layers
    attn_every: int = 0                # 0 = never (pure SSM)

    # encoder-decoder --------------------------------------------------------
    n_encoder_layers: int = 0          # >0 => enc-dec; decoder gets cross-attn
    enc_len_ratio: int = 8             # encoder frames = seq_len // ratio (audio)
    dec_enc_len: int = 4096            # encoder memory length for decode shapes

    # modality frontend stub (audio frames / vision patches) ------------------
    frontend: Optional[str] = None     # None | "audio_frames" | "vision_patches"
    n_frontend_tokens: int = 256       # VLM: patch tokens prepended to text

    # Delphi (the paper's technique, T1) --------------------------------------
    dual_head: bool = False            # event+time competing-exponential head
    age_encoding: bool = False         # continuous age encoding (replaces pos enc)
    death_token: int = 1               # termination token id ("Death")
    max_age: float = 85.0              # years (paper default)
    no_event_token: int = 2            # padding/"no event" token (loss-masked)

    # numerics / runtime -------------------------------------------------------
    dtype: str = "bfloat16"            # activation dtype on the TPU path
    param_dtype: str = "float32"
    use_pallas: bool = False           # kernels validated separately; jnp path default
    remat: bool = False                # activation checkpointing over layer scan
    # cost-accounting mode (dry-run FLOPs compile): XLA's CPU cost analysis
    # counts while-loop bodies ONCE, so the dry-run re-lowers with unrolled
    # python-loop layer stacks + direct (loop-free) attention to obtain exact
    # HLO FLOP counts.  Never used for the deployment graph.
    unroll_layers: bool = False
    attn_direct: bool = False
    # §Perf variant: shard attention score/context compute over the sequence
    # dim on the "model" axis (context parallelism).  Fixes replicated
    # attention compute when head counts don't divide the model axis
    # (e.g. qwen2.5's 40 q / 8 kv heads on a 16-way axis).
    seq_shard_attn: bool = False

    # ------------------------------------------------------------------------
    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // max(self.n_heads, 1))
        assert self.arch_type in (DENSE, MOE, SSM, HYBRID, ENC_DEC, VLM, AUDIO), self.arch_type
        if self.n_heads:
            assert self.n_heads % max(self.n_kv_heads, 1) == 0, "GQA requires n_heads % n_kv_heads == 0"
        if self.n_experts:
            assert 0 < self.top_k <= self.router_experts
            assert 0 <= self.expert_offset <= self.router_experts - self.n_experts
        assert self.router_score in ("softmax", "sigmoid"), self.router_score

    # convenience -------------------------------------------------------------
    @property
    def is_attention_free(self) -> bool:
        return self.arch_type == SSM

    @property
    def has_encoder(self) -> bool:
        return self.n_encoder_layers > 0

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_n_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def router_experts(self) -> int:
        """Experts the router scores: all of them, held here or not."""
        return self.n_router_experts or self.n_experts

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def q_per_kv(self) -> int:
        if self.n_heads == 0:
            return 1
        return self.n_heads // max(self.n_kv_heads, 1)

    def with_sliding_window(self, window: int) -> "ModelConfig":
        """Sub-quadratic long-context variant (DESIGN.md long_500k policy)."""
        return dataclasses.replace(self, sliding_window=window)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "ModelConfig":
        """Smoke-test variant: same family, 2 layers, d_model<=512, <=4 experts."""
        kw = dict(
            n_layers=2,
            d_model=256,
            head_dim=64,
            d_ff=512,
            vocab_size=min(self.vocab_size, 512),
            max_seq_len=256,
        )
        if self.n_heads:
            # 4 query heads, preserving the GQA ratio where possible
            kw["n_heads"] = 4
            kw["n_kv_heads"] = max(1, 4 // min(self.q_per_kv, 4))
        if self.n_experts:
            kw.update(n_experts=4, top_k=min(self.top_k, 2), moe_d_ff=128,
                      n_shared_experts=min(self.n_shared_experts, 1),
                      n_router_experts=0, expert_offset=0)
        if self.kv_lora_rank:   # four different widths: a slice of one
            kw.update(kv_lora_rank=48, qk_nope_head_dim=32,  # for another
                      qk_rope_head_dim=16, v_head_dim=24)    # shows
        if self.ssm_state:
            kw.update(ssm_state=16, ssm_head_dim=32, ssm_chunk=32)
        if self.attn_every:
            kw.update(attn_every=1, n_layers=2)
        if self.n_encoder_layers:
            kw.update(n_encoder_layers=2, dec_enc_len=64)
        if self.sliding_window:
            kw.update(sliding_window=64)
        if self.frontend:
            kw.update(n_frontend_tokens=8)
        return self.replace(**kw)


@dataclass(frozen=True)
class InputShape:
    """A named (seq_len, global_batch, mode) workload."""
    name: str
    seq_len: int
    global_batch: int
    mode: str            # "train" | "prefill" | "decode"

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


TRAIN_4K = InputShape("train_4k", 4_096, 256, "train")
PREFILL_32K = InputShape("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = InputShape("decode_32k", 32_768, 128, "decode")
LONG_500K = InputShape("long_500k", 524_288, 1, "decode")

INPUT_SHAPES: Tuple[InputShape, ...] = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
SHAPES_BY_NAME = {s.name: s for s in INPUT_SHAPES}


def get_shape(name: str) -> InputShape:
    return SHAPES_BY_NAME[name]
