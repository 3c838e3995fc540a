"""Configuration system for repro.

Dataclass-based, mirroring the paper's separation between the *model graph*
(what computation), *placement* (where it runs = mesh/sharding here), and the
*step* being executed (train / prefill / decode).

Every assigned architecture lives in ``repro.configs.<id>`` exposing
``CONFIG`` (full-size, dry-run only) and ``smoke_config()`` (reduced, runs on
CPU). ``repro.config.get_config(arch)`` resolves either.
"""

from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field
from typing import Any, Sequence

# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------

# Layer kinds used by blocks patterns.
ATTN = "attn"            # full global attention block
LOCAL_ATTN = "local"     # sliding-window attention block
MAMBA = "mamba"          # Mamba2 SSD block
SHARED_ATTN = "shared"   # zamba2-style shared-weight attention block


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    experts_per_token: int
    d_ff_expert: int
    capacity_factor: float = 1.25
    router_jitter: float = 0.0
    # Experts [0, experts_held) of the num_experts the router scores live in
    # this model's expert leaves: one chip's share of expert parallelism.
    # None holds them all.
    experts_held: int | None = None

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held


@dataclass(frozen=True)
class YarnScaling:
    """YaRN rope scaling (arXiv:2309.00071) of the full-attention layers:
    frequencies below the ``beta_slow`` .. ``beta_fast`` rotation band at
    ``original_max_positions`` are divided by ``factor``, those above it
    kept, a linear ramp between; cos and sin are scaled by
    ``attention_factor``."""
    factor: float
    original_max_positions: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclass(frozen=True)
class SSMConfig:
    state_dim: int            # N (ssm_state)
    head_dim: int = 64        # P
    expand: int = 2           # d_inner = expand * d_model
    conv_kernel: int = 4
    chunk_size: int = 256
    n_groups: int = 1

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str               # dense | moe | ssm | hybrid | encdec | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 128
    # --- attention options -------------------------------------------------
    rope_theta: float = 10000.0
    rope_sections: tuple[int, ...] | None = None   # M-RoPE (qwen2-vl): (t,h,w)
    rope_scaling: YarnScaling | None = None         # full ("attn") layers only
    qk_norm: bool = False                           # qwen3 family
    attn_logit_softcap: float | None = None         # gemma2 (50.0), grok
    final_logit_softcap: float | None = None        # gemma2 (30.0)
    sliding_window: int | None = None               # local-attn window size
    attn_scale: float | None = None                 # override 1/sqrt(head_dim)
    # --- block structure ----------------------------------------------------
    # Pattern of layer kinds, tiled to num_layers. Examples:
    #   ("attn",)                      -> plain decoder
    #   ("local", "attn")              -> gemma2 alternating
    #   ("mamba",)*5 + ("mamba+shared",)  -> zamba2 period (see transformer.py)
    block_pattern: tuple[str, ...] = (ATTN,)
    shared_attn_period: int = 0      # zamba2: apply shared attn block every k layers
    # --- MLP ------------------------------------------------------------------
    mlp_activation: str = "silu"     # silu (SwiGLU) | gelu (GeGLU) | gelu_mlp (ungated)
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    post_block_norm: bool = False    # gemma2 applies post-norms as well
    tie_embeddings: bool = False
    embedding_scale: bool = False    # gemma2 scales embeddings by sqrt(d_model)
    # --- mixture / ssm -----------------------------------------------------
    moe: MoEConfig | None = None
    ssm: SSMConfig | None = None
    # --- encoder-decoder ----------------------------------------------------
    encoder_layers: int = 0          # >0 => enc-dec (whisper)
    encoder_seq_len: int = 0         # fixed encoder context (1500 audio frames)
    # --- modality frontend stub ----------------------------------------------
    frontend: str | None = None      # "audio" | "vision" -> input_specs stubs
    # --- numerics -------------------------------------------------------------
    dtype: str = "bfloat16"
    sub_quadratic: bool = False      # eligible for long_500k

    def __post_init__(self):
        # groups given as dicts (a JSON override) become their dataclasses
        for name, kind in (("moe", MoEConfig), ("rope_scaling", YarnScaling)):
            v = getattr(self, name)
            if isinstance(v, dict):
                object.__setattr__(self, name, kind(**v))

    @property
    def padded_vocab_size(self) -> int:
        """Vocab padded to a multiple of 256 so the table shards over any
        mesh "model" axis (Megatron-style); losses mask padded columns."""
        return -(-self.vocab_size // 256) * 256

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    def layer_kinds(self) -> tuple[str, ...]:
        """Expanded per-layer kind list of length num_layers."""
        pat = self.block_pattern
        reps = -(-self.num_layers // len(pat))
        return (pat * reps)[: self.num_layers]

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + head)."""
        d, L = self.d_model, self.num_layers
        n = self.vocab_size * d  # embedding
        if not self.tie_embeddings:
            n += self.vocab_size * d  # lm head
        for kind in self.layer_kinds():
            if kind in (ATTN, LOCAL_ATTN):
                n += self._attn_params() + self._mlp_params() + 2 * d
            elif kind == MAMBA:
                n += self._mamba_params() + d
        if self.shared_attn_period:
            n += self._attn_params() + self._mlp_params() + 2 * self.d_model
        if self.encoder_layers:
            # encoder self-attn + mlp, decoder already counted; add cross-attn
            n += self.encoder_layers * (
                self._attn_params() + self._mlp_params() + 2 * d
            )
            n += self.num_layers * (self._attn_params() + d)  # cross attn
        return n

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only routed experts)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        full = self.param_count()
        per_expert = 3 * self.d_model * m.d_ff_expert
        # of the held experts, a token meets k * held / E on average
        active = m.experts_per_token * m.held // m.num_experts
        return full - self.num_layers * (m.held - active) * per_expert

    def _attn_params(self) -> int:
        d = self.d_model
        return d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d

    def _mlp_params(self) -> int:
        if self.moe is not None:
            m = self.moe
            return self.d_model * m.num_experts + (
                m.held * 3 * self.d_model * m.d_ff_expert
            )
        mats = 2 if self.mlp_activation == "gelu_mlp" else 3
        return mats * self.d_model * self.d_ff

    def _mamba_params(self) -> int:
        assert self.ssm is not None
        s = self.ssm
        d, di = self.d_model, s.d_inner(self.d_model)
        nh, N = s.n_heads(self.d_model), s.state_dim
        in_proj = d * (2 * di + 2 * s.n_groups * N + nh)
        conv = s.conv_kernel * (di + 2 * s.n_groups * N)
        return in_proj + conv + nh + nh + di * d + di  # A, D, out_proj, norm


# ---------------------------------------------------------------------------
# Shapes (assigned input-shape set)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                # train | prefill | decode


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def shape_applicable(model: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Whether an (arch, shape) cell runs, per DESIGN.md §4."""
    if shape.name == "long_500k" and not model.sub_quadratic:
        return False, "skipped (full attention; long_500k needs sub-quadratic)"
    return True, ""


# ---------------------------------------------------------------------------
# Parallelism / run configuration
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParallelConfig:
    # How weight matrices map to the mesh; see spmd/sharding.py.
    fsdp: bool = False            # shard params over "data" too (all-gather in scan)
    zero1: bool = True            # shard optimizer state over "data"
    remat: str = "full"           # none | dots | full
    microbatches: int = 1         # gradient accumulation
    seq_shard_activations: bool = False  # sequence-parallel saved activations
    expert_ff_2d: bool = False    # serving: shard expert d_ff over (data,model)
                                  # instead of FSDP (kills per-step gathers)
    # note: decode KV caches are always sequence-sharded over "model" when
    # divisible (flash-decode LSE stitch); see spmd/steps.cache_shardings.


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"
    lr: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    schedule: str = "cosine"       # constant | cosine | linear
    total_steps: int = 10_000
    compression: str = "none"      # none | int8_ef (error-feedback int8 all-reduce)
    slot_dtype: str = "float32"    # "bfloat16" halves moment memory
                                   # (masters stay fp32; math in fp32)


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    shape: ShapeConfig
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    seed: int = 0

    def replace(self, **kw: Any) -> "RunConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

ARCHS: tuple[str, ...] = (
    "glm4_9b",
    "starcoder2_3b",
    "gemma2_27b",
    "qwen3_32b",
    "whisper_large_v3",
    "zamba2_2p7b",
    "qwen2_vl_2b",
    "qwen3_moe_30b_a3b",
    "grok1_314b",
    "mamba2_370m",
    "mellum2_12b_a2p5b",
)

# Accept dashed ids from the assignment table as aliases.
_ALIASES = {
    "glm4-9b": "glm4_9b",
    "starcoder2-3b": "starcoder2_3b",
    "gemma2-27b": "gemma2_27b",
    "qwen3-32b": "qwen3_32b",
    "whisper-large-v3": "whisper_large_v3",
    "zamba2-2.7b": "zamba2_2p7b",
    "qwen2-vl-2b": "qwen2_vl_2b",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
    "grok-1-314b": "grok1_314b",
    "mamba2-370m": "mamba2_370m",
    "mellum2-12b-a2.5b": "mellum2_12b_a2p5b",
}


def canonical_arch(arch: str) -> str:
    arch = _ALIASES.get(arch, arch)
    if arch not in ARCHS and arch != "lstm_lm":
        raise ValueError(f"unknown arch {arch!r}; known: {ARCHS}")
    return arch


def get_config(arch: str, smoke: bool = False) -> ModelConfig:
    arch = canonical_arch(arch)
    mod = importlib.import_module(f"repro.configs.{arch}")
    return mod.smoke_config() if smoke else mod.CONFIG


def all_configs(smoke: bool = False) -> dict[str, ModelConfig]:
    return {a: get_config(a, smoke=smoke) for a in ARCHS}
