"""Architecture + experiment configuration system.

Each assigned architecture lives in ``repro/configs/<id>.py`` and exports a
``CONFIG: ArchConfig`` with the exact published hyper-parameters (source
cited in the file) plus a ``reduced()`` variant for CPU smoke tests.

Input shapes are the four assigned workload shapes; ``decode_*`` shapes
lower ``serve_step`` (single-token decode against a seq_len KV cache/state),
the others lower ``train_step``.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Literal

Family = Literal["dense", "moe", "ssm", "hybrid", "vlm", "audio"]


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    # identity -------------------------------------------------------------
    name: str
    family: Family
    source: str  # citation: arXiv id or HF model card

    # trunk ------------------------------------------------------------------
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // num_heads

    # attention options ------------------------------------------------------
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    sliding_window: int = 0  # 0 -> full attention
    # layer pattern: e.g. gemma3 "LLLLLG" (5 local : 1 global), griffin "RRA".
    # One char per pattern element: L=local attn, G=global attn, R=recurrent,
    # A=(local) attn, X=cross-attn insert, S=self-attn, M=moe, D=dense-ff.
    layer_pattern: str = ""
    attn_logit_softcap: float = 0.0
    # per-layer mixer of a dense stack, one entry a layer: "attention" or
    # "mamba" (a Mamba-2 mixer in the attention's place, granite-4.0-h);
    # () means attention in every layer
    layer_types: tuple[str, ...] = ()
    # softmax scale of attention; 0 -> 1/sqrt(head_dim)
    attention_multiplier: float = 0.0

    # MoE --------------------------------------------------------------------
    num_experts: int = 0
    num_experts_per_tok: int = 0
    num_shared_experts: int = 0
    moe_capacity_factor: float = 1.25
    router_aux_loss: float = 0.01
    d_ff_dense: int = 0  # deepseek: dense FFN width for 'D' pattern layers

    # SSM (mamba2 / SSD) -----------------------------------------------------
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_chunk: int = 256
    conv_width: int = 4

    # RG-LRU (griffin/recurrentgemma) -----------------------------------------
    rnn_width: int = 0  # lru width; 0 -> d_model
    rnn_conv_width: int = 4

    # multimodal ---------------------------------------------------------------
    cross_attn_every: int = 0  # vlm: insert a cross-attn layer every N layers
    num_image_tokens: int = 0  # vlm: patch embeddings per image
    num_audio_frames: int = 0  # audio: encoder frames
    encoder_layers: int = 0  # audio: encoder depth (decoder = num_layers)

    # positions: "rope" (default), "learned" (whisper) or "none" (NoPE)
    pos_embed: str = "rope"
    max_position: int = 0  # learned pos table size; 0 -> unused

    # granite scalars: the embedding is multiplied by embedding_multiplier,
    # each residual branch by residual_multiplier, and the logits divided
    # by logits_scaling (1.0 each -> none of them applies)
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0

    # training ---------------------------------------------------------------
    param_dtype: str = "bfloat16"
    cache_dtype: str = "bfloat16"  # KV-cache dtype; fp8 = quantized serving
    tie_embeddings: bool = True
    rms_norm_eps: float = 1e-6
    # remat: "none" | "layer" | "full"; microbatches: grad-accumulation steps
    remat: str = "layer"
    microbatches: int = 1
    # sharding rule set: "default" | "fsdp" (see repro/sharding.py)
    sharding_rules: str = "default"

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        if self.family == "hybrid" and self.rnn_width == 0:
            object.__setattr__(self, "rnn_width", self.d_model)
        if self.layer_types:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
            if len(self.layer_types) != self.num_layers or not set(
                self.layer_types
            ) <= {"attention", "mamba"}:
                raise ValueError(
                    "layer_types names each layer's mixer, 'attention' or "
                    f"'mamba', once a layer: {self.layer_types!r} for "
                    f"{self.num_layers} layers"
                )
            if self.family != "dense":
                raise ValueError("layer_types applies to the dense family")

    # -- derived -----------------------------------------------------------

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // max(1, self.num_kv_heads)

    @property
    def d_inner(self) -> int:
        """SSM inner width."""
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def is_subquadratic(self) -> bool:
        """Whether long_500k decode is architecturally supported.

        True for SSM / hybrid archs and for dense archs whose *native* layer
        pattern includes sliding-window local attention (gemma3).  Pure
        full-attention archs skip long_500k (DESIGN.md §Arch-applicability).
        """
        if self.family in ("ssm", "hybrid"):
            return True
        return self.sliding_window > 0 and "L" in self.layer_pattern

    @property
    def has_decoder(self) -> bool:
        return True  # all assigned archs have an AR decoder

    def param_count(self) -> int:
        """Analytic parameter count (approximate; used for 6ND rooflines)."""
        d, L, V = self.d_model, self.num_layers, self.vocab_size
        hd, H, K = self.head_dim, self.num_heads, self.num_kv_heads
        emb = V * d * (1 if self.tie_embeddings else 2)
        if self.family == "ssm":
            di, s = self.d_inner, self.ssm_state
            # in_proj (2*di + 2*groups*s + heads), conv, dt, out_proj
            per_layer = d * (2 * di + 2 * s + self.ssm_heads) + di * d + 3 * di
        else:
            attn = d * H * hd + 2 * d * K * hd + H * hd * d
            if self.family == "moe":
                E = self.num_experts + self.num_shared_experts
                ff = E * 3 * d * self.d_ff + d * self.num_experts
            else:
                ff = 3 * d * self.d_ff
            per_layer = attn + ff
            if self.family == "hybrid":
                # ~2/3 of layers swap attn for an RG-LRU block of similar size
                per_layer = attn + 3 * d * self.d_ff
        n = emb + L * per_layer
        if self.cross_attn_every:
            n += (L // self.cross_attn_every) * (2 * d * H * hd + 2 * d * K * hd)
        if self.encoder_layers:
            n += self.encoder_layers * (4 * d * d + 3 * d * self.d_ff)
        return n

    def active_param_count(self) -> int:
        """Params touched per token (MoE: routed top-k + shared only)."""
        if self.family != "moe":
            return self.param_count()
        d, L = self.d_model, self.num_layers
        E_active = self.num_experts_per_tok + self.num_shared_experts
        attn = d * self.num_heads * self.head_dim * 2 + 2 * d * self.num_kv_heads * self.head_dim
        ff_active = E_active * 3 * d * self.d_ff
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return emb + L * (attn + ff_active)


@dataclasses.dataclass(frozen=True)
class ReplayConfig:
    """Device-resident trajectory replay (repro/replay/).

    ``capacity`` and ``sample_batch_size`` are *global* counts; the Sebulba
    learner mesh shards both evenly across its cores, so each must divide by
    the learner count.  ``prioritized`` switches uniform -> PER sampling
    (Schaul et al. 2016): draws proportional to ``p^priority_exponent``,
    bias-corrected by ``(size * P(i))^-importance_exponent`` weights.

    ``importance_anneal_updates`` enables the original PER recipe's beta
    schedule: the importance exponent anneals linearly from
    ``importance_exponent`` to 1.0 (full bias correction) over that many
    learner updates, computed on device inside the fused off-policy step
    (``importance_beta``); 0 keeps beta fixed.

    Recurrent agents (R2D2): no extra replay config is needed — the
    per-sequence stored state (``Trajectory.init_carry``) is an ordinary
    trajectory leaf, so each ring slot carries it automatically and
    sampled sequences replay from the actor's recorded state; burn-in is
    the learner-side ``SebulbaConfig.burn_in`` (see ARCHITECTURE.md
    §Recurrent agents).
    """

    capacity: int = 4096  # trajectory slots across all learner shards
    sample_batch_size: int = 32  # replay trajectories drawn per update
    min_size: int = 256  # warmup: inserts only until this many slots filled
    prioritized: bool = True
    priority_exponent: float = 0.6  # PER alpha
    importance_exponent: float = 0.4  # PER beta (the t=0 value when annealed)
    importance_anneal_updates: int = 0  # 0 -> fixed beta
    priority_epsilon: float = 1e-3  # floor so no slot starves

    def __post_init__(self):
        if self.capacity < self.sample_batch_size:
            raise ValueError("replay capacity must cover one sample batch")
        if self.min_size > self.capacity:
            raise ValueError("replay min_size cannot exceed capacity")
        if self.min_size < 1:
            raise ValueError(
                "replay min_size must be >= 1: warmup must insert at least "
                "once before sampling (an empty ring samples NaN probs)"
            )
        if self.importance_anneal_updates < 0:
            raise ValueError("importance_anneal_updates must be >= 0")
        if not 0.0 <= self.importance_exponent <= 1.0:
            raise ValueError(
                "importance_exponent (PER beta) must lie in [0, 1]: it is "
                "the t=0 point of an anneal that ends at 1.0"
            )

    def importance_beta(self, update_idx):
        """PER beta at learner update ``update_idx`` (int or traced scalar).

        Linear anneal ``importance_exponent -> 1.0`` over
        ``importance_anneal_updates`` updates, clamped at 1.0 after; with
        annealing disabled this is the constant ``importance_exponent`` (so
        callers can thread it through jit unconditionally).
        """
        beta0 = self.importance_exponent
        if self.importance_anneal_updates <= 0:
            return beta0
        import jax.numpy as jnp

        frac = jnp.minimum(
            jnp.asarray(update_idx, jnp.float32)
            / self.importance_anneal_updates,
            1.0,
        )
        return beta0 + (1.0 - beta0) * frac


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"]


INPUT_SHAPES: dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}

ARCH_IDS = [
    "mamba2_1p3b",
    "gemma3_4b",
    "recurrentgemma_2b",
    "granite_moe_1b",
    "llama3_405b",
    "deepseek_moe_16b",
    "qwen2_1p5b",
    "llama32_vision_11b",
    "whisper_medium",
    "qwen3_4b",
]

# CLI aliases matching the assignment table exactly.
ALIASES = {
    "mamba2-1.3b": "mamba2_1p3b",
    "gemma3-4b": "gemma3_4b",
    "recurrentgemma-2b": "recurrentgemma_2b",
    "granite-moe-1b-a400m": "granite_moe_1b",
    "llama3-405b": "llama3_405b",
    "deepseek-moe-16b": "deepseek_moe_16b",
    "qwen2-1.5b": "qwen2_1p5b",
    "llama-3.2-vision-11b": "llama32_vision_11b",
    "whisper-medium": "whisper_medium",
    "qwen3-4b": "qwen3_4b",
}


def get_config(arch: str) -> ArchConfig:
    arch = ALIASES.get(arch, arch)
    if arch not in ARCH_IDS:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(ALIASES)}")
    mod = importlib.import_module(f"repro.configs.{arch}")
    return mod.CONFIG


def get_reduced_config(arch: str) -> ArchConfig:
    arch = ALIASES.get(arch, arch)
    mod = importlib.import_module(f"repro.configs.{arch}")
    return mod.reduced()


def all_configs() -> dict[str, ArchConfig]:
    return {a: get_config(a) for a in ARCH_IDS}
