"""Configuration dataclasses for the repro framework.

Everything is a frozen dataclass so configs are hashable and can be used as
jit static arguments. Architecture configs live in ``repro.configs.<id>``
and register themselves via :mod:`repro.config.registry`.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Literal, Optional, Tuple

LayerKind = Literal["attn", "mamba"]
FFNKind = Literal["dense", "moe"]


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts FFN configuration."""

    num_experts: int
    top_k: int
    d_ff: int                       # per-expert hidden size
    num_shared_experts: int = 0     # always-on shared experts (llama4-style)
    # "softmax": top-k of the softmax, renormalised over the k picks
    # (Mixtral); "sparsemixer": SparseMixer's inference routing
    # (Phi-3.5-MoE, models/moe.py ``sparsemixer``), not renormalised
    router: Literal["softmax", "sparsemixer"] = "softmax"
    # the router's jitter eps (``router_jitter_noise``): SparseMixer masks
    # logits farther than 2·eps, relatively, below each pick
    router_jitter: float = 0.0
    capacity_factor: float = 1.25   # train-time dispatch capacity (drops ok)
    serve_capacity_factor: float = 8.0  # prefill/serve: effectively dropless
    aux_loss_coef: float = 0.01     # load-balance loss weight


@dataclass(frozen=True)
class LongRoPE:
    """LongRoPE rotary scaling as Phi-3.5 publishes it (``rope_scaling``
    of type ``longrope``): each rotary frequency is divided by a
    per-frequency factor and cos and sin are multiplied by an mscale —
    the short factors and mscale up to
    ``original_max_position_embeddings`` positions, the long ones past
    it. The long regime re-rotates every position, cached keys too, which
    a KV cache cannot do: the program holds the short regime alone and
    refuses a capacity beyond it (``ModelConfig.check_capacity``)."""

    short_factor: Tuple[float, ...]     # one per frequency: head_dim / 2
    short_mscale: float
    original_max_position_embeddings: int


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 SSD configuration."""

    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    chunk_size: int = 256

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def num_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclass(frozen=True)
class ModelConfig:
    """Architecture config. One instance per ``--arch`` id."""

    name: str
    family: Literal["dense", "moe", "audio", "hybrid", "vlm", "ssm"]
    num_layers: int
    d_model: int
    num_heads: int          # query heads (0 for attn-free archs)
    num_kv_heads: int
    d_ff: int               # dense FFN hidden size (0 if every FFN is MoE)
    vocab_size: int
    head_dim: int = 0       # 0 -> d_model // num_heads
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    # Layer pattern --------------------------------------------------------
    # attn/mamba interleave, repeated cyclically over num_layers.
    layer_pattern: Tuple[LayerKind, ...] = ("attn",)
    # Which layers get the MoE FFN: every `moe_every` layers starting at
    # `moe_offset` (1 -> all layers are MoE).
    moe_every: int = 1
    moe_offset: int = 0
    # Sliding-window pattern: window size per pattern slot, -1 = global.
    # e.g. gemma3: (1024,)*5 + (-1,) repeated. Empty -> all global.
    window_pattern: Tuple[int, ...] = ()
    # Attention details ----------------------------------------------------
    qkv_bias: bool = False
    attn_out_bias: bool = False      # a bias on the output projection (bo)
    rope_theta: float = 10000.0
    rope_scaling: Optional[LongRoPE] = None
    mrope: bool = False              # multimodal 3D RoPE (qwen2-vl)
    logit_softcap: float = 0.0
    tie_embeddings: bool = False
    lm_head_bias: bool = False       # a bias on the logits
    # "rms": RMSNorm, a scale; "layer": LayerNorm, a scale and a bias
    norm: Literal["rms", "layer"] = "rms"
    # Encoder-decoder -----------------------------------------------------
    encoder_layers: int = 0          # >0 -> enc-dec model (seamless)
    # Modality frontend stub: inputs are precomputed embeddings of this dim
    # instead of token ids (audio/vlm encoders).
    frontend_embed_dim: int = 0
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    max_seq_len: int = 131072

    def __post_init__(self):
        if self.head_dim == 0 and self.num_heads > 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)

    # Derived -------------------------------------------------------------
    def check_capacity(self, capacity: int) -> None:
        """Raises where a KV capacity of ``capacity`` positions is one the
        model's rotary embedding cannot serve from a cache: LongRoPE past
        ``original_max_position_embeddings`` switches every position,
        cached keys included, to the long factors."""
        rs = self.rope_scaling
        if rs is not None and capacity > rs.original_max_position_embeddings:
            raise ValueError(
                f"{self.name}: capacity {capacity} > LongRoPE's "
                f"original_max_position_embeddings "
                f"{rs.original_max_position_embeddings}: past it every "
                f"position, cached keys too, turns to the long factors, "
                f"and a KV cache cannot re-rotate the keys it holds")

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def attn_free(self) -> bool:
        return all(k == "mamba" for k in self.layer_pattern)

    def layer_kind(self, i: int) -> LayerKind:
        return self.layer_pattern[i % len(self.layer_pattern)]

    def is_moe_layer(self, i: int) -> bool:
        return self.moe is not None and (i % self.moe_every) == self.moe_offset

    def window_for_layer(self, i: int) -> int:
        if not self.window_pattern:
            return -1
        return self.window_pattern[i % len(self.window_pattern)]

    @property
    def sub_quadratic(self) -> bool:
        """True if long-context (500k) decode is architecturally sensible."""
        if self.attn_free:
            return True
        n_attn = sum(1 for i in range(self.num_layers) if self.layer_kind(i) == "attn")
        if n_attn <= self.num_layers // 4:   # hybrid (jamba)
            return True
        if self.window_pattern and sum(1 for w in self.window_pattern if w > 0) * 2 >= len(self.window_pattern):
            return True                       # mostly sliding-window (gemma3)
        return False

    # Parameter counts (analytic; used by roofline + cache sizing) --------
    def _attn_params(self) -> int:
        hd = self.head_dim
        return self.d_model * hd * (self.num_heads + 2 * self.num_kv_heads) + \
            self.num_heads * hd * self.d_model

    def _dense_ffn_params(self) -> int:
        return 3 * self.d_model * self.d_ff

    def _moe_ffn_params(self, active_only: bool = False) -> int:
        m = self.moe
        e = (m.top_k + m.num_shared_experts) if active_only else (m.num_experts + m.num_shared_experts)
        return 3 * self.d_model * m.d_ff * e

    def _mamba_params(self) -> int:
        s = self.ssm
        di = s.d_inner(self.d_model)
        nh = s.num_heads(self.d_model)
        # in_proj (z,x,B,C,dt) + out_proj + conv + A,D
        return self.d_model * (2 * di + 2 * s.d_state + nh) + di * self.d_model + \
            (di + 2 * s.d_state) * s.d_conv + 2 * nh

    def param_count(self, active_only: bool = False) -> int:
        total = self.vocab_size * self.d_model * (1 if self.tie_embeddings else 2)
        if self.frontend_embed_dim:
            total += self.frontend_embed_dim * self.d_model
        layers = self.num_layers + self.encoder_layers
        for i in range(layers):
            kind = self.layer_kind(i)
            if kind == "attn":
                total += self._attn_params()
            else:
                total += self._mamba_params()
            if self.is_moe_layer(i):
                total += self._moe_ffn_params(active_only)
                total += self.moe.num_experts * self.d_model  # router
            elif self.d_ff > 0:
                total += self._dense_ffn_params()
        if self.encoder_layers:  # cross-attention in decoder
            total += self.num_layers * self._attn_params()
        return int(total)

    def expert_bytes(self, bytes_per_param: int = 2) -> int:
        """Size of a single expert's weights (the cache slot unit)."""
        if self.moe is None:
            return 0
        return 3 * self.d_model * self.moe.d_ff * bytes_per_param


@dataclass(frozen=True)
class CacheConfig:
    """Set-associative expert-cache configuration (the paper's §III-B)."""

    num_indexes: int          # N: cached layers 0..N-1 (one set per layer)
    num_ways: int             # M: expert slots per set
    policy: Literal["lru", "fifo", "random"] = "lru"

    @property
    def num_slots(self) -> int:
        return self.num_indexes * self.num_ways

    @staticmethod
    def from_memory(mem_bytes: int, expert_bytes: int, num_ways: int,
                    policy: str = "lru", max_layers: int = 10 ** 9) -> "CacheConfig":
        """Paper: S = mem/expert_size, N = floor(S/M)."""
        slots = int(mem_bytes // max(expert_bytes, 1))
        n = min(slots // num_ways, max_layers)
        if n < 1:
            raise ValueError(
                f"cache memory {mem_bytes} too small for even one {num_ways}-way set "
                f"of {expert_bytes}-byte experts")
        return CacheConfig(num_indexes=n, num_ways=num_ways, policy=policy)  # type: ignore[arg-type]


@dataclass(frozen=True)
class ShapeConfig:
    """Input-shape cell: what step gets lowered and with what geometry."""

    name: str
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"]


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


@dataclass(frozen=True)
class OptimizerConfig:
    lr: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    warmup_steps: int = 100
    total_steps: int = 10000
    grad_clip: float = 1.0
    # int8 gradient compression across the (slow) pod axis
    compress_pod_grads: bool = False


@dataclass(frozen=True)
class RuntimeConfig:
    """Distributed runtime knobs."""

    remat: bool = True
    remat_policy: str = "dots_with_no_batch_dims"
    donate_state: bool = True
    # Checkpointing
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_every: int = 100
    keep_ckpts: int = 3
    async_ckpt: bool = True
    # Fault tolerance
    heartbeat_interval_s: float = 10.0
    straggler_grace_s: float = 30.0
    elastic: bool = True


def _period(cfg: ModelConfig) -> int:
    return max(len(cfg.layer_pattern), len(cfg.window_pattern) or 1,
               cfg.moe_every)


def reduced(cfg: ModelConfig, **overrides) -> ModelConfig:
    """A tiny same-family config for CPU smoke tests."""
    period = _period(cfg)
    changes = dict(
        num_layers=min(cfg.num_layers, 2 * period),
        d_model=128,
        num_heads=4 if cfg.num_heads else 0,
        num_kv_heads=min(cfg.num_kv_heads, 2) if cfg.num_kv_heads else 0,
        head_dim=32 if cfg.num_heads else 0,
        d_ff=256 if cfg.d_ff else 0,
        vocab_size=512,
        encoder_layers=2 if cfg.encoder_layers else 0,
        frontend_embed_dim=64 if cfg.frontend_embed_dim else 0,
        max_seq_len=512,
    )
    if cfg.moe is not None:
        changes["moe"] = dataclasses.replace(
            cfg.moe, num_experts=min(cfg.moe.num_experts, 8),
            top_k=min(cfg.moe.top_k, 2), d_ff=128)
    if cfg.ssm is not None:
        changes["ssm"] = dataclasses.replace(
            cfg.ssm, d_state=32, head_dim=32, chunk_size=64)
    if cfg.window_pattern:
        changes["window_pattern"] = tuple(64 if w > 0 else -1 for w in cfg.window_pattern)
    if cfg.rope_scaling is not None:
        # one factor per frequency of the smaller head, evenly spaced
        rs, half = cfg.rope_scaling, changes["head_dim"] // 2
        step = len(rs.short_factor) // half
        changes["rope_scaling"] = dataclasses.replace(
            rs, short_factor=rs.short_factor[::step][:half])
    changes.update(overrides)
    return dataclasses.replace(cfg, **changes)


def with_layers(cfg: ModelConfig, num_layers: int) -> ModelConfig:
    """The published config cut in depth only: every width, the vocabulary
    and the expert count stay. ``num_layers`` must be a whole number of
    layer periods, so every layer kind of the stack is still served."""
    period = _period(cfg)
    if not 1 <= num_layers <= cfg.num_layers or num_layers % period:
        raise ValueError(
            f"{cfg.name}: num_layers must be a multiple of its layer period "
            f"{period} in [1, {cfg.num_layers}], got {num_layers}")
    return dataclasses.replace(cfg, num_layers=num_layers)
