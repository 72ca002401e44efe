"""Config dataclasses for the SLW framework.

Everything is a frozen dataclass so configs are hashable and safe to use as
compile-cache keys (the SLW curriculum compiles one step function per sequence
length bucket).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class ModelConfig:
    """Architecture definition. One instance per assigned architecture."""

    name: str
    family: str  # dense | moe | rwkv | hybrid
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // n_heads
    # attention options
    qk_norm: bool = False
    qkv_bias: bool = False
    # attention kind: "gqa" (q/k/v projections per head, n_kv_heads shared)
    # | "mla" (DeepSeek-V2/V3 latent attention, training only: q_proj;
    # keys and values from a kv_lora_rank-wide latent through an RMSNorm
    # and kv_b_proj; RoPE on qk_rope_head_dim dims of q and of one k head
    # shared by all heads; q/k heads qk_nope_head_dim + qk_rope_head_dim
    # wide, v heads v_head_dim wide)
    attn_kind: str = "gqa"
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # train/prefill attention backend:
    #   blockwise        – jnp online-softmax scan (the XLA oracle; default)
    #   flash            – Pallas flash-attention kernel (fwd + custom-VJP
    #                      bwd) on TPU; silently falls back to blockwise on
    #                      other backends so presets stay lowerable anywhere
    #   flash_interpret  – force the kernel in interpret mode (CPU
    #                      validation / tests; slow)
    attn_backend: str = "blockwise"
    # decode (serving) attention backend, mirroring ssm/rwkv backends:
    #   reference        – jnp masked softmax over the full cache (default;
    #                      materializes the (B, KV, G, 1, S_max) score row)
    #   kernel           – Pallas split-KV flash-decode kernel on TPU;
    #                      silently falls back to reference off-TPU
    #   kernel_interpret – force the kernel in interpret mode (CPU tests)
    decode_backend: str = "reference"
    rope_theta: float = 10000.0
    pos_emb: str = "rope"  # rope | learned | none
    # block options
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    mlp: str = "swiglu"  # swiglu | gelu
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # MoE
    # n_experts: the experts the router spans (its published width)
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    # global: paper-era single global capacity buffer (pathological under
    # SPMD — see EXPERIMENTS.md §Perf); row_local: per-batch-row ranking,
    # shard-local dispatch arithmetic (production default); dropless: the
    # expert-share layer (models/moe.py: no capacity, this chip computes
    # only the experts it holds, through the moe_gmm kernels)
    moe_dispatch: str = "row_local"
    # the experts held here (dropless only): ids expert_offset ..
    # expert_offset + n_held_experts - 1 of the n_experts; 0 holds all
    n_held_experts: int = 0
    expert_offset: int = 0
    # the dropless dispatch routes as DeepSeek-V3's noaux_tc (models/moe.py
    # route): top-k of sigmoid scores plus a per-expert correction bias,
    # weights normalised over the k, then scaled by routed_scaling_factor
    routed_scaling_factor: float = 1.0
    # leading dense layers (DeepSeek's first_k_dense_replace) and their
    # MLP width; the n_layers - n_dense_layers after them are MoE
    n_dense_layers: int = 0
    dense_d_ff: int = 0
    router_aux_coef: float = 0.01
    router_z_coef: float = 1e-3
    # SSM (Mamba-2 / SSD)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 128
    conv_kernel: int = 4
    # SSD train/prefill backend (mirrors attn_backend):
    #   reference        – pure-jnp chunked scan (the oracle; default)
    #   kernel           – Pallas SSD kernel (fwd + custom-VJP bwd) on TPU;
    #                      silently falls back to reference off-TPU so
    #                      presets stay lowerable anywhere
    #   kernel_interpret – force the kernel in interpret mode (CPU tests)
    ssm_backend: str = "reference"
    # hybrid (zamba2): one *shared* attention+MLP block applied every attn_every
    # SSM layers (shared weights, per-application KV cache)
    attn_every: int = 0
    # rwkv6
    rwkv_head_dim: int = 64
    rwkv_lora_rank: int = 64
    rwkv_chunk: int = 64
    # WKV train/prefill backend: reference | kernel | kernel_interpret
    # (same semantics as ssm_backend)
    rwkv_backend: str = "reference"
    # modality frontend stubs (backbone-only per the assignment):
    #   none           – token LM
    #   audio_frames   – input_specs provide precomputed frame embeddings (B,S,D)
    #   vision_patches – tokens plus a fixed image-patch embedding prefix (B,P,D)
    frontend: str = "none"
    prefix_tokens: int = 0
    max_seq_len: int = 532480  # generous default; shapes clamp per cell
    # numerics
    logits_softcap: float = 0.0

    def __post_init__(self):
        if self.moe_dispatch != "dropless" and (
                self.n_held_experts or self.expert_offset
                or self.routed_scaling_factor != 1.0):
            raise ValueError(
                f"{self.name}: n_held_experts, expert_offset and "
                "routed_scaling_factor are read only by the dropless "
                f"dispatch, not by {self.moe_dispatch!r}")

    @property
    def held_experts(self) -> int:
        return self.n_held_experts or self.n_experts

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    @property
    def is_attention_free(self) -> bool:
        return self.family == "rwkv"

    @property
    def sub_quadratic(self) -> bool:
        """Whether the arch supports 500K-context decode (SSM/hybrid/linear)."""
        return self.family in ("rwkv", "hybrid")

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class ShapeConfig:
    """One (input-shape) cell. kind selects which step function is lowered."""

    name: str  # train_4k | prefill_32k | decode_32k | long_500k
    kind: str  # train | prefill | decode
    seq_len: int
    global_batch: int


# The assigned LM shape set (identical across the 10 architectures).
LM_SHAPES: Tuple[ShapeConfig, ...] = (
    ShapeConfig("train_4k", "train", 4096, 256),
    ShapeConfig("prefill_32k", "prefill", 32768, 32),
    ShapeConfig("decode_32k", "decode", 32768, 128),
    ShapeConfig("long_500k", "decode", 524288, 1),
)


@dataclass(frozen=True)
class SLWConfig:
    """Sequence Length Warmup — the paper's contribution (Section 4): the
    parameters of the ``seqlen`` regulator, which runs only when
    ``TrainConfig.regulators`` lists it."""

    # pacing: linear (paper default) | root | two_stage (Shortformer baseline)
    #         | variance_gated (beyond-paper)
    pacing: str = "linear"
    start_seq_len: int = 8  # seqlen_s
    end_seq_len: int = 0  # seqlen_e; 0 -> full shape seq_len
    duration_steps: int = 0  # T;  0 -> 2x LR warmup steps
    root_degree: float = 2.0
    # hardware rounding. Paper: 8 (V100 tensor cores). TPU: 128 (lane dim).
    round_multiple: int = 8
    # bucketing bounds the number of XLA recompiles (TPU adaptation; the paper's
    # eager implementation pays no recompile cost).
    max_buckets: int = 32
    # truncate: paper-faithful (drops tail tokens).  repack: beyond-paper —
    # reshape (B, S) -> (B*S//s_t, s_t) so token throughput stays constant.
    mode: str = "truncate"
    # two_stage (Shortformer) parameters
    two_stage_short_len: int = 128
    two_stage_switch_step: int = 0  # 0 -> duration_steps
    # variance_gated parameters: advance only while var_max < gate * trailing
    variance_gate: float = 2.0


@dataclass(frozen=True)
class BatchWarmupConfig:
    """GPT-3 style batch-size warmup (baseline the paper compares against):
    the parameters of the ``batch_warmup`` regulator, which runs only when
    ``TrainConfig.regulators`` lists it."""

    start_batch: int = 16
    warmup_tokens: int = 4_000_000_000


@dataclass(frozen=True)
class RegulatorSpec:
    """One entry in ``TrainConfig.regulators`` — the composable control plane.

    ``kind`` selects the regulator; the remaining fields parameterize
    ``var_lr_throttle``.  The other kinds read their parameters from their
    own records (``seqlen`` <- SLWConfig, ``batch_warmup`` <-
    BatchWarmupConfig, ``lr`` <- OptimizerConfig, ``critical_batch`` <-
    GNSConfig), so for them one spec entry is the switch that turns them on.

    Kinds:
      seqlen            — SLW curriculum (pacing + variance gate), SLWConfig
      batch_warmup      — GPT-3-style linear batch warmup, BatchWarmupConfig
      lr                — token-/step-wise LR schedule, OptimizerConfig
      var_lr_throttle   — multiplicative LR/grad-clip backoff while the Adam
                          variance max spikes above its trailing mean
                          (Kosson et al.-style warmup-free LR control)
      critical_batch    — B_noise-measured batch warmup (repro.gns): grow
                          the batch while the measured gradient noise scale
                          exceeds ``TrainConfig.gns.headroom`` x the current
                          batch, hold otherwise; reads its parameters
                          from ``TrainConfig.gns``.
    """

    kind: str
    # var_lr_throttle
    gate: float = 2.0  # throttle when var_max > gate * trailing mean
    floor: float = 0.1  # never scale LR below floor * scheduled
    backoff: float = 0.5  # scale *= backoff on a spike
    recovery: float = 1.2  # scale *= recovery per calm step (capped at 1)


@dataclass(frozen=True)
class GNSConfig:
    """Gradient-noise-scale measurement + pre-spike forecasting (repro.gns).

    ``enabled`` turns on the in-step estimator: the batch is viewed as
    ``shards`` emulated data-parallel replicas inside the jitted train step
    and the per-shard/full-batch gradient-norm pair feeds the unbiased
    ``B_noise = tr(Sigma)/|G|^2`` estimate (McCandlish et al.).  The
    precursor fields parameterize the Molybog et al.-style time-lagged
    autocorrelation of per-leaf gradient *directions* (random-sign sketches
    in a short ring) that forecasts a loss spike before the detector's
    var/norm excursion fires.  The critical-batch fields drive the
    ``critical_batch`` regulator kind (B_noise-measured batch warmup).
    """

    enabled: bool = False
    # emulated per-replica shard count for the small-batch estimator (the
    # realized count is the largest divisor of the step's batch <= this)
    shards: int = 4
    # EMA horizon (steps) for the |G|^2 / tr(Sigma) numerator+denominator
    ema_window: int = 32
    # observations before B_noise is considered warmed up
    warmup_obs: int = 8
    # --- critical_batch regulator -------------------------------------
    min_batch: int = 0        # 0 -> full_batch // 8
    headroom: float = 2.0     # grow batch while B_noise > headroom * batch
    growth: float = 1.5       # multiplicative batch growth per trigger
    # --- pre-spike precursor ------------------------------------------
    precursor_window: int = 12   # sketch ring length (0 disables sketches)
    precursor_dim: int = 16      # random-projection sketch dimension
    precursor_lags: int = 3      # autocorrelation lags averaged per leaf
    precursor_gate: float = 0.8  # absolute per-leaf correlation gate
                                 # (ambient plateau correlation measures
                                 # ~0.75 peak on the bench corpus; real
                                 # excursions reach 0.9+)
    precursor_rise: float = 0.25  # ... and score - trailing > rise.
                                  # Additive on purpose: scores are
                                  # bounded cosines, so a multiplicative
                                  # baseline gate would be unreachable
                                  # for naturally-correlated leaves
    precursor_grace: int = 6     # score observations before firing is legal
    precursor_cooldown_steps: int = 8   # LR cool-down window on an event
    precursor_cooldown_factor: float = 0.5  # LR multiplier during cool-down
    sketch_seed: int = 17        # fixed PRNG seed for the per-leaf signs


@dataclass(frozen=True)
class OptimizerConfig:
    lr: float = 6e-4
    min_lr: float = 1e-5
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.01
    grad_clip: float = 1.0
    # --- composable optimizer chain (repro.optim.transforms) -------------
    # core preconditioner: adamw (legacy-exact default) | sm3 | shampoo
    # (block-diagonal Kronecker preconditioning grafted onto the Adam
    # update magnitude)
    optimizer: str = "adamw"
    # weight-decay mask: "all" decays every leaf (legacy-exact default);
    # "std" exempts biases/norm gains (1-D-per-layer leaves)
    decay_mask: str = "all"
    # adaptive gradient clipping (Brock et al.): per-leaf grad/param-norm
    # ratio clip, composing after the global clip (grad_clip=0 replaces it;
    # the global-norm telemetry is still measured).  0 disables.
    agc_clip: float = 0.0
    agc_eps: float = 1e-3
    # per-leaf LR scaling: ((label_substring, factor), ...) — factors
    # multiply the update of every param leaf whose label matches
    lr_scales: Tuple[Tuple[str, float], ...] = ()
    # telemetry: "scalar" (legacy globals only — one reduction pass) |
    # "per_leaf" (adds fixed-size named vectors: var_max / grad-norm /
    # update-norm / param-norm per labeled leaf, for per-layer regulators)
    telemetry_level: str = "scalar"
    # sm3 heavy-ball momentum on the preconditioned update (0 disables)
    sm3_momentum: float = 0.9
    # shampoo: max block side preconditioned (bigger leaves fall back to
    # Adam), eigh refresh cadence, and the statistics/eigenvalue ridge
    shampoo_block_size: int = 128
    shampoo_interval: int = 10
    shampoo_eps: float = 1e-6
    # token_wise cosine decay (paper Appendix A.2) or step_wise (baseline GPT-2)
    schedule: str = "token_cosine"  # token_cosine | step_cosine | constant
    warmup_steps: int = 0
    warmup_tokens: int = 0
    total_steps: int = 0
    total_tokens: int = 0
    # 1-bit-Adam style compressed gradient all-reduce (beyond-paper extension)
    grad_compression: bool = False
    compression_warmup_steps: int = 0


@dataclass(frozen=True)
class TrainConfig:
    model: ModelConfig
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    slw: SLWConfig = field(default_factory=SLWConfig)
    batch_warmup: BatchWarmupConfig = field(default_factory=BatchWarmupConfig)
    # Composable control plane (core.regulators), in stack order.  The
    # tuple is the one switch: SLW runs iff it lists "seqlen", batch warmup
    # iff it lists "batch_warmup" (``slw`` and ``batch_warmup`` above only
    # parameterize them).  The paper's *joint* recipe is
    # (seqlen, batch_warmup, lr).
    regulators: Tuple[RegulatorSpec, ...] = (RegulatorSpec("seqlen"),
                                             RegulatorSpec("lr"))
    # gradient-noise-scale measurement + pre-spike forecasting (repro.gns);
    # disabled by default — the train step's trace is untouched unless on
    gns: GNSConfig = field(default_factory=GNSConfig)
    seq_len: int = 1024
    global_batch: int = 512
    seed: int = 1234
    # remat: none | full | dots  (activation checkpointing policy for the layer scan)
    remat: str = "full"
    # sharding rule set: "baseline" (paper-era DP+TP) | "fsdp" (optimized)
    sharding_rules: str = "fsdp"
    # cast params to bf16 *before* they are consumed (so FSDP all-gathers move
    # bf16 bytes, not fp32) — perf lever, see EXPERIMENTS.md §Perf
    cast_params_before_use: bool = True
    eval_interval: int = 100
    log_interval: int = 10
    checkpoint_interval: int = 500
    checkpoint_dir: str = ""
    keep_checkpoints: int = 3


@dataclass(frozen=True)
class ArchSpec:
    """An assigned architecture: model + its shape cells + dry-run notes."""

    model: ModelConfig
    shapes: Tuple[ShapeConfig, ...] = LM_SHAPES
    source: str = ""
    notes: str = ""

    def shape(self, name: str) -> ShapeConfig:
        for s in self.shapes:
            if s.name == name:
                return s
        raise KeyError(f"{self.model.name} has no shape {name!r}")

    def runnable_shapes(self) -> Tuple[ShapeConfig, ...]:
        """Cells actually lowered. long_500k only for sub-quadratic archs."""
        out = []
        for s in self.shapes:
            if s.name == "long_500k" and not self.model.sub_quadratic:
                continue  # documented skip: full-attention arch at 500K context
            out.append(s)
        return tuple(out)
