"""Architecture / run configuration dataclasses.

Every assigned architecture gets one module in this package exporting
``CONFIG: ArchConfig``.  ``reduced()`` derives the CPU smoke-test variant
(2 layers, d_model<=512, <=4 experts) of the same family.
"""
from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field, replace
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Input shapes (assigned; see system brief)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Federated-learning configuration (the paper's knobs)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class FedConfig:
    """Paper knobs: rounds of E local SGD steps, scheme-based aggregation."""

    scheme: str = "C"              # "A" | "B" | "C"  (Section 4.1)
    local_epochs: int = 2          # E
    clients_per_round: int = 8     # C simulated clients in one jit'd round
    # client_parallel: clients vmapped over the data axis (paper breadth).
    # client_sequential: lax.scan over clients, each client data-parallel.
    mode: str = "client_parallel"
    # fast-reboot (Cor 4.0.2): arriving device coefficient boost.
    reboot_boost: float = 3.0
    # staircase learning rate eta_tau = eta0 / tau (Sec 5.1).
    eta0: float = 0.01


# ---------------------------------------------------------------------------
# Architecture configuration
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    vocab: int
    # --- attention ---
    n_heads: int = 0               # 0 => attention-free (pure SSM)
    n_kv_heads: int = 0
    head_dim: int = 128
    rope_theta: float = 10000.0
    pos_emb: str = "rope"          # rope | sinusoidal | none
    sliding_window: int = 0        # 0 => full attention
    attn_logit_softcap: float = 0.0
    # --- mlp ---
    d_ff: int = 0
    activation: str = "silu"       # silu | gelu | geglu | sq_relu
    gated_mlp: bool = True         # gated (SwiGLU/GeGLU) vs plain 2-matmul
    # --- norm / structure ---
    norm: str = "rmsnorm"          # rmsnorm | layernorm
    norm_eps: float = 1e-5
    use_bias: bool = False
    parallel_residual: bool = False  # cohere-style parallel attn+ffn
    embed_scale: bool = False        # gemma: scale embeddings by sqrt(d)
    tie_embeddings: bool = True
    # --- MLA (deepseek) ---
    use_mla: bool = False
    q_lora_rank: int = 0           # 0 => direct q projection
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    # --- MoE ---
    n_experts: int = 0             # routed experts; 0 => dense FFN
    n_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    first_k_dense: int = 0         # leading layers with dense FFN
    router_score: str = "softmax"  # softmax | sigmoid (v3)
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.001
    moe_shared_d_ff: int = 0       # shared-expert width; 0 => moe_d_ff x
    #                                n_shared_experts
    moe_routed_scale: float = 1.0  # routed gates scaled after the top-k
    #                                renormalisation (Nemotron-H: 2.5)
    # the experts this device holds, [moe_expert_offset, + held): 0 => all
    # of them, capacity-bounded dispatch (moe._moe_ffn_dense); n => the
    # router still scores every expert and the dropless grouped-matmul
    # dispatch computes the held experts' part of the result
    moe_experts_held: int = 0
    moe_expert_offset: int = 0
    # the grouped matmul as the Pallas kernel (megablox gmm); set for a
    # round placed on one device (fed.task.LMTask.placed)
    moe_kernel: bool = False
    # --- SSM (mamba2 SSD) ---
    ssm_d_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_n_groups: int = 1
    ssm_d_conv: int = 4
    ssm_chunk: int = 256
    ssm_d_inner: int = 0           # 0 => ssm_expand x d_model
    ssm_norm_groups: int = 1       # groups of the gated RMSNorm
    # the SSD as the fused Pallas scan (kernels/ssd_chunk) where its shapes
    # fit; set for a round placed on one device (fed.task.LMTask.placed)
    ssm_kernel: bool = False
    # --- single-mixer layer pattern (Nemotron-H) ---
    # one character a layer: M Mamba-2 mixer, E routed-expert FFN, *
    # attention (params.PATTERN_KINDS); "" => the family's stacks
    layer_pattern: str = ""
    # --- multimodal stub frontends ---
    n_patches: int = 0             # vlm: patch embeddings prepended
    n_codebooks: int = 0           # audio: EnCodec codebooks (summed embed)
    # --- extras ---
    mtp_depth: int = 0             # deepseek-v3 multi-token prediction
    dtype: str = "bfloat16"
    # --- federated / distribution ---
    fed: FedConfig = field(default_factory=FedConfig)
    remat: bool = True
    # beyond-paper §Perf optimizations (flags so the paper-faithful
    # baseline stays reproducible; see EXPERIMENTS.md §Perf):
    seq_parallel: bool = False     # Megatron-style sequence sharding of the
    #                                residual stream over the model axis.
    #                                REFUTED on this GSPMD version: the
    #                                partitioner reshards the remat carries
    #                                with full-rematerialization copies and
    #                                collectives blow up 7x (EXPERIMENTS.md
    #                                §Perf iteration 2) — off by default.
    remat_attention: bool = True   # nested remat of the q-chunk scan (do
    #                                not save per-chunk softmax probs)
    expand_gqa: bool = True        # train/prefill: repeat kv heads to H so
    #                                every attention tensor shards on one
    #                                head axis — kills the per-chunk score
    #                                all-gathers GSPMD inserts when
    #                                n_kv_heads < the model axis (§Perf it.4)
    attn_impl: str = "chunked"     # "chunked" (jnp, differentiable) or
    #                                "flash" (Pallas kernel, forward-only:
    #                                serving prefill on real TPUs; runs in
    #                                interpret mode on CPU)
    source: str = ""               # citation

    # -- derived ----------------------------------------------------------
    @property
    def attn_free(self) -> bool:
        return self.n_heads == 0

    @property
    def vocab_padded(self) -> int:
        """Vocab padded to a multiple of 256 so embedding/head shard over
        the 16-way model axis; padded logits are masked in the loss."""
        return -(-self.vocab // 256) * 256

    @property
    def d_inner(self) -> int:      # ssm inner width
        return self.ssm_d_inner or self.ssm_expand * self.d_model

    @property
    def ssm_n_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim if self.ssm_d_state else 0

    @property
    def moe_layers(self) -> int:
        return (self.n_layers - self.first_k_dense) if self.n_experts else 0

    @property
    def dense_layers(self) -> int:
        return self.n_layers - self.moe_layers

    def supports_shape(self, shape_name: str) -> bool:
        """long_500k only for sub-quadratic archs (see DESIGN.md)."""
        if shape_name != "long_500k":
            return True
        return self.family in ("ssm", "hybrid") or self.sliding_window > 0

    def reduced(self) -> "ArchConfig":
        """Smoke-test variant: 2 layers, d_model<=512, <=4 experts."""
        d = min(self.d_model, 256)
        hd = 32 if self.n_heads else self.head_dim
        n_h = min(self.n_heads, 4) if self.n_heads else 0
        n_kv = min(self.n_kv_heads, max(1, n_h // 2)) if self.n_kv_heads else 0
        changes = dict(
            n_layers=2,
            d_model=d,
            vocab=min(self.vocab, 512),
            n_heads=n_h,
            n_kv_heads=n_kv,
            head_dim=hd,
            d_ff=min(self.d_ff, 4 * d) if self.d_ff else 0,
            sliding_window=min(self.sliding_window, 64) if self.sliding_window else 0,
            dtype="float32",
            remat=False,
            fed=replace(self.fed, clients_per_round=4, local_epochs=2),
        )
        if self.use_mla:
            changes.update(
                q_lora_rank=64 if self.q_lora_rank else 0,
                kv_lora_rank=64, qk_nope_dim=32, qk_rope_dim=16, v_head_dim=32,
                head_dim=48,  # qk_nope + qk_rope
            )
        if self.n_experts:
            changes.update(
                n_experts=4,
                top_k=min(self.top_k, 2),
                n_shared_experts=min(self.n_shared_experts, 1),
                moe_d_ff=2 * d,
                first_k_dense=min(self.first_k_dense, 1),
            )
        if self.ssm_d_state:
            changes.update(ssm_d_state=16, ssm_head_dim=32, ssm_chunk=32)
        if self.n_patches:
            changes.update(n_patches=8)
        if self.mtp_depth:
            changes.update(mtp_depth=1)
        return replace(self, **changes)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

ARCH_IDS = [
    "llava-next-34b",
    "gemma-7b",
    "hymba-1.5b",
    "starcoder2-3b",
    "mamba2-130m",
    "command-r-plus-104b",
    "musicgen-medium",
    "deepseek-v2-lite-16b",
    "nemotron-4-15b",
    "deepseek-v3-671b",
]

PAPER_IDS = ["mnist_mlp", "emnist_cnn", "synthetic_lr"]


def get_config(arch_id: str) -> ArchConfig:
    mod = importlib.import_module(
        f"repro.configs.{arch_id.replace('-', '_').replace('.', '_')}"
    )
    return mod.CONFIG


def all_configs() -> dict:
    return {a: get_config(a) for a in ARCH_IDS}
