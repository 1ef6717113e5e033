"""Config system: one `ModelConfig` describes every supported architecture.

Architectures are decomposed into *segments*: homogeneous runs of layers
that can be `lax.scan`-ned together (keeps HLO size O(1) in depth), plus
optional unrolled special layers (e.g. deepseek's dense first layer,
zamba2's shared attention block between mamba segments).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple


# ---------------------------------------------------------------------------
# Input shapes assigned to this paper (global shapes; sharded by the mesh).
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int          # routed experts
    top_k: int
    d_ff_expert: int
    n_shared: int = 0       # shared (always-on) experts
    capacity_factor: float = 1.25
    router_z_loss: float = 1e-3
    aux_loss: float = 1e-2
    # per-cohort capacity sizing: size per-expert capacity as if this many
    # experts were active (None = n_experts). A cohort whose widest client
    # keeps E' < E experts sets this to E' so the dispatch buffer — and the
    # Pallas gather-reduce row traffic — scales with the *active* expert
    # count while staying in parent coordinates (static: part of the
    # compiled program, like capacity_factor).
    capacity_experts: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int
    expand: int = 2
    head_dim: int = 64
    d_conv: int = 4
    n_groups: int = 1
    chunk: int = 256        # SSD chunk length for the chunked train scan
    # CFL elasticity: a submodel keeps a prefix of SSD heads, so its
    # d_inner is no longer expand*d_model — extract_transformer pins it
    d_inner_override: Optional[int] = None

    def d_inner(self, d_model: int) -> int:
        if self.d_inner_override is not None:
            return self.d_inner_override
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class Segment:
    """A run of layers lowered as one `lax.scan`.

    kind:
      "attn"     — transformer blocks (attention + MLP/MoE)
      "ssm"      — mamba2 blocks
      "attn_pair"— pair-scan of (local, global) attention blocks (gemma2)
    """
    kind: str
    n_layers: int
    # per-segment overrides
    sliding_window: Optional[int] = None       # window for "attn" segments
    use_moe: bool = False
    # for "attn_pair": local window for even member; odd member is global
    pair_local_window: Optional[int] = None
    # hybrid: append the shared attention block (single shared params) after
    # this segment
    shared_attn_after: bool = False


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                       # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    segments: Tuple[Segment, ...]

    # attention details
    attn_type: str = "gqa"            # gqa | mla | none
    qk_norm: bool = False
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    rope_theta: float = 10_000.0
    sliding_window: Optional[int] = None
    causal: bool = True

    # norms / mlp / embedding
    norm_type: str = "rmsnorm"        # rmsnorm | layernorm
    norm_eps: float = 1e-6
    act: str = "silu"                 # silu | gelu
    mlp_gated: bool = True            # GLU-style MLP (SwiGLU/GeGLU)
    post_norms: bool = False          # gemma2 sandwich norms
    embed_scale: bool = False         # gemma: scale embeddings by sqrt(d)
    tie_embeddings: bool = True

    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    mla: Optional[MLAConfig] = None

    # hybrid (zamba2): shared transformer block interleaved between segments
    shared_attn_d_ff: int = 0

    # modality frontend stub: None | "audio" | "vision"
    frontend: Optional[str] = None
    frontend_tokens: int = 0          # patch/frame tokens prepended (vlm/audio)
    encoder_only: bool = False        # hubert: bidirectional, no decode

    # which input shapes this arch supports (None => all); decode shapes are
    # dropped automatically for encoder_only archs.
    supported_shapes: Optional[Tuple[str, ...]] = None

    # CFL elasticity: allowed width fractions + depth granularity
    elastic_widths: Tuple[float, ...] = (0.25, 0.5, 0.75, 1.0)

    # ------------------------------------------------------------------
    def supports(self, shape_name: str) -> bool:
        shape = INPUT_SHAPES[shape_name]
        if self.encoder_only and shape.kind == "decode":
            return False
        if self.supported_shapes is not None:
            return shape_name in self.supported_shapes
        return True

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // max(self.n_kv_heads, 1)

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to 256 lanes (TP-shardable; standard practice —
        padded rows are unused classes)."""
        return -(-self.vocab_size // 256) * 256

    def param_count(self) -> int:
        """Analytic parameter count (used by latency LUT + roofline)."""
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        total = v * d  # embedding
        if not self.tie_embeddings:
            total += v * d
        for seg in self.segments:
            per_layer = 0
            if seg.kind in ("attn", "attn_pair"):
                per_layer += self._attn_params() + self._mlp_params(seg)
                per_layer += 2 * d  # norms
                if self.post_norms:
                    per_layer += 2 * d
            elif seg.kind == "ssm":
                per_layer += self._ssm_params() + d
            n = seg.n_layers * (2 if seg.kind == "attn_pair" else 1)
            total += per_layer * n
            if seg.shared_attn_after:
                # shared params counted once (they are shared!)
                pass
        if self.shared_attn_d_ff:
            total += self._attn_params() + 2 * d * self.shared_attn_d_ff + 2 * d
        total += d  # final norm
        return total

    def active_param_count(self) -> int:
        """Params active per token (MoE: only top_k + shared experts)."""
        if self.moe is None:
            return self.param_count()
        d = self.d_model
        m = self.moe
        per_expert = 3 * d * m.d_ff_expert if self.mlp_gated else 2 * d * m.d_ff_expert
        inactive = (m.n_experts - m.top_k) * per_expert
        n_moe_layers = sum(
            s.n_layers * (2 if s.kind == "attn_pair" else 1)
            for s in self.segments if s.use_moe)
        return self.param_count() - inactive * n_moe_layers

    def _attn_params(self) -> int:
        d = self.d_model
        if self.attn_type == "mla":
            assert self.mla is not None
            c = self.mla
            qk_dim = c.qk_nope_dim + c.qk_rope_dim
            p = d * self.n_heads * qk_dim                      # q proj
            p += d * (c.kv_lora_rank + c.qk_rope_dim)          # kv down
            p += c.kv_lora_rank * self.n_heads * (c.qk_nope_dim + c.v_head_dim)
            p += self.n_heads * c.v_head_dim * d               # o proj
            return p
        if self.attn_type == "none":
            return 0
        h, kv, hd = self.n_heads, self.n_kv_heads, self.head_dim
        return d * h * hd + 2 * d * kv * hd + h * hd * d

    def _mlp_params(self, seg: Segment) -> int:
        d = self.d_model
        if seg.use_moe and self.moe is not None:
            m = self.moe
            per = (3 if self.mlp_gated else 2) * d * m.d_ff_expert
            return (m.n_experts + m.n_shared) * per + d * m.n_experts
        return (3 if self.mlp_gated else 2) * d * self.d_ff

    def _ssm_params(self) -> int:
        assert self.ssm is not None
        d, s = self.d_model, self.ssm
        di = s.d_inner(d)
        nh = s.n_heads(d)
        ng = s.n_groups
        # in_proj -> [z, x, B, C, dt]
        proj_out = 2 * di + 2 * ng * s.d_state + nh
        p = d * proj_out
        p += s.d_conv * (di + 2 * ng * s.d_state)   # conv over x,B,C
        p += nh * 3                                  # A_log, D, dt_bias
        p += di                                      # gated rmsnorm
        p += di * d                                  # out_proj
        return p


def uniform_segments(n_layers: int, *, kind: str = "attn",
                     use_moe: bool = False,
                     sliding_window: Optional[int] = None) -> Tuple[Segment, ...]:
    return (Segment(kind=kind, n_layers=n_layers, use_moe=use_moe,
                    sliding_window=sliding_window),)


def reduced(cfg: ModelConfig, *, n_layers: int = 2, d_model: int = 256,
            seq_len: int = 64) -> ModelConfig:
    """Smoke-test variant: same family/feature set, tiny dims.

    2 layers, d_model<=512, <=4 experts per the assignment.
    """
    del seq_len
    d_model = min(d_model, 512)
    head_dim = 32
    n_heads = max(2, d_model // (head_dim * 2))
    n_kv = max(1, min(cfg.n_kv_heads, n_heads))
    # preserve the MHA-vs-GQA character of the parent
    if cfg.n_kv_heads == cfg.n_heads:
        n_kv = n_heads
    else:
        n_kv = max(1, n_heads // max(1, cfg.q_per_kv))
    d_ff = d_model * 2
    moe = None
    if cfg.moe is not None:
        moe = dataclasses.replace(cfg.moe, n_experts=4, top_k=2,
                                  d_ff_expert=d_model // 2,
                                  n_shared=min(cfg.moe.n_shared, 1))
    ssm = None
    if cfg.ssm is not None:
        ssm = dataclasses.replace(cfg.ssm, d_state=16, head_dim=32, chunk=16)
    mla = None
    if cfg.mla is not None:
        mla = MLAConfig(kv_lora_rank=64, qk_nope_dim=32, qk_rope_dim=16,
                        v_head_dim=32)

    # rebuild segments with the same structural flavour at depth n_layers
    segs = []
    kinds = {s.kind for s in cfg.segments}
    if "attn_pair" in kinds:
        segs = [Segment(kind="attn_pair", n_layers=max(1, n_layers // 2),
                        pair_local_window=64)]
    elif "ssm" in kinds and any(s.shared_attn_after for s in cfg.segments):
        segs = [Segment(kind="ssm", n_layers=1, shared_attn_after=True),
                Segment(kind="ssm", n_layers=max(1, n_layers - 1))]
    elif "ssm" in kinds:
        segs = [Segment(kind="ssm", n_layers=n_layers)]
    else:
        use_moe = any(s.use_moe for s in cfg.segments)
        sw = cfg.sliding_window and min(cfg.sliding_window, 32)
        segs = [Segment(kind="attn", n_layers=n_layers, use_moe=use_moe,
                        sliding_window=sw)]

    return dataclasses.replace(
        cfg,
        name=cfg.name + "-smoke",
        n_layers=n_layers,
        d_model=d_model,
        n_heads=n_heads,
        n_kv_heads=n_kv,
        head_dim=head_dim,
        d_ff=d_ff,
        vocab_size=min(cfg.vocab_size, 512),
        segments=tuple(segs),
        moe=moe,
        ssm=ssm,
        mla=mla,
        sliding_window=cfg.sliding_window and min(cfg.sliding_window, 32),
        shared_attn_d_ff=(d_model * 2 if cfg.shared_attn_d_ff else 0),
        frontend_tokens=min(cfg.frontend_tokens, 16),
    )


def depth_cut(cfg: ModelConfig, n_layers: int) -> ModelConfig:
    """Published widths, depth cut to the first ``n_layers`` layers.

    Segments keep their order and kind; the last one kept is shortened
    (an ``attn_pair`` segment counts two layers per scan step and keeps at
    least one pair). Every width — d_model, heads, head_dim, d_ff, vocab,
    experts, SSM state — is the config's own."""
    segs, left = [], int(n_layers)
    for seg in cfg.segments:
        if left <= 0:
            break
        per = 2 if seg.kind == "attn_pair" else 1
        take = min(seg.n_layers, max(1, left // per))
        segs.append(dataclasses.replace(seg, n_layers=take))
        left -= take * per
    depth = sum(s.n_layers * (2 if s.kind == "attn_pair" else 1)
                for s in segs)
    return dataclasses.replace(cfg, name=f"{cfg.name}-{depth}L",
                               n_layers=depth, segments=tuple(segs))


def flops_per_token(cfg: ModelConfig, seq_len: int) -> float:
    """Rough fwd FLOPs/token: 2*active_params + attention term."""
    base = 2.0 * cfg.active_param_count()
    attn = 0.0
    for seg in cfg.segments:
        n = seg.n_layers * (2 if seg.kind == "attn_pair" else 1)
        if seg.kind == "ssm":
            s = cfg.ssm
            attn += n * 2.0 * s.d_inner(cfg.d_model) * s.d_state * 2
            continue
        window = seg.sliding_window or cfg.sliding_window or seq_len
        eff = min(window, seq_len)
        attn += n * 2.0 * 2 * cfg.n_heads * cfg.head_dim * eff / 2
    return base + attn


def config_fingerprint(cfg) -> str:
    """Stable identity string for a family config — the class name plus
    every dataclass field (works for ``ModelConfig`` here and
    ``CNNConfig`` in `src/repro/configs/paper_cnn.py` alike). Fleet
    checkpoints store it (`src/repro/checkpoint/fleet.py`) so a snapshot
    refuses to restore into a different architecture up front instead of
    failing deep inside a parameter-tree merge."""
    if dataclasses.is_dataclass(cfg):
        fields = ",".join(f"{f.name}={getattr(cfg, f.name)!r}"
                          for f in dataclasses.fields(cfg))
        return f"{type(cfg).__name__}({fields})"
    return repr(cfg)


MESH_AXES_SINGLE = ("data", "model")
MESH_AXES_MULTI = ("pod", "data", "model")
