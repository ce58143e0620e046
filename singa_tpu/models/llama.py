"""Llama-3 family — the flagship stretch workload (BASELINE.json:11:
"stretch singa.autograd + Graph scheduler to a modern LLM").

Architecture: pre-RMSNorm decoder blocks, rotary position embeddings,
grouped-query attention (n_kv_heads < n_heads), SwiGLU FFN, untied LM
head (a head tied to the embedding is `models/zaya.py`'s) — all
expressed through singa_tpu.autograd operators so the whole training
step (fwd + bwd + optim + collectives) compiles into one XLA module.

Scaling design (task directive: multi-chip via jax.sharding.Mesh):
SHARD_RULES gives 2-D parallelism out of the box —
  * 'data' axis: batch sharding (DP) via DistOpt/graph executor;
  * 'model' axis: Megatron TP — qkv/gate/up column-parallel, o/down
    row-parallel, embeddings + head vocab/hidden sharded;
  * 'seq' axis: sequence sharding of activations for long context
    (ring attention lives in singa_tpu.ops.ring_attention).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax

from .. import autograd, layer, model
from ..ops import kv_cache as kv_ops
from ..ops import rope as rope_ops
from ..ops.ring_attention import ring_attention
from ..tensor import Tensor
from ._generate import GenerateMixin
from .transformer import next_token_loss, next_token_loss_fused

__all__ = ["LlamaConfig", "Llama", "LLAMA_SHARD_RULES"]

LLAMA_SHARD_RULES = [
    (r"(q_proj|k_proj|v_proj|gate|up)\.W$", (None, "model")),
    (r"(o_proj|down)\.W$", ("model", None)),
    (r"tok_emb\.table$", (None, "model")),
    (r"lm_head\.W$", (None, "model")),
]


@dataclass
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    ffn_dim: int = 14336
    max_position: int = 8192
    rope_theta: float = 500000.0
    # Llama-3.1-style frequency-dependent RoPE interpolation: 0 = off;
    # e.g. 8.0 extends the usable context ~8x past
    # rope_scaling_original_max_position (the PRETRAINED window the
    # interpolation bands anchor to).  Raise max_position alongside —
    # tables are sized by it, and generate()/training length checks
    # enforce it loudly.
    rope_scaling: float = 0.0
    rope_scaling_original_max_position: int = 8192
    # Mistral-style sliding-window attention: query t attends keys in
    # (t - window, t].  0 = full causal context.  Long sequences run
    # the chunked banded path (ops.attention.banded_attention — O(T*W)
    # memory); incompatible with the 'seq' (ring attention) axis.
    sliding_window: int = 0
    # width of one attention head; 0 = dim // num_heads.  Models whose
    # q/k/v projections are wider or narrower than the residual stream
    # state it (o_proj then maps num_heads * head_size back to dim).
    head_size: int = 0
    # per-block attention type, one of "sliding_attention" (window
    # `sliding_window`, the plain RoPE table) or "full_attention"
    # (causal, no window; the YaRN table when `yarn_factor` is set) for
    # each of the num_layers blocks.  () = every block alike: windowed
    # by `sliding_window` if that is set, one RoPE table (Mistral).
    layer_types: tuple = ()
    # YaRN for the full-attention blocks of `layer_types`
    # (ops.rope.yarn_frequencies: beta_fast 32, beta_slow 1 and the
    # attention factor 0.1 ln(factor) + 1 are fixed there): 0 = off
    yarn_factor: float = 0.0
    yarn_original_max_position: int = 8192
    eps: float = 1e-5
    # opt-in chunked fused lm-head+CE loss (never materializes the
    # (B*T, V) logits; autograd.FusedLinearCrossEntropy).  NOTE: with it
    # on, train_one_batch returns (loss, loss) instead of (logits, loss)
    # -- hence opt-in; the bench/dryrun/example enable it explicitly
    fused_loss: bool = False
    # rows per chunk of the fused loss's lax.scan.  Bigger chunks =
    # fewer scan iterations and fewer lm-head weight re-reads, at the
    # cost of a (chunk, V) logits block live per iteration
    # (4096 x 32k x bf16 = 256 MB)
    fused_loss_chunk: int = 512
    # activation checkpointing per transformer block (layer.Remat):
    # block internals recomputed in backward — O(layers) less activation
    # HBM for one extra forward; param paths unchanged
    remat: bool = False
    # pipeline parallelism over the 'pipe' mesh axis: blocks divide into
    # this many stages driven by the GPipe schedule
    # (layer.PipelineStack — global-semantics vmap+roll formulation, so
    # it composes with DistOpt/'data' sharding and remat).  0 = off.
    # Param paths are unchanged, so checkpoints round-trip between
    # pipelined and sequential configs.
    pipeline_stages: int = 0
    # microbatches per step when pipelining (default: = stages)
    pipeline_microbatches: int = 0
    # Mixtral-style MoE: >0 replaces every block's SwiGLU FFN with a
    # top-`moe_top_k` mixture of `num_experts` SwiGLU experts
    # (layer.MoE, expert weights sharded over the 'expert' mesh axis).
    # The Switch balance aux losses are summed into the training loss
    # at weight `moe_aux_weight`.  Incompatible with pipeline_stages
    # (the router's aux side channel cannot replay inside the
    # schedule) — the stack falls back to sequential with a warning.
    # `remat` is likewise inert for MoE blocks: layer.Remat skips
    # layers whose subtree carries a side channel (REMAT_SAFE=False),
    # so a remat+MoE config trains at no-remat activation memory.
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # exact top-k with every assignment computed, in every path of the
    # model (layer.MoE(dropless=True)): what a served model needs, where
    # a capacity drop silently changes a token.  Off: capacity routing.
    moe_dropless: bool = False

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def tiny() -> "LlamaConfig":
        return LlamaConfig(vocab_size=256, dim=64, num_layers=2,
                           num_heads=4, num_kv_heads=2, ffn_dim=128,
                           max_position=128, rope_theta=10000.0)

    @staticmethod
    def serve_bench() -> "LlamaConfig":
        """The CPU serve-bench config (bench.py bench_serve and the
        autotune serve sweep share it — the table entry the bench
        resolves must come from a sweep of the SAME architecture):
        big enough that decode reads real weight traffic (the tiny
        test config is per-op-overhead bound, which under-rewards
        batched decode), small enough to stay in a CPU bench budget."""
        return LlamaConfig(vocab_size=1024, dim=256, num_layers=4,
                           num_heads=8, num_kv_heads=4, ffn_dim=688,
                           max_position=128)

    @staticmethod
    def small() -> "LlamaConfig":
        """~110M-param config for single-chip benchmarking."""
        return LlamaConfig(vocab_size=32000, dim=768, num_layers=12,
                           num_heads=12, num_kv_heads=4, ffn_dim=2048,
                           max_position=2048)

    @staticmethod
    def base() -> "LlamaConfig":
        """~0.9B-param flagship config for one v5e chip, sized so the
        MXU dominates: f32 params + momentum + grads at 8x1024 fit the
        chip's 16 GB (chip_smoke.py runs exactly this)."""
        return LlamaConfig(vocab_size=32000, dim=2048, num_layers=16,
                           num_heads=16, num_kv_heads=8, ffn_dim=5632,
                           max_position=2048)

    @property
    def head_dim(self) -> int:
        return self.head_size or self.dim // self.num_heads

    def layer_type(self, i: int) -> Optional[str]:
        """Block i's entry of `layer_types`, None when there are none."""
        if not self.layer_types:
            return None
        if len(self.layer_types) != self.num_layers:
            raise ValueError(
                f"layer_types has {len(self.layer_types)} entries for "
                f"{self.num_layers} layers")
        kind = self.layer_types[i]
        if kind not in ("sliding_attention", "full_attention"):
            raise ValueError(f"unknown layer type {kind!r}")
        return kind


class _LlamaAttention(layer.Layer):
    def __init__(self, cfg: LlamaConfig, kind: Optional[str] = None,
                 name=None):
        super().__init__(name)
        c = cfg
        self.cfg = c
        # this block's window (0 = full causal) and RoPE table
        self.window = 0 if kind == "full_attention" else c.sliding_window
        self._scope = "attn.sliding" if self.window else "attn.full"
        self.q_proj = layer.Linear(c.num_heads * c.head_dim, bias=False)
        self.k_proj = layer.Linear(c.num_kv_heads * c.head_dim, bias=False)
        self.v_proj = layer.Linear(c.num_kv_heads * c.head_dim, bias=False)
        self.o_proj = layer.Linear(c.dim, bias=False)
        if kind == "full_attention" and c.yarn_factor:
            self._rope = rope_ops.yarn_frequencies(
                c.head_dim, c.max_position, c.rope_theta, c.yarn_factor,
                c.yarn_original_max_position)
        else:
            self._rope = rope_ops.rope_frequencies(
                c.head_dim, c.max_position, c.rope_theta, c.rope_scaling,
                c.rope_scaling_original_max_position)

    def _banded(self, q, k, v, device):
        """Sliding-window attention: causal AND within the last
        `sliding_window` keys.  All backend selection lives in the
        BandedSDPA op (Pallas banded kernel on TPU, chunked O(T*W) jnp
        elsewhere, full-mask reference for degenerate chunkings)."""
        del device
        from ..ops.attention import banded_attention
        from ..parallel import mesh as mesh_mod
        m_ = mesh_mod.current_mesh()
        if m_ is not None and m_.shape.get("seq", 1) > 1:
            raise NotImplementedError(
                "sliding_window attention does not compose with the "
                "'seq' (ring attention) mesh axis — drop the seq axis "
                "or use full causal attention")
        return banded_attention(q, k, v, self.window)

    def forward(self, x: Tensor, cache=None, pos=0):
        with jax.named_scope(self._scope):
            return self._forward(x, cache, pos)

    def _forward(self, x: Tensor, cache, pos):
        c = self.cfg
        B, T, _ = x.shape
        cos, sin = self._rope
        q = self.q_proj(x).reshape((B, T, c.num_heads, c.head_dim))
        k = self.k_proj(x).reshape((B, T, c.num_kv_heads, c.head_dim))
        v = self.v_proj(x).reshape((B, T, c.num_kv_heads, c.head_dim))
        q = rope_ops.apply_rope(q, cos, sin, offset=pos)
        k = rope_ops.apply_rope(k, cos, sin, offset=pos)
        windowed = bool(self.window) and self.window < T
        if cache is not None:
            ck, cv = kv_ops.update_cache(cache[0], cache[1],
                                         k.data, v.data, pos)
            if isinstance(pos, int) and pos == 0:
                # prefill: attend within the prompt through the regular
                # stack (flash kernel when the shape tiles)
                o = self._banded(q, k, v, x.device) if windowed \
                    else ring_attention(q, k, v, causal=True)
            else:
                o_arr = kv_ops.cached_sdpa(
                    q.data, ck, cv, limit=pos + T,
                    window=self.window or None)
                o = Tensor(data=o_arr, device=x.device, requires_grad=False)
            out = self.o_proj(o.reshape((B, T, c.num_heads * c.head_dim)))
            return out, (ck, cv)
        if windowed:
            o = self._banded(q, k, v, x.device)
        else:
            # ring attention when a 'seq' mesh axis is installed
            # (cross-chip context parallelism); fused SDPA otherwise
            o = ring_attention(q, k, v, causal=True)
        return self.o_proj(o.reshape((B, T, c.num_heads * c.head_dim)))


class _SwiGLU(layer.Layer):
    def __init__(self, cfg: LlamaConfig, name=None):
        super().__init__(name)
        self.gate = layer.Linear(cfg.ffn_dim, bias=False)
        self.up = layer.Linear(cfg.ffn_dim, bias=False)
        self.down = layer.Linear(cfg.dim, bias=False)

    def forward(self, x):
        return self.down(autograd.silu(self.gate(x)) * self.up(x))


class _LlamaBlock(layer.Layer):
    def __init__(self, cfg: LlamaConfig, kind: Optional[str] = None,
                 name=None):
        super().__init__(name)
        self.attn_norm = layer.RMSNorm(cfg.dim, eps=cfg.eps)
        self.attn = _LlamaAttention(cfg, kind)
        self.ffn_norm = layer.RMSNorm(cfg.dim, eps=cfg.eps)
        if cfg.num_experts:
            self.ffn = layer.MoE(cfg.num_experts, ffn_dim=cfg.ffn_dim,
                                 capacity_factor=cfg.moe_capacity_factor,
                                 top_k=cfg.moe_top_k, act="swiglu",
                                 dropless=cfg.moe_dropless)
        else:
            self.ffn = _SwiGLU(cfg)

    def forward(self, x, cache=None, pos=0):
        if cache is not None:
            a, new_cache = self.attn(self.attn_norm(x), cache, pos)
            x = x + a
            x = x + self.ffn(self.ffn_norm(x))
            return x, new_cache
        x = x + self.attn(self.attn_norm(x))
        x = x + self.ffn(self.ffn_norm(x))
        return x


class Llama(GenerateMixin, model.Model):
    SHARD_RULES = LLAMA_SHARD_RULES

    def __init__(self, cfg: Optional[LlamaConfig] = None, **kw):
        super().__init__()
        self.cfg = cfg or LlamaConfig(**kw)
        c = self.cfg
        self.tok_emb = layer.Embedding(c.vocab_size, c.dim)
        blocks = [_LlamaBlock(c, c.layer_type(i))
                  for i in range(c.num_layers)]
        if c.pipeline_stages and len(set(c.layer_types)) > 1:
            raise NotImplementedError(
                "pipeline_stages runs one block's program over stacked "
                "weights: blocks of different layer_types cannot share it")
        if c.pipeline_stages:
            # embed and lm head stay outside the pipeline (replicated /
            # 'model'-sharded as usual); only the shape-preserving block
            # stack rides the 'pipe' axis.  remat folds into the stack
            # (per-block jax.checkpoint inside the schedule).
            self.blocks = layer.PipelineStack(
                blocks, stages=c.pipeline_stages,
                n_micro=c.pipeline_microbatches or None, remat=c.remat)
        else:
            if c.remat:
                blocks = [layer.Remat(b) for b in blocks]
            self.blocks = blocks
        self.norm_f = layer.RMSNorm(c.dim, eps=c.eps)
        self.lm_head = layer.Linear(c.vocab_size, bias=False)

    def features(self, ids: Tensor) -> Tensor:
        """Final hidden states (B, T, dim) — everything but the lm head."""
        x = self.tok_emb(ids)
        if isinstance(self.blocks, layer.PipelineStack):
            x = self.blocks(x)
        else:
            for blk in self.blocks:
                x = blk(x)
        return self.norm_f(x)

    def forward(self, ids: Tensor) -> Tensor:
        return self.lm_head(self.features(ids))

    # -- KV-cached decoding (ops/kv_cache.py; VERDICT r2 item 4) ------------
    def init_caches(self, batch: int, max_len: int):
        c = self.cfg
        import jax.numpy as jnp
        dtype = jnp.bfloat16 if self.tok_emb.table.dtype == jnp.bfloat16 \
            else jnp.float32
        return kv_ops.init_cache(c.num_layers, batch, max_len,
                                 c.num_kv_heads, c.head_dim, dtype)

    def forward_cached(self, ids: Tensor, caches, pos):
        x = self.tok_emb(ids)
        new_caches = []
        for blk, cache in zip(self.blocks, caches):
            x, nc = blk(x, cache, pos)
            new_caches.append(nc)
        return self.lm_head(self.norm_f(x)), new_caches

    def _moe_aux_loss(self) -> Optional[Tensor]:
        """Summed router balance losses of every MoE block (None when
        dense or nothing accumulated)."""
        from ..layer import MoE, _walk_layers
        total = None
        for l in _walk_layers(self):
            if isinstance(l, MoE):
                a = l.pop_aux_loss()
                if a is not None:
                    total = a if total is None else total + a
        return total

    def train_one_batch(self, ids: Tensor, labels: Optional[Tensor] = None):
        tgt = labels if labels is not None else ids
        if self.cfg.fused_loss:
            loss = next_token_loss_fused(self.features(ids), self.lm_head,
                                         tgt,
                                         chunk_rows=self.cfg.fused_loss_chunk)
        else:
            logits = self.forward(ids)
            loss = next_token_loss(logits, tgt)
        if self.cfg.num_experts:
            aux = self._moe_aux_loss()
            if aux is not None:
                loss = loss + autograd.mul(aux, self.cfg.moe_aux_weight)
        self.optimizer(loss)
        if self.cfg.fused_loss:
            return loss, loss
        return logits, loss

    def num_params(self) -> int:
        return sum(p.size for p in self.get_params().values())

    def flops_per_token(self, seq_len: int) -> float:
        """Training FLOPs/token ≈ 6·N_matmul + 12·L·dim·T (qk^T and
        probs·v matmuls fwd+bwd at sequence length T) — honest MFU
        accounting, SURVEY.md §7.3 item 6.  N_matmul EXCLUDES the
        token-embedding table: its lookup is a gather, not a matmul
        (same convention as BERT.flops_per_token; r1-r4 included it,
        over-counting ~19% at the `small` config — caught in r5 by
        walking the compiled step's jaxpr, which this formula now
        matches to <1%: utils.flops.jaxpr_matmul_conv_flops).  The
        lm-head stays IN: its projection is a real matmul.  The fused
        chunked loss recomputes the lm-head matmul in backward:
        + 2·dim·V.  For MoE configs N counts only the ACTIVE
        parameters per token (top-k of num_experts expert FFNs), not
        the full expert bank."""
        c = self.cfg
        n = self.num_params()
        if n:
            n -= c.vocab_size * c.dim        # tok_emb gather
        if c.num_experts:
            # each expert FFN: 3 SwiGLU matmuls of dim x ffn_dim.
            # Clamped at 0: before the first forward num_params() is 0
            # (lazy init) and the subtraction would go negative.  The
            # active-FLOPs basis also ignores the capacity-factor
            # over-compute (padded expert slots) — conservative for MFU.
            expert_p = 3 * c.dim * c.ffn_dim
            n = max(n - c.num_layers * (c.num_experts - c.moe_top_k)
                    * expert_p, 0)
        # sliding-window attention computes only min(T, W) keys/query
        spans = sum(min(seq_len, c.sliding_window) if c.sliding_window
                    and c.layer_type(i) != "full_attention" else seq_len
                    for i in range(c.num_layers))
        f = 6 * n + 12 * c.num_heads * c.head_dim * spans
        if c.fused_loss:
            f += 2 * c.dim * c.vocab_size
        return f
