"""GLM-4.7-Flash (Z.ai; `glm4_moe_lite`): a decoder whose attention is
multi-head latent attention (MLA, DeepSeek-V2 arXiv:2405.04434) over a
cache of one compressed row a token, and whose feed-forward is, after
``first_dense`` dense layers, a mixture of sigmoid-routed experts with a
correction bias beside a shared expert; the output head is untied.

``h = tok_emb[ids]``; per layer ``h += MLA(RMSNorm(h))``, ``h +=
FFN(RMSNorm(h))``; ``logits = RMSNorm(h) W_head``.

* ``MLA``: ``c_q = RMSNorm(x W_qa)``, ``[q_nope | q_rope]_h = (c_q
  W_qb)_h``; ``[c_kv | k_rope] = x W_kva``, ``c_kv = RMSNorm(c_kv)``;
  ``[k_nope | v]_h = (c_kv W_kvb)_h``; RoPE on ``q_rope`` and on the one
  ``k_rope`` every head shares; ``score = (q_nope.k_nope + q_rope.k_rope)
  / sqrt(qk_nope + qk_rope)``, causal softmax, ``concat_h(p v_h) W_o``.
  A token caches ``c_kv`` and the rotated ``k_rope`` and nothing else.
  The program attends in the ABSORBED form only: ``W_kvb``'s key half
  folded into the query (``q_lat_h = q_nope_h W_UK_h^T``,
  ``kv_lora_rank`` wide) and its value half into the output (``o_h = (p
  c_kv) W_UV_h``), so every head reads the latent rows themselves; a
  decode token through ``ops.mla_attention``'s decode kernel where the
  arena's pools are read in place, a prefill chunk over its gathered
  view through the same module's prefill kernel where
  ``ops.kv_cache.attends_view`` takes the view (bf16 or f32, whole
  128-lane latent rows, a TPU): the chunk's
  rows times the heads against the view's tiles up to ``pos + T``,
  online softmax, no score written to HBM; elsewhere (the CPU, other
  shapes, ``forward()`` without a cache) masked softmax over the whole
  view.  The
  EXPANDED form (per-head keys and values from ``W_kvb`` over every row
  attended) is the reference's: on the chip it took 1.35x as long as
  the absorbed at a 256-row chunk over 16.6k cached rows and 1.13x at
  512 rows (PERF.md §6, PR 39), so no shape this engine runs takes it.
* FFN of layers below ``first_dense``: a SwiGLU of ``dense_ffn_dim``.
  Above: ``layer.MoE`` with a sigmoid router (the choice on score +
  correction bias, the gates the chosen scores renormalised, times
  ``route_scale``), ``experts_held`` where several chips divide a layer,
  plus one shared SwiGLU expert every token passes.

The cache of a layer is ``(c_kv, k_rope)``, (B, S, kv_lora_rank) and
(B, S, rope_lanes): the rope part zero-padded to whole 128-lane rows,
which a TPU lays it out in anyway (``ops/mla_attention.py``).  The
paged arena keeps them as a layer's two pools, where other decoders
keep keys and values.

Inference only.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import autograd, layer, model
from ..ops import kv_cache as kv_ops
from ..ops.mla_attention import mla_decode_attention, mla_prefill_attention
from ..ops.rope import apply_rope
from ..tensor import Tensor
from ._generate import GenerateMixin

__all__ = ["GlmMlaConfig", "GlmMla"]


@dataclass
class GlmMlaConfig:
    vocab_size: int = 154880
    dim: int = 2048
    num_layers: int = 47
    num_heads: int = 20
    q_lora_rank: Optional[int] = 768    # None: no query compression
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    eps: float = 1e-5
    first_dense: int = 1            # first_k_dense_replace
    dense_ffn_dim: int = 10240      # intermediate_size
    num_experts: int = 64
    # ids of the experts this chip holds (None: all of them)
    experts_held: Optional[Tuple[int, ...]] = None
    ffn_dim: int = 1536             # one routed expert's width
    shared_dim: int = 1536          # n_shared_experts x moe_intermediate
    moe_top_k: int = 4
    route_scale: float = 1.8        # routed_scaling_factor
    max_position: int = 202752
    # the two latent norms' weights are drawn uniform in this range (a
    # trained model's are far from 1; at 1 random weights give scores
    # of unit spread, and attention over thousands of rows is then an
    # average that nothing downstream can tell from another)
    latent_norm_init: Tuple[float, float] = (1.0, 1.0)

    @staticmethod
    def tiny() -> "GlmMlaConfig":
        return GlmMlaConfig(
            vocab_size=256, dim=64, num_layers=3, num_heads=4, q_lora_rank=48,
            kv_lora_rank=128, qk_nope_head_dim=24, qk_rope_head_dim=16,
            v_head_dim=32, rope_theta=10000.0, dense_ffn_dim=96,
            num_experts=8, experts_held=(0, 1, 2), ffn_dim=32, shared_dim=32,
            moe_top_k=3, route_scale=1.8, max_position=4096,
            latent_norm_init=(2.0, 3.0))

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def rope_lanes(self) -> int:
        """Width of the cached rope part: whole 128-lane rows."""
        return -(-self.qk_rope_head_dim // 128) * 128


@functools.lru_cache(maxsize=8)
def _rope_tables(dim: int, max_len: int, theta: float):
    """(cos, sin) of shape (max_len, dim // 2): the angles and their
    cosines in float64 on the host, rounded once to f32.  Built in f32 on
    a TPU (``ops.rope.rope_frequencies``) a cosine is off by up to 0.018
    at 16k positions, which nine layers carry to 1.5% of the logits
    (PERF.md §6, PR 39)."""
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = np.arange(max_len, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def _tensor(a, like: Tensor) -> Tensor:
    return Tensor(data=a, device=like.device, requires_grad=False)


def _rms(x, w, eps):
    f = x.astype(jnp.float32)
    f = f * jax.lax.rsqrt(jnp.mean(f * f, -1, keepdims=True) + eps)
    return (f * w.astype(jnp.float32)).astype(x.dtype)


def _f32(a, b, spec):
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


class _MLA(layer.Layer):
    """The latent attention of a layer.  ``cfg.q_lora_rank`` None: the
    queries straight from the input, ``q = x W_q``, with no norm.
    ``head_gate``: each head's output scaled by ``sigmoid(x w_h)``
    before ``W_o`` (``x`` the layer's normed input; one weight column a
    head, parameter ``gate.W``)."""

    def __init__(self, cfg: GlmMlaConfig, head_gate: bool = False,
                 name=None):
        super().__init__(name)
        c = self.cfg = cfg
        H = c.num_heads
        if c.q_lora_rank:
            self.q_a = layer.Linear(c.q_lora_rank, bias=False)
            self.q_b = layer.Linear(H * c.qk_head_dim, bias=False)
        else:
            self.q = layer.Linear(H * c.qk_head_dim, bias=False)
        self.head_gate = head_gate
        if head_gate:
            self.gate = layer.Linear(H, bias=False)
        self.kv_a = layer.Linear(c.kv_lora_rank + c.qk_rope_head_dim,
                                 bias=False)
        self.kv_b = layer.Linear(H * (c.qk_nope_head_dim + c.v_head_dim),
                                 bias=False)
        self.o = layer.Linear(c.dim, bias=False)
        self._rope = _rope_tables(c.qk_rope_head_dim, c.max_position,
                                  float(c.rope_theta))
        # whether the latest cached forward over a dense view that was
        # traced took the prefill kernel
        self.took_kernel = False

    def initialize(self, x: Tensor, *_):
        lo, hi = self.cfg.latent_norm_init

        def norm(n):
            return Tensor((n,), x.device, np.float32).uniform(lo, hi)

        if self.cfg.q_lora_rank:
            self.q_a_norm = self.register_param(
                "q_a_norm", norm(self.cfg.q_lora_rank))
        self.kv_a_norm = self.register_param(
            "kv_a_norm", norm(self.cfg.kv_lora_rank))
        # read as a matrix by the absorbed form and never called, so it
        # is initialised here
        self.kv_b.initialize(
            Tensor((1, self.cfg.kv_lora_rank), x.device, np.float32))
        self.kv_b._initialized = True

    def _kv_b(self, dtype):
        """``W_kvb`` as (r, H, dn + dv): a head's key and value halves."""
        c = self.cfg
        return self.kv_b.W.data.astype(dtype).reshape(
            c.kv_lora_rank, c.num_heads, c.qk_nope_head_dim + c.v_head_dim)

    def forward(self, x: Tensor, cache=None, pos=0):
        with jax.named_scope("attn.mla"):
            return self._forward(x, cache, pos)

    def _forward(self, x: Tensor, cache, pos):
        c = self.cfg
        B, T, _ = x.shape
        H, dn, dr, r = (c.num_heads, c.qk_nope_head_dim, c.qk_rope_head_dim,
                        c.kv_lora_rank)
        cos, sin = self._rope
        with jax.named_scope("attn.mla.q"):
            if c.q_lora_rank:
                cq = self.q_a(x).data
                cq = _rms(cq, self.q_a_norm.data, c.eps)
                q = self.q_b(_tensor(cq, x)).data
            else:
                q = self.q(x).data
            q = q.reshape(B, T, H, c.qk_head_dim)
            q_nope = q[..., :dn]
            q_rope = apply_rope(q[..., dn:], cos, sin, pos)
        with jax.named_scope("attn.mla.kv_a"):
            kv = self.kv_a(x).data
            c_kv = _rms(kv[..., :r], self.kv_a_norm.data, c.eps)
            k_rope = apply_rope(kv[..., None, r:], cos, sin, pos)[:, :, 0]
        scale = 1.0 / math.sqrt(c.qk_head_dim)
        wkv = self._kv_b(x.data.dtype)
        if cache is None:
            o = self._attend(q_nope, q_rope, c_kv, k_rope, wkv, T, scale)
            return self._out(o, x)
        pad = ((0, 0), (0, 0), (0, c.rope_lanes - dr))
        ck, cr = kv_ops.update_cache(cache[0], cache[1], c_kv,
                                     jnp.pad(k_rope, pad), pos)
        if isinstance(ck, kv_ops.PagedKV):
            # one token a slot, written into the pools in place and
            # attended through each slot's table row
            q_lat = self._absorb(q_nope, wkv)
            with jax.named_scope("attn.mla.decode"):
                o_lat = mla_decode_attention(
                    q_lat[:, 0], jnp.pad(q_rope[:, 0], pad),
                    ck.pool, cr.pool, ck.tables, pos + 1, ck.plan,
                    scale=scale)
            o = self._unabsorb(o_lat[:, None], wkv)
        else:
            # the rows over the slot's dense view: a prefill chunk, a
            # verify's k + 1 rows, a dense cache's decode token
            self.took_kernel = kv_ops.attends_view(ck)
            with jax.named_scope("attn.mla.prefill"):
                if self.took_kernel:
                    o_lat = mla_prefill_attention(
                        self._absorb(q_nope, wkv), q_rope, ck, cr, pos + T,
                        scale=scale)
                    o = self._unabsorb(o_lat, wkv)
                else:
                    o = self._attend(q_nope, q_rope, ck, cr[..., :dr], wkv,
                                     pos + T, scale)
        return self._out(o, x), (ck, cr)

    def _out(self, o, x):
        c = self.cfg
        B, T = o.shape[:2]
        with jax.named_scope("attn.mla.out"):
            if self.head_gate:
                o = o * jax.nn.sigmoid(
                    self.gate(x).data.astype(jnp.float32))[..., None]
            return self.o(_tensor(o.reshape(B, T, c.num_heads * c.v_head_dim)
                                  .astype(x.data.dtype), x))

    def _absorb(self, q_nope, wkv):
        with jax.named_scope("attn.mla.absorb"):
            return _f32(q_nope, wkv[..., :self.cfg.qk_nope_head_dim],
                        "bthd,rhd->bthr").astype(q_nope.dtype)

    def _unabsorb(self, o_lat, wkv):
        with jax.named_scope("attn.mla.absorb"):
            return _f32(o_lat.astype(wkv.dtype),
                        wkv[..., self.cfg.qk_nope_head_dim:],
                        "bthr,rhv->bthv")

    @staticmethod
    def _mask(sc, limit):
        """Keep cached position s for query row t where s < limit - T +
        t + 1 (bottom-right-aligned causality under a per-row limit)."""
        T, S = sc.shape[-2:]
        lim = jnp.asarray(limit)
        lim = lim.reshape((-1, 1, 1, 1)) if lim.ndim else lim
        qpos = lim - T + jnp.arange(T)[:, None]
        return jnp.where(jnp.arange(S)[None, :] <= qpos, sc, -1e30)

    def _attend(self, q_nope, q_rope, ck, cr, wkv, limit, scale):
        """Absorbed attention of the rows (B, T, H, .) over a dense
        latent cache (B, S, .) under per-row limits."""
        q_lat = self._absorb(q_nope, wkv)
        sc = (_f32(q_lat, ck.astype(q_lat.dtype), "bthr,bsr->bhts")
              + _f32(q_rope, cr.astype(q_rope.dtype), "bthe,bse->bhts"))
        p = jax.nn.softmax(self._mask(sc * scale, limit), axis=-1)
        o_lat = _f32(p.astype(ck.dtype), ck, "bhts,bsr->bhtr")
        return self._unabsorb(o_lat.swapaxes(1, 2), wkv)


class _SwiGLU(layer.Layer):
    def __init__(self, dim: int, width: int, scope: str, name=None):
        super().__init__(name)
        self.scope = scope
        self.gate = layer.Linear(width, bias=False)
        self.up = layer.Linear(width, bias=False)
        self.down = layer.Linear(dim, bias=False)

    def forward(self, x):
        with jax.named_scope(self.scope):
            return self.down(autograd.silu(self.gate(x)) * self.up(x))


class _GlmBlock(layer.Layer):
    def __init__(self, cfg: GlmMlaConfig, dense: bool, name=None):
        super().__init__(name)
        self.attn_norm = layer.RMSNorm(cfg.dim, eps=cfg.eps)
        self.attn = _MLA(cfg)
        self.ffn_norm = layer.RMSNorm(cfg.dim, eps=cfg.eps)
        self.dense = dense
        if dense:
            self.mlp = _SwiGLU(cfg.dim, cfg.dense_ffn_dim, "mlp.dense")
        else:
            self.ffn = layer.MoE(cfg.num_experts, ffn_dim=cfg.ffn_dim,
                                 top_k=cfg.moe_top_k, act="swiglu",
                                 dropless=True,
                                 experts_held=cfg.experts_held,
                                 score="sigmoid",
                                 route_scale=cfg.route_scale)
            self.shared = _SwiGLU(cfg.dim, cfg.shared_dim, "moe.shared")

    def forward(self, x, cache=None, pos=0):
        a = self.attn(self.attn_norm(x), cache, pos)
        if cache is not None:
            a, cache = a
        x = _tensor(x.data + a.data, x)
        h = self.ffn_norm(x)
        f = self.mlp(h).data if self.dense \
            else self.ffn(h).data + self.shared(h).data
        x = _tensor(x.data + f, x)
        return x if cache is None else (x, cache)


class GlmMla(GenerateMixin, model.Model):
    def __init__(self, cfg: Optional[GlmMlaConfig] = None, **kw):
        super().__init__()
        self.cfg = cfg or GlmMlaConfig(**kw)
        c = self.cfg
        self.tok_emb = layer.Embedding(c.vocab_size, c.dim)
        self.blocks = [_GlmBlock(c, i < c.first_dense)
                       for i in range(c.num_layers)]
        self.norm_f = layer.RMSNorm(c.dim, eps=c.eps)
        self.lm_head = layer.Linear(c.vocab_size, bias=False)

    @property
    def latent_heads(self) -> int:
        """Query heads of a latent layer: how many rows a slot stacks
        into the decode kernel's groups (``ops.mla_attention``)."""
        return self.cfg.num_heads

    def _head(self, x: Tensor) -> Tensor:
        with jax.named_scope("lm_head"):
            return self.lm_head(self.norm_f(x))

    def forward(self, ids: Tensor) -> Tensor:
        x = self.tok_emb(ids)
        for blk in self.blocks:
            x = blk(x)
        return self._head(x)

    def init_caches(self, batch: int, max_len: int):
        """Per layer ``(c_kv, k_rope)``: (B, max_len, kv_lora_rank) and
        (B, max_len, rope_lanes) in the embedding's dtype."""
        c = self.cfg
        dtype = jnp.bfloat16 if self.tok_emb.table.dtype == jnp.bfloat16 \
            else jnp.float32
        return [(jnp.zeros((batch, max_len, c.kv_lora_rank), dtype),
                 jnp.zeros((batch, max_len, c.rope_lanes), dtype))
                for _ in range(c.num_layers)]

    @property
    def prefill_kernel(self) -> bool:
        """Whether the latest traced cached forward over dense views
        attended through ``ops.mla_attention``'s prefill kernel: set
        while a program traces, so a serving engine reads it once for
        its compiled prefill program."""
        return any(b.attn.took_kernel for b in self.blocks)

    def forward_cached(self, ids: Tensor, caches, pos):
        x = self.tok_emb(ids)
        new_caches = []
        for blk, cache in zip(self.blocks, caches):
            x, nc = blk(x, cache, pos)
            new_caches.append(nc)
        return self._head(x), new_caches

    def train_one_batch(self, *_):
        raise NotImplementedError("GlmMla is inference only")

    def num_params(self) -> int:
        return sum(p.size for p in self.get_params().values())
