"""ZAYA1 (Zyphra; arXiv:2511.17127): a decoder whose attention runs in a
compressed latent with convolutional mixing (CCA, arXiv:2510.04476:
``ops/cca.py`` has the equations) over a top-1 mixture of experts
routed by a small MLP, with the output head tied to the embedding.

Block: ``x += CCA(RMSNorm(x))``, ``x += MoE(RMSNorm(x))``.  ``CCA``: q,
k and v from ``ops.cca.cca_qkv``, rotary embedding on the first
``partial_rotary_factor`` of each head's dims, causal grouped-query
attention at scale 1/sqrt(head), and a projection from the latent
(``num_heads x head_size``, half the stream at the published sizes)
back to ``dim``.  ``MoE``: ``layer.MoE`` with ``layer.MLPRouter``, top 1,
the gate the chosen expert's probability, dropless.  Logits are
``RMSNorm(x) @ tok_emb.table.T``: the model has no ``lm_head`` parameter.

A separate model and not a layer kind of ``models/llama.py``'s block:
the attention's cache is a triple, its projections are of other
shapes, the FFN's router is a sub-module and the head is tied, so the
two blocks would share the two norms and the residual adds.

The cache of a layer is ``(k, v, state)``: the KV cache every decoder
here keeps, and the CCA side state of ``ops/cca.py`` (B, S) beside it.
``forward_cached`` takes ``state_rows`` for a caller that must know the
state after other rows than the last (``serve/engine.py``: after each
KV block's last row and after a padded chunk's last valid row).

Inference only: the CCA layer computes on arrays and no gradient flows
through it.  Not built, as no key of the published config names them
(the family's descriptions mention them): exponential depth averaging
of the router's input across layers, a skip expert (mixture of
depths), learned scales on the residual path.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import layer, model
from ..ops import cca as cca_ops
from ..ops import kv_cache as kv_ops
from ..ops import rope as rope_ops
from ..tensor import Tensor
from ._generate import GenerateMixin

__all__ = ["ZayaConfig", "Zaya"]


@dataclass
class ZayaConfig:
    vocab_size: int = 262272
    dim: int = 2048
    num_layers: int = 40
    num_heads: int = 8
    num_kv_heads: int = 2
    head_size: int = 128
    # taps of the depthwise and of the per-head convolution
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5000000.0
    max_position: int = 131072
    eps: float = 1e-5
    num_experts: int = 16
    ffn_dim: int = 2048             # one expert's width
    router_hidden: int = 256
    # read by ServeEngine's host-side count of routed assignments
    moe_top_k: int = 1

    @staticmethod
    def tiny() -> "ZayaConfig":
        return ZayaConfig(vocab_size=256, dim=64, num_layers=2, num_heads=4,
                          num_kv_heads=2, head_size=16, rope_theta=10000.0,
                          max_position=128, num_experts=4, ffn_dim=32,
                          router_hidden=16)

    @property
    def state_size(self) -> int:
        return cca_ops.state_size(self.num_heads, self.num_kv_heads,
                                  self.head_size, self.cca_time0,
                                  self.cca_time1)


class _CCAttention(layer.Layer):
    def __init__(self, cfg: ZayaConfig, name=None):
        super().__init__(name)
        c = self.cfg = cfg
        if c.num_kv_heads % 2 or c.num_heads % c.num_kv_heads:
            raise ValueError(
                f"CCA shifts half of the KV heads in time and groups the "
                f"query heads by KV head: {c.num_heads} query / "
                f"{c.num_kv_heads} KV heads do not divide so")
        self.o_proj = layer.Linear(c.dim, bias=False)
        self._rope = rope_ops.rope_frequencies(
            int(c.head_size * c.partial_rotary_factor), c.max_position,
            c.rope_theta)

    def initialize(self, x: Tensor, *_):
        c, dev = self.cfg, x.device
        heads, d = c.num_heads + c.num_kv_heads, c.head_size

        def normal(shape, std):
            return Tensor(shape, dev, np.float32).gaussian(0.0, std)

        def proj(name, width):
            return self.register_param(name, layer._xavier_uniform(
                (c.dim, width), c.dim, width, dev))

        self.wq = proj("wq", c.num_heads * d)
        self.wk = proj("wk", c.num_kv_heads * d)
        # the value's two halves: the heads that see the current token
        # and those that see the one before it
        self.wv_cur = proj("wv_cur", (c.num_kv_heads // 2) * d)
        self.wv_prev = proj("wv_prev", (c.num_kv_heads // 2) * d)

        # a convolution's output keeps its input's variance; the biases
        # start off non-zero so that random weights exercise them
        self.conv0_w = self.register_param(
            "conv0_w", normal((c.cca_time0, heads * d),
                              c.cca_time0 ** -0.5))
        self.conv0_b = self.register_param(
            "conv0_b", normal((heads * d,), 0.02))
        self.conv1_w = self.register_param(
            "conv1_w", normal((c.cca_time1, heads, d, d),
                              (c.cca_time1 * d) ** -0.5))
        self.conv1_b = self.register_param(
            "conv1_b", normal((heads * d,), 0.02))
        self.k_scale = self.register_param(
            "k_scale", Tensor((c.num_kv_heads,), dev,
                              np.float32).set_value(1.0))

    def forward(self, x: Tensor, cache=None, pos=0, state_rows=None):
        with jax.named_scope("attn.cca"):
            return self._forward(x, cache, pos, state_rows)

    def _forward(self, x: Tensor, cache, pos, state_rows):
        c = self.cfg
        B, T, _ = x.shape
        h = x.data
        state = jnp.zeros((B, c.state_size), h.dtype) if cache is None \
            else cache[2]
        q, k, v, state = cca_ops.cca_qkv(
            h, state, self.wq.data, self.wk.data, self.wv_cur.data,
            self.wv_prev.data, self.conv0_w.data,
            self.conv0_b.data, self.conv1_w.data, self.conv1_b.data,
            self.k_scale.data, num_heads=c.num_heads,
            num_kv_heads=c.num_kv_heads, head_dim=c.head_size,
            state_rows=state_rows)
        cos, sin = self._rope
        q = rope_ops.apply_rope(q, cos, sin, offset=pos)
        k = rope_ops.apply_rope(k, cos, sin, offset=pos)
        if cache is None:
            o = kv_ops.cached_sdpa(q, k, v, limit=T)
        else:
            k, v = kv_ops.update_cache(cache[0], cache[1], k, v, pos)
            o = kv_ops.cached_sdpa(q, k, v, limit=pos + T)
        o = Tensor(data=o.reshape(B, T, c.num_heads * c.head_size),
                   device=x.device, requires_grad=False)
        out = self.o_proj(o)
        return out if cache is None else (out, (k, v, state))


class _ZayaBlock(layer.Layer):
    def __init__(self, cfg: ZayaConfig, name=None):
        super().__init__(name)
        self.attn_norm = layer.RMSNorm(cfg.dim, eps=cfg.eps)
        self.attn = _CCAttention(cfg)
        self.ffn_norm = layer.RMSNorm(cfg.dim, eps=cfg.eps)
        self.ffn = layer.MoE(
            cfg.num_experts, ffn_dim=cfg.ffn_dim, top_k=cfg.moe_top_k,
            act="swiglu", dropless=True,
            router=layer.MLPRouter(cfg.num_experts, cfg.router_hidden))

    def forward(self, x, cache=None, pos=0, state_rows=None):
        a = self.attn(self.attn_norm(x), cache, pos, state_rows)
        if cache is not None:
            a, cache = a
        x = x + a
        x = x + self.ffn(self.ffn_norm(x))
        return x if cache is None else (x, cache)


class Zaya(GenerateMixin, model.Model):
    def __init__(self, cfg: Optional[ZayaConfig] = None, **kw):
        super().__init__()
        self.cfg = cfg or ZayaConfig(**kw)
        c = self.cfg
        self.tok_emb = layer.Embedding(c.vocab_size, c.dim)
        self.blocks = [_ZayaBlock(c) for _ in range(c.num_layers)]
        self.norm_f = layer.RMSNorm(c.dim, eps=c.eps)

    def _head(self, x: Tensor) -> Tensor:
        """Logits through the head tied to the embedding: the one array
        `tok_emb.table`, read across its rows."""
        with jax.named_scope("lm_head.tied"):
            h = self.norm_f(x).data
            logits = jnp.einsum("btd,vd->btv", h,
                                self.tok_emb.table.data.astype(h.dtype))
        return Tensor(data=logits, device=x.device, requires_grad=False)

    def forward(self, ids: Tensor) -> Tensor:
        x = self.tok_emb(ids)
        for blk in self.blocks:
            x = blk(x)
        return self._head(x)

    def init_caches(self, batch: int, max_len: int):
        """Per layer ``(k, v, state)``: the KV cache (B, max_len, Hkv, d)
        and the CCA side state (B, S), in the embedding's dtype."""
        c = self.cfg
        dtype = jnp.bfloat16 if self.tok_emb.table.dtype == jnp.bfloat16 \
            else jnp.float32
        return [(k, v, jnp.zeros((batch, c.state_size), dtype))
                for k, v in kv_ops.init_cache(
                    c.num_layers, batch, max_len, c.num_kv_heads,
                    c.head_size, dtype)]

    def forward_cached(self, ids: Tensor, caches, pos, state_rows=None):
        """As every decoder's here; ``state_rows`` (R,) asks for each
        layer's side state after those rows of ``ids`` instead of after
        the last: (B, R, S) in the returned caches."""
        x = self.tok_emb(ids)
        new_caches = []
        for blk, cache in zip(self.blocks, caches):
            x, nc = blk(x, cache, pos, state_rows)
            new_caches.append(nc)
        return self._head(x), new_caches

    def train_one_batch(self, *_):
        raise NotImplementedError(
            "Zaya is inference only: its CCA layer carries no gradient")

    def num_params(self) -> int:
        return sum(p.size for p in self.get_params().values())
