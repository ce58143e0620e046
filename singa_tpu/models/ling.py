"""Ling-3.0-flash (inclusionAI; `bailing_hybrid`), the language model of
Ling-3.0-flash-VL: a decoder whose token mixer is, five layers in six,
Kimi delta attention (KDA: a delta rule whose f32 state decays channel
by channel, ``ops/kda.py``) and, in the sixth, multi-head latent
attention (MLA) with a head-wise output gate over a paged latent cache;
after ``first_dense`` dense layers, a mixture of sigmoid-routed experts
chosen within the best groups of experts, beside a shared expert; the
output head is untied.

``h = tok_emb[ids]``; per layer ``h += Mixer(RMSNorm(h))``, ``h +=
FFN(RMSNorm(h))``; ``logits = RMSNorm(h) W_head``.  Layer i is MLA when
``(i + 1) % layer_group_size == 0``, else KDA.

* ``KDA`` (H heads of dk = dv = ``head_dim``): ``q, k, v = silu(
  conv(x W_qkv))``, a depthwise causal convolution of ``conv_size`` taps
  and no bias over the three together; ``q, k`` L2-normed a head, ``q *=
  dk^-1/2``; ``beta = sigmoid(x W_b)`` a head; the log decay ``g =
  lower_bound * sigmoid(exp(A_log)_h (x W_f + dt_bias))`` a channel;
  the recurrence of ``ops/kda.py``; ``o = RMSNorm_head(o) * o_norm *
  sigmoid(x W_g)``, ``o W_o``.
* ``MLA``: ``models/glm_mla.py``'s, with the queries straight from the
  input (``q_lora_rank`` None: ``q = x W_q``, no norm) and each head's
  output scaled by ``sigmoid(x w_h)`` before ``W_o``.
* FFN of layers below ``first_dense``: a SwiGLU of ``dense_ffn_dim``.
  Above: ``layer.MoE`` with a sigmoid router (the choice on score +
  correction bias among the ``groups[1]`` best of ``groups[0]`` groups,
  the gates the chosen scores renormalised, times ``route_scale``),
  ``experts_held`` where several chips divide a layer, plus one shared
  SwiGLU expert every token passes.

The cache of a layer is what it needs: an MLA layer ``(c_kv, k_rope)``
as ``glm_mla``'s, a KDA layer ``(None, None, S, window)``: S (B, H, dk,
dv) in f32 whatever the weights are and the convolution's last
``conv_size - 1`` input rows (B, conv_size - 1, 3 H dk).  A paged arena
therefore holds one layer's latent blocks and, per slot and in
snapshots, the other layers' state (``serve/slots.py``).  A decode tick
updates each S through ``ops/kda.py``'s kernel on a TPU, in place, so
this model asks the engine not to mask the state of idle slots
(:attr:`Ling.updates_state_in_place`): an idle slot's state is never
read before a prefill writes it again.

Inference only: the mixers compute on arrays and no gradient flows
through them.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import layer, model
from ..ops import kda as kda_ops
from ..ops import ssm as ssm_ops
from ..tensor import Tensor
from ._generate import GenerateMixin
from .glm_mla import GlmMlaConfig, _MLA, _SwiGLU

__all__ = ["LingConfig", "Ling"]

#: a KDA channel's mean decay a token, exp(lower_bound * sigmoid(exp(A_log)
#: dt_bias)), is drawn log-uniform so that it forgets over this many
#: tokens, with exp(A_log) uniform in _DECAY_GAIN a head
_DECAY_TOKENS = (1.0, 1000.0)
_DECAY_GAIN = (0.05, 0.5)


@dataclass
class LingConfig:
    vocab_size: int = 157184
    dim: int = 2560
    num_layers: int = 42
    layer_group_size: int = 6       # every sixth layer is MLA
    num_heads: int = 32             # KDA and MLA alike
    head_dim: int = 128             # a KDA head's dk = dv
    conv_size: int = 4              # short_conv_kernel_size
    kda_lower_bound: float = -5.0
    kda_chunk: int = 64
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    eps: float = 1e-6
    first_dense: int = 2            # first_k_dense_replace
    dense_ffn_dim: int = 6144       # intermediate_size
    num_experts: int = 512
    # ids of the experts this chip holds (None: all of them)
    experts_held: Optional[Tuple[int, ...]] = None
    ffn_dim: int = 768              # one routed expert's width
    shared_dim: int = 768
    moe_top_k: int = 8
    groups: Tuple[int, int] = (8, 4)    # (n_group, topk_group)
    route_scale: float = 2.5
    max_position: int = 131072
    # the latent norm's weights are drawn uniform in this range
    # (glm_mla.GlmMlaConfig.latent_norm_init)
    latent_norm_init: Tuple[float, float] = (1.0, 1.0)

    @staticmethod
    def tiny() -> "LingConfig":
        return LingConfig(
            vocab_size=256, dim=64, num_layers=4, layer_group_size=3,
            num_heads=2, head_dim=16, kda_chunk=16, kv_lora_rank=128,
            qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
            rope_theta=10000.0, first_dense=1, dense_ffn_dim=96,
            num_experts=16, experts_held=(0, 1, 2, 5), ffn_dim=32,
            shared_dim=32, moe_top_k=3, groups=(4, 2), max_position=4096,
            latent_norm_init=(2.0, 3.0))

    def is_mla(self, i: int) -> bool:
        return (i + 1) % self.layer_group_size == 0

    @property
    def kda_width(self) -> int:
        return self.num_heads * self.head_dim

    def mla_config(self) -> GlmMlaConfig:
        """The MLA layer's widths, as ``glm_mla._MLA`` reads them."""
        return GlmMlaConfig(
            dim=self.dim, num_heads=self.num_heads, q_lora_rank=None,
            kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, rope_theta=self.rope_theta,
            eps=self.eps, max_position=self.max_position,
            latent_norm_init=self.latent_norm_init)


def _tensor(a, like: Tensor) -> Tensor:
    return Tensor(data=a, device=like.device, requires_grad=False)


class _KDA(layer.Layer):
    def __init__(self, cfg: LingConfig, name=None):
        super().__init__(name)
        c = self.cfg = cfg
        self.qkv = layer.Linear(3 * c.kda_width, bias=False)
        self.b = layer.Linear(c.num_heads, bias=False)
        self.f = layer.Linear(c.kda_width, bias=False)
        self.g = layer.Linear(c.kda_width, bias=False)
        self.o = layer.Linear(c.dim, bias=False)

    def initialize(self, x: Tensor, *_):
        c, dev = self.cfg, x.device
        H = c.num_heads

        def new(shape):
            return Tensor(shape, dev, np.float32)

        def derived(a):
            return Tensor(data=a, device=dev)

        # a convolution's output keeps its input's variance
        self.conv_w = self.register_param(
            "conv_w", new((c.conv_size, 3 * c.kda_width)).gaussian(
                0.0, c.conv_size ** -0.5))
        # drawn so that the state matters: a channel's mean decay
        # exp(lower * sigmoid(A dt_bias)) forgets over _DECAY_TOKENS
        # tokens, and A is small enough that x W_f moves it by a factor
        # of a few a token, not from nothing to everything
        gain = new((H,)).uniform(*_DECAY_GAIN).data
        self.A_log = self.register_param("A_log", derived(jnp.log(gain)))
        lo, hi = (math.log(1.0 / n) for n in reversed(_DECAY_TOKENS))
        share = jnp.exp(new((c.kda_width,)).uniform(lo, hi).data) \
            / -c.kda_lower_bound
        self.dt_bias = self.register_param("dt_bias", derived(
            (jnp.log(share) - jnp.log1p(-share))
            / jnp.repeat(gain, c.head_dim)))
        self.o_norm = self.register_param(
            "o_norm", new((c.head_dim,)).set_value(1.0))

    def forward(self, x: Tensor, cache=None, pos=0, state_rows=None):
        del pos             # the state carries the order
        with jax.named_scope("kda"):
            return self._forward(x, cache, state_rows)

    def _forward(self, x: Tensor, cache, state_rows):
        c = self.cfg
        B, T, _ = x.shape
        H, d, w = c.num_heads, c.head_dim, c.kda_width
        f32 = lambda a: a.astype(jnp.float32)
        with jax.named_scope("kda.proj"):
            h = self.qkv(x).data
            beta = jax.nn.sigmoid(f32(self.b(x).data))
            g = kda_ops.kda_gate(self.f(x).data.reshape(B, T, H, d),
                                 self.A_log.data, self.dt_bias.data,
                                 c.kda_lower_bound)
        if cache is None:
            S = jnp.zeros((B, H, d, d), jnp.float32)
            window = jnp.zeros((B, c.conv_size - 1, 3 * w), h.dtype)
        else:
            S, window = cache[2], cache[3]
        with jax.named_scope("kda.conv"):
            h, window = ssm_ops.causal_conv(
                h, window, self.conv_w.data, jnp.zeros((), jnp.float32),
                state_rows)
            q, k, v = (a.reshape(B, T, H, d) for a in jnp.split(h, 3, -1))
            q = kda_ops.l2_normalize(q) * d ** -0.5
            k = kda_ops.l2_normalize(k)
        if T == 1 and cache is not None and state_rows is None:
            with jax.named_scope("kda.decode"):
                step = kda_ops.kda_decode \
                    if kda_ops.reads_state_in_place(S) else kda_ops.kda_step
                o, S = step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], S)
                o = o[:, None]
        else:
            with jax.named_scope("kda.prefill"):
                o, S = kda_ops.kda_chunked(q, k, v, g, beta, S, state_rows,
                                           c.kda_chunk)
        with jax.named_scope("kda.out"):
            o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + c.eps)
            o = o * f32(self.o_norm.data) \
                * jax.nn.sigmoid(f32(self.g(x).data)).reshape(B, T, H, d)
            out = self.o(_tensor(o.reshape(B, T, w).astype(x.data.dtype), x))
        return out if cache is None else (out, (None, None, S, window))


class _LingBlock(layer.Layer):
    def __init__(self, cfg: LingConfig, i: int, name=None):
        super().__init__(name)
        self.mla = cfg.is_mla(i)
        self.attn_norm = layer.RMSNorm(cfg.dim, eps=cfg.eps)
        self.attn = _MLA(cfg.mla_config(), head_gate=True) if self.mla \
            else _KDA(cfg)
        self.ffn_norm = layer.RMSNorm(cfg.dim, eps=cfg.eps)
        self.dense = i < cfg.first_dense
        if self.dense:
            self.mlp = _SwiGLU(cfg.dim, cfg.dense_ffn_dim, "mlp.dense")
        else:
            self.ffn = layer.MoE(cfg.num_experts, ffn_dim=cfg.ffn_dim,
                                 top_k=cfg.moe_top_k, act="swiglu",
                                 dropless=True,
                                 experts_held=cfg.experts_held,
                                 score="sigmoid", groups=cfg.groups,
                                 route_scale=cfg.route_scale)
            self.shared = _SwiGLU(cfg.dim, cfg.shared_dim, "moe.shared")

    def forward(self, x, cache=None, pos=0, state_rows=None):
        h = self.attn_norm(x)
        a = self.attn(h, cache, pos) if self.mla \
            else self.attn(h, cache, pos, state_rows)
        if cache is not None:
            a, cache = a
        x = _tensor(x.data + a.data, x)
        h = self.ffn_norm(x)
        f = self.mlp(h).data if self.dense \
            else self.ffn(h).data + self.shared(h).data
        x = _tensor(x.data + f, x)
        return x if cache is None else (x, cache)


class Ling(GenerateMixin, model.Model):
    #: a decode tick's state update writes every slot's state in place
    #: (``ops/kda.py``'s kernel): the engine hands it the idle slots'
    #: state unmasked, which nothing reads before a prefill rewrites it
    updates_state_in_place = True

    def __init__(self, cfg: Optional[LingConfig] = None, **kw):
        super().__init__()
        self.cfg = cfg or LingConfig(**kw)
        c = self.cfg
        self.tok_emb = layer.Embedding(c.vocab_size, c.dim)
        self.blocks = [_LingBlock(c, i) for i in range(c.num_layers)]
        self.norm_f = layer.RMSNorm(c.dim, eps=c.eps)
        self.lm_head = layer.Linear(c.vocab_size, bias=False)

    @property
    def latent_heads(self) -> int:
        """Query heads of the MLA layers (``GlmMla.latent_heads``)."""
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
        """Per layer what the layer needs: ``(c_kv, k_rope)`` (B,
        max_len, kv_lora_rank) and (B, max_len, rope_lanes) for an MLA
        layer, ``(None, None, S, window)`` for a KDA layer, S (B, H, dk,
        dv) in f32, the window (B, conv_size - 1, 3 H dk); the rest in
        the embedding's dtype."""
        c = self.cfg
        dtype = jnp.bfloat16 if self.tok_emb.table.dtype == jnp.bfloat16 \
            else jnp.float32
        lanes = c.mla_config().rope_lanes
        return [(jnp.zeros((batch, max_len, c.kv_lora_rank), dtype),
                 jnp.zeros((batch, max_len, lanes), dtype))
                if c.is_mla(i) else
                (None, None,
                 jnp.zeros((batch, c.num_heads, c.head_dim, c.head_dim),
                           jnp.float32),
                 jnp.zeros((batch, c.conv_size - 1, 3 * c.kda_width), dtype))
                for i in range(c.num_layers)]

    @property
    def prefill_kernel(self) -> bool:
        """As ``GlmMla.prefill_kernel``: whether the latest traced cached
        forward's latent attention took ``ops.mla_attention``'s prefill
        kernel."""
        return any(b.attn.took_kernel for b in self.blocks if b.mla)

    def forward_cached(self, ids: Tensor, caches, pos, state_rows=None):
        """As every decoder's here; ``state_rows`` (R,) asks for each KDA
        layer's state after those rows of ``ids`` instead of after the
        last: (B, R, ...) in the returned caches."""
        x = self.tok_emb(ids)
        new_caches = []
        for blk, cache in zip(self.blocks, caches):
            x, nc = blk(x, cache, pos, state_rows)
            new_caches.append(nc)
        return self._head(x), new_caches

    def train_one_batch(self, *_):
        raise NotImplementedError(
            "Ling is inference only: its mixers carry no gradient")

    def num_params(self) -> int:
        return sum(p.size for p in self.get_params().values())
