"""Granite 4.0-H (IBM; `granitemoehybrid`): a decoder whose token mixer
is, layer by layer, a Mamba-2 state-space layer or grouped-query
attention without positional embedding, over a mixture of experts with
a shared MLP beside it, four scalar multipliers on embedding, residual,
attention and logits, and the output head tied to the embedding.

``h = tok_emb[ids] * embedding_multiplier``; per layer ``h +=
residual_multiplier * Mixer(RMSNorm(h))``, then with ``x = RMSNorm(h)``,
``h += residual_multiplier * (MoE(x) + Shared(x))``; ``logits =
RMSNorm(h) @ tok_emb.T / logits_scaling``.

* ``attention``: q, k, v without bias, no rotary or other positional
  embedding (the causal mask and the state-space layers around it
  carry order), softmax of ``q.k * attention_multiplier``.
* ``mamba`` (Mamba-2, ``ops/ssm.py``): ``z, xBC, dt = split(x W_in)``;
  ``xBC`` through a depthwise causal convolution of ``mamba_d_conv``
  taps and silu; ``x, B, C = split(xBC)``; ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; the scan with an f32 state of
  ``heads x head_dim x d_state`` a sequence; ``y = RMSNorm(y *
  silu(z)) * w``; ``y W_out``.  One group of B and C.
* ``MoE``: ``layer.MoE``, a linear router over ``num_experts``, top
  ``moe_top_k`` renormalised, gated silu experts, dropless;
  ``experts_held`` names the experts this chip holds where several
  chips divide a layer.  ``Shared``: one gated MLP every token passes.

The cache of a layer is ``(k, v, *state)`` as every decoder's here, and
a layer has what it needs: an attention layer ``(k, v)``, a mamba layer
``(None, None, S, window)`` with S (B, heads, head_dim, d_state) in f32
and the last ``d_conv - 1`` rows before the convolution (B, d_conv - 1,
conv_dim).  ``forward_cached(state_rows=)`` is ``models/zaya.py``'s:
the state after those rows of the chunk instead of after its last.

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

from .. import autograd, layer, model
from ..ops import kv_cache as kv_ops
from ..ops import ssm as ssm_ops
from ..tensor import Tensor
from ._generate import GenerateMixin

__all__ = ["GraniteHybridConfig", "GraniteHybrid"]


@dataclass
class GraniteHybridConfig:
    vocab_size: int = 100352
    dim: int = 4096
    num_layers: int = 40
    # "mamba" or "attention" a layer; the published pattern is nine
    # mamba layers to one attention layer
    layer_types: Tuple[str, ...] = ()
    num_heads: int = 32
    num_kv_heads: int = 8
    head_size: int = 128
    attention_multiplier: float = 0.0078125
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    eps: float = 1e-5
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    num_experts: int = 72
    # ids of the experts this chip holds (None: all of them)
    experts_held: Optional[Tuple[int, ...]] = None
    ffn_dim: int = 768              # one expert's width
    shared_dim: int = 1536          # the shared MLP's width
    # read by ServeEngine's host-side count of routed assignments
    moe_top_k: int = 10
    max_position: int = 131072

    @staticmethod
    def tiny() -> "GraniteHybridConfig":
        return GraniteHybridConfig(
            vocab_size=256, dim=64, num_layers=3,
            layer_types=("mamba", "attention", "mamba"), num_heads=4,
            num_kv_heads=2, head_size=16, attention_multiplier=1.0 / 16,
            mamba_heads=4, mamba_head_dim=32, mamba_d_state=16,
            mamba_chunk_size=16, num_experts=8, experts_held=(0, 1, 2),
            ffn_dim=32, shared_dim=48, moe_top_k=3, max_position=4096)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_d_state

    def layer_type(self, i: int) -> str:
        if len(self.layer_types) != self.num_layers:
            raise ValueError(
                f"layer_types has {len(self.layer_types)} entries for "
                f"{self.num_layers} layers")
        kind = self.layer_types[i]
        if kind not in ("mamba", "attention"):
            raise ValueError(f"unknown layer type {kind!r}")
        return kind


def _tensor(a, like: Tensor) -> Tensor:
    return Tensor(data=a, device=like.device, requires_grad=False)


class _NopeAttention(layer.Layer):
    def __init__(self, cfg: GraniteHybridConfig, name=None):
        super().__init__(name)
        c = self.cfg = cfg
        self.q_proj = layer.Linear(c.num_heads * c.head_size, bias=False)
        self.k_proj = layer.Linear(c.num_kv_heads * c.head_size, bias=False)
        self.v_proj = layer.Linear(c.num_kv_heads * c.head_size, bias=False)
        self.o_proj = layer.Linear(c.dim, bias=False)

    def forward(self, x: Tensor, cache=None, pos=0, state_rows=None):
        del state_rows      # no state beside the keys and values
        c = self.cfg
        B, T, _ = x.shape
        with jax.named_scope("attn.full"):
            q = self.q_proj(x).data.reshape(B, T, c.num_heads, c.head_size)
            k = self.k_proj(x).data.reshape(B, T, c.num_kv_heads, c.head_size)
            v = self.v_proj(x).data.reshape(B, T, c.num_kv_heads, c.head_size)
            if cache is None:
                o = kv_ops.cached_sdpa(q, k, v, limit=T,
                                       scale=c.attention_multiplier)
            else:
                k, v = kv_ops.update_cache(cache[0], cache[1], k, v, pos)
                o = kv_ops.cached_sdpa(q, k, v, limit=pos + T,
                                       scale=c.attention_multiplier)
            out = self.o_proj(_tensor(
                o.reshape(B, T, c.num_heads * c.head_size), x))
        return out if cache is None else (out, (k, v))


class _Mamba2(layer.Layer):
    def __init__(self, cfg: GraniteHybridConfig, name=None):
        super().__init__(name)
        c = self.cfg = cfg
        self.in_proj = layer.Linear(c.d_inner + c.conv_dim + c.mamba_heads,
                                    bias=False)
        self.out_proj = layer.Linear(c.dim, bias=False)

    def initialize(self, x: Tensor, *_):
        c, dev = self.cfg, x.device
        H, K = c.mamba_heads, c.mamba_d_conv

        def new(shape):
            return Tensor(shape, dev, np.float32)

        def derived(a):
            return Tensor(data=a, device=dev)

        # a convolution's output keeps its input's variance; the bias
        # starts off non-zero so that random weights exercise it
        self.conv_w = self.register_param(
            "conv_w", new((K, c.conv_dim)).gaussian(0.0, K ** -0.5))
        self.conv_b = self.register_param(
            "conv_b", new((c.conv_dim,)).gaussian(0.0, 0.02))
        # drawn so that the state matters: a head forgets over
        # 1 / (dt |A|) tokens, here 1 to 1,000 (A in [1, 16], dt
        # log-uniform in [1e-3, 0.1]); dt_bias is dt's inverse softplus
        self.A_log = self.register_param(
            "A_log", derived(jnp.log(new((H,)).uniform(1.0, 16.0).data)))
        dt = jnp.exp(new((H,)).uniform(math.log(1e-3), math.log(0.1)).data)
        self.dt_bias = self.register_param(
            "dt_bias", derived(dt + jnp.log(-jnp.expm1(-dt))))
        self.D = self.register_param("D", new((H,)).set_value(1.0))
        self.norm_w = self.register_param(
            "norm_w", new((c.d_inner,)).set_value(1.0))

    def forward(self, x: Tensor, cache=None, pos=0, state_rows=None):
        del pos             # the state carries the order
        with jax.named_scope("ssm"):
            return self._forward(x, cache, state_rows)

    def _forward(self, x: Tensor, cache, state_rows):
        c = self.cfg
        B, T, _ = x.shape
        H, P, N, di = c.mamba_heads, c.mamba_head_dim, c.mamba_d_state, \
            c.d_inner
        f32 = lambda a: a.astype(jnp.float32)
        with jax.named_scope("ssm.in_proj"):
            h = self.in_proj(x).data
            z, xBC, dt = jnp.split(h, [di, di + c.conv_dim], axis=-1)
        if cache is None:
            S = jnp.zeros((B, H, P, N), jnp.float32)
            window = jnp.zeros((B, c.mamba_d_conv - 1, c.conv_dim), h.dtype)
        else:
            S, window = cache[2], cache[3]
        with jax.named_scope("ssm.conv"):
            xBC, window = ssm_ops.causal_conv(
                xBC, window, self.conv_w.data, self.conv_b.data, state_rows)
            xs, Bm, Cm = jnp.split(xBC, [di, di + N], axis=-1)
        dt = jax.nn.softplus(f32(dt) + f32(self.dt_bias.data))
        A, D = -jnp.exp(f32(self.A_log.data)), f32(self.D.data)
        if T == 1 and cache is not None and state_rows is None:
            with jax.named_scope("ssm.step"):
                y, S = ssm_ops.ssm_step(xs[:, 0].reshape(B, H, P), dt[:, 0],
                                        A, Bm[:, 0], Cm[:, 0], D, S)
        else:
            with jax.named_scope("ssm.scan"):
                y, S = ssm_ops.ssd(xs.reshape(B, T, H, P), dt, A, Bm, Cm, D,
                                   S, state_rows, c.mamba_chunk_size)
        with jax.named_scope("ssm.norm"):
            g = f32(y.reshape(B, T, di)) * jax.nn.silu(f32(z))
            g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + c.eps)
            g = (g * f32(self.norm_w.data)).astype(h.dtype)
        with jax.named_scope("ssm.out_proj"):
            out = self.out_proj(_tensor(g, x))
        return out if cache is None else (out, (None, None, S, window))


class _SharedMLP(layer.Layer):
    def __init__(self, cfg: GraniteHybridConfig, name=None):
        super().__init__(name)
        self.gate = layer.Linear(cfg.shared_dim, bias=False)
        self.up = layer.Linear(cfg.shared_dim, bias=False)
        self.down = layer.Linear(cfg.dim, bias=False)

    def forward(self, x):
        with jax.named_scope("moe.shared"):
            return self.down(autograd.silu(self.gate(x)) * self.up(x))


class _GraniteBlock(layer.Layer):
    def __init__(self, cfg: GraniteHybridConfig, kind: str, name=None):
        super().__init__(name)
        self.cfg = cfg
        self.mixer_norm = layer.RMSNorm(cfg.dim, eps=cfg.eps)
        self.mixer = _Mamba2(cfg) if kind == "mamba" else _NopeAttention(cfg)
        self.ffn_norm = layer.RMSNorm(cfg.dim, eps=cfg.eps)
        self.ffn = layer.MoE(cfg.num_experts, ffn_dim=cfg.ffn_dim,
                             top_k=cfg.moe_top_k, act="swiglu", dropless=True,
                             experts_held=cfg.experts_held)
        self.shared = _SharedMLP(cfg)

    def forward(self, x, cache=None, pos=0, state_rows=None):
        r = self.cfg.residual_multiplier
        a = self.mixer(self.mixer_norm(x), cache, pos, state_rows)
        if cache is not None:
            a, cache = a
        x = _tensor(x.data + r * a.data, x)
        h = self.ffn_norm(x)
        x = _tensor(x.data + r * (self.ffn(h).data + self.shared(h).data), x)
        return x if cache is None else (x, cache)


class GraniteHybrid(GenerateMixin, model.Model):
    def __init__(self, cfg: Optional[GraniteHybridConfig] = None, **kw):
        super().__init__()
        self.cfg = cfg or GraniteHybridConfig(**kw)
        c = self.cfg
        self.tok_emb = layer.Embedding(c.vocab_size, c.dim)
        self.blocks = [_GraniteBlock(c, c.layer_type(i))
                       for i in range(c.num_layers)]
        self.norm_f = layer.RMSNorm(c.dim, eps=c.eps)

    def _embed(self, ids: Tensor) -> Tensor:
        x = self.tok_emb(ids)
        return _tensor(x.data * self.cfg.embedding_multiplier, x)

    def _head(self, x: Tensor) -> Tensor:
        """Logits through the head tied to the embedding (the one array
        `tok_emb.table`, read across its rows), over `logits_scaling`."""
        with jax.named_scope("lm_head.tied"):
            h = self.norm_f(x).data
            logits = jnp.einsum("btd,vd->btv", h,
                                self.tok_emb.table.data.astype(h.dtype))
        return _tensor(logits / self.cfg.logits_scaling, x)

    def forward(self, ids: Tensor) -> Tensor:
        x = self._embed(ids)
        for blk in self.blocks:
            x = blk(x)
        return self._head(x)

    def init_caches(self, batch: int, max_len: int):
        """Per layer what the layer needs: ``(k, v)`` (B, max_len, Hkv,
        d) in the embedding's dtype for an attention layer; ``(None,
        None, S, window)`` for a mamba layer, S (B, heads, head_dim,
        d_state) in f32 whatever the weights are, the convolution's
        window (B, d_conv - 1, conv_dim) in the embedding's dtype."""
        c = self.cfg
        dtype = jnp.bfloat16 if self.tok_emb.table.dtype == jnp.bfloat16 \
            else jnp.float32
        kv = (batch, max_len, c.num_kv_heads, c.head_size)
        return [(jnp.zeros(kv, dtype), jnp.zeros(kv, dtype))
                if c.layer_type(i) == "attention" else
                (None, None,
                 jnp.zeros((batch, c.mamba_heads, c.mamba_head_dim,
                            c.mamba_d_state), jnp.float32),
                 jnp.zeros((batch, c.mamba_d_conv - 1, c.conv_dim), dtype))
                for i in range(c.num_layers)]

    def forward_cached(self, ids: Tensor, caches, pos, state_rows=None):
        """As every decoder's here; ``state_rows`` (R,) asks for each
        mamba layer's state after those rows of ``ids`` instead of
        after the last: (B, R, ...) in the returned caches."""
        x = self._embed(ids)
        new_caches = []
        for blk, cache in zip(self.blocks, caches):
            x, nc = blk(x, cache, pos, state_rows)
            new_caches.append(nc)
        return self._head(x), new_caches

    def train_one_batch(self, *_):
        raise NotImplementedError(
            "GraniteHybrid is inference only: its mixers carry no gradient")

    def num_params(self) -> int:
        return sum(p.size for p in self.get_params().values())
