"""Autoregressive generation with a static KV cache (VERDICT r2 item 4).

TPU-native decode loop: one jitted `prefill` (prompt forward — flash
attention when the shape tiles — plus cache write) and one jitted
`decode` (Tq=1 against the full cache, position passed as a traced
scalar), so the per-token cost is O(S_max) and INDEPENDENT of how many
tokens have been generated — each decode step re-executes the same
compiled module with a different `pos` value.  Contrast with the r2
`examples/onnx/gpt2.py` loop, which re-ran the full fixed-length
forward per token (O(P^2) total).

Parameters are threaded through jit as arguments (same rebinding
pattern as model._StepExecutor._traced_step) so weights are NOT baked
into the executable as constants.
"""

from __future__ import annotations

import functools
from contextlib import contextmanager
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import autograd
from ..tensor import Tensor

__all__ = ["GenerateMixin", "prefill_step", "decode_step", "resume_step"]


@contextmanager
def _bound(model, params: Dict, buffers: Dict):
    from .. import tensor as tensor_mod
    ptens = model.get_params()
    btens = model._get_buffers()
    saved_p = {n: t.data for n, t in ptens.items()}
    saved_b = {n: t.data for n, t in btens.items()}
    saved_training = autograd.is_training()
    saved_key = tensor_mod._rng_key   # any in-trace split must not leak
    autograd.set_training(False)
    try:
        for n, t in ptens.items():
            t.data = params[n]
        for n, t in btens.items():
            t.data = buffers[n]
        yield
    finally:
        autograd.set_training(saved_training)
        tensor_mod._rng_key = saved_key
        for n, t in ptens.items():
            t.data = saved_p[n]
        for n, t in btens.items():
            t.data = saved_b[n]


def prefill_step(model, total_len: int, last_only: bool = True):
    """Build the prompt-forward closure shared by `_GenSession` and the
    serving engine (serve.engine): (params, buffers, ids (B, P)) ->
    (logits, caches) with fresh (B, total_len) caches written for
    positions [0, P).  `last_only` returns just the last position's
    (B, V) logits (the generate() path); the engine keeps the full
    (B, P, V) block so it can gather at each request's true length
    inside its own jitted wrapper."""

    def prefill(params, buffers, ids):
        with _bound(model, params, buffers):
            t = Tensor(data=ids, device=_dev(model), requires_grad=False)
            logits, caches = model.forward_cached(
                t, caches=model.init_caches(ids.shape[0], total_len), pos=0)
        lg = logits.data
        return (lg[:, -1, :] if last_only else lg), caches

    return prefill


def decode_step(model):
    """Build the one-token decode closure shared by `_GenSession` and
    the serving engine: (params, buffers, tok (B, 1), pos, caches) ->
    (logits (B, V), caches).  `pos` may be a traced scalar (all rows at
    the same depth — generate()) or a traced (B,) vector (every slot at
    its own depth — serve.engine); the ops layer (rope offset, cache
    scatter, per-row attention limit) handles both inside ONE compiled
    program."""

    def decode(params, buffers, tok, pos, caches):
        with _bound(model, params, buffers):
            t = Tensor(data=tok, device=_dev(model), requires_grad=False)
            logits, caches = model.forward_cached(t, caches=caches,
                                                  pos=pos)
        return logits.data[:, 0, :], caches

    return decode


def resume_step(model, device=None):
    """Build the chunked-prefill closure of the paged serving engine
    (serve.engine): (params, buffers, ids (B, C), pos, caches) ->
    (logits (B, C, V), caches).  Unlike :func:`prefill_step` it takes
    the CALLER's caches and a traced scalar ``pos`` offset, so a prompt
    prefills as a sequence of fixed-(B, C) chunks — each chunk writes
    its k/v at [pos, pos+C) and attends the cache below ``pos + C``
    (``cached_sdpa``'s bottom-right-aligned causal window), which is
    what lets a shared-prefix request skip the chunks that are already
    resident in the arena.

    ``device``: the Device the token ids enter on (default: the
    model's).  Its ``default_dtype`` is the dtype the embedding casts
    the activations to, and every layer follows them: a device made
    with ``default_dtype=float32`` runs f32 weights in f32 on a TPU,
    where the model's own device would compute in bf16.

    ``state_rows`` (an int32 vector of rows of the chunk), for a model
    whose layers keep side state beside their KV cache
    (``models/zaya.py``): the returned caches then hold that state as it
    stood after each of those rows, not after the chunk's last."""

    def resume(params, buffers, ids, pos, caches, state_rows=None):
        rows = {} if state_rows is None else {"state_rows": state_rows}
        with _bound(model, params, buffers):
            t = Tensor(data=ids, device=device or _dev(model),
                       requires_grad=False)
            logits, caches = model.forward_cached(t, caches=caches,
                                                  pos=pos, **rows)
        return logits.data, caches

    return resume


class _GenSession:
    """Compiled prefill + whole-generation programs for one
    (batch, prompt, total) shape.

    `decode` is the single-token program (a building block for custom
    host-driven loops); `decode_all_fn` / `beam_all_fn` return
    whole-generation programs — pick/select + decode for all N tokens
    under ONE lax.scan, so generation is exactly two dispatches
    (prefill, decode_all) and one host fetch.  The per-token host
    round-trip a host-driven loop pays (fetch tok, enqueue next step)
    is paid once per generation instead of once per token."""

    def __init__(self, model, batch: int, prompt_len: int, total_len: int):
        self.model = model
        self.prompt_len = prompt_len
        self.total_len = total_len
        self._decode_all_cache: Dict = {}
        self._beam_all_cache: Dict = {}
        # prefill/decode closures shared with serve.engine (one source
        # of truth for the cached forward — the engine's greedy decode
        # is token-identical by construction)
        self.prefill = jax.jit(prefill_step(model, total_len))
        self.decode = jax.jit(decode_step(model), donate_argnums=(4,))

    def decode_all_fn(self, n: int, temperature: float,
                      top_k: Optional[int], top_p: Optional[float],
                      eos_id: Optional[int]):
        """Jitted (params, buffers, logits0, caches, rng) -> (B, n)
        tokens: the full pick→decode loop as one lax.scan.  Sampling
        controls are trace-time constants (same cache-key discipline as
        _pick's static_argnums).  eos semantics match the host loop:
        rows keep decoding until EVERY row has emitted eos, then the
        remaining positions emit eos."""
        key = (n, temperature, top_k, top_p, eos_id)
        fn = self._decode_all_cache.get(key)
        if fn is not None:
            return fn
        model, P = self.model, self.prompt_len

        def decode_all(params, buffers, logits0, caches, rng):
            def body(carry, _):
                logits, pos, caches, rng, done, stopped = carry
                rng, sub = jax.random.split(rng)
                tok = _pick_impl(logits, temperature, sub, top_k, top_p)
                if eos_id is not None:
                    tok = jnp.where(stopped, eos_id, tok)
                    done = done | (tok == eos_id)
                    stopped = jnp.all(done)
                tok = tok.astype(jnp.int32)

                # the final iteration's decode fills cache slot
                # total_len-1 and its logits go unused — still in bounds
                def step(args):
                    logits, caches = args
                    with _bound(model, params, buffers):
                        t = Tensor(data=tok[:, None], device=_dev(model),
                                   requires_grad=False)
                        nxt, caches = model.forward_cached(
                            t, caches=caches, pos=pos)
                    # canonical f32 carry: prefill and decode logits
                    # dtypes can differ (param_dtype casts), and scan /
                    # cond require a stable carry type
                    return nxt.data[:, 0, :].astype(jnp.float32), caches

                if eos_id is not None:
                    # once every row has finished, skip the forward
                    # entirely — the scan still iterates but each
                    # remaining tick is a no-op branch, preserving the
                    # old host loop's early-exit cost profile
                    logits, caches = jax.lax.cond(
                        stopped, lambda args: args, step, (logits, caches))
                else:
                    logits, caches = step((logits, caches))
                return (logits, pos + 1, caches, rng, done, stopped), tok

            B = logits0.shape[0]
            carry = (logits0.astype(jnp.float32),
                     jnp.asarray(P, jnp.int32), caches, rng,
                     jnp.zeros((B,), bool), jnp.asarray(False))
            _, toks = jax.lax.scan(body, carry, None, length=n)
            return jnp.swapaxes(toks, 0, 1)

        # no donate_argnums: caches are not among decode_all's outputs,
        # so XLA cannot alias them (it would just warn) — they die
        # inside the program after their last scan iteration anyway
        fn = jax.jit(decode_all)
        self._decode_all_cache[key] = fn
        return fn

    def beam_all_fn(self, n: int, num_beams: int, eos_id: Optional[int]):
        """Jitted (params, buffers, logits0, caches) ->
        (seqs (B,K,n), scores (B,K), done (B,K), gen_len (B,K)): the
        full beam-search loop — select, beam bookkeeping, cache
        reorder, decode — as one lax.scan.  Semantics mirror the old
        host-driven loop exactly: frozen beams expand only to eos at
        zero incremental score, the cache gather is skipped (runtime
        lax.cond) when every beam kept its slot, and once every beam of
        every row is done the remaining ticks are no-ops."""
        key = (n, num_beams, eos_id)
        fn = self._beam_all_cache.get(key)
        if fn is not None:
            return fn
        model, P, K = self.model, self.prompt_len, num_beams

        def beam_all(params, buffers, logits0, caches):
            BK = logits0.shape[0]
            B = BK // K
            offsets = (jnp.arange(B)[:, None] * K).astype(jnp.int32)
            arangeK = jnp.arange(K, dtype=jnp.int32)

            def tick(carry, i):
                logits, scores, caches, seqs, done, gen_len, stopped = carry
                beam_idx, tok, scores = _beam_select(
                    logits, scores, K,
                    done if eos_id is not None else None,
                    eos_id)
                gather = jnp.take_along_axis
                seqs = gather(seqs, beam_idx[:, :, None], axis=1)
                done = gather(done, beam_idx, axis=1)
                gen_len = gather(gen_len, beam_idx, axis=1)
                seqs = seqs.at[:, :, i].set(tok.astype(jnp.int32))
                if eos_id is not None:
                    # length counts the eos token itself, then freezes
                    gen_len = jnp.where(done, gen_len, i + 1)
                    done = done | (tok == eos_id)
                else:
                    gen_len = jnp.full_like(gen_len, i + 1)

                def advance(args):
                    logits, caches = args

                    def reorder(caches):
                        perm = (beam_idx + offsets).reshape(-1)
                        return _beam_reorder(caches, perm)

                    # skip the full-cache gather when every beam kept
                    # its own slot (always true at K=1)
                    caches = jax.lax.cond(
                        jnp.any(beam_idx != arangeK[None, :]),
                        reorder, lambda c: c, caches)
                    with _bound(model, params, buffers):
                        t = Tensor(data=tok.reshape(-1, 1).astype(
                            jnp.int32), device=_dev(model),
                            requires_grad=False)
                        nxt, caches = model.forward_cached(
                            t, caches=caches, pos=P + i)
                    return nxt.data[:, 0, :].astype(jnp.float32), caches

                if eos_id is not None:
                    # every beam of every row just finished: skip the
                    # reorder + decode, like the old host loop's break
                    stopped = jnp.all(done)
                    logits, caches = jax.lax.cond(
                        stopped, lambda args: args, advance,
                        (logits, caches))
                else:
                    logits, caches = advance((logits, caches))
                return (logits, scores, caches, seqs, done, gen_len,
                        stopped), None

            def body(carry, i):
                if eos_id is None:
                    return tick(carry, i)
                # all beams of all rows finished: every remaining tick
                # is a no-op (the old host loop broke here)
                stopped = carry[-1]
                carry, _ = jax.lax.cond(
                    stopped, lambda c, _i: (c, None), tick, carry, i)
                stopped = jnp.all(carry[4])
                return carry[:-1] + (stopped,), None

            # before the first expansion all K beams are identical:
            # only beam 0 may seed the frontier
            scores0 = jnp.full((B, K), -jnp.inf,
                               jnp.float32).at[:, 0].set(0.0)
            carry = (logits0.astype(jnp.float32), scores0, caches,
                     jnp.zeros((B, K, n), jnp.int32),
                     jnp.zeros((B, K), bool),
                     jnp.zeros((B, K), jnp.int32),
                     jnp.asarray(False))
            carry, _ = jax.lax.scan(body, carry,
                                    jnp.arange(n, dtype=jnp.int32))
            _, scores, _, seqs, done, gen_len, _ = carry
            return seqs, scores, done, gen_len

        fn = jax.jit(beam_all)
        self._beam_all_cache[key] = fn
        return fn


def _dev(model):
    from ..model import model_device
    return model_device(model)


def _pick_impl(logits, temperature: float, rng_key, top_k: Optional[int],
               top_p: Optional[float]):
    """Greedy (temperature 0) or sampled pick with optional top-k /
    nucleus (top-p) filtering.  The controls are trace-time constants
    (closed-over inside decode_all's scan body)."""
    if not temperature or temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    lg = logits.astype(jnp.float32) / temperature
    if top_k is not None and 0 < top_k < lg.shape[-1]:
        kth = jax.lax.top_k(lg, top_k)[0][:, -1:]
        lg = jnp.where(lg < kth, -jnp.inf, lg)
    if top_p is not None and 0.0 < top_p < 1.0:
        sorted_lg = jnp.sort(lg, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_lg, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative mass >= top_p (the
        # first token is always kept); the cutoff is the SMALLEST kept
        # logit — everything below it is masked
        keep = cum - probs < top_p
        cutoff = jnp.min(jnp.where(keep, sorted_lg, jnp.inf), axis=-1,
                         keepdims=True)
        lg = jnp.where(lg < cutoff, -jnp.inf, lg)
    return jax.random.categorical(rng_key, lg, axis=-1)


def _beam_select(logits, scores, k: int, done=None, eos_id=None):
    """One beam-search expansion (traced inside beam_all_fn's scan):
    combine the (B*K, V) next-token logits with the (B, K) running
    scores, flatten each batch's K*V candidates, and keep the top K.  A
    finished beam (done mask + eos_id) admits only eos at zero
    incremental cost, so its raw score freezes.  Returns
    (beam_idx (B,K), tok (B,K), new_scores (B,K))."""
    B, K = scores.shape
    V = logits.shape[-1]
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    lp = lp.reshape(B, K, V)
    if done is not None:
        eos_row = jnp.where(jnp.arange(V) == eos_id, 0.0, -jnp.inf)
        lp = jnp.where(done[:, :, None], eos_row, lp)
    cand = scores[:, :, None] + lp
    top, flat_idx = jax.lax.top_k(cand.reshape(B, K * V), k)
    return flat_idx // V, flat_idx % V, top


def _beam_reorder(caches, perm):
    """Gather the KV caches onto the surviving beams (batch axis 0);
    traced inside beam_all_fn's scan."""
    return jax.tree.map(lambda c: jnp.take(c, perm, axis=0), caches)


#: :meth:`GenerateMixin.greedy_margin` tolerance for bf16 logits: 4 ulp
#: at the magnitude of a top logit of a random-weight decoder (2..4,
#: where bf16 is spaced 2**-6 apart)
GREEDY_TOL_BF16 = 4 * 2.0 ** -6


class GenerateMixin:
    """Adds `generate()` to decoder models exposing
    `forward_cached(ids, caches, pos)` and `init_caches(batch, max_len)`."""

    def _gen_setup(self, prompt_ids, max_new_tokens: int, rows_mult: int,
                   param_dtype=None):
        """Shared session/validation preamble for generate/generate_beam:
        normalize the prompt, enforce max_position, fetch-or-compile the
        (rows, P, S) session, and snapshot params/buffers.

        `param_dtype` (e.g. jnp.bfloat16) casts the float params ONCE
        for the whole generation — decode is weight-read bound, so bf16
        weights halve the per-token HBM traffic vs streaming f32
        masters through the cast inside the step."""
        ids = np.asarray(prompt_ids)
        if ids.ndim == 1:
            ids = ids[None, :]
        B, P = ids.shape
        S = P + max_new_tokens
        max_pos = getattr(getattr(self, "cfg", None), "max_position", None)
        if max_pos is not None and S > max_pos:
            # positions past max_position would silently clamp inside jit
            # (embedding gather / RoPE-table dynamic_slice) — refuse loudly
            raise ValueError(
                f"prompt ({P}) + max_new_tokens ({max_new_tokens}) = {S} "
                f"exceeds the model's max_position ({max_pos})")
        sessions = getattr(self, "_gen_sessions", None)
        if sessions is None:
            sessions = self._gen_sessions = {}
        key = (B * rows_mult, P, S)
        sess = sessions.get(key)
        if sess is None:
            sess = sessions[key] = _GenSession(self, B * rows_mult, P, S)
        params = {n: t.data for n, t in self.get_params().items()}
        buffers = {n: t.data for n, t in self._get_buffers().items()}
        if param_dtype is not None:
            params = {n: (a.astype(param_dtype)
                          if jnp.issubdtype(a.dtype, jnp.floating) else a)
                      for n, a in params.items()}
        return ids, B, P, S, sess, params, buffers

    def generate(self, prompt_ids, max_new_tokens: int,
                 temperature: float = 0.0, seed: int = 0,
                 eos_id: Optional[int] = None, top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 param_dtype=None) -> np.ndarray:
        """Greedy (temperature=0) or sampled decoding, with optional
        top-k and/or nucleus (top-p) filtering when sampling.

        prompt_ids: int array (B, P). Always returns (B, P +
        max_new_tokens) — static shape. When `eos_id` is given and every
        row has emitted it, the remaining positions are filled with
        eos_id; per-row truncation is the caller's job.

        The whole pick→decode loop runs as ONE jitted lax.scan
        (sess.decode_all_fn): two dispatches and one host fetch per
        generation, independent of max_new_tokens — a host-driven
        per-token loop pays a device round-trip per token, which
        dominates on a remote-attached device."""
        ids, B, P, S, sess, params, buffers = self._gen_setup(
            prompt_ids, max_new_tokens, 1, param_dtype)
        rng = jax.random.PRNGKey(seed)

        logits, caches = sess.prefill(params, buffers,
                                      jnp.asarray(ids, jnp.int32))
        # normalize inert controls so they don't fragment the trace
        # cache: greedy ignores top_k/top_p entirely, and out-of-range
        # values are no-ops inside _pick_impl
        temp = float(temperature) if temperature and temperature > 0 \
            else 0.0
        vocab = logits.shape[-1] if hasattr(logits, "shape") else None
        if temp == 0.0 or not (top_k and 0 < top_k < (vocab or top_k + 1)):
            top_k = None
        if temp == 0.0 or not (top_p and 0.0 < top_p < 1.0):
            top_p = None
        fn = sess.decode_all_fn(max_new_tokens, temp, top_k, top_p,
                                eos_id)
        toks = fn(params, buffers, logits, caches, rng)
        return np.concatenate([np.asarray(ids, np.int32),
                               np.asarray(toks, np.int32)], axis=1)

    def greedy_margin(self, seq, prompt_len: int) -> float:
        """How far ``seq[prompt_len:]`` is from a greedy continuation of
        ``seq[:prompt_len]`` under this model's own forward: one
        teacher-forced pass over ``seq``, then the largest gap between
        a row's best logit and the logit of the token actually there.
        0.0 means every token is the arg-max.

        This is the stream check that survives bf16.  Two correct
        decoders (chunked prefill through the cache vs whole-prompt
        prefill) round differently there and flip near-ties, so their
        streams need not be equal — but each stays greedy up to a few
        ulp (:data:`GREEDY_TOL_BF16`).  On CPU/f32 the streams are
        bitwise equal and the tests assert that instead."""
        from .. import tensor

        seq = np.asarray(seq, np.int32).reshape(-1)
        # pad to a multiple of 128: few distinct shapes to compile, and
        # tile-aligned so a long sequence takes the flash path on chip
        # (causal: the padding cannot reach the rows that are read)
        pad = -seq.size % 128
        max_pos = getattr(getattr(self, "cfg", None), "max_position", None)
        if max_pos is not None:
            pad = min(pad, max_pos - seq.size)
        ids = np.concatenate([seq, np.zeros((pad,), np.int32)])[None]
        self.eval()
        logits = np.asarray(self(tensor.from_numpy(ids)).to_numpy(),
                            np.float32)[0]
        if not np.isfinite(logits).all():
            raise FloatingPointError("non-finite logits")
        rows = logits[prompt_len - 1:seq.size - 1]
        served = seq[prompt_len:]
        return float((rows.max(axis=-1)
                      - rows[np.arange(served.size), served]).max())

    def generate_beam(self, prompt_ids, max_new_tokens: int,
                      num_beams: int = 4, length_penalty: float = 1.0,
                      eos_id: Optional[int] = None,
                      return_scores: bool = False, param_dtype=None):
        """Beam-search decoding (static shapes: the K beams ride the
        batch axis, so the same compiled prefill as `generate` serves a
        (B*K)-row batch).  The whole search — expansion, beam
        bookkeeping, cache reorder, decode — runs as ONE jitted
        lax.scan (sess.beam_all_fn): two dispatches and one host fetch
        per search, independent of max_new_tokens.

        Once a beam emits `eos_id` its hypothesis is frozen: its only
        expansion is eos at zero cost, so its RAW cumulative score stays
        constant — but it remains in the single K-wide frontier and can
        still be evicted by K continuing candidates with higher raw
        scores (no separate finished-hypothesis pool, unlike e.g. the
        HF implementation).  `length_penalty` is applied only at the
        END, ranking the K survivors by cumulative logprob /
        length**length_penalty.  Returns the best survivor per batch
        row — shape (B, P + max_new_tokens), eos-padded; with
        `return_scores`, also the (B,) cumulative logprob of each
        returned hypothesis (its exact sum of chosen-token logprobs)."""
        K = int(num_beams)
        if K < 1:
            raise ValueError(f"num_beams must be >= 1, got {K}")
        ids, B, P, S, sess, params, buffers = self._gen_setup(
            prompt_ids, max_new_tokens, K, param_dtype)
        rep = np.repeat(ids, K, axis=0)                      # (B*K, P)
        logits, caches = sess.prefill(params, buffers,
                                      jnp.asarray(rep, jnp.int32))
        fn = sess.beam_all_fn(max_new_tokens, K, eos_id)
        seqs, scores, done, gen_len = (np.asarray(a) for a in fn(
            params, buffers, logits, caches))

        final = np.asarray(scores) / np.maximum(
            gen_len, 1).astype(np.float32) ** length_penalty
        best = final.argmax(axis=1)
        out = np.full((B, S), eos_id if eos_id is not None else 0,
                      np.int32)
        out[:, :P] = ids
        for b in range(B):
            n = int(gen_len[b, best[b]]) if eos_id is not None \
                else max_new_tokens
            out[b, P:P + n] = seqs[b, best[b], :n]
            if eos_id is not None and bool(done[b, best[b]]):
                out[b, P + n:] = eos_id
        if return_scores:
            raw = np.asarray(scores)
            return out, raw[np.arange(B), best]
        return out
