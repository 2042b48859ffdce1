"""Autoregressive generation for TransformerLM: jitted KV-cache prefill
+ a lax.scan decode loop (ONE device dispatch per generate call, not one
per token).

The train-time params are reused verbatim; only the config flips to
``decode=True`` (attention keeps per-layer KV caches sized max_seq_len).
Prompts are right-padded to a compile bucket with position id -1 — the
decode attention masks pad slots by cached position, so padding never
changes the numbers. Sampling: greedy (temperature=0), temperature, and
optional top-k, all inside the compiled loop.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .transformer import TransformerConfig, TransformerLM, init_cache


def pow2_bucket(n: int, cap: int) -> int:
    """The prompt/length compile-bucket policy (powers of two from 8,
    capped): ONE implementation, shared by the one-shot LMGenerator and
    the serving DecodeEngine — if the policies diverged, the engine's
    greedy outputs could stop matching the parity oracle's compiles."""
    b = 8
    while b < n:
        b *= 2
    return min(b, cap)


def prefill_chunks(tail_len: int, chunk: int, cap: int) -> list:
    """The chunked-prefill schedule for a ``tail_len``-token prompt
    tail: [(offset, length, bucket)] with every chunk ``chunk`` tokens
    except the remainder, each bucketed by ``pow2_bucket`` — all full
    chunks share ONE prefill compile and the tail chunk reuses the
    small-prompt buckets the engine already warms. This is the
    bucket-policy contract above extended to chunked admission: the
    DecodeEngine's prefill cursor walks exactly this schedule (same
    min/pow2_bucket math), and tests/bench derive expected dispatch
    counts and compile buckets from it."""
    out = []
    off = 0
    while off < tail_len:
        length = min(chunk, tail_len - off)
        out.append((off, length, pow2_bucket(length, cap)))
        off += length
    return out


def decode_config(cfg: TransformerConfig,
                  max_len: Optional[int] = None) -> TransformerConfig:
    """The serving-time decode variant of a train config: KV caches on,
    single-chip XLA attention (the decode step is one token — flash and
    the parallelism knobs are training-shape machinery). Shared by the
    one-shot LMGenerator and the continuous-batching DecodeEngine so
    the parity oracle and the engine compile the SAME model."""
    return dataclasses.replace(
        cfg, decode=True, remat=False, sp=False, cp=1, attn_impl="xla",
        max_seq_len=max_len or cfg.max_seq_len)


def _sample(logits: jnp.ndarray, rng, temperature, top_k) -> jnp.ndarray:
    """logits [B, V] -> token ids [B]: the one-row formula. temperature/
    top_k are TRACED scalars (sampling knobs never trigger a recompile —
    they are client-controlled on the serving path): temperature<=0
    selects greedy, top_k<=0 disables the top-k filter. A part that is
    not asked for is not built: ``rng`` None gives the argmax alone,
    ``top_k`` None the draw without the filter — the three forms
    ``sample_rows`` picks between, once a step."""
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if rng is None:
        return greedy
    scaled = logits / jnp.maximum(temperature, 1e-6)
    if top_k is not None:
        # k-th largest per row via a dynamic slice into the sorted row
        # (start index clamps when top_k <= 0, and the mask is disabled).
        srt = jnp.sort(scaled, axis=-1)
        kth = jax.lax.dynamic_slice_in_dim(
            srt, jnp.maximum(V - top_k, 0), 1, axis=-1)  # [B, 1]
        scaled = jnp.where((top_k > 0) & (scaled < kth), -jnp.inf, scaled)
    sampled = jax.random.categorical(rng, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


def sample_needs(temperature, top_k, live) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """What a step's rows ask of the sampler, as two scalars: whether
    any live row draws (temperature > 0) and whether one of those also
    filters (top_k > 0). ``live`` [B] gates both: a slot keeps a retired
    request's knobs, and its token is never emitted."""
    drawing = live & ~(temperature <= 0.0)
    return jnp.any(drawing), jnp.any(drawing & (top_k > 0))


@jax.named_scope("sample")  # the sampler's ops by name in a trace
def sample_rows(logits: jnp.ndarray, keys, temperature, top_k,
                live) -> jnp.ndarray:
    """logits [B, V], keys [B, 2], temperature/top_k/live [B] -> token
    ids [B]: ``_sample`` a row with its own key and knobs, in the form
    the step's live rows need. The choice is made once for the batch, on
    the device, OUTSIDE the vmap (a cond on a batched predicate lowers
    to a select that runs every side): all greedy -> the argmax and
    nothing else; draws but no top_k -> no vocabulary sort. Every live
    row gets the token the full form gives it, to the last bit: a greedy
    row is the argmax in every form, and with top_k <= 0 the full form's
    mask is off. Every form is ``_sample``'s, so what wraps that one
    function (benchmark/tests/broken_replica.py) sees every token."""
    draws, sorts = sample_needs(temperature, top_k, live)

    def rows(filtered: bool):
        return lambda: jax.vmap(
            lambda l, kk, t, tk: _sample(
                l[None], kk, t, tk if filtered else None)[0]
        )(logits, keys, temperature, top_k)

    return jax.lax.switch(
        draws.astype(jnp.int32) + sorts.astype(jnp.int32),
        [lambda: _sample(logits, None, None, None), rows(False), rows(True)])


class LMGenerator:
    """Owns the decode-mode model + compiled prefill/decode functions.

    Compile granularity: one (prompt_bucket, max_new_tokens) pair per
    jitted generate; buckets are powers of two so repeat traffic shares
    compiles (the serving layer pre-warms its buckets like JaxPredictor).
    """

    def __init__(self, cfg: TransformerConfig, params,
                 max_len: Optional[int] = None):
        self.cfg = decode_config(cfg, max_len)
        import jax as _jax

        # Device-commit once: params arrive as host numpy from the
        # export loaders, and a jit call does NOT cache host-array
        # transfers — without this every generate() would re-upload the
        # full tree (~1.9G at base) through the device link.
        self.params = _jax.device_put(params)
        self.model = TransformerLM(self.cfg)
        # Keyed (batch, prompt bucket, max_new bucket) — the sampling
        # knobs are TRACED arguments, never part of the compile key.
        self._compiled: Dict[Tuple[int, int, int], Callable[..., Any]] = {}

    # -- the compiled path --------------------------------------------------
    def _generate_fn(self, prompt_pad: int, max_new: int):
        """One compile per (batch, prompt bucket, max_new bucket);
        sampling knobs ride in as traced arrays. ``params`` is a jit
        ARGUMENT, never a closure: a closed-over param tree is embedded
        in the lowered program as constants — 1.9G of MLIR at the base
        preset, which broke the remote-compile transport (and bloated
        every compile's payload by the model size)."""
        model, cfg = self.model, self.cfg

        @jax.jit
        def run(params, tokens, true_len, rngs, temperature, top_k):
            """tokens [B, prompt_pad] (right-padded), true_len [B];
            rngs [B, 2], temperature [B], top_k [B]: a row's stream
            and knobs are its own, as a slot's are in the engine."""
            B = tokens.shape[0]
            pos = jnp.arange(prompt_pad, dtype=jnp.int32)[None, :]
            pos = jnp.where(pos < true_len[:, None], pos, -1)
            pos = jnp.broadcast_to(pos, tokens.shape)
            # Prefill into an empty cache (the layer scan carries it,
            # so it is made out here, not by the first apply).
            logits, vars_ = model.apply(
                {"params": params, "cache": init_cache(cfg, B)}, tokens,
                positions=pos, mutable=["cache"])
            cache = vars_["cache"]
            # The next-token context is the LAST REAL prompt token's
            # logits, not the pad tail's.
            last = jnp.take_along_axis(
                logits, (true_len - 1)[:, None, None].astype(jnp.int32),
                axis=1)[:, 0]  # [B, V]

            def step(carry, _):
                cache, prev_logits, cur_pos, rngs = carry
                split = jax.vmap(jax.random.split)(rngs)  # [B, 2, 2]
                rngs, sub = split[:, 0], split[:, 1]
                tok = sample_rows(prev_logits, sub, temperature, top_k,
                                  jnp.ones((B,), bool))
                logits, vars_ = model.apply(
                    {"params": params, "cache": cache}, tok[:, None],
                    positions=cur_pos[:, None], mutable=["cache"])
                return ((vars_["cache"], logits[:, 0], cur_pos + 1, rngs),
                        tok)

            init = (cache, last, true_len, rngs)
            _, toks = jax.lax.scan(step, init, None, length=max_new)
            return toks.T  # [B, max_new]

        return run

    # -- public -------------------------------------------------------------
    _bucket = staticmethod(pow2_bucket)

    def generate(self, prompts, max_new_tokens: int = 32,
                 temperature: float = 0.0, top_k: int = 0,
                 seed: int = 0) -> list:
        """prompts: list of token-id lists (any lengths). Returns a list
        of generated id lists (length max_new_tokens each)."""
        cap = self.cfg.max_seq_len
        longest = max(len(p) for p in prompts)
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        # max_new is bucketed (powers of two) so client-varied lengths
        # share compiles; the tail is sliced off after the scan.
        new_bucket = self._bucket(max_new_tokens, cap)
        if longest + new_bucket > cap:
            if longest + max_new_tokens > cap:
                raise ValueError(
                    f"prompt ({longest}) + max_new_tokens "
                    f"({max_new_tokens}) exceeds the cache capacity {cap}")
            new_bucket = max_new_tokens  # exact fit, no bucket headroom
        pad = self._bucket(longest, cap - new_bucket)
        B = len(prompts)
        tokens = np.zeros((B, pad), np.int32)
        true_len = np.zeros((B,), np.int32)
        for i, p in enumerate(prompts):
            tokens[i, :len(p)] = p
            true_len[i] = len(p)
        key = (B, pad, new_bucket)
        fn = self._compiled.get(key)
        if fn is None:
            fn = self._generate_fn(pad, new_bucket)
            self._compiled[key] = fn
        # Row i draws from seed + i, as DecodeEngine.generate seeds its
        # requests: the oracle's sampled rows are the engine's too.
        rngs = jax.vmap(jax.random.PRNGKey)(seed + jnp.arange(B))
        out = fn(self.params, jnp.asarray(tokens), jnp.asarray(true_len),
                 rngs, jnp.full((B,), temperature, jnp.float32),
                 jnp.full((B,), top_k, jnp.int32))
        return np.asarray(out)[:, :max_new_tokens].tolist()
