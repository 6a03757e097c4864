"""Batched serving engine: prefill + decode with jitted step functions.

Serves a single model (codistillation is a *training* mechanism — one of its
selling points, Section 6.6, is that only one model is needed at inference).
Supports greedy and temperature sampling, batched requests of equal prompt
length, and — via ``prompt_lens`` — ragged batches of MIXED prompt lengths:
rows are prefilled in exact-length groups (no pad token ever enters a cache
or a recurrent state) and then decoded together with per-row cache positions.
Ragged batched generation is token-identical to per-request generation at
temperature 0 — the invariant the continuous-batching fleet
(``repro.serve.fleet``) is built on.

The fleet layer scales this engine out: many engines (one per codistilled
peer) behind a router, each running a continuous batcher over a paged KV
pool instead of the dense per-call cache used here.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

PyTree = Any


def default_cache_dtype():
    """bf16 KV/state caches on TPU (halves HBM for the dominant serving
    tensor); fp32 in interpret/CPU mode where bf16 emulation is slow and
    tests want reference numerics."""
    return jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32


def resolve_cache_dtype(name: Optional[str]):
    """CLI spelling -> dtype; None/'auto' defers to the backend default.

    Quantized spellings (``int8``, ``fp8``/``float8_e4m3fn``) resolve to
    paged-pool storage dtypes — only the fleet engine serves them (the
    dense ``Engine`` cache is never quantized).
    """
    if name is None or name == "auto":
        return default_cache_dtype()
    table = {"bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
             "fp32": jnp.float32, "float32": jnp.float32,
             "fp16": jnp.float16, "float16": jnp.float16,
             "int8": jnp.int8,
             "fp8": jnp.float8_e4m3fn, "float8_e4m3fn": jnp.float8_e4m3fn}
    if name not in table:
        raise ValueError(f"unknown cache dtype {name!r}; "
                         f"valid names: auto, {', '.join(table)}")
    return table[name]


@dataclass
class GenerationResult:
    tokens: jax.Array        # (B, prompt+generated)
    prompt_len: int
    logprobs: Optional[jax.Array] = None
    # ragged batches: per-row true prompt lengths (tokens[r, :prompt_lens[r]]
    # is the prompt, tokens[r, prompt_len:] the generated continuation)
    prompt_lens: Optional[List[int]] = None


class Engine:
    def __init__(self, model, params: PyTree, cache_dtype=None):
        from repro.kernels.paged_cache import is_quantized_dtype
        self.model = model
        self.params = params
        self.cache_dtype = (default_cache_dtype() if cache_dtype is None
                            else cache_dtype)
        if is_quantized_dtype(self.cache_dtype):
            raise ValueError(
                f"cache_dtype {jnp.dtype(self.cache_dtype).name} is a "
                "quantized paged-pool dtype: only the fleet engine "
                "(repro.serve.fleet) serves quantized KV — the dense "
                "Engine cache supports bf16/fp16/fp32")
        self._prefill = jax.jit(self._prefill_impl, static_argnums=(2,))
        self._decode = jax.jit(self._decode_impl)

    # -- jitted internals ----------------------------------------------------
    def _prefill_impl(self, params, batch, cap):
        return self.model.prefill(params, batch, cap,
                                  cache_dtype=self.cache_dtype)

    def _decode_impl(self, params, cache, tokens, pos):
        return self.model.decode(params, cache, tokens, pos)

    # -- public API ------------------------------------------------------------
    def generate(self, batch: Dict, max_new_tokens: int,
                 temperature: float = 0.0, seed: int = 0,
                 prompt_lens: Optional[List[int]] = None) -> GenerationResult:
        """batch: model inputs incl. 'tokens' (B, prompt_len) prompts.

        ``prompt_lens``: per-row true lengths for a RIGHT-PADDED mixed-length
        batch — row r's prompt is ``tokens[r, :prompt_lens[r]]``; pad columns
        are ignored entirely (grouped exact-length prefill + per-row decode
        positions), so output tokens match per-request generation.
        """
        if prompt_lens is not None:
            return self._generate_ragged(batch, max_new_tokens, temperature,
                                         seed, prompt_lens)
        prompt = batch["tokens"]
        b, prompt_len = prompt.shape
        # VLM: the patch prefix occupies cache slots before the prompt
        prefix = getattr(self.model.cfg, "num_patches", 0) or 0
        if "patches" not in batch:
            prefix = 0
        cap = prefix + prompt_len + max_new_tokens
        logits, cache = self._prefill(self.params, batch, cap)
        key = jax.random.key(seed)
        out_tokens = [prompt]
        tok = self._select(logits[:, -1], temperature, key)
        out_tokens.append(tok)
        for i in range(1, max_new_tokens):
            pos = jnp.asarray(prefix + prompt_len + i - 1, jnp.int32)
            logits, cache = self._decode(self.params, cache, tok, pos)
            key, sub = jax.random.split(key)
            tok = self._select(logits[:, -1], temperature, sub)
            out_tokens.append(tok)
        return GenerationResult(jnp.concatenate(out_tokens, axis=1), prompt_len)

    def _generate_ragged(self, batch: Dict, max_new_tokens: int,
                         temperature: float, seed: int,
                         prompt_lens: List[int]) -> GenerationResult:
        assert "patches" not in batch and "frames" not in batch, \
            "ragged batching supports token-only LM inputs"
        assert getattr(self.model.cfg, "sliding_window", 0) <= 0, \
            "ragged batching needs a full-length cache (no ring buffer)"
        prompt = batch["tokens"]
        b, max_len = prompt.shape
        lens = [int(x) for x in prompt_lens]
        assert len(lens) == b and all(1 <= l <= max_len for l in lens), \
            (lens, prompt.shape)
        cap = max_len + max_new_tokens

        # group rows by true length: each group prefills its EXACT-length
        # slice (pads never enter attention caches or recurrent states)
        groups: Dict[int, List[int]] = {}
        for r, l in enumerate(lens):
            groups.setdefault(l, []).append(r)
        order: List[int] = []
        caches, first_logits = [], []
        for l in sorted(groups):
            rows = groups[l]
            order.extend(rows)
            pb = {"tokens": prompt[jnp.asarray(rows), :l]}
            logits, cache = self._prefill(self.params, pb, cap)
            caches.append(cache)
            first_logits.append(logits[:, -1])
        # merge the group caches along the batch axis, back to row order
        inv = jnp.argsort(jnp.asarray(order))
        cache = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=1)[:, inv], *caches)
        logits_last = jnp.concatenate(first_logits, axis=0)[inv]

        key = jax.random.key(seed)
        tok = self._select(logits_last, temperature, key)
        gen = [tok]
        lens_arr = jnp.asarray(lens, jnp.int32)
        for i in range(1, max_new_tokens):
            pos = lens_arr + (i - 1)  # per-row absolute position of `tok`
            logits, cache = self._decode(self.params, cache, tok, pos)
            key, sub = jax.random.split(key)
            tok = self._select(logits[:, -1], temperature, sub)
            gen.append(tok)
        tokens = jnp.concatenate([prompt] + gen, axis=1)
        return GenerationResult(tokens, max_len, prompt_lens=lens)

    @staticmethod
    def _select(logits: jax.Array, temperature: float, key) -> jax.Array:
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        return jax.random.categorical(key, logits / temperature)[:, None].astype(jnp.int32)
