"""The frame of a served decoder — written once for every family.

A family (models/llama.py ... models/gdn_hybrid.py) writes its config, its
weights, its mixers and its ``layer``, and declares its cache kinds
(models/paged.py ``CacheKind``).  What a tick's layers share is
:func:`paged.tick`; what stands round the layers — the tick, the embedding,
the counters, the loop, the head — is :func:`forward`; and the two cached
entry points the serving engine and the tests call, ``apply_cached`` and
``greedy_cached``, are :func:`cached_pair` of the family's forward
(docs/serving.md#what-a-served-model-module-exports).
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

from . import layers as L
from . import paged


def forward(layer: Callable, logits: Callable,
            kinds: Tuple[paged.CacheKind, ...], params, tokens: jax.Array,
            cfg, cache: Any, tables: Any, lengths: jax.Array,
            n_new: jax.Array, head: Callable,
            counters: Tuple[str, ...] = (), **tick):
    """The tick's rows through the stack: (``head(t, logits, x)`` of the
    tick, the family's logits function and its rows' last hidden states
    ``[1, R, dim]``, under the ``head`` scope; cache[; counters]).

    ``layer(i, p, x, cache, t)`` is the family's: layer i with its weights
    ``p`` on the rows ``x`` -> ``(x, cache)``, and behind them its counters
    int32[len(counters) - 1] where the family counts (``counters``, its
    ``TICK_COUNTERS``: a first ``ticks`` and what each layer adds).
    ``kinds`` and ``tick`` (``max_seq``, ``reads``) are :func:`paged.tick`'s;
    the rows are ``cfg.max_tick_tokens``.  Everything but the attention's
    core is a token's own and runs on the tick's ROWS (paged.pack): the
    valid positions packed to the front when the engine promises fewer of
    them than the slab has positions, so that a prefill-wide tick does not
    push every slot's padding through every matrix."""
    t = paged.tick(kinds, cache, tables, lengths, n_new, tokens.shape[1],
                   rows=cfg.max_tick_tokens, **tick)
    with jax.named_scope("embed"):
        x = L.embedding(params["embed"], t.take(tokens)).astype(cfg.dtype)
    if counters:
        counted = jnp.zeros(len(counters) - 1, jnp.int32)
    for i, p in enumerate(params["layers"][:cfg.n_layers]):
        x, cache, *more = layer(i, p, x, cache, t)
        if counters:    # load_max too: a sum over the layers
            counted = counted + more[0]
    with jax.named_scope("head"):
        out = head(t, logits, x)
    if not counters:
        return out, cache
    return out, cache, jnp.concatenate([jnp.ones(1, jnp.int32), counted])


def greedy(logits: jax.Array, cfg=None) -> jax.Array:
    """The greedy token of logits ``[.., vocab]``: their float32 argmax, as
    int32.  Ties break identically on every rank (SPMD determinism).
    :func:`cached_pair`'s ``sample`` unless a family hands in its own rule,
    which may read the config."""
    return jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)


def cached_pair(family: Callable, read: bool = False,
                sample: Callable = greedy) -> Tuple[Callable, Callable]:
    """(``apply_cached``, ``greedy_cached``) of a family's forward
    ``family(params, tokens, cfg, cache, block_tables, lengths, n_new, head,
    **kw)`` (which hands ``head`` to :func:`forward`; ``kw`` is the family's
    own, as models/moe_llama.py's ``moe_fn``).

    ``apply_cached(params, tokens, cfg, cache, block_tables, lengths,
    n_new)``: mixed prefill/decode forward over the paged cache.  ``tokens``
    [S, C] int32 — slot s's next ``n_new[s]`` tokens (0 = inactive slot),
    starting at context length ``lengths[s]``; ``block_tables`` [S,
    max_blocks] int32 indexes the pool (-1 = unassigned), a dict by kind
    where the module declares cache kinds, like ``cache``.  Returns (logits
    [S, C, vocab], updated cache[, counters int32[len(TICK_COUNTERS)]]); the
    caller samples from position ``n_new[s] - 1``: logits are defined at
    VALID positions only (zero where ``cfg.max_tick_tokens`` left a position
    out of the packed rows).  Prefill a prompt in ceil(len/C) calls, then
    decode one token per call.  The logit-level contract, which tests and
    references hold the model to; the serving engine's one jit'd tick
    (horovod_tpu/serve/engine.py), which donates ``cache`` — the stacked
    pools go through the layers whole —, asks for the tokens it reads and
    no logits:

    ``greedy_cached``, in one of two forms (serve/engine.py ``samples_read``
    tells them by the signature).  With ``read``:
    ``greedy_cached(.., n_new, read)`` -> (tokens int32 [S, W], cache, ..),
    the greedy token after column ``read[s, j]`` of slot s — ``sample`` of
    the logits row ``apply_cached`` has there.  ``read`` [S, W] int32 names
    the columns the tick reads (inside ``0 .. C-1``; ``tick_program``); the
    final norm, the head and the argmax run on those ``S * W`` rows alone
    (paged.Slab.at), so a chunk-wide tick builds neither ``[S, C, vocab]``
    nor ``[R, vocab]``.  A column past ``n_new[s]``, or one the pack left
    out, yields a token nobody may use.  Without: ``greedy_cached(..,
    n_new)`` -> (``sample`` of every position [S, C], cache, ..), taken on
    the packed rows ``[1, R, vocab]``; what comes back to the slab is an id
    a position — or whatever the family's ``sample(logits, cfg)`` gives in
    the greedy token's place, a pytree of arrays a row
    (models/blockdiff_moe.py: candidate and confidence)."""
    def apply_cached(params, tokens, cfg, cache, block_tables, lengths,
                     n_new, **kw):
        """:func:`cached_pair`'s, which has the contract."""
        return family(params, tokens, cfg, cache, block_tables, lengths,
                      n_new, lambda t, logits, x: t.slab(logits(x)), **kw)

    if read:
        def greedy_cached(params, tokens, cfg, cache, block_tables, lengths,
                          n_new, read, **kw):
            """:func:`cached_pair`'s, sampled at ``read``."""
            return family(
                params, tokens, cfg, cache, block_tables, lengths, n_new,
                lambda t, logits, x: sample(logits(t.slab.at(x, read)), cfg),
                **kw)
    else:
        def greedy_cached(params, tokens, cfg, cache, block_tables, lengths,
                          n_new, **kw):
            """:func:`cached_pair`'s, sampled at every position."""
            return family(
                params, tokens, cfg, cache, block_tables, lengths, n_new,
                lambda t, logits, x: jax.tree_util.tree_map(
                    t.slab, sample(logits(x), cfg)), **kw)
    return apply_cached, greedy_cached
