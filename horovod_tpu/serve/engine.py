"""Continuous-batching inference engine over the trained models.

The serving plane's core (docs/serving.md): one preallocated,
mesh-sharded paged KV cache (models/llama.py ``init_cache``), a
static-shape slot table, and ONE jit'd mixed prefill/decode step per
tick, run at the chunk's width when the tick holds a prefill chunk and
at a decode row's width when it holds none (``tick_width``).  Horovod's
product was "wrap your optimizer, training scales" (arxiv 1802.05799);
the serving analog here is "hand the engine your trained checkpoint, it
serves" — no model rewrite, the same mesh, launcher and observability
stack as training.

Scheduling (in-flight/continuous batching, the Orca/vLLM discipline):

  * **admit-on-slot-free**: the waiting queue is FCFS; a request is
    admitted the tick a slot AND its worst-case cache blocks are free,
    never at epoch/batch boundaries;
  * **max_batch_tokens admission**: each tick processes at most that
    many tokens across the table — decode slots cost 1 each (served
    first: latency-critical), prefill slots consume chunks of
    ``prefill_chunk``, new admissions eat leftover budget;
  * **evict-on-EOS/max-len**: a finished request frees its slot and
    blocks the same tick, so the next waiting request replaces it
    mid-flight.

The decode chain closes on the device (docs/serving.md#the-loops-order):
a tick's program hands the next, as device arrays, every slot's length, the
end of its stream and the token history its n-gram drafter reads, so
``step()`` launches tick N+1 while tick N is still unfenced and only then
fences N and emits what the device reports.  The next program is queued on
the device when the running one ends; the host's plan, staging, copy and
publishing run behind a program, not between two.

Determinism: greedy (argmax) sampling on device, FCFS admission, LIFO
block reuse — given the same request sequence every rank computes the
same plans and tokens, which is what lets a multi-host fleet run the
engine in lockstep from a rank-0-published plan stream (serve/worker.py)
with no new transport.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import inspect
import json
import os
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..models import paged
from ..utils.profiler import PhaseClock, annotate
from .arrivals import SUMS as _ARRIVAL_SUMS
from .commit import SKIPS, SUMS as _COMMIT_SUMS, CommitPoint
from .config import ServeConfig


# ------------------------------------------------------------ block pool
class BlockAllocator:
    """Refcounted free-list over the paged cache pool.  LIFO reuse: the
    blocks a finished request frees are the first ones the next request
    gets — deterministic across ranks and trivially observable in tests
    (paged-cache block reuse).

    With prefix sharing (PrefixCache) one block can back several
    sequences plus the cache itself: ``alloc`` hands blocks out at
    refcount 1, ``incref`` adds an owner, ``free`` releases one owner —
    a block returns to the free list only when its LAST owner lets go."""

    def __init__(self, num_blocks: int):
        self.num_blocks = int(num_blocks)
        self._free: List[int] = list(range(self.num_blocks - 1, -1, -1))
        self._refs: Dict[int, int] = {}

    @property
    def free_count(self) -> int:
        return len(self._free)

    def ref(self, block: int) -> int:
        return self._refs.get(block, 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        """All-or-nothing: a request that cannot get its worst-case
        block count is not admitted (no mid-flight OOM-evict)."""
        if n > len(self._free):
            return None
        blocks = [self._free.pop() for _ in range(n)]
        for b in blocks:
            self._refs[b] = 1
        return blocks

    def incref(self, blocks: List[int]) -> None:
        for b in blocks:
            self._refs[b] += 1

    def free(self, blocks: List[int]) -> None:
        for b in reversed(blocks):
            left = self._refs[b] = self._refs[b] - 1
            if left == 0:
                del self._refs[b]
                self._free.append(b)

    def occupancy(self) -> Dict[str, int]:
        """Pool occupancy for the memory plane (perf/memstats.py;
        docs/memory.md#kv-pool): used/free split plus the blocks more
        than one owner maps (prefix-cache / CoW sharing) — the bytes the
        used count would double-book if summed per sequence."""
        return {
            "num_blocks": self.num_blocks,
            "used_blocks": self.num_blocks - len(self._free),
            "free_blocks": len(self._free),
            "shared_blocks": sum(1 for c in self._refs.values() if c > 1),
        }


# ----------------------------------------------------- radix prefix cache
class _PrefixNode:
    """One radix-tree node = one pool block's worth of cached prompt KV:
    ``tokens`` are the token ids whose KV the block holds (a full block,
    or a partial tail shorter than block_size), children keyed by the
    NEXT block's token tuple.  ``block`` is None while the node's KV
    lives in the host spill tier (HostSpillPool) — the node stays in
    the tree so the prefix stays matchable and reloads on hit."""

    __slots__ = ("tokens", "block", "children", "parent", "stamp")

    def __init__(self, tokens: Tuple[int, ...], block: Optional[int],
                 parent):
        self.tokens = tokens
        self.block = block
        self.children: Dict[Tuple[int, ...], "_PrefixNode"] = {}
        self.parent = parent
        self.stamp = 0


# ------------------------------------------------------- host spill tier
class HostSpillPool:
    """Host-RAM tier behind the device paged pool
    (docs/serving.md#replicated-tier): cold radix-tree blocks —
    allocator refcount exactly 1, i.e. held by nobody but the tree —
    migrate here instead of being dropped at eviction, and reload into
    a fresh device block on the next prefix hit.  Capacity-bounded in
    blocks; when full, the least-recently-touched held block (the
    prefix cache's own deterministic ``stamp`` clock) is dropped for
    good.  Pure host state driven by the request stream (no clock, no
    RNG — the hvdlint serve-determinism scope covers this class), so a
    lockstep fleet spills and reloads identically on every rank.

    ``read_block(block) -> payload`` and ``write_block(block, payload)``
    are engine-provided device accessors (numpy copies of one pool
    block across layers); the pool itself never touches jax."""

    def __init__(self, capacity_blocks: int, read_block, write_block):
        self.capacity = int(capacity_blocks)
        self._read = read_block
        self._write = write_block
        self._held: Dict[int, Any] = {}     # id(node) -> payload
        self._nodes: Dict[int, Any] = {}    # id(node) -> node (for LRU)
        self.spilled_total = 0
        self.reloaded_total = 0
        self.dropped_total = 0
        self.bytes_held = 0

    @property
    def blocks_held(self) -> int:
        return len(self._held)

    def _payload_bytes(self, payload) -> int:
        return sum(int(a.nbytes) for a in payload.values())

    def _drop_coldest(self) -> None:
        victim_key, victim = None, None
        for key in sorted(self._nodes):
            node = self._nodes[key]
            if victim is None or node.stamp < victim.stamp:
                victim_key, victim = key, node
        if victim_key is None:
            return
        payload = self._held.pop(victim_key)
        del self._nodes[victim_key]
        self.bytes_held -= self._payload_bytes(payload)
        self.dropped_total += 1
        # the node's KV is gone for good: unlink it from the tree so
        # match() never offers a prefix nobody can reload
        if victim.parent is not None and not victim.children:
            victim.parent.children.pop(victim.tokens, None)

    def spill(self, node: _PrefixNode) -> bool:
        """Migrate one tree-held block to host RAM.  Returns False when
        capacity is 0 (spill off) — the caller evicts normally."""
        if self.capacity <= 0:
            return False
        while len(self._held) >= self.capacity:
            self._drop_coldest()
        payload = self._read(node.block)
        self._held[id(node)] = payload
        self._nodes[id(node)] = node
        self.bytes_held += self._payload_bytes(payload)
        self.spilled_total += 1
        return True

    def reload(self, node: _PrefixNode, block: int) -> None:
        """Write a held node's KV back into device ``block`` (the
        caller allocated it; the tree takes the ref)."""
        payload = self._held.pop(id(node))
        del self._nodes[id(node)]
        self.bytes_held -= self._payload_bytes(payload)
        self._write(block, payload)
        self.reloaded_total += 1

    def holds(self, node: _PrefixNode) -> bool:
        return id(node) in self._held

    def counters(self) -> Dict[str, Any]:
        return {
            "capacity_blocks": self.capacity,
            "held_blocks": len(self._held),
            "held_bytes": self.bytes_held,
            "spilled_total": self.spilled_total,
            "reloaded_total": self.reloaded_total,
            "dropped_total": self.dropped_total,
        }


class PrefixCache:
    """Radix tree over token-block keys (the automatic-prefix-caching
    discipline on this repo's paged pool): sequences with a common
    prefix map the SAME KV blocks, so repeated prefills of shared system
    prompts / few-shot templates become cache hits.

      * full blocks are shared in place (allocator refcount, zero copy);
      * divergence INSIDE a cached block — including a partial tail —
        is shared copy-on-write: the matcher gets a device-side clone of
        the block (models/llama.py ``copy_blocks``) holding the common
        positions and overwrites its own suffix;
      * when the pool runs dry, admission evicts LRU leaves nobody
        references but the cache (refcount exactly 1).

    Pure host state driven only by the request stream, never by timing —
    every rank replaying the same plan stream computes the identical
    tree, which is what keeps the fleet lockstep (docs/serving.md)."""

    def __init__(self, block_size: int, allocator: BlockAllocator,
                 spill: Optional[HostSpillPool] = None):
        self.block_size = int(block_size)
        self.allocator = allocator
        self.spill = spill
        self.root = _PrefixNode((), -1, None)
        self._clock = 0          # deterministic LRU clock (touch order)
        self.hits = 0            # admissions with a nonzero prefix hit
        self.hit_tokens = 0      # prompt tokens served from cache
        self.blocks_shared = 0   # full blocks mapped instead of computed
        self.cow_copies = 0
        self.evictions = 0

    def _touch(self, node: _PrefixNode) -> None:
        self._clock += 1
        node.stamp = self._clock

    def _reload(self, node: _PrefixNode) -> bool:
        """Bring a spilled node's KV back into a fresh device block (the
        tree takes the ref, exactly like insert()).  The alloc may
        itself evict — eviction never selects spilled nodes, so this
        cannot recurse into the node being reloaded."""
        if self.spill is None or not self.spill.holds(node):
            return False
        blocks = self.allocator.alloc(1)
        if blocks is None:
            if self.evict(1) < 1:
                return False
            blocks = self.allocator.alloc(1)
            if blocks is None:
                return False
        self.spill.reload(node, blocks[0])
        node.block = blocks[0]
        from ..utils import metrics as M
        M.SERVE_SPILL_RELOADS.inc()
        return True

    def match(self, prompt: List[int]
              ) -> Tuple[List[int], Optional[Tuple[int, int]], int]:
        """Longest cached prefix of ``prompt``, capped at
        ``len(prompt) - 1``: at least one prompt token is always
        recomputed so the admitting tick has logits to sample the first
        output from (a zero-token prefill chunk would wedge).  Returns
        ``(full_blocks, cow, hit_tokens)`` — ``full_blocks`` are shared
        as-is (caller increfs); ``cow`` is ``(src_block, n_valid)`` when
        the tail diverges inside a cached block, and the caller owns a
        device-side copy."""
        bs = self.block_size
        limit = len(prompt) - 1
        node, full, pos = self.root, [], 0
        while limit - pos >= bs:
            child = node.children.get(tuple(prompt[pos:pos + bs]))
            if child is None:
                break
            if child.block is None and not self._reload(child):
                break  # spilled and unreloadable: the match ends here
            self._touch(child)
            full.append(child.block)
            node, pos = child, pos + bs
        # Divergence within a block: best partial overlap among this
        # node's children (sorted scan = deterministic tie-break),
        # shared by copy-on-write.
        want = tuple(prompt[pos:limit])
        best, best_n = None, 0
        for key in sorted(node.children):
            child = node.children[key]
            n = 0
            for a, b in zip(want, child.tokens):
                if a != b:
                    break
                n += 1
            if n > best_n:
                best, best_n = child, n
        cow = None
        if best is not None and best_n >= 1:
            if best.block is None and not self._reload(best):
                return full, None, pos
            self._touch(best)
            cow = (best.block, best_n)
        return full, cow, pos + best_n

    def insert(self, prompt: List[int], blocks: List[int]) -> None:
        """Register a finished prefill: ``blocks`` is the slot's table
        row, whose i-th entry holds the prompt's i-th block of KV.
        Existing nodes win (dedup: a prefix computed twice concurrently
        stays owned by its second request and is freed normally); new
        nodes take one cache ref on their block so eviction — not a
        request finishing — decides their lifetime."""
        bs = self.block_size
        node, pos = self.root, 0
        for i in range(len(prompt) // bs):
            key = tuple(prompt[pos:pos + bs])
            child = node.children.get(key)
            if child is None:
                child = _PrefixNode(key, blocks[i], node)
                node.children[key] = child
                self.allocator.incref([child.block])
                self._touch(child)
            node, pos = child, pos + bs
        tail = tuple(prompt[pos:])
        if tail and tail not in node.children:
            child = _PrefixNode(tail, blocks[len(prompt) // bs], node)
            node.children[tail] = child
            self.allocator.incref([child.block])
            self._touch(child)

    def evict(self, n_blocks: int) -> int:
        """Free up to ``n_blocks`` by dropping least-recently-touched
        leaves only the cache references (allocator refcount exactly 1);
        returns how many were freed.  Interior nodes are never dropped —
        that would orphan reachable children.  With a spill tier
        attached, a victim's KV migrates to host RAM first (the node
        stays in the tree, block None, reloadable on the next hit);
        spilled nodes themselves are never victims — they hold no
        device block."""
        freed = 0
        while freed < n_blocks:
            victim = None
            for node in self._walk(self.root):
                if node is self.root or node.children:
                    continue
                if node.block is None:
                    continue  # already spilled: nothing on device
                if self.allocator.ref(node.block) != 1:
                    continue
                if victim is None or node.stamp < victim.stamp:
                    victim = node
            if victim is None:
                break
            if self.spill is not None and self.spill.spill(victim):
                self.allocator.free([victim.block])
                victim.block = None
                from ..utils import metrics as M
                M.SERVE_SPILLS.inc()
            else:
                del victim.parent.children[victim.tokens]
                self.allocator.free([victim.block])
            self.evictions += 1
            freed += 1
        return freed

    def _walk(self, node: _PrefixNode):
        yield node
        for key in sorted(node.children):
            yield from self._walk(node.children[key])

    @property
    def size(self) -> int:
        """Cached blocks currently held by the tree."""
        return sum(1 for _ in self._walk(self.root)) - 1


# --------------------------------------------------------------- request
class Request:
    """One generation request moving waiting -> prefill -> decode ->
    done.  ``ctx_len`` counts tokens written into the cache; ``pos``
    counts prompt tokens consumed."""

    def __init__(self, tokens, max_new_tokens: int,
                 req_id: Optional[str] = None,
                 eos_id: Optional[int] = None):
        self.tokens = [int(t) for t in tokens]
        if not self.tokens:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens={max_new_tokens} invalid")
        self.max_new_tokens = int(max_new_tokens)
        self.req_id = req_id or f"req-{id(self):x}"
        self.eos_id = eos_id
        self.state = "waiting"
        self.out_tokens: List[int] = []
        # Block denoising (docs/serving.md#block-denoising): the pass,
        # 0-based and counted a block, in which each served token was fixed;
        # the block being filled, {position: (token, pass)}; and what the
        # last block holds behind the answer's cut, [[token, pass], ..]
        self.steps: List[int] = []
        self.block: Dict[int, Tuple[int, int]] = {}
        self.tail: List[List[int]] = []
        self.pos = 0        # prompt tokens consumed
        self.ctx_len = 0    # tokens written into the cache
        self.slot: Optional[int] = None
        self.blocks: List[int] = []
        # the blocks of each window kind's ring (Scheduler._with_rings)
        self.ring_blocks: Dict[str, List[int]] = {}
        # its history is on the device and a row of it has been launched:
        # its decode rows take token and length from the tick before
        self.carried = False
        # its launched rows that sample and no step() has fenced yet (at
        # most one between two): each emits a token, or its stream has ended
        self.unfenced = 0
        self._bigram: Dict[Tuple[int, int], int] = {}
        self._indexed = 0   # context positions already in the index
        self.submitted_t = time.perf_counter()
        self.admitted_t: Optional[float] = None
        self.first_token_t: Optional[float] = None
        self.done_t: Optional[float] = None
        self.finish_reason: Optional[str] = None
        # Causal trace context (serve/trace.py): minted by the router at
        # admission, attached at submit, rides the handoff record to the
        # decode side so both fleets' spans link on the same rid.
        self.trace: Optional[Dict[str, Any]] = None
        self.handoff_s: float = 0.0  # decode-side measured export->import
        # Prefill-side component durations that rode the handoff record
        # (queue_s/prefill_s): the decode fleet cannot recompute them —
        # perf_counter stamps are process-local.
        self.upstream: Optional[Dict[str, float]] = None
        # Hops and loop phases of this request's life (docs/serving.md
        # #request-lifecycle): the front's pickup wait, the engine clock's
        # snapshot at submit, and from it the ticks up to the first token
        # and the whole delta at finish.
        self.pickup_s: Optional[float] = None
        self.loop0: Optional[Dict[str, Any]] = None
        self.prefill_ticks: Optional[int] = None
        self.loop: Optional[Dict[str, Any]] = None

    @property
    def prompt_len(self) -> int:
        return len(self.tokens)

    def ttft(self) -> Optional[float]:
        if self.first_token_t is None:
            return None
        return self.first_token_t - self.submitted_t

    def tpot(self) -> Optional[float]:
        if self.done_t is None or self.first_token_t is None or \
                len(self.out_tokens) < 2:
            return None
        return (self.done_t - self.first_token_t) / \
            (len(self.out_tokens) - 1)

    # ----------------------------------------------------- spec drafting
    def _ctx_tok(self, i: int) -> int:
        n = len(self.tokens)
        return self.tokens[i] if i < n else self.out_tokens[i - n]

    def draft_lookup(self, k: int) -> List[int]:
        """N-gram / prompt-lookup drafting (the draft-model-free leg of
        speculative decoding): find the most recent PRIOR occurrence of
        the context's final bigram and propose up to ``k`` tokens that
        followed it.  The bigram index grows incrementally (O(1) per
        generated token) and deliberately excludes the final bigram
        itself, so a repeating tail still finds its earlier occurrence.
        A pure function of prompt + emitted tokens — deterministic on
        every rank (the lockstep contract).  The serving tick drafts by
        this rule on the device (``draft_rows``); this is the rule as the
        host states it, the oracle the tests hold the device to."""
        L = len(self.tokens) + len(self.out_tokens)
        if k < 1 or L < 3:
            return []
        for i in range(max(self._indexed, 1), L - 1):
            self._bigram[(self._ctx_tok(i - 1), self._ctx_tok(i))] = i + 1
        self._indexed = max(self._indexed, L - 1)
        p = self._bigram.get((self._ctx_tok(L - 2), self._ctx_tok(L - 1)))
        if p is None:
            return []
        return [self._ctx_tok(p + j) for j in range(min(k, L - p))]


# ------------------------------------------------------------- scheduler
class _Ring:
    """One window kind's host state (models/paged.py CacheKind with a
    ``window``): a slot keeps at most ``entries`` blocks of it, addressed as
    a ring, whatever its context's length — the window plus the widest
    tick's columns, rounded up to blocks (paged.ring_blocks, where the
    speculative margin is argued).  The pool holds ``max_slots * entries``
    blocks, so a free slot always finds its ring; the allocator is there so
    that admission counts, ``finish`` returns and ``kv_pool`` reports this
    kind as they do the other."""

    def __init__(self, kind, cfg: ServeConfig):
        self.kind = kind
        self.entries = paged.ring_blocks(kind.window, cfg.prefill_chunk,
                                         cfg.block_size,
                                         cfg.max_blocks_per_seq)
        self.positions = self.entries * cfg.block_size   # a slot's at most
        self.allocator = BlockAllocator(cfg.max_slots * self.entries)
        self.tables = -np.ones((cfg.max_slots, self.entries), np.int32)
        # summed over dispatched ticks and their slots: the slots, the
        # positions this kind holds, those of them inside the window (what a
        # tick's attention reads), and the positions a full-context cache
        # would hold for the same slots
        self.slot_ticks = 0
        self.resident_position_ticks = 0
        self.window_position_ticks = 0
        self.full_position_ticks = 0

    def count(self, ctx_len: int) -> None:
        """One slot of one dispatched tick, its context ``ctx_len`` long once
        the tick's positions are written."""
        self.slot_ticks += 1
        self.full_position_ticks += ctx_len
        self.resident_position_ticks += min(ctx_len, self.positions)
        self.window_position_ticks += min(ctx_len, self.kind.window)


class _State:
    """One state kind's host state (models/paged.py CacheKind with a
    ``state``): a slot keeps ``columns`` columns of it whatever its
    context's length — what a tick reads back plus the widest verify row
    (paged.state_columns, where the margin for rejected drafts is argued).
    The pool holds ``max_slots`` of them: a slot IS its state, so there is
    nothing to allocate, admit against or give back, and a slot's next
    tenant reads none of it (paged.state_read, paged.carry_read).  What a
    column holds is the model's — a position's input ``[d]``, or the state a
    scan left after it ``[d_state, d]`` (models/sambay.py) —, so a kind's
    bytes a slot are read off its pool, never reckoned from ``columns``
    (:meth:`ServeEngine._kind_pool`); a model may keep several such kinds.
    A kind that declares what a row feeds its state (``kind.replay``;
    models/gdn_hybrid.py: a matrix a head) keeps ONE state a slot and is
    sized by that: ``columns`` is then the rows of the verify row that the
    next tick may have to replay (paged.replay_rows)."""

    def __init__(self, kind, cfg: ServeConfig):
        self.kind = kind
        self.columns = (paged.replay_rows(decode_width(cfg)) if kind.replay
                        else paged.state_columns(kind.state,
                                                 decode_width(cfg)))
        # summed over dispatched ticks and their slots: the slots, and the
        # positions a whole-context cache would hold for them
        self.slot_ticks = 0
        self.full_position_ticks = 0

    def count(self, ctx_len: int) -> None:
        """One slot of one dispatched tick (as :meth:`_Ring.count`)."""
        self.slot_ticks += 1
        self.full_position_ticks += ctx_len


class Scheduler:
    """Deterministic slot-table scheduler (pure host state, no jax) —
    unit-testable without a model.  ``plan()`` returns this tick's
    (slot, request, n_tokens) work list and performs admissions;
    ``finish()`` evicts.

    ``role`` is the prefill/decode disaggregation split
    (docs/serving.md#replicated-tier): a ``mixed`` scheduler (the
    default, byte-for-byte the pre-split engine) runs both phases; a
    ``prefill`` scheduler admits from the waiting queue but its engine
    hands finished prefills off instead of decoding them; a ``decode``
    scheduler admits ONLY imported handoffs (``queue_import``) — its
    waiting queue is never drained, so a stray submit cannot double-run
    a prompt both sides of the split."""

    ROLES = ("mixed", "prefill", "decode")

    def __init__(self, cfg: ServeConfig, role: str = "mixed", kinds=(),
                 block: int = 0):
        """``kinds``: the served model's cache kinds (paged.CacheKind; none
        = one kind that keeps whole contexts).  A kind with a window gets a
        ring a slot (:class:`_Ring`), a kind with a fixed state its columns
        a slot (:class:`_State`), beside the block table of the kind that
        keeps whole contexts.  ``block``: the positions a served model
        denoises at a time (:func:`block_length`; 0 = it decodes a token
        after another)."""
        if role not in self.ROLES:
            raise ValueError(f"scheduler role {role!r} invalid; expected "
                             f"one of {self.ROLES}")
        self.cfg = cfg
        self.role = role
        self.block = int(block)
        if self.block:
            # A cached block of keys is a prefix's to share only where it
            # ends on a boundary of the model's blocks AND was computed by
            # a prefill: under the block mask a position's keys depend on
            # its whole block, so a partial tail's, a copy-on-write
            # clone's and a block still being denoised are another
            # prompt's keys.  Sound for whole prefilled blocks where
            # block_size % B == 0; not built, so refused, never silently
            # wrong (docs/serving.md#block-denoising).
            for on, what in ((cfg.prefix_cache, "the radix prefix cache "
                              "(HOROVOD_SERVE_PREFIX_CACHE) and its "
                              "copy-on-write"),
                             (cfg.spill_blocks, "the host spill tier "
                              "(HOROVOD_SERVE_SPILL_BLOCKS)"),
                             (role != "mixed", f"the {role!r} role's prefill "
                              "hand-off")):
                if on:
                    raise ValueError(
                        f"the served model denoises blocks of {self.block} "
                        f"positions: {what} cannot run over it — a "
                        "position's keys depend on its whole block, so only "
                        "whole prefilled blocks are a prefix's to share, "
                        "spill or hand off, and no path keeps to them yet; "
                        "turn it off (docs/serving.md#block-denoising)")
        self.kinds = tuple(kinds)
        self.rings = {k.name: _Ring(k, cfg) for k in self.kinds
                      if k.window is not None}
        self.states = {k.name: _State(k, cfg) for k in self.kinds
                       if k.state is not None}
        # what counts a dispatched tick's slots (_Ring.count, _State.count)
        self.counted = (*self.rings.values(), *self.states.values())
        # the kinds over which no block is a prefix's, named for a refusal
        self.unshared = " and ".join(
            f"{what} cache kinds ({', '.join(names)})" for what, names
            in (("window", self.rings), ("state", self.states)) if names)
        if self.unshared:
            # A block of keys is a prefix's only with every layer's state
            # at its end.  A ring's block stops holding a prefix's positions
            # once the stream has passed it: a hit at n tokens would need
            # every window layer's positions n - window .. n - 1, which no
            # other slot's ring and no block of the tree keeps; a fixed
            # state keeps its slot's last columns and no other position's.
            # Refused, never silently wrong (docs/serving.md#cache-kinds).
            for on, what in ((cfg.prefix_cache, "the radix prefix cache "
                              "(HOROVOD_SERVE_PREFIX_CACHE) and its "
                              "copy-on-write"),
                             (cfg.spill_blocks, "the host spill tier "
                              "(HOROVOD_SERVE_SPILL_BLOCKS)"),
                             (role != "mixed", f"the {role!r} role's prefill "
                              "hand-off")):
                if on:
                    raise ValueError(
                        f"the served model keeps {self.unshared}: {what} "
                        "cannot run over them — a window layer's block is "
                        "overwritten once the stream has passed it and a "
                        "fixed state keeps no earlier position's, so a block "
                        "is no prefix's to share, spill or hand off; turn it "
                        "off (docs/serving.md#cache-kinds)")
        self.slots: List[Optional[Request]] = [None] * cfg.max_slots
        self.waiting: "collections.deque[Request]" = collections.deque()
        self.allocator = BlockAllocator(cfg.cache_blocks)
        self.prefix = (PrefixCache(cfg.block_size, self.allocator)
                       if cfg.prefix_cache else None)
        self.block_tables = -np.ones(
            (cfg.max_slots, cfg.max_blocks_per_seq), np.int32)
        self.completed = 0
        self.admissions = 0
        self.imports = 0
        # CoW copies the NEXT dispatch must run before its writes:
        # (src_block, dst_block) pairs, at most one per admission.
        self.pending_copies: List[Tuple[int, int]] = []
        # Disaggregation intake: handoffs waiting for a slot, the
        # device-block writes the next dispatch must apply before its
        # step reads the cache, and the emissions (the prefill rank's
        # first token) the next report must carry.
        self.import_queue: "collections.deque" = collections.deque()
        self.pending_writes: List[Tuple[int, Any]] = []
        self.import_emits: List[Tuple[Request, List[int]]] = []

    # ------------------------------------------------------------ intake
    def submit(self, req: Request) -> Request:
        if req.prompt_len + req.max_new_tokens > self.cfg.max_seq_len:
            raise ValueError(
                f"request {req.req_id}: prompt {req.prompt_len} + "
                f"max_new {req.max_new_tokens} exceeds "
                f"HOROVOD_SERVE_MAX_SEQ_LEN={self.cfg.max_seq_len}")
        self.waiting.append(req)
        return req

    @property
    def queue_depth(self) -> int:
        return len(self.waiting)

    @property
    def active(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    def has_work(self) -> bool:
        return self.active > 0 or bool(self.waiting) or \
            bool(self.import_queue)

    # -------------------------------------------------------------- plan
    def plan(self) -> List[Tuple[int, Request, int]]:
        """One tick's work under the token budget: decode slots first
        (1 token + room for spec_k verified drafts each, latency-critical),
        prefill continuations next, FCFS admissions into the remainder.
        Deterministic given state.  A request is in ``decode`` from the
        launch of its prompt's last chunk on (ServeEngine._dispatch): its
        decode row follows without waiting for that tick's fence.

        For a model that denoises blocks of B positions a decode row is a
        BLOCK ROW of B columns whatever the pass, a prompt's ``p // B``
        whole blocks are prefilled in chunks that end on block boundaries
        (its last ``p % B`` tokens are known positions of the first block
        row), and a stream's end is the device's to find: its rows are
        planned until a fence has finished it."""
        self._drain_imports()
        chunk = self.cfg.prefill_chunk
        unit = self.block or 1      # what a row's columns are a multiple of
        work, budget = self._running_rows()
        while self.waiting and budget >= unit and self.role != "decode":
            free_slots = [i for i, s in enumerate(self.slots) if s is None]
            if not free_slots:
                break
            req = self.waiting[0]
            row = self._admit_blocks(req)
            if row is None:
                break  # FCFS head-of-line: no skip-ahead, deterministic
            self.waiting.popleft()
            self.admissions += 1
            slot = free_slots[0]
            req.slot, req.blocks = slot, row
            req.state = "prefill"
            req.admitted_t = time.perf_counter()
            self.slots[slot] = req
            self.block_tables[slot, :] = -1
            self.block_tables[slot, :len(row)] = row
            for name, blocks in req.ring_blocks.items():
                self.rings[name].tables[slot, :len(blocks)] = blocks
            # prefix-hit tokens are already resident: prefill resumes at
            # req.pos (match() keeps >= 1 token to compute, so n >= 1)
            n = min(chunk, self.prefill_end(req) - req.pos, budget)
            if n == 0:
                # a prompt shorter than the model's block: nothing to
                # prefill, its first row is a block row
                req.state, n = "decode", unit
            work.append((slot, req, n))
            budget -= n
        return work

    def _running_rows(self) -> Tuple[List[Tuple[int, Request, int]], int]:
        """The rows of the streams that hold a slot — decode rows, then
        prefill continuations — and what they leave of the tick's token
        budget.  Changes nothing."""
        budget = self.cfg.max_batch_tokens
        chunk = self.cfg.prefill_chunk
        unit = self.block or 1
        work: List[Tuple[int, Request, int]] = []
        for i, req in enumerate(self.slots):
            if req is not None and req.state == "decode" and budget >= unit:
                if not self.block and len(req.out_tokens) + req.unfenced \
                        >= req.max_new_tokens:
                    continue    # the unfenced tick ends it: nothing to run
                # A decode row is charged the columns it may fill: the
                # device drafts (``tick_program``) and the plan
                # does not wait to learn how much.  Its width is capped
                # by the tick budget (each column costs 1) and the verify
                # row (bonus token + K drafts); the device caps the draft
                # by the remaining generation (a draft past max_new could
                # be verified at positions the reservation never covered).
                n = unit
                if self.cfg.spec_decode:
                    n += min(self.cfg.spec_k, budget - 1,
                             self.cfg.prefill_chunk - 1)
                work.append((i, req, n))
                budget -= n
        budget -= budget % unit
        for i, req in enumerate(self.slots):
            if req is not None and req.state == "prefill" and budget >= unit:
                n = min(chunk, self.prefill_end(req) - req.pos, budget)
                if n >= 1:
                    work.append((i, req, n))
                    budget -= n
        return work, budget

    def room(self) -> bool:
        """Whether a request that arrives now could still get a row of the
        next tick: nothing waits in front of it, a slot is free and the
        streams that hold one leave some of the budget (ServeEngine.
        commit_due; a ``decode`` role's arrivals are hand-offs, which need
        the same)."""
        return not self.waiting and not self.import_queue and \
            None in self.slots and \
            self._running_rows()[1] >= (self.block or 1)

    def prefill_end(self, req: Request) -> int:
        """The prompt tokens a request's prefill consumes: all of them, or
        under a block length the prompt's whole blocks."""
        return req.prompt_len - (req.prompt_len % self.block
                                 if self.block else 0)

    def _admit_blocks(self, req: Request) -> Optional[List[int]]:
        """One admission's block-table row.  With the prefix cache on,
        the worst-case reservation counts only NEW blocks — prefix-hit
        blocks are already resident (the sharing dividend: without this
        the conservative math would refuse admissible requests).  Shared
        blocks are increfed BEFORE the alloc/evict so eviction can never
        recycle what this admission just matched; a failed alloc undoes
        the increfs and leaves the request queued (all-or-nothing)."""
        need = -(-(req.prompt_len + req.max_new_tokens)
                 // self.cfg.block_size)
        if self.prefix is None:
            return self._with_rings(req, need, self.allocator.alloc(need))
        shared, cow, hit = self.prefix.match(req.tokens)
        self.allocator.incref(shared)
        need_new = need - len(shared)
        blocks = self.allocator.alloc(need_new)
        if blocks is None:
            short = need_new - self.allocator.free_count
            if self.prefix.evict(short) >= short:
                blocks = self.allocator.alloc(need_new)
        if blocks is None:
            self.allocator.free(shared)  # undo: tree refs keep them alive
            return None
        if cow is not None:
            # Divergence inside a cached block: clone it on device into
            # this request's first new block, then overwrite the suffix.
            # The source needs no extra ref: the copy runs at the START
            # of the next dispatch, and any later reuse of the source
            # block writes in the SAME step after the copy's gather
            # (functional semantics) or in a later, device-ordered one.
            src, cow_tokens = cow
            self.pending_copies.append((src, blocks[0]))
            hit = len(shared) * self.cfg.block_size + cow_tokens
            self.prefix.cow_copies += 1
        if hit:
            from ..utils import metrics as M
            self.prefix.hits += 1
            self.prefix.hit_tokens += hit
            self.prefix.blocks_shared += len(shared)
            M.SERVE_PREFIX_HITS.inc()
            if shared:
                M.SERVE_PREFIX_BLOCKS_SHARED.inc(len(shared))
        req.pos = req.ctx_len = hit
        return shared + blocks

    def _with_rings(self, req: Request, need: int,
                    row: Optional[List[int]]) -> Optional[List[int]]:
        """``row`` once every window kind has reserved what it will hold of
        the request: ``min(need, ring entries)`` blocks each; all or nothing
        across the kinds."""
        if row is None:
            return None
        got: Dict[str, List[int]] = {}
        for name, ring in self.rings.items():
            blocks = ring.allocator.alloc(min(need, ring.entries))
            if blocks is None:
                for done, held in got.items():
                    self.rings[done].allocator.free(held)
                self.allocator.free(row)
                return None
            got[name] = blocks
        req.ring_blocks = got
        return row

    def pool_blocks(self):
        """Blocks of the pool, as ``init_cache`` and ``cache_shardings`` take
        them — of each kind's pool where the model declares kinds: a window
        kind's holds every slot's ring and is sized by the model's window,
        the slots and the chunk, not by ``cache_blocks``; a state kind's is
        (slots, columns a slot), sized by the model's state and the verify
        row (with ``replay``: ONE state a slot and that many rows to
        replay)."""
        if not self.kinds:
            return self.cfg.cache_blocks
        return {k.name: (
            self.rings[k.name].allocator.num_blocks if k.name in self.rings
            else (self.cfg.max_slots, self.states[k.name].columns)
            if k.name in self.states else self.cfg.cache_blocks)
            for k in self.kinds}

    def device_tables(self):
        """What the tick addresses its pools with: the block table, or for
        a model that declares cache kinds ``{kind: table}`` — the whole-
        context kind's block table and each window kind's ring table; a
        state kind has none."""
        if not self.kinds:
            return self.block_tables
        return {k.name: (self.rings[k.name].tables if k.name in self.rings
                         else self.block_tables)
                for k in self.kinds if k.state is None}

    def take_copies(self) -> List[Tuple[int, int]]:
        copies, self.pending_copies = self.pending_copies, []
        return copies

    # ----------------------------------------------- disaggregated intake
    def queue_import(self, req: Request, payloads: List[Any],
                     first_token: int) -> None:
        """Decode-side intake of one prefill-rank handoff: the request,
        its prompt blocks' KV payloads (engine-decoded numpy dicts, one
        per full-or-partial prompt block), and the first output token
        the prefill rank already sampled.  Queued FCFS; ``plan()``
        installs it the tick a slot and blocks free up."""
        self.import_queue.append((req, payloads, int(first_token)))

    def _drain_imports(self) -> None:
        """Install queued handoffs straight into decode state: allocate
        the full worst-case row (prompt + max_new blocks), stage the KV
        payload writes for the next dispatch, emit the prefill rank's
        first token.  FCFS head-of-line like admission — an uninstallable
        handoff blocks the ones behind it (deterministic)."""
        while self.import_queue:
            free_slots = [i for i, s in enumerate(self.slots) if s is None]
            if not free_slots:
                break
            req, payloads, first = self.import_queue[0]
            need = -(-(req.prompt_len + req.max_new_tokens)
                     // self.cfg.block_size)
            blocks = self.allocator.alloc(need)
            if blocks is None and self.prefix is not None:
                short = need - self.allocator.free_count
                if self.prefix.evict(short) >= short:
                    blocks = self.allocator.alloc(need)
            if blocks is None:
                break
            self.import_queue.popleft()
            slot = free_slots[0]
            req.slot, req.blocks = slot, blocks
            req.state = "decode"
            req.pos = req.prompt_len
            req.ctx_len = req.prompt_len
            req.out_tokens = [first]
            req.admitted_t = time.perf_counter()
            req.first_token_t = req.admitted_t
            self.slots[slot] = req
            self.block_tables[slot, :] = -1
            self.block_tables[slot, :need] = blocks
            self.admissions += 1
            self.imports += 1
            self.import_emits.append((req, [first]))
            if (req.eos_id is not None and first == req.eos_id) or \
                    req.max_new_tokens <= 1:
                reason = ("eos" if req.eos_id is not None
                          and first == req.eos_id else "completed")
                self.finish(req, reason)
                continue  # done on arrival: no KV writes needed
            for b, payload in zip(blocks, payloads):
                self.pending_writes.append((b, payload))
            if self.prefix is not None:
                self.prefix.insert(req.tokens, blocks)

    def take_pending_writes(self) -> List[Tuple[int, Any]]:
        writes, self.pending_writes = self.pending_writes, []
        return writes

    def take_import_emits(self) -> List[Tuple[Request, List[int]]]:
        emits, self.import_emits = self.import_emits, []
        return emits

    def register_prefix(self, req: Request) -> None:
        """Engine callback at prefill completion: the slot's prompt
        blocks now hold fully-computed KV and become shareable."""
        if self.prefix is not None and req.slot is not None:
            self.prefix.insert(req.tokens, req.blocks)

    # ------------------------------------------------------------- evict
    def finish(self, req: Request, reason: str) -> None:
        req.state = "done"
        req.finish_reason = reason
        req.done_t = time.perf_counter()
        if req.slot is not None:
            self.block_tables[req.slot, :] = -1
            for ring in self.rings.values():
                ring.tables[req.slot, :] = -1
            self.slots[req.slot] = None
        self.allocator.free(req.blocks)
        req.blocks = []
        for name, blocks in req.ring_blocks.items():
            self.rings[name].allocator.free(blocks)
        req.ring_blocks = {}
        req.slot = None
        self.completed += 1


# ------------------------------------------------------------ shardings
def _make_global(arr: np.ndarray, sharding):
    """Host array -> global jax.Array under ``sharding``.  Works in
    multi-controller runs (every process holds the full host value and
    contributes its addressable shards) — jax.device_put alone cannot
    target non-addressable devices."""
    import jax
    arr = np.asarray(arr)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda idx: arr[idx])


def _global_zeros(shape, dtype, sharding):
    import jax

    def cb(idx):
        slice_shape = tuple(
            len(range(*s.indices(d))) for s, d in zip(idx, shape))
        return np.zeros(slice_shape, dtype)  # ml_dtypes covers bf16
    return jax.make_array_from_callback(tuple(shape), sharding, cb)


def replicate_global(tree, mesh):
    """Replicate a host pytree over the whole (possibly multi-process)
    mesh — the serving twin of parallel/data_parallel.replicate, built
    on make_array_from_callback so it also works multi-controller."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    sharding = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda x: _make_global(np.asarray(x), sharding), tree)


# --------------------------------------------------- block payload codec
def encode_block_payload(payload: Dict[str, Any]) -> Dict[str, Any]:
    """One pool block's KV (the ``_read_block`` numpy dict, e.g.
    {"k": [L, bs, kv_heads, hd], "v": ...}) as a JSON-safe record —
    dtype/shape plus hex bytes — for the prefill->decode handoff ride
    over the direct-stream path (serve/stream.py).  Hex doubles the
    bytes but keeps the record line-framed JSON like every other stream
    record; the payload is one block, not a sequence."""
    out: Dict[str, Any] = {}
    for k, a in payload.items():
        a = np.ascontiguousarray(a)
        out[k] = {"dtype": str(a.dtype), "shape": list(a.shape),
                  "hex": a.tobytes().hex()}
    return out


def _np_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes  # low-bit dtypes jax serves in (bf16 etc.)
        return np.dtype(getattr(ml_dtypes, name))


def decode_block_payload(enc: Dict[str, Any]) -> Dict[str, Any]:
    return {k: np.frombuffer(bytes.fromhex(v["hex"]),
                             dtype=_np_dtype(v["dtype"]))
            .reshape(v["shape"]).copy()
            for k, v in enc.items()}


# ---------------------------------------------------------------- engine
def block_length(model_cfg) -> int:
    """The positions a served model denoises at a time, as its config
    declares them (``block_length``; models/blockdiff_moe.py), or 0: it
    decodes a token after another."""
    return int(getattr(model_cfg, "block_length", 0) or 0)


def decode_width(cfg: ServeConfig, block: int = 0) -> int:
    """Columns a decode row can fill: the bonus token + ``spec_k`` drafts
    of a speculative verify row, else 1 (``validate`` holds it to
    ``prefill_chunk``); a block row's ``block`` columns for a model that
    denoises blocks (:func:`block_length`)."""
    return block or (1 + cfg.spec_k if cfg.spec_decode else 1)


def tick_width(cfg: ServeConfig, work, block: int = 0) -> int:
    """Columns of one tick's token slab, read off its plan: the decode
    width when every row of ``work`` fits it — decode rows, verify rows
    and a prefill tail that short —, ``prefill_chunk`` when any row is
    longer.  A pure function of the plan, which ``sched_digest`` folds
    with every ``n``, so the ranks of a lockstep fleet agree on it."""
    narrow = decode_width(cfg, block)
    return narrow if max(row[2] for row in work) <= narrow \
        else cfg.prefill_chunk


# What the host tells the tick's program of its rows, one int32 a slot each:
# the rows of the one ``[len(ROW), slots]`` array a tick stages beside its
# block tables and its token slab (ServeEngine._dispatch).  ``len`` counts
# only where ``carried`` is 0; ``limit`` is prompt + ``max_new_tokens``, the
# context's length at which the stream ends; ``eos`` -1 where it has none.
ROW = ("len", "n", "kind", "carried", "limit", "eos", "copy_src", "copy_dst")
_LEN, _N, _KIND, _CARRIED, _LIMIT, _EOS, _COPY_SRC, _COPY_DST = range(len(ROW))
# A row's kind: no row, a prompt's chunk that samples nothing, its last
# chunk (samples the first token), a decode or verify row (a block row).
IDLE, CHUNK, LAST, DECODE = range(4)
# ... and of a model that denoises blocks (``block_tick_program``) one more:
# the prompt's length, from which on a block's positions are generated.
BLOCK_ROW = ROW + ("prompt",)
_PROMPT = len(ROW)


def draft_rows(hist, ctx, cap, width: int):
    """``Request.draft_lookup`` for every slot at once, on the device:
    ``hist`` ``[slots, positions]`` int32 holds each slot's prompt and emitted
    tokens, ``ctx`` how many of them, ``cap`` the most a slot may draft.  The
    most recent PRIOR occurrence of a context's final bigram proposes the
    tokens that followed it, at most ``min(cap, width)``, never past the
    context's end.  Returns ``(draft [slots, width], n [slots])``; columns
    from ``n`` on hold nothing a reader may use."""
    import jax.numpy as jnp
    S, H = hist.shape
    at = lambda i: jnp.take_along_axis(
        hist, jnp.clip(i, 0, H - 1)[:, None], axis=1)
    i = jnp.arange(H)[None, :]
    before = jnp.pad(hist, ((0, 0), (1, 0)))[:, :H]     # before[i] = hist[i-1]
    # the final bigram itself (i = ctx - 1) is no prior occurrence
    seen = ((before == at(ctx - 2)) & (hist == at(ctx - 1))
            & (i >= 1) & (i <= ctx[:, None] - 2))
    last = jnp.max(jnp.where(seen, i, -1), axis=1)      # -1: never seen
    p = last + 1
    n = jnp.where((last >= 0) & (ctx >= 3),
                  jnp.clip(jnp.minimum(cap, ctx - p), 0, width), 0)
    draft = jnp.take_along_axis(
        hist, jnp.clip(p[:, None] + jnp.arange(width)[None, :], 0, H - 1),
        axis=1)
    return draft, n.astype(jnp.int32)


def samples_read(model) -> bool:
    """Whether the module samples only where the tick reads: its
    ``greedy_cached`` takes ``read``, each slot's ``decode_width`` columns
    whose greedy token the tick uses, and returns those tokens alone
    (models/llama.py, moe_llama.py, latent_moe.py).  The module's signature
    declares it; nothing else chooses (docs/serving.md
    #what-a-served-model-module-exports)."""
    greedy = getattr(model, "greedy_cached", None)
    return greedy is not None and \
        "read" in inspect.signature(greedy).parameters


def tick_program(model, mcfg, cfg: ServeConfig):
    """One tick as a function of device arrays, for ``jit`` (ServeEngine.
    _build_step; tests/test_tpu_compile.py compiles it for a described
    chip): ``(params, cache, hist, length, done, block_tables, rows, tokens)
    -> (cache, hist, length, done, report, counters)``.  ``cache`` and the
    chain — ``hist`` ``[slots, max_seq_len]``, ``length`` and ``done``
    ``[slots]``, int32 — are the tick before's and are donated; ``rows`` is
    ``ROW``; ``report`` holds, a slot a row, the greedy tokens — of the W
    columns the tick reads where the module samples there
    (:func:`samples_read`), else of every column —, the verify row as it was
    fed, the row's columns and the length it ran at (ServeEngine._harvest
    reads it)."""
    import jax
    import jax.numpy as jnp

    if block_length(mcfg):
        return block_tick_program(model, mcfg, cfg)
    W = decode_width(cfg)
    counted = bool(model.TICK_COUNTERS)
    reads = samples_read(model)

    def read_columns(decode, n_new, C):
        """(0 .. W-1, the columns [S, W] whose greedy token a ``[S, C]``
        tick reads): a decode or verify row's from 0, a prompt's last
        chunk's last; what lies past a row's columns is read by no one
        (``_emit`` on the host, the chain below)."""
        cols = jnp.arange(W)[None, :]
        first = jnp.where(decode, 0, n_new - 1)[:, None]
        return cols, jnp.clip(first + cols, 0, C - 1)

    def step_fn(params, cache, hist, length, done, block_tables, rows,
                tokens):
        S, C = tokens.shape
        # CoW prefix sharing: clone diverged blocks BEFORE this
        # tick's writes (padding entries route dst out of bounds and
        # drop).  The gather reads the pre-step pool, so a source
        # block recycled in this same tick still copies its old
        # content (functional semantics — see Scheduler._admit_blocks).
        with jax.named_scope("tick/copy_blocks"):
            cache = model.copy_blocks(cache, rows[_COPY_SRC],
                                      rows[_COPY_DST])
        # The chain, in: a decode row's token and length are the tick
        # before's, its drafts the history's (draft_rows); a row whose
        # stream ended in a tick the host had not fenced runs 0 columns.
        with jax.named_scope("tick/chain"):
            decode = rows[_KIND] == DECODE
            carried = rows[_CARRIED] > 0
            ctx = jnp.where(carried, length, rows[_LEN])
            ended = carried & (done > 0)
            # a draft past max_new could be verified at positions the
            # reservation never covered: limit - (ctx + 1) - 1 at most
            draft, n_draft = draft_rows(
                hist, ctx + 1,
                jnp.minimum(rows[_N] - 1, rows[_LIMIT] - ctx - 2), W - 1)
            last = jnp.take_along_axis(
                hist, jnp.clip(ctx, 0, hist.shape[1] - 1)[:, None], 1)
            n_new = jnp.where(decode,
                              jnp.where(ended, 0, 1 + n_draft), rows[_N])
            fed = tokens.at[:, :W].set(jnp.where(
                decode[:, None], jnp.concatenate([last, draft], axis=1),
                tokens[:, :W]))
            # a row that runs nothing reads nothing, like a free slot
            lengths = jnp.where(n_new > 0, ctx, 0)
            if reads:
                cols, read = read_columns(decode, n_new, C)
        greedy = getattr(model, "greedy_cached", None)
        with jax.named_scope("tick/model"):
            out = (greedy or model.apply_cached)(
                params, fed, mcfg, cache, block_tables, lengths, n_new,
                *((read,) if reads else ()))
        logits, cache = out[:2]
        counters = out[2] if counted else None
        if greedy is not None:      # the module sampled on its rows
            next_tokens = logits
        else:
            # Greedy sampling ON DEVICE at EVERY chunk position: row
            # [s, j] is the greedy continuation after consuming tokens
            # [s, :j+1] — prefill reads its last valid position,
            # speculative decode verifies its whole draft row against
            # it.  Argmax ties break identically on every rank (SPMD
            # determinism).
            with jax.named_scope("tick/sample"):
                next_tokens = jnp.argmax(
                    logits.astype(jnp.float32),
                    axis=-1).astype(jnp.int32)
        # The chain, out: what ``_emit`` will do with this tick's tokens
        # at its fence, done here for the tick after.  draft[j] is
        # accepted iff it EQUALS the greedy token before it; the stream
        # ends at ``eos`` or at its limit; the history takes what was
        # emitted.
        with jax.named_scope("tick/chain"):
            if reads:       # [S, W], sampled at ``read`` and nowhere else
                new = next_tokens
            else:           # [S, C]: the S x W of them that are read
                cols, read = read_columns(decode, n_new, C)
                new = jnp.take_along_axis(next_tokens, read, axis=1)
            # (a decode row reads from column 0: its first W greedy tokens
            # are ``new``'s in either form)
            agree = ((fed[:, 1:W] == next_tokens[:, :W - 1])
                     & (cols[:, 1:] < n_new[:, None]) & decode[:, None])
            accepted = jnp.sum(jnp.cumprod(agree, axis=1), axis=1)
            emits = (n_new > 0) & (decode | (rows[_KIND] == LAST))
            eos_at = jnp.min(jnp.where(
                (new == rows[_EOS][:, None]) & (cols <= accepted[:, None]),
                cols, W), axis=1)
            n_out = jnp.where(emits,
                              jnp.minimum(accepted, eos_at) + 1, 0)
            # the history's length before: the decode row's own token
            # is in it, a prompt whole from its admission
            held = jnp.where(decode, ctx + 1, ctx + n_new)
            hist = hist.at[jnp.arange(S)[:, None], jnp.where(
                cols < n_out[:, None], held[:, None] + cols,
                hist.shape[1])].set(new, mode="drop")
            ran = n_new > 0
            length = jnp.where(
                ran, ctx + jnp.where(decode, 1 + accepted, n_new), length)
            done = jnp.where(ran, (emits & (
                (eos_at <= accepted) | (held + n_out >= rows[_LIMIT]))
                ).astype(jnp.int32), done)
            # one array for the fence's one copy: the greedy tokens (the
            # W that are read, or every column's), the verify rows as they
            # were fed, each row's columns and the length it ran at
            report = jnp.concatenate(
                [next_tokens, fed[:, :W], n_new[:, None], ctx[:, None]],
                axis=1).astype(jnp.int32)
        return cache, hist, length, done, report, counters

    return step_fn


def block_tick_program(model, mcfg, cfg: ServeConfig):
    """:func:`tick_program` for a model that fills a block of B positions by
    denoising (docs/serving.md#block-denoising): ``(params, cache, hist,
    length, done, masked, passes, block_tables, rows, tokens) -> (cache,
    hist, length, done, masked, passes, report, counters)``.  The chain
    carries two more states a slot: ``masked`` ``[slots, B]``, the positions
    of the slot's block that no pass has fixed yet (a state of the chain,
    not ``token == M``: a prompt may hold M as an ordinary id; a position
    below the row's ``prompt`` is known whatever the bit), and ``passes``
    ``[slots]``, the denoising passes the block has had.  ``rows`` is
    ``BLOCK_ROW``.

    A decode row is a BLOCK ROW: the B positions from the slot's length,
    fed from the history, M where masked.  A row that comes in with a
    position masked is a DENOISING pass: the module's candidates and
    confidences (``greedy_cached``), the module's rule (``fix_positions``),
    the fixed tokens into the history; when that fills the block the stream
    has ended if the block reaches its limit or a served position holds
    ``eos``.  A row that comes in with none masked is the COMMIT pass: its
    keys and values, computed from the full block, stand in the pool, the
    length moves by B and the next block is all masked.  A denoising pass's
    keys and values are written too and the block's next pass overwrites
    them: the length has not moved, so no reader sees them.  A prompt's
    chunk samples nothing.  ``report``: a slot a row, the block's tokens
    after the pass, which of them this pass fixed, which by the threshold,
    the pass's number, whether it committed, the row's columns and the
    length it ran at (ServeEngine._emit_block reads it)."""
    import jax
    import jax.numpy as jnp

    B = block_length(mcfg)
    M = int(mcfg.mask_token_id)
    counted = bool(model.TICK_COUNTERS)

    def step_fn(params, cache, hist, length, done, masked, passes,
                block_tables, rows, tokens):
        S, C = tokens.shape
        cols = jnp.arange(B)[None, :]
        # The chain, in: a block row's tokens, length and masked positions
        # are the tick before's; a row whose stream ended in a tick the
        # host had not fenced runs 0 columns.
        with jax.named_scope("tick/chain"):
            decode = rows[_KIND] == DECODE
            carried = rows[_CARRIED] > 0
            ctx = jnp.where(carried, length, rows[_LEN])
            ended = carried & (done > 0)
            n_new = jnp.where(decode, jnp.where(ended, 0, B), rows[_N])
            pos = ctx[:, None] + cols
            at = jnp.clip(pos, 0, hist.shape[1] - 1)
            mk = (masked > 0) & (pos >= rows[_PROMPT][:, None])
            ids = jnp.where(mk, M, jnp.take_along_axis(hist, at, axis=1))
            fed = tokens.at[:, :B].set(
                jnp.where(decode[:, None], ids, tokens[:, :B]))
            # a row that runs nothing reads nothing, like a free slot
            lengths = jnp.where(n_new > 0, ctx, 0)
        with jax.named_scope("tick/model"):
            (cand, conf), cache, *more = model.greedy_cached(
                params, fed, mcfg, cache, block_tables, lengths, n_new)
        counters = more[0] if counted else None
        # The chain, out: what ``_emit_block`` will do with this tick's
        # report at its fence, done here for the tick after.
        with jax.named_scope("tick/unmask"):
            block = decode & (n_new > 0)
            denoise = block & jnp.any(mk, axis=1)
            commit = block & ~denoise
            fix, sure = model.fix_positions(
                conf[:, :B], mk & denoise[:, None], mcfg)
            toks = jnp.where(fix, cand[:, :B], ids)
            left = mk & ~fix
        with jax.named_scope("tick/chain"):
            hist = hist.at[jnp.arange(S)[:, None], jnp.where(
                fix, pos, hist.shape[1])].set(toks, mode="drop")
            full = denoise & ~jnp.any(left, axis=1)
            limit = rows[_LIMIT][:, None]
            served = (pos >= rows[_PROMPT][:, None]) & (pos < limit)
            over = full & ((ctx + B >= rows[_LIMIT]) | jnp.any(
                served & (toks == rows[_EOS][:, None]), axis=1))
            ran = n_new > 0
            length = jnp.where(
                ran, ctx + jnp.where(decode, B * commit, n_new), length)
            done = jnp.where(ran, over.astype(jnp.int32), done)
            masked = jnp.where(commit[:, None], 1, jnp.where(
                denoise[:, None], left.astype(jnp.int32), masked))
            report = jnp.concatenate(
                [toks, fix, sure, passes[:, None], commit[:, None],
                 n_new[:, None], ctx[:, None]], axis=1).astype(jnp.int32)
            passes = jnp.where(commit, 0, passes + denoise)
        return cache, hist, length, done, masked, passes, report, counters

    return step_fn


def admit_program(hist, slot, row):
    """An admitted request's tokens into its slot's history (donated)."""
    import jax
    return jax.lax.dynamic_update_slice(hist, row[None, :], (slot, 0))


def block_admit_program(hist, masked, passes, slot, row):
    """:func:`admit_program` for a model that denoises blocks: the slot's
    first block is all masked and has had no pass (the chain, donated)."""
    import jax
    import jax.numpy as jnp
    ones = jnp.ones((1, masked.shape[1]), masked.dtype)
    return (admit_program(hist, slot, row),
            jax.lax.dynamic_update_slice(masked, ones, (slot, 0)),
            jax.lax.dynamic_update_slice(passes, jnp.zeros(1, passes.dtype),
                                         (slot,)))


# The host's critical path between two programs, part by part: what lies
# between the instant a tick's tokens were found ready and the return of the
# next tick's launch (ServeEngine._count_gap).
TURNAROUND_PARTS = ("fence_copy", "harvest_emit", "plan", "stage", "launch")
# What the loop keeps beside its phases (PhaseClock.add): cumulative, in a
# request's done record by the clock's delta, and a second in its timeline.
_TURN_SUMS = tuple("turn_" + part for part in TURNAROUND_PARTS)
_LOOP_SUMS = ("fence_ready_s", "fence_copy_s", "narrow", "narrow_wait_s",
              "wide", "wide_wait_s", "used", "turnaround_s", "turnaround_n",
              "after_idle_n", "iteration_s", "ahead_n",
              "ahead_idle_rows") + _TURN_SUMS + _COMMIT_SUMS + _ARRIVAL_SUMS


def _loop_figures(sums: Dict[str, float]) -> Dict[str, Any]:
    """A clock snapshot's sums under the names ``stats()["loop"]`` gives
    them."""
    by_width = {w: {"ticks": int(sums[w]), "wait_s": sums[w + "_wait_s"]}
                for w in ("narrow", "wide")}
    parts = {part: sums[name] for part, name in
             zip(TURNAROUND_PARTS, _TURN_SUMS)}
    # the code between the spans, and the spans' own entry and exit
    parts["unspanned"] = sums["turnaround_s"] - sum(parts.values())
    return {
        "fence_ready_s": sums["fence_ready_s"],
        "fence_copy_s": sums["fence_copy_s"],
        "turnaround_s": sums["turnaround_s"],
        "turnaround_n": int(sums["turnaround_n"]),
        "turnaround_parts_s": parts,
        "after_idle_n": int(sums["after_idle_n"]),
        "ahead_n": int(sums["ahead_n"]),
        "ahead_idle_rows": int(sums["ahead_idle_rows"]),
        "hold_n": int(sums["hold_n"]),
        "hold_s": sums["hold_s"],
        "hold_skipped_n": {why: int(sums["hold_skip_" + why])
                           for why in SKIPS},
        "late_n": int(sums["late_n"]),
        # how the loop learns of a request (serve/arrivals.py): records the
        # reader handed over and their lag behind the router's stamp; an
        # idle loop's waits, and those of them that a record ended
        "arrival_n": int(sums["arrival_n"]),
        "arrival_lag_s": sums["arrival_lag_s"],
        "arrival_wake_n": int(sums["arrival_wake_n"]),
        "idle_wait_n": int(sums["idle_wait_n"]),
        "iteration_s": sums["iteration_s"],
        "by_width": by_width,
        "narrow_ticks": by_width["narrow"]["ticks"],
        "narrow_wait_s": by_width["narrow"]["wait_s"]}


class ServeEngine:
    """The continuous-batching engine: host scheduler + one jit'd mixed
    prefill/decode step over the paged cache, compiled at two widths
    (``tick_width``): a tick without a prefill chunk does not pay for
    ``prefill_chunk`` positions a slot.  ``stats()["loop"]`` counts the
    narrow ticks and their wait on the device, how much of a wide tick's
    rows and attention blocks its plan filled, and how much of what the
    block tables cover the ticks' attention read.

    ``model`` is a model module that defines ``init_cache``,
    ``copy_blocks``, ``apply_cached``, ``cache_shardings``, ``attn_blocks``
    and ``TICK_COUNTERS`` (models/llama.py, models/moe_llama.py,
    models/latent_moe.py, models/swa_moe.py, models/conv_moe.py;
    docs/serving.md#what-a-served-model-module-exports); ``model_cfg`` its
    config dataclass; ``params`` the trained pytree (host or global
    arrays).  Three optional declarations: ``BOUNDED_READ`` — true where
    the cached attention reads a slot's context only as far as it reaches
    (models/paged.py ``attend_by_blocks`` with a ``Bound``; swa_moe.py reads
    whole tables), which ``stats()["loop"]["context_read_share"]`` counts
    by —; ``cache_kinds(model_cfg)`` — the
    kinds of cache its layers keep (models/paged.py ``CacheKind``); its
    cache, its block tables and ``init_cache`` / ``cache_shardings``'s
    block counts are then dicts by kind, a kind with a window is a ring a
    slot and a kind with a fixed state ``(slots, columns)`` with no table
    (docs/serving.md#cache-kinds; swa_moe.py declares whole contexts and a
    window, conv_moe.py whole contexts and a state, sambay.py all three and
    a second state, a scan's carry, gdn_hybrid.py whole contexts, a state
    and ONE matrix state a slot with the rows to replay; the other modules
    none: one pool, one table) —, and ``greedy_cached``, the tick's greedy tokens
    in place of its logits, for a vocabulary whose ``[slots, chunk, vocab]``
    slab should never exist: with ``read``, the columns whose token the tick
    reads, the head runs on those rows alone and the tokens come back
    ``[slots, decode width]`` (llama.py, moe_llama.py, latent_moe.py,
    sambay.py, gdn_hybrid.py; ``samples_read``); without, on every packed row, ``[slots, chunk]``
    (swa_moe.py, conv_moe.py, until the PR that next changes their programs
    moves them over: ROADMAP S11, S12).  ``stats()["loop"]`` counts the rows
    of the wide ticks and those their head ran on (``packed_rows``,
    ``head_rows``).
    """

    def __init__(self, model, model_cfg, params, cfg: ServeConfig,
                 mesh=None, role: str = "mixed"):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        # a model that fills a block of positions by denoising says so in
        # its config (block_length): the engine takes its rule from there
        self._block = block_length(model_cfg)
        cfg.validate(model_max_seq=model_cfg.max_seq,
                     model_block=self._block)
        # The tick's token budget is the scheduler's: the model packs a
        # tick's valid tokens into that many rows (models/paged.py pack).
        model_cfg = dataclasses.replace(
            model_cfg, max_tick_tokens=cfg.max_batch_tokens)
        self.model = model
        self.model_cfg = model_cfg
        self.cfg = cfg
        if mesh is None:
            from .. import runtime as _rt
            mesh = _rt.get().mesh
        self.mesh = mesh
        kinds = (tuple(model.cache_kinds(model_cfg))
                 if hasattr(model, "cache_kinds") else ())
        self.scheduler = Scheduler(cfg, role=role, kinds=kinds,
                                   block=self._block)
        self._repl = NamedSharding(mesh, P())
        num_blocks = self.scheduler.pool_blocks()
        self._cache_shd = model.cache_shardings(mesh, model_cfg, num_blocks)
        leaves = jax.tree_util.tree_leaves(params)
        if leaves and isinstance(leaves[0], jax.Array):
            self.params = params
        else:
            self.params = replicate_global(params, mesh)
        cache_struct = jax.eval_shape(
            lambda: model.init_cache(model_cfg, num_blocks, cfg.block_size))
        # one sharding a leaf: a model with cache kinds gives one a kind
        self._leaf_shd = (
            {k.name: jax.tree_util.tree_map(
                lambda _, n=k.name: self._cache_shd[n], cache_struct[k.name])
             for k in kinds} if kinds else
            jax.tree_util.tree_map(lambda _: self._cache_shd, cache_struct))
        self.cache = jax.tree_util.tree_map(
            lambda x, shd: _global_zeros(x.shape, x.dtype, shd),
            cache_struct, self._leaf_shd)
        # Host-RAM spill tier behind the device pool
        # (docs/serving.md#replicated-tier): evicted-but-warm radix
        # blocks migrate to host instead of dying, reload on hit.
        self._spill: Optional[HostSpillPool] = None
        if cfg.spill_blocks > 0 and self.scheduler.prefix is not None:
            self._spill = HostSpillPool(cfg.spill_blocks,
                                        self._read_block,
                                        self._write_block)
            self.scheduler.prefix.spill = self._spill
        self._handoffs = 0
        # Where a tick's host time goes, phase by phase (utils/profiler.py
        # PhaseClock); the serving loop (serve/worker.py) times its own
        # phases on the same clock.
        self.clock = PhaseClock()
        for name in _LOOP_SUMS:     # every figure is there from the start
            self.clock.add(name, 0)
        # The gap between two programs (_count_gap): the ready stamp and the
        # two spans of the tick fenced last, None once a launch has followed
        # it or the engine has run dry, and when the launch before returned.
        self._fenced: Optional[Tuple[float, Any, Any]] = None
        self._launch_t = 0.0
        self._launched = False      # this step() launched a tick
        # When the loop should commit the next tick's plan (commit_due).
        self._commit = CommitPoint(self.clock.add)
        # The decode chain's state, the tick program's own (``tick_program``):
        # every slot's token history, its length, whether its stream has
        # ended.  A tick takes them from the tick before and returns them
        # for the tick after; the host writes an admitted prompt into the
        # history (_admit_history) and reads none of them.
        # A model that denoises blocks adds the block's masked positions
        # and its passes so far (``block_tick_program``).
        hist = (cfg.max_slots, cfg.max_seq_len)
        self._chain = tuple(
            _global_zeros(shape, np.int32, self._repl)
            for shape in (hist, hist[:1], hist[:1]) + (
                ((hist[0], self._block), hist[:1]) if self._block else ()))
        # What block denoising did, counted at the fences
        # (stats()["diffusion"]; docs/profiling.md).
        self._diffusion = dict.fromkeys(
            ("slot_passes", "commit_passes", "tokens_fixed",
             "fixed_by_threshold", "fixed_as_surest", "blocks_done"), 0)
        # What the tick counts beside its logits: one small vector a tick
        # (the expert layers' assignments, models/latent_moe.py), summed at
        # every harvest.
        self._counter_names = tuple(model.TICK_COUNTERS)
        self._counters = np.zeros(len(self._counter_names), np.int64)
        self._step_fn = self._build_step()
        # The step's executable at each tick width and the history's
        # (``_admit``, ``admit_program``), all compiled at the first dispatch
        # (_compile_steps): no later tick lowers anything.
        self._steps: Dict[int, Any] = {}
        self._admit = None
        # What the wide ticks' plans filled of what the model computed:
        # [valid tokens, rows] and [attention blocks at chunk width, blocks]
        # (``model.attn_blocks``: slots a block, a narrow block's columns).
        self._wide_rows = np.zeros(2, np.int64)
        self._wide_blocks = np.zeros(2, np.int64)
        # ... and how many rows the wide ticks' output head ran on: the
        # columns the tick reads where the module samples there
        # (``samples_read``), else every row of the program.
        self._samples_read = samples_read(model)
        self._head_rows = 0
        self._attn_blocks = model.attn_blocks(
            model_cfg, cfg.max_slots, cfg.prefill_chunk,
            cfg.max_blocks_per_seq * cfg.block_size)
        # What every dispatched tick's attention read of what its tables
        # cover, and its blocks of slots that held no stream and read
        # nothing (paged.read_counts: [positions read, positions covered,
        # dead blocks, blocks]); a module that bounds its reads by its
        # slots' lengths says so (``BOUNDED_READ``), another reads whole
        # tables.
        self._bounded_read = bool(getattr(model, "BOUNDED_READ", False))
        self._read = np.zeros(4, np.int64)
        # The launched ticks no step() has fenced yet, oldest first: (tick,
        # width, rows, the device's report, counters).  Two while a step()
        # runs (it launches before it fences), at most one between two.
        self._inflight: "collections.deque" = collections.deque()
        self.tick = 0
        self._tokens_prefill = 0
        self._tokens_decode = 0
        self._last_fill = 0.0
        self._prefill_chunks = 0
        self._spec_drafted = 0
        self._spec_accepted = 0
        # Rolling digest of every dispatch's scheduling decisions
        # (admission prefix hits, chunk boundaries, draft tokens, CoW
        # copies).  Rank 0 publishes it in the plan stream and followers
        # assert equality — lockstep divergence is caught at the tick it
        # happens, not when token digests drift (serve/worker.py).
        self.sched_digest = ""
        # The pool's true byte footprint: the preallocated cache pytree
        # itself (this rank's shards of it are the resident bytes the
        # memory plane attributes to the kv_pool plane).
        nbytes = lambda tree: sum(
            int(np.prod(x.shape)) * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(tree))
        self._pool_bytes = nbytes(cache_struct)
        self._kind_bytes = {k.name: nbytes(cache_struct[k.name])
                            for k in kinds}
        # ... and where the device put it: what this process's devices hold
        # of the pool as laid out (a last axis padded to the device's tiles
        # is more than its elements), and each leaf's axes from major to
        # minor.  The shape decides both (docs/serving.md
        # #where-the-pool-lies); a run's record says what it measured.
        leaves, _ = jax.tree_util.tree_flatten_with_path(self.cache)
        self._pool_resident = sum(
            shard.data.on_device_size_in_bytes()
            for _, leaf in leaves for shard in leaf.addressable_shards)
        self._pool_layout = {
            paged.leaf_key(path): list(leaf.format.layout.major_to_minor)
            for path, leaf in leaves}
        try:
            from ..perf.memstats import set_kv_pool_provider
            set_kv_pool_provider(self.kv_pool)
        except Exception:
            pass  # the memory plane must never block engine bring-up

    # ----------------------------------------------------------- compile
    def _build_step(self):
        import jax
        n = len(self._chain)
        return jax.jit(
            tick_program(self.model, self.model_cfg, self.cfg),
            donate_argnums=tuple(range(1, 2 + n)),  # the pool and the chain
            out_shardings=(
                self._leaf_shd, *(self._repl,) * (n + 1),
                self._repl if self._counter_names else None))

    def _tick_shapes(self, width: int):
        """What a tick of ``width`` columns stages, as shapes: the block
        tables, the rows (``ROW``) and the token slab."""
        import jax
        cfg = self.cfg
        shape = lambda *dims: jax.ShapeDtypeStruct(dims, np.int32,
                                                   sharding=self._repl)
        return (jax.tree_util.tree_map(lambda t: shape(*t.shape),
                                       self.scheduler.device_tables()),
                shape(len(BLOCK_ROW if self._block else ROW), cfg.max_slots),
                shape(cfg.max_slots, width))

    def _compile_steps(self) -> None:
        """The step's executable at both widths of ``tick_width`` and the
        history's, lowered at the first dispatch from the shapes a tick
        stages: a process's first decode-only tick (or its first chunk, on
        a decode-role engine) finds its program ready."""
        import jax
        cfg = self.cfg
        for width in {decode_width(cfg, self._block), cfg.prefill_chunk}:
            self._steps[width] = self._step_fn.lower(
                self.params, self.cache, *self._chain,
                *self._tick_shapes(width)).compile()
        # what an admission rewrites of the chain: the history, and a
        # block's masked positions and passes
        held = (self._chain[0], *self._chain[3:])
        self._admit = jax.jit(
            block_admit_program if self._block else admit_program,
            donate_argnums=tuple(range(len(held))),
            out_shardings=(self._repl,) * len(held) if self._block
            else self._repl
        ).lower(*held, *(
            jax.ShapeDtypeStruct(dims, np.int32, sharding=self._repl)
            for dims in ((), held[0].shape[1:]))).compile()

    # ------------------------------------------------------------ intake
    def submit(self, tokens, max_new_tokens: int,
               req_id: Optional[str] = None,
               eos_id: Optional[int] = None,
               trace: Optional[Dict[str, Any]] = None) -> Request:
        req = Request(tokens, max_new_tokens, req_id=req_id,
                      eos_id=eos_id if eos_id is not None
                      else self.cfg.eos_id)
        req.trace = trace
        req.loop0 = self.clock.snapshot()
        return self.scheduler.submit(req)

    def has_work(self) -> bool:
        return self.scheduler.has_work() or bool(self._inflight)

    # ---------------------------------------------------- block transfer
    def _read_block(self, block: int) -> Dict[str, Any]:
        """One pool block as host numpy (spill, the hand-off's export)."""
        return paged.read_block(self.cache, block)

    def _write_block(self, block: int, payload: Dict[str, Any]) -> None:
        """Runs between steps, so the next dispatch reads it."""
        self.cache = paged.write_block(self.cache, block, payload)

    def _whole_contexts_only(self, what: str) -> None:
        """Block transfer reads "a block in every layer": refused where a
        window kind's ring or a state kind has no such block
        (Scheduler.__init__)."""
        if self.scheduler.unshared:
            raise ValueError(
                f"{what} cannot run over the served model's "
                f"{self.scheduler.unshared}; docs/serving.md#cache-kinds")

    # ------------------------------------------------------ disaggregation
    def export_handoff(self, req: Request, first_token: int
                       ) -> Dict[str, Any]:
        """Serialize one finished prefill for a decode engine: the
        request identity/budget, the first sampled token, and the
        prompt blocks' KV as encoded payloads.  Pure read — the caller
        decides when to finish the request."""
        self._whole_contexts_only("the prefill hand-off's export")
        bs = self.cfg.block_size
        n_blocks = -(-req.prompt_len // bs)
        return {
            "req_id": req.req_id,
            "tokens": list(req.tokens),
            "max_new_tokens": req.max_new_tokens,
            "eos_id": req.eos_id,
            "first_token": int(first_token),
            "trace": req.trace,
            "queue_s": (req.admitted_t - req.submitted_t
                        if req.admitted_t is not None else None),
            "prefill_s": (time.perf_counter() - req.admitted_t
                          if req.admitted_t is not None else None),
            # Wall clock, not perf_counter: the export/import stamps
            # cross process boundaries (the handoff component of the
            # per-request SLO attribution is their difference).
            "exported_t": time.time(),
            "blocks": [encode_block_payload(self._read_block(b))
                       for b in req.blocks[:n_blocks]],
        }

    def import_prefill(self, handoff: Dict[str, Any]) -> Request:
        """Decode-side intake of a prefill rank's handoff record: queue
        it for installation (Scheduler._drain_imports) — the request
        enters the slot table directly in decode state with its prompt
        KV written from the payload, skipping prefill entirely."""
        self._whole_contexts_only("the prefill hand-off's import")
        req = Request(handoff["tokens"], int(handoff["max_new_tokens"]),
                      req_id=handoff.get("req_id"),
                      eos_id=(handoff.get("eos_id")
                              if handoff.get("eos_id") is not None
                              else self.cfg.eos_id))
        req.trace = handoff.get("trace")
        req.loop0 = self.clock.snapshot()
        req.upstream = {k: float(handoff[k])
                        for k in ("queue_s", "prefill_s")
                        if handoff.get(k) is not None} or None
        exported_t = handoff.get("exported_t")
        if exported_t is not None:
            req.handoff_s = max(0.0, time.time() - float(exported_t))
        payloads = [decode_block_payload(p) for p in handoff["blocks"]]
        self.scheduler.queue_import(req, payloads,
                                    int(handoff["first_token"]))
        from ..utils import metrics as M
        M.SERVE_IMPORTS.inc()
        self._span("HANDOFF", req, req.handoff_s,
                   end_t=time.perf_counter(),
                   extra={"blocks": len(handoff["blocks"])})
        return req

    def prefix_fps(self) -> Tuple[List[str], str]:
        """This engine's radix-tree advertisement for the replica
        router: (fingerprints, digest) — what rank 0 piggybacks on the
        stats publish (serve/replica.py)."""
        from .replica import prefix_fingerprints, fold_digest
        if self.scheduler.prefix is None:
            return [], fold_digest([])
        fps = prefix_fingerprints(self.scheduler.prefix)
        return fps, fold_digest(fps)

    # -------------------------------------------------------------- tick
    def step(self) -> Dict[str, Any]:
        """Run one engine tick: launch the next program, THEN fence the one
        before it.  Returns the fenced tick's report (one tick of pipeline
        lag): {"tick", "processed", "emitted": {req_id: [new tokens]},
        "finished": [Request]} — an idle report when none was fenced.
        It never waits before it launches: holding the launch until the
        tick in flight is about to end is the serving loop's
        (:meth:`commit_due`; serve/worker.py ``FleetFrontend._hold``)."""
        self.clock.second()     # the open bucket is this step's second
        self._dispatch()
        report = self._harvest()
        # Handoff installs surface their first token (sampled by the
        # prefill rank) in this report — the emission order a mixed
        # engine would have produced at prefill completion.
        for req, toks in self.scheduler.take_import_emits():
            report["emitted"].setdefault(req.req_id, []).extend(toks)
            if req.state == "done":
                report["finished"].append(req)
        if not self.has_work():
            self._fenced = None     # the next launch waits for traffic
        self._update_gauges()
        return report

    def flush(self) -> List[Dict[str, Any]]:
        """Drain until idle (no planned work, nothing in flight)."""
        out = []
        while self.has_work():
            out.append(self.step())
        return out

    def _width(self, C: int) -> str:
        """Which of the two executables a tick of ``C`` columns ran."""
        return "narrow" if C < self.cfg.prefill_chunk else "wide"

    def commit_due(self) -> Optional[float]:
        """For the loop that drives ``step()``, asked once an iteration
        after it has published the fenced tick's tokens: the
        ``perf_counter`` instant until which to hold before it polls,
        submits and calls ``step()`` — the tick in flight is then about to
        end, and the plan the launch fixes is a few milliseconds old
        instead of a whole tick (serve/commit.py;
        docs/serving.md#the-loops-order).  None: do not hold
        (``stats()["loop"]["hold_skipped_n"]`` says why)."""
        behind = self._width(self._inflight[0][1]) if self._inflight else None
        return self._commit.due(behind, self.scheduler.room)

    def _dispatch(self) -> None:
        """Plan, stage and launch the next tick, whether or not the one
        before has been fenced: what a decode row needs of it (its last
        token, its length, its drafts, the end of its stream) the program
        takes from that tick's on the device (``tick_program``).  The host
        gives what only it knows: the rows, prefill chunks' tokens, an
        admitted prompt's history, block tables, CoW copies."""
        self._launched = False
        with self.clock.span("plan") as plan:
            work, copies = self._plan()
        if not work:
            return
        cfg = self.cfg
        with self.clock.span("stage") as stage:
            built = not self._steps
            if built:
                self._compile_steps()
            S, C = cfg.max_slots, tick_width(cfg, work, self._block)
            tokens = np.zeros((S, C), np.int32)
            rows = np.zeros((len(BLOCK_ROW if self._block else ROW), S),
                            np.int32)
            rows[_COPY_DST] = cfg.cache_blocks      # no-op: dropped
            launched = []
            for slot, req, n in work:
                if not req.carried:
                    self._admit_history(slot, req)
                kind = DECODE
                if req.state == "prefill":
                    tokens[slot, :n] = req.tokens[req.pos:req.pos + n]
                    rows[_LEN, slot] = req.pos
                    req.pos += n
                    kind = CHUNK
                    if req.pos == self.scheduler.prefill_end(req):
                        # its decode row follows without waiting for this
                        # tick's fence; a prefill rank's job ends here.  A
                        # block model's prompt samples nothing: its first
                        # tokens come of its first block row
                        kind = CHUNK if self._block else LAST
                        req.state = ("handoff" if self.scheduler.role ==
                                     "prefill" else "decode")
                else:
                    # Speculative verify row: the last emitted token plus
                    # the drafts, both the device's — one multi-token
                    # apply_cached call scores every draft position.  A
                    # hand-off's first row runs at the host's length.
                    rows[_LEN, slot] = req.ctx_len
                    rows[_CARRIED, slot] = req.carried
                req.carried = True
                req.unfenced += kind != CHUNK
                rows[_N, slot], rows[_KIND, slot] = n, kind
                rows[_LIMIT, slot] = req.prompt_len + req.max_new_tokens
                rows[_EOS, slot] = -1 if req.eos_id is None else req.eos_id
                if self._block:
                    rows[_PROMPT, slot] = req.prompt_len
                launched.append((slot, req, n, kind))
            for j, pair in enumerate(copies):
                rows[_COPY_SRC:, j] = pair
            # Async dispatch: device_put + jit return immediately; this
            # tick's H2D staging and compute run behind the tick before
            # and the caller's host work.
            put = lambda a: _make_global(a, self._repl)
            tables = self.scheduler.device_tables()
            dev = [{k: put(t) for k, t in tables.items()}
                   if isinstance(tables, dict) else put(tables),
                   put(rows), put(tokens)]
        with self.clock.span("launch") as launch:
            self.cache, *self._chain, report, counters = self._steps[C](
                self.params, self.cache, *self._chain, *dev)
        self._count_gap(plan, stage, launch)
        behind = self._inflight[-1] if self._inflight else None
        self._commit.launched(
            launch.t1, behind and self._width(behind[1]),
            late=behind is not None and behind[3].is_ready(),
            timed=not built)
        self._inflight.append((self.tick, C, launched, report, counters))
        self._launched = True
        self.tick += 1

    def _admit_history(self, slot: int, req: Request) -> None:
        """A request's first row: its prompt (a hand-off's first token
        behind it) into its slot's history, device-ordered between the tick
        before and its own."""
        row = np.zeros(self.cfg.max_seq_len, np.int32)
        held = req.tokens + req.out_tokens
        row[:len(held)] = held
        at = (_make_global(np.int32(slot), self._repl),
              _make_global(row, self._repl))
        if self._block:
            hist, *state = self._admit(self._chain[0], *self._chain[3:], *at)
            self._chain = (hist, *self._chain[1:3], *state)
        else:
            self._chain = (self._admit(self._chain[0], *at),
                           *self._chain[1:])

    def _count_gap(self, plan, stage, launch) -> None:
        """One launch against the tick before it.  Ahead — that tick is
        unfenced, so this program is queued behind it on the device —,
        nothing of the host's lay between the two: a turnaround of 0
        (``ahead_n``).  With nothing in flight the device waited for this
        launch: from a fence's ready stamp, the seconds to the launch's
        return are the host's whole critical path between two programs
        (``turnaround_s``), kept with the spans it is made of (the rest of
        it, the caller's own work between two ``step()`` among it, is
        ``unspanned``).  Launch return to launch return is the busy loop's
        period (``iteration_s``).  A launch with no fence before it waited
        for traffic, not for the host: counted, not timed."""
        add = self.clock.add
        if self._inflight:
            add("ahead_n", 1)
            add("turnaround_n", 1)
            add("iteration_s", launch.t1 - self._launch_t)
        elif self._fenced is None:
            add("after_idle_n", 1)
        else:
            ready, wait, emit = self._fenced
            add("turnaround_s", launch.t1 - ready)
            add("turnaround_n", 1)
            for name, part in zip(_TURN_SUMS, (
                    wait.t1 - ready, emit.t1 - emit.t0, plan.t1 - plan.t0,
                    stage.t1 - stage.t0, launch.t1 - launch.t0)):
                add(name, part)
            add("iteration_s", launch.t1 - self._launch_t)
        self._fenced = None
        self._launch_t = launch.t1

    def _plan(self):
        """The host's decisions for one dispatch: the scheduler's work
        list and this tick's CoW copies, with handoff imports landed and
        the decisions folded into the lockstep digest."""
        prefix = self.scheduler.prefix
        spill = prefix.spill if prefix is not None else None
        reloads0 = spill.reloaded_total if spill is not None else 0
        work = self.scheduler.plan()
        # Handoff imports staged by the plan: land the prompt KV in the
        # pool BEFORE this tick's step reads it (functional .at writes,
        # device-ordered ahead of the step call).
        for b, payload in self.scheduler.take_pending_writes():
            self._write_block(b, payload)
        for slot, req, n in work:
            if req.admitted_t is not None and not req.carried and \
                    req.state == "prefill":
                # queue-wait span, emitted once at admission
                self._span("NEGOTIATE", req,
                           req.admitted_t - req.submitted_t,
                           end_t=req.admitted_t)
        if spill is not None:
            delta = spill.reloaded_total - reloads0
            if delta > 0:
                for slot, req, n in work:
                    if req.state == "prefill":
                        self._span("SPILL_RELOAD", req, 0.0,
                                   end_t=time.perf_counter(),
                                   extra={"reloads": delta})
                        break
        if not work:
            return work, []
        copies = self.scheduler.take_copies()
        # Fold the dispatch's scheduling decisions into the rolling digest:
        # slot/request/phase/width (width encodes chunk boundaries), the
        # admission-resume positions (prefix hits) and the CoW copy pairs;
        # the drafts follow at the fence that reports them (_emit).
        self._fold_sched([[(slot, req.req_id, req.state, n, req.pos)
                           for slot, req, n in work], copies])
        return work, copies

    def _fold_sched(self, decisions) -> None:
        """Fold what every rank must have decided alike into the rolling
        digest (serve/worker.py compares it tick by tick)."""
        rec = json.dumps(decisions, separators=(",", ":"))
        self.sched_digest = hashlib.sha1(
            (self.sched_digest + rec).encode()).hexdigest()[:16]

    def _harvest(self) -> Dict[str, Any]:
        """Fence the oldest launched tick and emit what the device reports
        of it — once a newer one is queued behind it, or when this step()
        had nothing to launch."""
        if len(self._inflight) <= self._launched:
            return {"tick": None, "processed": 0, "emitted": {},
                    "finished": [], "handoff": []}
        tick, C, launched, report, counters = self._inflight.popleft()
        clock = self.clock
        # The fence in two parts under its one phase: the device still runs
        # (or has not started), then it is done while the host fetches the
        # report.
        with clock.span("harvest_wait") as wait:
            exact = not report.is_ready()   # the wait will see it end
            with annotate("hvd:fence_ready"):
                report.block_until_ready()
            ready = time.perf_counter()
            clock.second()      # a tick lies in the second it was fenced in
            with annotate("hvd:fence_copy"):
                report_host = np.asarray(report)
                if counters is not None:
                    self._counters += np.asarray(counters)
        W = decode_width(self.cfg, self._block)
        width = self._width(C)
        self._commit.fenced(width, ready, exact, ahead=bool(self._inflight))
        clock.add("fence_ready_s", ready - wait.t0)
        clock.add("fence_copy_s", wait.t1 - ready)
        clock.add(width, 1)
        clock.add(width + "_wait_s", wait.t1 - wait.t0)
        # what the tick ran, by the device's word: each slot's columns and
        # the length it ran them at
        n_new, lengths = report_host[:, -2], report_host[:, -1]
        used = int(n_new.sum())
        clock.add("used", used)
        self._last_fill = used / self.cfg.max_batch_tokens
        self._read += paged.read_counts(
            lengths * (n_new > 0), n_new, C, *self._attn_blocks,
            self.cfg.block_size, self.cfg.max_blocks_per_seq,
            self._bounded_read)
        if width == "wide":
            self._count_wide(n_new, used)
        with clock.span("harvest_emit") as emit:
            if self._block:
                out = self._emit_block(tick, launched, report_host, n_new,
                                       lengths)
            else:
                T = W if self._samples_read else C  # the tokens' columns
                out = self._emit(tick, launched, report_host[:, :T],
                                 report_host[:, T:T + W], n_new, lengths)
        self._fenced = (ready, wait, emit)
        return out

    def _count_wide(self, n_new: np.ndarray, used: int) -> None:
        """One wide tick's rows against the program that ran them: the rows
        the model computed (its slab's positions, or the token budget it
        packs them into), those of them its output head ran on (the
        columns the tick reads, where the module samples there) and the
        blocks of slots that attended at chunk width.  Host arithmetic on
        the report's columns."""
        cfg = self.cfg
        packed = min(cfg.max_slots * cfg.prefill_chunk, cfg.max_batch_tokens)
        self._wide_rows += (used, packed)
        self._head_rows += (cfg.max_slots * decode_width(cfg)
                            if self._samples_read else packed)
        self._wide_blocks += paged.wide_blocks(n_new.astype(np.int64),
                                               *self._attn_blocks)

    def _emit(self, tick, launched, tokens_host, fed, n_new, lengths
              ) -> Dict[str, Any]:
        """The host half of a fence: advance every request of the tick by
        what the device reports — the greedy tokens (of the columns the tick
        reads, a prompt's last in column 0, where the module samples there;
        else of every column), the verify rows as it fed them, each row's
        columns and length —, finish those that are done.  The device has
        advanced its own chain by the same rules (``tick_program``)."""
        from ..utils import metrics as M
        now = time.perf_counter()
        emitted: Dict[str, List[int]] = {}
        finished: List[Request] = []
        handoffs: List[Dict[str, Any]] = []
        drafts = []
        for slot, req, n, kind in launched:
            if not self._fenced_row(slot, req, kind, n_new, lengths):
                continue
            for kept in self.scheduler.counted:
                kept.count(req.ctx_len + int(n_new[slot]))
            if kind != DECODE:
                req.ctx_len += n
                self._tokens_prefill += n
                self._prefill_chunks += 1
                M.SERVE_TOKENS.inc(n, phase="prefill")
                M.SERVE_PREFILL_CHUNKS.inc()
                if kind == CHUNK:
                    continue  # still prefilling
                sampled = int(tokens_host[
                    slot, 0 if self._samples_read else n - 1])
                if self.scheduler.role == "prefill":
                    # Disaggregation: this rank's job ends at prefill
                    # completion — export the prompt KV + first token
                    # for a decode engine, keep the prefix warm in OUR
                    # tree (the next shared prompt still hits), free
                    # the slot.  The first token is NOT emitted here;
                    # the decode side emits it (exactly-once).
                    self.scheduler.register_prefix(req)
                    handoffs.append(self.export_handoff(req, sampled))
                    self.scheduler.finish(req, "prefill_done")
                    self._close_loop(req)
                    finished.append(req)
                    self._handoffs += 1
                    M.SERVE_HANDOFFS.inc()
                    continue
                self.scheduler.register_prefix(req)
                new_toks = [sampled]
            else:
                # Greedy verification: row[j] is the greedy continuation
                # after consuming input positions <= j, so draft[j] is
                # accepted iff it EQUALS the previous greedy token —
                # emitted output is bit-identical to plain greedy, only
                # the tokens-per-tick rate changes.
                row = tokens_host[slot]
                draft = fed[slot, 1:n_new[slot]].tolist()
                new_toks = [int(row[0])]
                for j, d in enumerate(draft):
                    if d != new_toks[-1]:
                        break
                    new_toks.append(int(row[j + 1]))
                accepted = len(new_toks) - 1
                req.ctx_len += 1 + accepted
                if draft:
                    drafts.append((slot, draft))
                    self._spec_drafted += len(draft)
                    self._spec_accepted += accepted
                    M.SERVE_SPEC_DRAFTED.inc(len(draft))
                    if accepted:
                        M.SERVE_SPEC_ACCEPTED.inc(accepted)
            emitted_n = self._serve(req, new_toks, now, emitted, finished)
            if kind == DECODE:
                self._tokens_decode += emitted_n
                M.SERVE_TOKENS.inc(emitted_n, phase="decode")
        if drafts:
            self._fold_sched(drafts)    # the device's drafts, rank by rank
        from .. import postmortem as PM
        PM.record_step(tick)  # engine liveness on the /health plane
        return {"tick": tick, "processed": int(n_new.sum()),
                "emitted": emitted, "finished": finished,
                "handoff": handoffs}

    def _fenced_row(self, slot, req, kind, n_new, lengths) -> bool:
        """One launched row at its fence, by the device's word: whether it
        ran.  The chain on the device and the host's view of it are one
        function of the plan stream: a fork is never served."""
        req.unfenced -= kind != CHUNK
        idle = kind == DECODE and not n_new[slot]
        if idle != (req.state == "done") or \
                not idle and req.ctx_len != lengths[slot]:
            raise RuntimeError(
                f"request {req.req_id} ({req.state}, {req.ctx_len} "
                f"tokens cached): the device ran {int(n_new[slot])} "
                f"columns at length {int(lengths[slot])}")
        if idle:
            # launched for a stream that ended in the tick before,
            # unfenced then: the row ran nothing and wrote nothing
            self.clock.add("ahead_idle_rows", 1)
        return not idle

    def _serve(self, req: Request, new_toks: List[int], now: float,
               emitted: Dict[str, List[int]], finished: List[Request]) -> int:
        """Hand a request the tokens a fence found for it, in order, as far
        as its stream goes: it ends at ``eos`` or at ``max_new_tokens``.
        Returns how many were served."""
        from ..utils import metrics as M
        emitted_n = 0
        for tok in new_toks:
            req.out_tokens.append(tok)
            emitted.setdefault(req.req_id, []).append(tok)
            emitted_n += 1
            if req.first_token_t is None:
                req.first_token_t = now
                if req.loop0 is not None:
                    req.prefill_ticks = self._ticks() - \
                        req.loop0["phase_n"].get("harvest_wait", 0)
                M.SERVE_TTFT.observe(req.ttft())
                self._span("PREFILL", req, now - req.admitted_t,
                           end_t=now, extra={"prompt": req.prompt_len})
            if (req.eos_id is not None and tok == req.eos_id) or \
                    len(req.out_tokens) >= req.max_new_tokens:
                reason = ("eos" if req.eos_id is not None
                          and tok == req.eos_id else "completed")
                self.scheduler.finish(req, reason)
                self._close_loop(req)
                finished.append(req)
                tpot = req.tpot()
                if tpot is not None:
                    M.SERVE_TPOT.observe(tpot)
                M.SERVE_REQUESTS.inc(outcome=reason)
                self._span("DECODE", req,
                           req.done_t - req.first_token_t,
                           end_t=req.done_t,
                           extra={"generated": len(req.out_tokens)})
                break  # verified-but-post-EOS drafts are discarded
        return emitted_n

    def _emit_block(self, tick, launched, report, n_new, lengths
                    ) -> Dict[str, Any]:
        """:meth:`_emit` for a model that denoises blocks, by the report of
        ``block_tick_program``: a prompt's chunk advances its context, a
        commit pass moves it by a block, a denoising pass notes the tokens
        it fixed and the pass's number.  The fence of the pass that FILLS a
        block serves its tokens, in order of position and cut to
        ``max_new_tokens`` (the commit pass that follows serves nothing);
        what the last block holds behind the cut is kept for the done
        record (``Request.tail``).  The device has advanced its own chain by
        the same rules."""
        from ..utils import metrics as M
        B, count = self._block, self._diffusion
        toks, fix, sure = (report[:, i * B:(i + 1) * B] for i in range(3))
        passes, commit = report[:, 3 * B], report[:, 3 * B + 1]
        now = time.perf_counter()
        emitted: Dict[str, List[int]] = {}
        finished: List[Request] = []
        for slot, req, n, kind in launched:
            if not self._fenced_row(slot, req, kind, n_new, lengths):
                continue
            if kind != DECODE:
                req.ctx_len += n
                self._tokens_prefill += n
                self._prefill_chunks += 1
                M.SERVE_TOKENS.inc(n, phase="prefill")
                M.SERVE_PREFILL_CHUNKS.inc()
                continue
            count["slot_passes"] += 1
            if commit[slot]:
                count["commit_passes"] += 1
                req.ctx_len += B
                continue
            start, p = req.ctx_len, req.prompt_len
            for j in np.flatnonzero(fix[slot]):
                req.block[start + int(j)] = (int(toks[slot, j]),
                                             int(passes[slot]))
            by_threshold = int(sure[slot].sum())
            count["tokens_fixed"] += int(fix[slot].sum())
            count["fixed_by_threshold"] += by_threshold
            count["fixed_as_surest"] += int(fix[slot].sum()) - by_threshold
            generated = range(max(start, p), start + B)
            if len(req.block) < len(generated):
                continue    # a position of the block is still masked
            count["blocks_done"] += 1
            block, req.block = req.block, {}
            room = req.max_new_tokens - len(req.out_tokens)
            served = self._serve(req, [block[q][0] for q in generated][:room],
                                 now, emitted, finished)
            req.steps += [block[q][1] for q in generated][:served]
            if req.state == "done":
                req.tail = [list(block[q]) for q in generated][served:]
            self._tokens_decode += served
            M.SERVE_TOKENS.inc(served, phase="decode")
        from .. import postmortem as PM
        PM.record_step(tick)  # engine liveness on the /health plane
        return {"tick": tick, "processed": int(n_new.sum()),
                "emitted": emitted, "finished": finished, "handoff": []}

    def _ticks(self) -> int:
        """Ticks harvested so far: one ``harvest_wait`` span each."""
        return self.clock.phase_n.get("harvest_wait", 0)

    def _close_loop(self, req: Request) -> None:
        """At finish: what the loop did in this request's life, from its
        ``submit`` to now (the done record's ``loop``)."""
        if req.loop0 is None:
            return
        d = self.clock.delta(req.loop0)
        sums = d["sums"]
        req.loop = {
            "ticks": d["phase_n"].get("harvest_wait", 0),
            "narrow_ticks": int(sums["narrow"]),
            "narrow_wait_s": round(sums["narrow_wait_s"], 6),
            "prefill_ticks": req.prefill_ticks,
            "phase_s": {k: round(v, 6) for k, v in d["phase_s"].items()},
            # how much of its life was the host's path between two programs
            "turnaround_s": round(sums["turnaround_s"], 6),
            "fence_copy_s": round(sums["fence_copy_s"], 6),
            # the loop's holds at the commit point in that life
            "hold_n": int(sums["hold_n"]),
            "hold_s": round(sums["hold_s"], 6),
            "late_n": int(sums["late_n"]),
            "compiles": d["compiles"]}
        req.loop0 = None

    def _update_gauges(self) -> None:
        from ..utils import metrics as M
        M.SERVE_QUEUE_DEPTH.set(self.scheduler.queue_depth)
        M.SERVE_BATCH_FILL.set(self._last_fill)

    # ------------------------------------------------------------- spans
    def _span(self, phase: str, req: Request, duration_s: float,
              end_t: float, extra: Optional[dict] = None) -> None:
        """Per-request phase span on the merged timeline's 'serve' lane
        (utils/timeline.record_span); no-op without an active timeline."""
        try:
            from .. import runtime as _rt
            if not _rt.is_initialized():
                return
            tl = getattr(_rt.get(), "timeline", None)
            if tl is None:
                return
            from . import trace as _trace
            args = _trace.span_args(getattr(req, "trace", None), phase,
                                    rid=req.req_id, req=req.req_id)
            if extra:
                args.update(extra)
            lag_us = (time.perf_counter() - end_t) * 1e6
            tl.record_span("serve", phase, max(duration_s, 0.0) * 1e6,
                           args=args, ts_us=tl.now_us() - lag_us
                           - max(duration_s, 0.0) * 1e6)
        except Exception:
            pass  # tracing must never take serving down

    # -------------------------------------------------------------- view
    def kv_pool(self) -> Dict[str, Any]:
        """KV-cache pool occupancy for ``GET /serve/stats`` and the
        memory plane (memstats.set_kv_pool_provider registers this at
        construction; docs/memory.md#kv-pool):

          * the allocator's used/free/shared block split;
          * ``pool_bytes`` — the preallocated cache pytree's logical size
            (blocks x block_bytes; resident whether or not blocks are
            used — a paged pool's cost is its reservation);
            ``resident_bytes`` — what this process's devices hold of it
            as laid out, and ``layout`` — each leaf's axes from major to
            minor;
          * ``fragmentation`` — the worst-case-reservation waste: 1 -
            tokens actually written over tokens reserved across active
            requests (prefix-cache-held blocks excluded — they hold
            real KV);
          * ``eviction_pressure`` — prefix-cache evictions per
            admission: > 0 means admissions only succeed by evicting
            cached prefixes (the pool is effectively full).
        """
        s = self.scheduler
        occ = s.allocator.occupancy()
        nb = max(occ["num_blocks"], 1)
        # a block of the allocator's own kind (the one that keeps whole
        # contexts, where the model declares kinds)
        full = self._full_kind()
        block_bytes = (self._kind_bytes[full.name] if full
                       else self._pool_bytes) // nb
        reserved_tokens = written_tokens = 0
        for req in s.slots:
            if req is not None:
                reserved_tokens += len(req.blocks) * self.cfg.block_size
                written_tokens += req.ctx_len
        frag = (1.0 - written_tokens / reserved_tokens
                if reserved_tokens else 0.0)
        evictions = s.prefix.evictions if s.prefix is not None else 0
        occ.update({
            "block_size": self.cfg.block_size,
            "block_bytes": block_bytes,
            "pool_bytes": self._pool_bytes,
            "resident_bytes": self._pool_resident,
            "layout": self._pool_layout,
            "used_bytes": occ["used_blocks"] * block_bytes,
            "fragmentation": round(frag, 4),
            "evictions": evictions,
            "eviction_pressure": (round(evictions / s.admissions, 4)
                                  if s.admissions else 0.0),
        })
        if self._spill is not None:
            spill = self._spill.counters()
            spill["held_bytes_est"] = \
                self._spill.blocks_held * block_bytes
            occ["spill"] = spill
        if s.kinds:
            occ["kinds"] = {k.name: self._kind_pool(k, written_tokens)
                            for k in s.kinds}
        return occ

    def _full_kind(self):
        """The kind that keeps whole contexts (a paged.CacheKind), where the
        model declares kinds."""
        return next((k for k in self.scheduler.kinds
                     if k.window is None and k.state is None), None)

    def _kind_pool(self, kind, written_tokens: int) -> Dict[str, Any]:
        """One cache kind's part of :meth:`kv_pool`: its pool's blocks in
        use and free, the positions it holds now (a ring: at most its own
        length a slot) beside the positions a full-context cache would hold
        for the same slots, and for a ring the same two summed over every
        dispatched tick (what ``kv.window_resident_share.serve`` reads).  A
        state kind has no blocks: its bytes, its columns a slot, and summed
        over every dispatched tick's slots what it holds for them beside
        what a key-value cache of the same layers would
        (``kv.state_resident_share.serve``)."""
        s = self.scheduler
        state = s.states.get(kind.name)
        if state:
            full = self._full_kind()
            # a cached position of one layer, as the paged kind holds it
            kv_bytes = (self._kind_bytes[full.name] // (
                s.allocator.num_blocks * self.cfg.block_size * full.layers)
                if full else 0)
            return {"layers": kind.layers, "window": None,
                    "state": kind.state, "state_columns": state.columns,
                    "slot_bytes": (self._kind_bytes[kind.name]
                                   // self.cfg.max_slots),
                    "pool_bytes": self._kind_bytes[kind.name],
                    "slots": self.cfg.max_slots,
                    "slots_used": sum(r is not None for r in s.slots),
                    "slot_ticks": state.slot_ticks,
                    "state_bytes_ticks": state.slot_ticks * (
                        self._kind_bytes[kind.name] // self.cfg.max_slots),
                    "kv_bytes_ticks": (state.full_position_ticks * kv_bytes
                                       * kind.layers)}
        ring = s.rings.get(kind.name)
        alloc = ring.allocator if ring else s.allocator
        out = {"layers": kind.layers, "window": kind.window,
               "pool_bytes": self._kind_bytes[kind.name],
               "num_blocks": alloc.num_blocks,
               "used_blocks": alloc.num_blocks - alloc.free_count,
               "free_blocks": alloc.free_count,
               "positions_resident": written_tokens,
               "positions_full_context": written_tokens}
        if ring:
            out.update(
                ring_positions=ring.positions,
                positions_resident=sum(min(r.ctx_len, ring.positions)
                                       for r in s.slots if r is not None),
                slot_ticks=ring.slot_ticks,
                resident_position_ticks=ring.resident_position_ticks,
                window_position_ticks=ring.window_position_ticks,
                full_position_ticks=ring.full_position_ticks)
        return out

    def close(self) -> None:
        """Unregister the memory plane's KV-pool provider — a torn-down
        engine must not keep reporting a stale pool."""
        try:
            from ..perf import memstats
            if memstats._kv_pool_fn == self.kv_pool:
                memstats.set_kv_pool_provider(None)
        except Exception:
            pass

    def stats(self) -> Dict[str, Any]:
        s = self.scheduler
        prefix = s.prefix
        out = {
            "tick": self.tick,
            "role": s.role,
            "active": s.active,
            "waiting": s.queue_depth,
            "completed": s.completed,
            "imports": s.imports,
            "handoffs": self._handoffs,
            "free_blocks": s.allocator.free_count,
            "kv_pool": self.kv_pool(),
            "batch_fill": round(self._last_fill, 4),
            "tokens_prefill": self._tokens_prefill,
            "tokens_decode": self._tokens_decode,
            "prefill_chunks": self._prefill_chunks,
            "prefix_cache": {"enabled": prefix is not None},
            "spec": {
                "enabled": bool(self.cfg.spec_decode),
                "drafted_tokens": self._spec_drafted,
                "accepted_tokens": self._spec_accepted,
                "accept_rate": (
                    round(self._spec_accepted / self._spec_drafted, 4)
                    if self._spec_drafted else None),
            },
        }
        share = lambda c: round(int(c[0]) / int(c[1]), 4) if c[1] else None
        snap = self.clock.snapshot()
        sums = snap.pop("sums")
        out["loop"] = dict(snap, **_loop_figures(sums),
                           timeline=self.clock.timeline(),
                           commit=self._commit.view(),
                           ticks=self._ticks(),
                           wide_rows_share=share(self._wide_rows),
                           head_rows=self._head_rows,
                           packed_rows=int(self._wide_rows[1]),
                           wide_blocks_share=share(self._wide_blocks),
                           context_read_share=share(self._read[:2]),
                           dead_blocks_share=share(self._read[2:]))
        if self._counter_names:
            out["moe"] = dict(zip(self._counter_names,
                                  map(int, self._counters)))
        if self._block:
            out["diffusion"] = dict(self._diffusion)
        if prefix is not None:
            out["prefix_cache"].update({
                "hits": prefix.hits,
                "hit_tokens": prefix.hit_tokens,
                "blocks_shared": prefix.blocks_shared,
                "cached_blocks": prefix.size,
                "cow_copies": prefix.cow_copies,
                "evictions": prefix.evictions,
                "hit_rate": (round(prefix.hits / s.admissions, 4)
                             if s.admissions else None),
            })
        if self._spill is not None:
            out["spill"] = self._spill.counters()
        return out


# ----------------------------------------------------- servable loading
SERVE_MANIFEST = "serve.json"

_MODEL_MODULES = {"llama": "horovod_tpu.models.llama",
                  "moe_llama": "horovod_tpu.models.moe_llama",
                  "latent_moe": "horovod_tpu.models.latent_moe",
                  "swa_moe": "horovod_tpu.models.swa_moe",
                  "conv_moe": "horovod_tpu.models.conv_moe",
                  "blockdiff_moe": "horovod_tpu.models.blockdiff_moe",
                  "sambay": "horovod_tpu.models.sambay",
                  "gdn_hybrid": "horovod_tpu.models.gdn_hybrid"}


def save_servable(directory: str, model_name: str, config, params,
                  step: int = 0) -> None:
    """Write a servable directory: ``serve.json`` (model family +
    config) beside a sharded checkpoint (checkpoint.py) — what
    ``hvdrun --serve DIR`` consumes."""
    from .. import checkpoint as ckpt
    os.makedirs(directory, exist_ok=True)
    cfg_dict = {k: v for k, v in dataclasses.asdict(config).items()
                if not hasattr(v, "dtype")}
    cfg_dict.pop("dtype", None)
    with open(os.path.join(directory, SERVE_MANIFEST), "w") as f:
        json.dump({"model": model_name, "config": cfg_dict}, f)
    ckpt.save_checkpoint(directory, step, params=params)


def load_servable(directory: str, mesh) -> Tuple[Any, Any, Any]:
    """Read a servable directory -> (model module, model config, global
    replicated params).  ``serve.json``: {"model": "llama"|"moe_llama"|
    "latent_moe"|"swa_moe"|"conv_moe"|"blockdiff_moe"|"sambay"|
    "gdn_hybrid",
    "config": <name in CONFIGS or kwarg dict>, "seed": int?}.  Params
    come from the latest checkpoint under the directory (restored
    through checkpoint.py into replicated shardings); with no
    checkpoint present, a seeded random init serves — the CPU-virtual
    smoke path, loudly labeled."""
    import importlib
    import sys

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    with open(os.path.join(directory, SERVE_MANIFEST)) as f:
        manifest = json.load(f)
    name = manifest.get("model", "llama")
    if name not in _MODEL_MODULES:
        raise ValueError(f"serve.json model {name!r} unknown; expected "
                         f"one of {sorted(_MODEL_MODULES)}")
    model = importlib.import_module(_MODEL_MODULES[name])
    spec = manifest.get("config", "tiny")
    if isinstance(spec, str):
        model_cfg = model.CONFIGS[spec]
    else:
        model_cfg = type(model.CONFIGS["tiny"])(**spec)

    seed = int(manifest.get("seed", 0))
    host = model.init(jax.random.PRNGKey(seed), model_cfg)
    repl = NamedSharding(mesh, P())
    from .. import checkpoint as ckpt
    try:
        mgr = ckpt.CheckpointManager(directory, max_to_keep=10_000)
        try:
            latest = mgr.latest_step()
            if latest is not None:
                template = jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=repl), host)
                params = mgr.restore(latest, params=template)["params"]
                return model, model_cfg, params
        finally:
            mgr.close()
    except FileNotFoundError:
        pass
    print(f"[hvd.serve] no checkpoint under {directory}; serving "
          f"seed={seed} random-init params (smoke mode)",
          file=sys.stderr, flush=True)
    return model, model_cfg, replicate_global(host, mesh)
