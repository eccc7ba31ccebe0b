"""Paged KV-cache block allocator (host-side bookkeeping) with automatic
prefix caching.

The device pool (paged.py) is a fixed array of NUM_BLOCKS fixed-size token
blocks; this allocator owns which block belongs to which sequence. The free
list is a stack (LIFO reuse keeps recently-touched blocks hot), a sequence's
block table is an append-only list, and free() releases the whole table in
one pass.

Block 0 is reserved as the NULL block: inactive decode slots point their
block tables at it so the compiled decode step can write the (masked,
garbage) KV of idle slots somewhere harmless without branching. The null
block is never handed out and never cached.

Prefix caching (vLLM-style, over FULL blocks only):

  * every block is refcounted; a block may appear in several sequences'
    tables at once (shared prompt prefix) — refcount == number of tables
    (plus copy-on-write pins) holding it;
  * a sequence's prompt is chain-hashed per full block (blake2b over the
    previous block's digest + this block's token ids), so a block's key
    identifies the whole prefix up to and including it;
  * `register_prefix` publishes a finished prefill's full prompt blocks
    into the hash index; `reserve_prefix` looks new prompts up and returns
    a table whose head is the shared cached blocks — the engine prefils
    only the unmatched suffix;
  * when a sequence's refcount on a hashed block drops to zero the block is
    NOT returned to the free list: it parks in an LRU pool of evictable
    cached blocks, still indexed, still matchable. Capacity pressure
    reclaims from the LRU tail only after the free list is empty;
  * a write may never land in a block another reader can see: full blocks
    are immutable by construction (only partial tail blocks are written,
    and those are never hashed/shared), and the one exception — a prompt
    that is ENTIRELY cached, whose re-decoded last token would land in the
    final shared block — is handled by copy-on-write: `reserve_prefix`
    forks that block (fresh private block in the table, the shared source
    pinned until the sequence finishes so the engine can copy its device
    contents before any eviction).

Occupancy/fragmentation are surfaced through the observability metrics
registry (always-on gauges — serving runs don't require FLAGS_metrics):

  serving_kv_blocks_total / _used / _free   pool shape
  serving_kv_cached_blocks                  evictable cached (refcount-0)
  serving_kv_tokens                         live tokens across sequences
  serving_kv_occupancy                      used blocks / allocatable blocks
  serving_kv_fragmentation                  1 - tokens/(used * block_size)

Gauge publication is O(1): running counters, never a sum over sequences.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

from ..observability.registry import counter as _counter, gauge as _gauge

_BLOCKS_TOTAL = _gauge("serving_kv_blocks_total",
                       "KV pool size in blocks (excl. the null block).",
                       always=True)
_BLOCKS_USED = _gauge("serving_kv_blocks_used",
                      "KV blocks currently assigned to sequences.",
                      always=True)
_BLOCKS_FREE = _gauge("serving_kv_blocks_free",
                      "KV blocks on the free list.", always=True)
_BLOCKS_CACHED = _gauge("serving_kv_cached_blocks",
                        "Evictable prefix-cache blocks (hashed, refcount 0).",
                        always=True)
_TOKENS = _gauge("serving_kv_tokens",
                 "Live KV tokens across all sequences.", always=True)
_OCCUPANCY = _gauge("serving_kv_occupancy",
                    "used / allocatable KV blocks.", always=True)
_FRAG = _gauge("serving_kv_fragmentation",
               "1 - tokens/(used*block_size): tail waste of partially "
               "filled last blocks.", always=True)
_PREFIX_HITS = _counter("serving_prefix_cache_hits_total",
                        "Admissions that matched >=1 cached prefix block.",
                        always=True)
_PREFIX_MISSES = _counter("serving_prefix_cache_misses_total",
                          "Admissions that matched no cached block.",
                          always=True)
_PREFIX_HIT_TOKENS = _counter("serving_prefix_hit_tokens_total",
                              "Prompt tokens served from the prefix cache "
                              "(prefill skipped).", always=True)
_PREFIX_EVICTIONS = _counter("serving_prefix_evictions_total",
                             "Cached blocks reclaimed under capacity "
                             "pressure.", always=True)
_PREFIX_DEDUPS = _counter("serving_prefix_dedup_blocks_total",
                          "Private prefilled blocks swapped for an "
                          "already-indexed twin at register time.",
                          always=True)
_PREFIX_IMPORTS = _counter("serving_prefix_imported_blocks_total",
                           "Streamed KV blocks admitted into the cache "
                           "after chain-hash verification.", always=True)
_PREFIX_IMPORT_DEDUPS = _counter("serving_prefix_import_dedup_total",
                                 "Streamed blocks whose digest was already "
                                 "resident (idempotent no-op).", always=True)


_WINDOW_BLOCKS = _counter(
    "serving_window_blocks_total",
    "Blocks of window layers' rings: written (a block of a sequence's keys "
    "went into a ring entry) and recycled (the entry it went into held an "
    "older block of the same sequence). written - recycled of a sequence "
    "never passes its ring.", labelnames=("event",), always=True)


class WindowRings:
    """Host-side bookkeeping of a WINDOW group's pool: a layer that sees
    only the last `window` keys needs no more of a sequence than that, so a
    sequence holds a fixed RING of `ring_blocks` blocks however long its
    context grows: position p lives in ring entry (p // block_size) %
    ring_blocks, the newest block overwriting the oldest, and the attention
    masks by absolute position (ops/pallas/paged_attention.py).

    ring_blocks = ceil(window / block_size) + 1: the window's blocks, and
    one more because a window seldom starts on a block's edge. The pool has
    a ring for each of `max_seqs` sequences plus the null block 0, so a
    sequence that has a slot has a ring; `can_reserve` is still asked at
    admission, as of every cache group."""

    NULL_BLOCK = 0

    def __init__(self, max_seqs: int, window: int, block_size: int):
        self.window = int(window)
        self.block_size = int(block_size)
        self.ring_blocks = -(-self.window // self.block_size) + 1
        self.max_seqs = int(max_seqs)
        self.num_blocks = 1 + self.max_seqs * self.ring_blocks
        self._free: List[int] = list(range(self.max_seqs - 1, -1, -1))
        self._rings: Dict[object, int] = {}

    def can_reserve(self) -> bool:
        return bool(self._free)

    def reserve(self, seq_id) -> List[int]:
        """The sequence's ring: its table row, fixed until it is freed."""
        if seq_id in self._rings:
            raise ValueError(f"sequence {seq_id!r} already holds a ring")
        if not self._free:
            raise MemoryError("no window ring free")
        self._rings[seq_id] = self._free.pop()
        return self.table(seq_id)

    def table(self, seq_id) -> List[int]:
        first = 1 + self._rings[seq_id] * self.ring_blocks
        return list(range(first, first + self.ring_blocks))

    def free(self, seq_id, n_tokens: int = 0) -> int:
        """Release the ring; `n_tokens` positions were written into it, and
        the counters say how many blocks that was and how many of them
        overwrote an older one. Returns the blocks held at the end."""
        self._free.append(self._rings.pop(seq_id))
        blocks = -(-int(n_tokens) // self.block_size)
        recycled = max(0, blocks - self.ring_blocks)
        if blocks:
            _WINDOW_BLOCKS.inc(blocks, event="written")
        if recycled:
            _WINDOW_BLOCKS.inc(recycled, event="recycled")
        return blocks - recycled

    def sequences(self):
        return list(self._rings)

    @property
    def used_blocks(self) -> int:
        return len(self._rings) * self.ring_blocks

    def check_invariants(self) -> None:
        held = set(self._rings.values())
        free = set(self._free)
        assert len(held) == len(self._rings), "two sequences share a ring"
        assert not (held & free), "a ring is held and free at once"
        assert held | free == set(range(self.max_seqs)), "a ring leaked"

    def conservation_ok(self) -> bool:
        return len(self._rings) + len(self._free) == self.max_seqs

    def occupancy_report(self) -> dict:
        return {"conservation_ok": self.conservation_ok(),
                "window": self.window, "ring_blocks": self.ring_blocks,
                "num_blocks": self.num_blocks - 1,
                "block_size": self.block_size,
                "used_blocks": self.used_blocks,
                "sequences": len(self._rings)}


class BlockAllocator:
    """Host-side allocator over a pool of `num_blocks` blocks of
    `block_size` tokens each. Block ids index the device pool directly."""

    NULL_BLOCK = 0

    def __init__(self, num_blocks: int, block_size: int,
                 prefix_cache: bool = True):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (one is the null block)")
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.prefix_cache = bool(prefix_cache)
        # stack: LIFO reuse; block 0 reserved (never handed out)
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        self._tables: Dict[object, List[int]] = {}
        self._lens: Dict[object, int] = {}
        # refcounts for LIVE blocks only (block in >=1 table or pinned)
        self._ref: Dict[int, int] = {}
        # content addressing: block -> chain digest, digest -> block. A
        # hashed block keeps its digest while live AND while evictable;
        # both maps drop the entry together on eviction.
        self._digest: Dict[int, bytes] = {}
        self._index: Dict[bytes, int] = {}
        # refcount-0 hashed blocks, LRU order (oldest first = evict first)
        self._evictable: "OrderedDict[int, None]" = OrderedDict()
        # copy-on-write source pins: seq_id -> blocks held alive beyond the
        # table so the engine can device-copy them before any eviction
        self._extra: Dict[object, List[int]] = {}
        self._tokens = 0            # running sum of _lens (O(1) publish)
        # table size at reservation: rollback never truncates below it (a
        # worst-case reservation must survive speculation intact)
        self._base: Dict[object, int] = {}
        self.last_fork: Optional[Tuple[int, int]] = None
        # register_prefix dedup swaps: [(table_index, private, canonical)]
        self.last_dedup: List[Tuple[int, int, int]] = []
        self._publish()

    # -- capacity ---------------------------------------------------------
    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return len(self._ref)

    @property
    def cached_blocks(self) -> int:
        return len(self._evictable)

    @property
    def available_blocks(self) -> int:
        """Blocks a new reservation can claim: free + evictable cached."""
        return len(self._free) + len(self._evictable)

    def blocks_for(self, n_tokens: int) -> int:
        return -(-int(n_tokens) // self.block_size)  # ceil div

    def can_allocate(self, n_tokens: int) -> bool:
        return self.blocks_for(n_tokens) <= self.available_blocks

    # -- content addressing -----------------------------------------------
    def chain_digest(self, prev: bytes, tokens) -> bytes:
        """One link of the chain hash: commits to `prev` (the previous
        full block's digest, b"" at the chain head) plus this block's
        token ids — so a digest identifies the whole prefix up to and
        including its block, and a receiver can verify a streamed block
        against nothing but the preceding digest and the claimed tokens."""
        h = hashlib.blake2b(prev, digest_size=16)
        for t in tokens:
            h.update(int(t).to_bytes(8, "little", signed=True))
        return h.digest()

    def block_hashes(self, tokens) -> List[bytes]:
        """Chain digests for every FULL block of `tokens`: digest i commits
        to tokens[0 : (i+1)*block_size], so equal digests imply equal whole
        prefixes (not just equal blocks)."""
        out: List[bytes] = []
        prev = b""
        bs = self.block_size
        for i in range(len(tokens) // bs):
            prev = self.chain_digest(prev, tokens[i * bs:(i + 1) * bs])
            out.append(prev)
        return out

    # -- KV-block streaming (disaggregated serving / live migration) -------
    def export_prefix(self, tokens) -> List[dict]:
        """Wire metadata for the RESIDENT full-block prefix of `tokens`:
        one record per indexed full block, in chain order, stopping at the
        first full block that is not in the index. Each record carries the
        chain digest, the previous link's digest, the block's token ids,
        and the local block id (so a caller that owns the device pool can
        attach the block's KV bytes). Read-only — no refcounts move."""
        out: List[dict] = []
        prev = b""
        bs = self.block_size
        for i in range(len(tokens) // bs):
            blk_tokens = [int(t) for t in tokens[i * bs:(i + 1) * bs]]
            key = self.chain_digest(prev, blk_tokens)
            blk = self._index.get(key)
            if blk is None:
                break
            out.append({"digest": key, "prev": prev, "block": blk,
                        "tokens": blk_tokens})
            prev = key
        return out

    def import_block(self, prev_digest: bytes, tokens,
                     digest: bytes) -> Tuple[int, bool]:
        """Admit one streamed FULL block into the cache. The chain digest
        is recomputed from `prev_digest` + `tokens` and must equal the
        claimed `digest` — a corrupted or mislabeled block is rejected
        (ValueError) before it can poison the index. Returns
        `(block_id, imported)`:

          * already-resident digest -> `(existing_block, False)`: the
            transfer is an idempotent no-op (its LRU position refreshes so
            a chain being streamed can't evict its own head);
          * otherwise a blank block is claimed (free stack, then LRU
            eviction) and published directly into the evictable cached
            pool — refcount 0, matchable, reclaimable — and the caller
            must scatter the block's KV bytes into the device pool at
            `block_id` before any reservation can match it.

        Conservation holds by construction: the block moves free/evicted ->
        evictable. Raises MemoryError when no blank block exists."""
        if not self.prefix_cache:
            raise ValueError("prefix cache disabled: an imported block "
                             "could never be matched")
        if len(tokens) != self.block_size:
            raise ValueError(f"imported block carries {len(tokens)} tokens, "
                             f"expected a full block of {self.block_size}")
        want = self.chain_digest(prev_digest, tokens)
        if want != bytes(digest):
            raise ValueError("chain-hash mismatch: streamed block rejected "
                             "(corrupt payload or broken chain)")
        blk = self._index.get(want)
        if blk is not None:
            if blk in self._evictable:
                self._evictable.move_to_end(blk)
            _PREFIX_IMPORT_DEDUPS.inc()
            return blk, False
        blk = self._pop_block()
        self._digest[blk] = want
        self._index[want] = blk
        self._evictable[blk] = None      # newest at the LRU tail
        _PREFIX_IMPORTS.inc()
        self._publish()
        return blk, True

    def _match(self, tokens) -> List[int]:
        """Longest run of cached blocks covering a prefix of `tokens`."""
        if not self.prefix_cache:
            return []
        matched: List[int] = []
        for key in self.block_hashes(tokens):
            blk = self._index.get(key)
            if blk is None:
                break
            matched.append(blk)
        return matched

    def peek_match(self, tokens) -> int:
        """Prompt tokens a reservation would serve from cache (no side
        effects; scheduler admission gating)."""
        m = len(self._match(tokens))
        return min(m * self.block_size, len(tokens))

    def blocks_needed(self, tokens, total_tokens: int) -> int:
        """NEW blocks a reserve_prefix() would claim from the pool (the
        suffix worst case, +1 when a full-prompt match forks its last
        block). Excludes revived cached blocks — those were already
        resident."""
        plen = len(tokens)
        matched = self._match(tokens)
        m = len(matched)
        need = self.blocks_for(max(int(total_tokens), plen, 1)) - m
        if m and m * self.block_size >= plen:
            need += 1   # copy-on-write fork of the last shared block
        return need

    def can_reserve_prefix(self, tokens, total_tokens: int) -> bool:
        """Admission gate: do the suffix's new blocks fit beside the
        matched blocks that must be revived out of the evictable pool?"""
        matched = self._match(tokens)
        revive = sum(1 for b in matched if b in self._evictable)
        plen = len(tokens)
        m = len(matched)
        need = self.blocks_for(max(int(total_tokens), plen, 1)) - m
        if m and m * self.block_size >= plen:
            need += 1
        return need + revive <= self.available_blocks

    # -- block pool internals ---------------------------------------------
    def _pop_block(self) -> int:
        """A blank block: the free stack first, then evict the LRU cached
        block (dropping its index entry — the prefix is gone)."""
        if self._free:
            return self._free.pop()
        if self._evictable:
            blk, _ = self._evictable.popitem(last=False)   # oldest first
            key = self._digest.pop(blk)
            del self._index[key]
            _PREFIX_EVICTIONS.inc()
            return blk
        raise MemoryError("KV pool exhausted")

    def _claim(self, need: int) -> List[int]:
        if need > self.available_blocks:
            raise MemoryError(
                f"KV pool exhausted: need {need} blocks, "
                f"{self.available_blocks} available")
        out = []
        for _ in range(need):
            blk = self._pop_block()
            self._ref[blk] = 1
            out.append(blk)
        return out

    def _decref(self, blk: int) -> bool:
        """Drop one reference; True when the block left the live set."""
        n = self._ref[blk] - 1
        if n > 0:
            self._ref[blk] = n
            return False
        del self._ref[blk]
        if blk in self._digest and self.prefix_cache:
            self._evictable[blk] = None          # newest at the LRU tail
        else:
            self._free.append(blk)
        return True

    def _revive(self, blk: int) -> None:
        """Take a matched block live (cached -> referenced, or +1 ref)."""
        if blk in self._ref:
            self._ref[blk] += 1
        else:
            del self._evictable[blk]
            self._ref[blk] = 1

    # -- lifecycle --------------------------------------------------------
    def allocate(self, seq_id, n_tokens: int) -> List[int]:
        """Claim blocks for a new sequence of `n_tokens` (prefill). Returns
        the block table. Raises KeyError on duplicate id, MemoryError when
        the pool can't hold it (callers queue the request instead)."""
        if seq_id in self._tables:
            raise KeyError(f"sequence {seq_id!r} already allocated")
        need = self.blocks_for(max(int(n_tokens), 1))
        table = self._claim(need)
        self._tables[seq_id] = table
        self._lens[seq_id] = int(n_tokens)
        self._tokens += int(n_tokens)
        self._base[seq_id] = len(table)
        self._publish()
        return table

    def reserve(self, seq_id, n_tokens: int, total_tokens: int) -> List[int]:
        """allocate(), but claim blocks for `total_tokens` (worst case)
        upfront while the live length starts at `n_tokens`. The table never
        grows mid-decode, so the serving engine uploads it to the device
        ONCE at admission and never touches it again — no per-step
        allocator call, no per-step table scatter. Costs nothing in
        capacity when admission already gates on the worst case."""
        if seq_id in self._tables:
            raise KeyError(f"sequence {seq_id!r} already allocated")
        need = self.blocks_for(max(int(total_tokens), int(n_tokens), 1))
        table = self._claim(need)
        self._tables[seq_id] = table
        self._lens[seq_id] = int(n_tokens)
        self._tokens += int(n_tokens)
        self._base[seq_id] = len(table)
        self._publish()
        return table

    def reserve_prefix(self, seq_id, tokens,
                       total_tokens: int) -> Tuple[List[int], int,
                                                   Optional[int], int]:
        """reserve(), but the table's head reuses cached blocks matching
        the prompt's full-block prefix. Returns
        `(table, matched_tokens, cow_src, new_blocks)`:

          * `matched_tokens` — prompt tokens whose KV is already resident;
            the engine prefils only `tokens[matched_tokens:]`;
          * `cow_src` — when the ENTIRE prompt matched, the engine enters
            decode directly and its first write would land in the last
            shared block: that table entry is a fresh private fork and
            `cow_src` is the shared source to device-copy from (pinned
            until free(seq_id) so concurrent admissions can't evict it);
          * `new_blocks` — blocks claimed from the pool (suffix worst case
            + fork), the number capacity actually shrank by.
        """
        if seq_id in self._tables:
            raise KeyError(f"sequence {seq_id!r} already allocated")
        plen = len(tokens)
        matched = self._match(tokens)
        m = len(matched)
        total = self.blocks_for(max(int(total_tokens), plen, 1))
        full_match = bool(m) and m * self.block_size >= plen
        need = total - m + (1 if full_match else 0)
        revive = sum(1 for b in matched if b in self._evictable)
        if need + revive > self.available_blocks:
            raise MemoryError(
                f"KV pool exhausted: need {need} blocks beside {revive} "
                f"revivals, {self.available_blocks} available")
        # revive FIRST: _pop_block must never evict a block we matched
        for blk in matched:
            self._revive(blk)
        fresh = self._claim(need)
        cow_src: Optional[int] = None
        if full_match:
            # fork the last shared block: the fresh block takes its table
            # slot, the source stays referenced (pinned outside the table)
            # until this sequence finishes so the engine can copy its
            # device contents without racing an eviction
            cow_src = matched[-1]
            table = matched[:-1] + [fresh[0]] + fresh[1:]
            self._extra.setdefault(seq_id, []).append(cow_src)
        else:
            table = matched + fresh
        self._tables[seq_id] = table
        self._lens[seq_id] = plen
        self._tokens += plen
        self._base[seq_id] = len(table)
        matched_tokens = min(m * self.block_size, plen)
        if m:
            _PREFIX_HITS.inc()
            _PREFIX_HIT_TOKENS.inc(matched_tokens)
        elif self.prefix_cache:
            _PREFIX_MISSES.inc()
        self._publish()
        return table, matched_tokens, cow_src, need

    def register_prefix(self, seq_id, tokens) -> int:
        """Publish a prefilled prompt's full blocks into the hash index so
        later prompts can share them. Call AFTER the prefix KV has been
        scattered into the pool pages. Idempotent. When a block's content
        key is ALREADY indexed under a different block (two identical
        prompts prefilled concurrently), the private duplicate is swapped
        for the canonical block — live dedup: the table adopts the
        canonical block, the duplicate returns to the free list, and the
        swap is recorded in `self.last_dedup` as
        `(table_index, private_blk, canonical_blk)` so a caller that owns
        device state can redirect its block-table row. Returns how many
        blocks were newly indexed."""
        if not self.prefix_cache:
            return 0
        table = self._tables[seq_id]
        added = 0
        self.last_dedup = []
        for i, key in enumerate(self.block_hashes(tokens)):
            blk = table[i]
            if blk == self.NULL_BLOCK or blk in self._digest:
                continue
            canon = self._index.get(key)
            if canon is not None and canon != blk:
                # identical content prefilled twice: share from now on.
                # The private block was claimed fresh (refcount 1, never
                # hashed), so the decref sends it straight to the free
                # stack. The canonical block may be parked evictable.
                self._revive(canon)
                table[i] = canon
                self._decref(blk)
                self.last_dedup.append((i, blk, canon))
                _PREFIX_DEDUPS.inc()
                continue
            self._digest[blk] = key
            self._index[key] = blk
            added += 1
        if self.last_dedup:
            self._publish()
        return added

    def rollback(self, seq_id, n_tokens: int) -> List[int]:
        """Rewind a sequence by `n_tokens` (speculative-decode rejection).
        The live length shrinks and any blocks appended PAST the original
        reservation that the shorter length no longer needs are released —
        the reservation itself (`reserve*`'s worst case) is never
        truncated, so a mid-flight sequence keeps its admission guarantee.
        Returns the (possibly trimmed) block table. The rejected tail's
        device KV is left in place as garbage masked by the length — full
        blocks are immutable/shared by construction, so rejected writes
        only ever landed in this sequence's private blocks."""
        n = int(n_tokens)
        if n < 0:
            raise ValueError("rollback count must be >= 0")
        if n == 0:
            return self._tables[seq_id]
        if n > self._lens[seq_id]:
            raise ValueError(
                f"rollback of {n} exceeds live length {self._lens[seq_id]}")
        table = self._tables[seq_id]
        new_len = self._lens[seq_id] - n
        keep = max(self.blocks_for(max(new_len, 1)),
                   self._base.get(seq_id, 0))
        while len(table) > keep:
            self._decref(table.pop())
        self._lens[seq_id] = new_len
        self._tokens -= n
        self._publish()
        return table

    def append_token(self, seq_id) -> List[int]:
        """Account one decoded token; grows the block table by one block
        when the sequence crosses a block boundary, and copy-on-write forks
        the destination block if it is shared (refcount > 1) or published
        in the prefix index — a write must never be visible to another
        reader. The fork is recorded in `self.last_fork = (src, dst)` so a
        caller that owns device state can copy the contents. Raises
        MemoryError when a needed block isn't there — the scheduler
        preempts or queues in that case."""
        table = self._tables[seq_id]
        n = self._lens[seq_id] + 1
        self.last_fork = None
        if self.blocks_for(n) > len(table):
            if not self.available_blocks:
                raise MemoryError("KV pool exhausted on append")
            blk = self._pop_block()
            self._ref[blk] = 1
            table.append(blk)
        else:
            bi = (n - 1) // self.block_size   # block receiving this token
            blk = table[bi]
            if self._ref.get(blk, 0) > 1 or blk in self._digest:
                dst = self._pop_block()
                self._ref[dst] = 1
                table[bi] = dst
                self._decref(blk)
                self.last_fork = (blk, dst)
        self._lens[seq_id] = n
        self._tokens += 1
        self._publish()
        return table

    def free(self, seq_id) -> int:
        """Release a sequence's references. Unhashed blocks whose refcount
        hits zero go straight back to the free stack (immediate LIFO
        reuse); hashed blocks park in the evictable LRU pool, still
        matchable. Returns how many blocks left the live set."""
        table = self._tables.pop(seq_id)
        self._tokens -= self._lens.pop(seq_id)
        self._base.pop(seq_id, None)
        released = 0
        for blk in reversed(table):      # LIFO: reuse hottest first
            released += self._decref(blk)
        for blk in self._extra.pop(seq_id, ()):
            released += self._decref(blk)
        self._publish()
        return released

    # -- introspection ----------------------------------------------------
    def table(self, seq_id) -> List[int]:
        return list(self._tables[seq_id])

    def seq_len(self, seq_id) -> int:
        return self._lens[seq_id]

    def sequences(self):
        return list(self._tables)

    def refcount(self, blk: int) -> int:
        return self._ref.get(blk, 0)

    def check_invariants(self) -> None:
        """Conservation + sharing invariants (tests call this after every
        mutation sequence; cheap enough for production asserts too)."""
        allocatable = self.num_blocks - 1
        live = set(self._ref)
        ev = set(self._evictable)
        free = set(self._free)
        assert not (live & ev) and not (live & free) and not (ev & free), \
            "a block is in two pools at once"
        assert len(live) + len(ev) + len(free) == allocatable, \
            f"conservation violated: {len(live)}+{len(ev)}+{len(free)} " \
            f"!= {allocatable}"
        assert self.NULL_BLOCK not in live | ev | free
        assert self.NULL_BLOCK not in self._digest
        # refcount >= number of live readers
        readers: Dict[int, int] = {}
        for t in self._tables.values():
            for b in t:
                readers[b] = readers.get(b, 0) + 1
        for pins in self._extra.values():
            for b in pins:
                readers[b] = readers.get(b, 0) + 1
        for b, r in readers.items():
            assert self._ref.get(b, 0) == r, \
                f"block {b}: refcount {self._ref.get(b, 0)} != {r} readers"
        assert set(readers) == live
        # index <-> digest are inverse bijections over hashed blocks
        assert {v: k for k, v in self._index.items()} == self._digest
        assert ev <= set(self._digest)
        assert self._tokens == sum(self._lens.values())

    def conservation_ok(self) -> bool:
        """O(1) conservation law: every allocatable block is in exactly one
        of live / evictable / free. False means a leak or double-free (KV
        corruption follows) — the serving anomaly engine samples this per
        tick; check_invariants() is the O(n) forensic version."""
        return (len(self._ref) + len(self._evictable) + len(self._free)
                == self.num_blocks - 1)

    def occupancy_report(self) -> dict:
        """Pool shape + occupancy/fragmentation, the dict the metrics
        gauges mirror."""
        allocatable = self.num_blocks - 1
        used = self.used_blocks
        tokens = self._tokens
        cap = used * self.block_size
        return {
            "conservation_ok": self.conservation_ok(),
            "num_blocks": allocatable,
            "block_size": self.block_size,
            "used_blocks": used,
            "free_blocks": len(self._free),
            "cached_blocks": len(self._evictable),
            "sequences": len(self._tables),
            "tokens": tokens,
            "occupancy": used / allocatable if allocatable else 0.0,
            # shared blocks can make per-sequence token sums exceed the
            # unique-block capacity; clamp at 0 (no tail waste)
            "fragmentation": max(0.0, 1.0 - tokens / cap) if cap else 0.0,
        }

    def _publish(self):
        # O(1): running counters only — never a sum over sequences
        allocatable = self.num_blocks - 1
        used = len(self._ref)
        cap = used * self.block_size
        _BLOCKS_TOTAL.set(allocatable)
        _BLOCKS_USED.set(used)
        _BLOCKS_FREE.set(len(self._free))
        _BLOCKS_CACHED.set(len(self._evictable))
        _TOKENS.set(self._tokens)
        _OCCUPANCY.set(used / allocatable if allocatable else 0.0)
        _FRAG.set(max(0.0, 1.0 - self._tokens / cap) if cap else 0.0)

    def __repr__(self):  # pragma: no cover
        r = self.occupancy_report()
        return (f"BlockAllocator(blocks={r['used_blocks']}/"
                f"{r['num_blocks']}, cached={r['cached_blocks']}, "
                f"seqs={r['sequences']}, occ={r['occupancy']:.2f})")
