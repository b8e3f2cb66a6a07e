"""Block-paged KV storage for the serving engine.

A pool that gave every slot a contiguous `seq_len` stripe of cache per
layer would scale decode HBM as `num_slots x seq_len` even though most
requests finish far short of `seq_len` — the padding resident and
unsellable. This module spends those bytes on admissible work:

* KV rows live in per-layer block ARENAS shaped
  `[num_blocks, block_size, kv_heads, head_dim]`, shared by every
  sequence on the server;
* a sequence's logical cache is its BLOCK TABLE — the ordered block
  ids covering positions `[j*block_size, (j+1)*block_size)`;
* `BlockAllocator` is the host-side accounting: alloc/extend/free are
  O(1) per block, and a RESERVATION ledger guarantees that a seated
  request can always extend to its full token budget — out-of-blocks
  is an admission-time condition (backpressure), never a mid-decode
  crash;
* `PagedKVPool` owns the device arenas and the write paths: the
  block-granular prompt insertion (one `dynamic_update_slice` per
  block, never a whole-slot copy) and the per-step decode-row scatter
  (`.at[bids, offs].set`, free lanes dropped via an out-of-bounds
  sentinel).

PREFIX SHARING (share_prefix=True): blocks are REFCOUNTED and full
prompt blocks are indexed in a content-addressed prefix trie keyed
`(parent block id, block token tuple)` — collision-free by
construction. A request whose prompt prefix matches a resident chain
seats by INCREMENTING refcounts instead of allocating + re-prefilling;
the engine then prefills only the unshared suffix. Invariants:

* only FULL blocks enter the index — every row of an indexed block is
  real prompt content, and its owner never writes it again (decode
  writes land at positions >= the prompt length, i.e. in later
  blocks);
* a block is freed (returned to the free list) only at refcount 0.
  Refcount-0 blocks that are still indexed become RECLAIMABLE: they
  sit in an LRU cache, revivable by a future prefix match at zero
  cost, and are evicted (leaf-first — a live block's ancestors are
  always live, so every reclaimable subtree has reclaimable leaves)
  when the free list runs dry. `available()` therefore counts
  free + reclaimable - reserved;
* COPY-ON-WRITE: a slot's write into a block with refcount > 1 first
  copies the block into a fresh one and repoints the slot's table
  (`cow`). The only planned CoW is the full-prompt-match seat (the
  last token must re-run for logits, re-writing its row into the
  shared tail block), and `alloc` RESERVES one block of CoW credit
  for it up front — the CoW fault draws from the slot's existing
  reservation, never from thin air, keeping out-of-blocks an
  admission-time condition.

INT8 ARENAS (model kv_cache_dtype="int8"): per-row scales are KV row
leaves too — the batch-1 cache template then carries
`[1, hkv, cache_len, 1]` f32 scale buffers beside the int8 rows, so
`build_pools` maps them to `[num_blocks, block_size, hkv, 1]` scale
arenas through the SAME `kv_row_leaf` convention, and every write path
here (block-granular prompt insertion, decode-row scatter, CoW block
copy) is tree-generic and carries scale leaves with no special case.
The quantize-at-insertion invariant: rows are quantized exactly where
they are produced (the model's prefill cache write / decode-tile sow)
and the arenas only ever RECEIVE quantized data; every read defers the
dequantize into the paged attention scan (k-scales fold into score
tiles, v-scales into weights — ops.attention.paged_decode_attention),
so no float copy of cached rows exists anywhere. The prefix trie is
keyed on TOKEN IDS, not bytes, so sharing/CoW/reclaim are dtype-blind.

TIERED HOST SPILL (host_bytes > 0): eviction no longer forgets a
chain — it DEMOTES it. When the reclaimable LRU must give up a
refcount-0 block, the block's rows (int8 rows AND f32 scale leaves,
through the same tree-generic `kv_row_leaf` paths that carry them
everywhere else) are copied into host numpy buffers and the trie entry
is re-keyed onto a stable negative VIRTUAL id, so the prefix index
keeps resolving chains that are no longer device-resident — the same
host⇄device split `embedding/host_spill.py` plays for embedding rows.
A later prompt that matches a spilled chain revives it by DEVICE
UPLOAD (a batched `dynamic_update_slice` scatter into freshly
allocated blocks, one executable per size bucket) instead of
re-running prefill; `plan`/`can_seat` charge each spilled chain block
exactly like a fresh draw, so admission and allocation cannot
disagree, and the admission cost of a warm prefix becomes upload
latency rather than prefill compute. Invariants:

* eviction is leaf-first in BOTH tiers: a block spills only when its
  indexed children are all spilled, and a spilled entry drops only
  when it has no indexed children at all — so every surviving trie
  path is complete (resident prefix, spilled suffix, never a hole);
* the host tier is BOUNDED (`host_bytes`, LRU drop of the oldest
  childless spilled entry) and never exceeds its budget;
* `flush_index` (hot reload) flushes BOTH tiers — stale-params rows
  must never seat a new request from either side of the PCIe bus;
* virtual ids are never reused, so a recycled device block id can
  never collide with a spilled entry's key.

UPDATED IN PLACE: every compiled program that takes the pool tree and
hands one back DONATES it (`donate_argnums=(0,)`: the pool is the
program's first argument and its first result), so the scatter or
`dynamic_update_slice` writes its few rows into the arenas themselves
— a program that did not donate would first copy every arena to make
its output (1.7 GB a launch at the serving cell's size). What that
obliges the callers to:

* after a donating call the tree that went in is DELETED. The pool is
  held in exactly one place, `PagedKVPool.pools` (the engine's draft
  pool in `_d_pool`), and `PagedKVPool.update` rebinds it from the
  call's result before it returns; nobody else keeps a pool tree
  across a call. Readers on the scheduler thread between calls (the
  spill gather, `export_chain`) get NEW arrays out of a program that
  donates nothing; readers on other threads (`disagg.proto_to_blocks`,
  the memory accountant) read shapes and dtypes recorded at
  construction (`row_shapes`, `leaf_dtypes()`, `bytes_total`), never a
  buffer;
* a donating call that raises AFTER it consumed its argument leaves no
  pool at all: `update` then drops the tree and raises `KVPoolLost`,
  and so does every later use — fail fast, never a "buffer has been
  deleted" out of a later tick. The scheduler treats it as any step
  that raises (`server.run`: crashed, every request aborted). A call
  that raises BEFORE consuming it (a trace or shape error) leaves the
  pool as it was;
* `run_inplace` counts `pool.launches` once a call and
  `pool.inplace_launches` when the tree that went in was in fact
  consumed (one leaf's `is_deleted()`, a host-side flag): their ratio
  is the share of pool updates that ran in place.

LEAVES BY KIND: the pool's tree mirrors the model's batch-1 decode
cache, and each leaf is of a DECLARED kind
(api/generation.cache_leaf_kinds; `PagedKVPool.kinds`), never told by
its rank:

* ROWS, `[1, hkv, cache_len, d]` in the template: a cached token a row,
  paged into a `[num_blocks, block_size, hkv, d]` arena by block table.
  Everything above (blocks, refcounts, the trie, the host tier, copy on
  write) is about these leaves alone, and `bytes_total`, `block_bytes`
  and admission count them alone: a sequence is charged blocks for its
  attention layers' rows and for nothing else;
* STATE, `[1, ...]`: what a state-space layer carries from token to
  token, the same size whatever the length. The pool keeps a
  `[num_slots, ...]` arena of it, slot i for lane i: seating writes the
  prefilled state of EVERY state leaf into the slot in ONE launch
  (`write_state`, donated like every other update), the decode step
  reads and writes lane i's state at slot i in place, and releasing a
  slot costs no device work, because the next seating overwrites the
  whole of it. A state cannot be shared by prefix, spilled or shipped
  as a chain (the state after a prompt's first blocks is not kept), so
  a pool with state leaves refuses prefix sharing, the host tier and
  chain export;
* SCALAR, the position counter: a zero-d placeholder that keeps the
  tree's structure.

BLOCK CLASSES BY LAYER WINDOW (`leaf_windows`; the engine hands them in
where prefix sharing, the host tier, the draft and chunked prefill are
all off): the ROWS leaves are grouped by their layer's attention window
into classes, each with its own BlockAllocator, its own arenas' block
count and its own table, a class's columns side by side in `tables`
(`table_of` names each layer's). A class that sees every key (window 0)
is what the whole pool is otherwise. A WINDOW class

* is CHARGED `min(blocks_for(tokens), blocks_for(window) + 2)` blocks
  for a sequence (`plan` / `can_seat` / `alloc` / `extend`): a row at
  position p sees keys (p - window, p], which lie in at most
  blocks_for(window) + 1 blocks, and one more is the slack between
  drawing the block being written and releasing the one behind;
* RELEASES behind the window: when `ensure_blocks` draws a lane a new
  block, every block that lies wholly behind the window of the position
  being written goes back to the class's free list and its table entry
  to -1 (`kv.window_blocks_released`); the paged attention reads no
  table entry below its live range (ops.attention.paged_live_blocks,
  the same arithmetic). Host work only and safe with a step in flight,
  as `release` is: what next writes the block is a program that takes
  the pool that step hands back;
* is seated with the blocks in reach of the PROMPT'S END only:
  `write_prompt` writes a block below that range to the whole-length
  classes alone (`prompt_write.blocks_skipped`);
* has arenas of `num_slots x (blocks_for(window) + 2)` blocks, capped
  by `num_blocks`, which goes on sizing the whole-length class.

What needs every block of every layer keeps the one-table pool: a
shared chain, a spilled one, a decode tile over earlier positions (the
draft's verify, a chunked prefill); the engine warns once, by name of
the option and with the charge (`engine._leaf_windows`). A pool of more
than one class refuses a chain export and a copy on write by name.

Block ids enter the compiled decode step as DEVICE arrays (the tables),
so slot churn and sequence growth never recompile anything. The tables
the step reads STAY on the device, carried from tick to tick in the
engine's lane state; `tables` here is the book they are rebuilt from,
and `tables_dirty` says that the host wrote a row of it (growth,
seating, copy-on-write, release) since the engine last sent it. The
attention that consumes this layout is
`ops.attention.paged_decode_attention`.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.api.generation import (
    ROWS,
    STATE,
    cache_leaf_kinds,
)
from elasticdl_tpu.observability import tracing


class OutOfBlocks(Exception):
    """The pool cannot cover a request's block budget right now. The
    scheduler treats this as backpressure: the request stays queued
    until completions free blocks (admission rejects outright only
    requests that could NEVER fit)."""


class KVPoolLost(RuntimeError):
    """A pool-updating program raised after it had consumed (donation)
    the pool it was handed: the arenas are gone and every sequence's
    rows with them. Raised by that call and by every later use of the
    pool; the scheduler dies of it like of any step that raises and
    aborts what is seated and queued (server.run)."""


def run_inplace(program, pools, *args, **kwargs):
    """Call ONE pool-updating program: `program(pools, *args)` donates
    the pool tree and returns the updated tree, alone or first of a
    `(pools, extra)` pair. `pools` is deleted when this returns — the
    caller rebinds its one reference from the result in the same
    statement. Counts the launch, and counts it as in place when the
    tree was consumed. A program that raises with the tree consumed
    raises KVPoolLost (from its error); one that raises with the tree
    intact re-raises as it is."""
    # jit hands a leaf the program returns untouched (the zero-d
    # position placeholder; the state arenas in a program that writes
    # rows, and the other way round) straight back, never donated: the
    # tree was consumed if ANY of its leaves was
    leaves = jax.tree.leaves(pools)

    def consumed():  # flags on the host: no sync
        return any(leaf.is_deleted() for leaf in leaves)

    try:
        out = program(pools, *args, **kwargs)
    except Exception as e:
        if consumed():
            raise KVPoolLost(
                "a pool-updating program raised after consuming the "
                "KV pool: %r" % (e,)
            ) from e
        raise
    tracing.count("pool.launches")
    if consumed():
        tracing.count("pool.inplace_launches")
    return out


_HLO_DTYPE = {"bfloat16": "bf16", "float16": "f16", "float32": "f32",
              "int8": "s8", "int32": "s32"}


def _leaf_bytes(leaf):
    """A leaf's bytes from its shape and dtype (an array or a shape):
    sizes never come from a buffer."""
    return int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize


def pool_aliasing(compiled, *pool_trees):
    """What a compiled pool-updating program does with the pool trees
    it takes (arrays or shapes): `pool_bytes` they hold, `alias_bytes`
    of its arguments that its results reuse
    (`memory_analysis().alias_size_in_bytes`), and `pool_shaped_copies`,
    the `copy` instructions of its optimized HLO whose result has an
    arena's shape. In place means alias_bytes >= pool_bytes (the chip
    gives the zero-d position placeholder a 512-byte tile) and no such
    copy. For scripts/check_pool_donation.py and the tests: compiled,
    nothing runs."""
    import re

    leaves = [leaf for tree in pool_trees
              for leaf in jax.tree.leaves(tree)]
    arenas = {
        "%s[%s]" % (_HLO_DTYPE[np.dtype(leaf.dtype).name],
                    ",".join(str(n) for n in leaf.shape))
        for leaf in leaves if len(leaf.shape)
    }
    copies = 0
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?\S+ = (.*?) copy(-start)?\(", line)
        if not m or not any(a in m.group(1) for a in arenas):
            continue
        if m.group(2):
            # an asynchronous copy names (destination, source): one
            # that crosses memory spaces (`S(n)` in one layout only) is
            # the compiler moving a small arena through on-chip memory
            # around the loop that reads it, not a second arena
            spaces = ["S(" in layout for layout in
                      re.findall(r"\]\{([^}]*)\}", m.group(1))[:2]]
            if len(spaces) == 2 and spaces[0] != spaces[1]:
                continue
        copies += 1
    return {
        "pool_bytes": sum(_leaf_bytes(leaf) for leaf in leaves),
        "alias_bytes": int(
            compiled.memory_analysis().alias_size_in_bytes),
        "pool_shaped_copies": copies,
    }


def blocks_for(tokens, block_size):
    """Blocks covering `tokens` cache rows (0 tokens -> 0 blocks)."""
    return -(-int(tokens) // int(block_size))


class BlockAllocator(object):
    """Host-side block accounting: free list, refcounts, per-slot block
    tables, the reservation ledger, and (share_prefix=True) the
    content-addressed prefix index with its reclaimable LRU.

    `alloc(slot, tokens, commit_tokens)` materializes the blocks for
    `tokens` rows and RESERVES (without materializing) enough blocks
    for `commit_tokens` total; `extend` then draws the growth blocks
    from that reservation, so a request admitted under its full budget
    can never strand mid-decode waiting for a block another request
    holds. With `prompt=` token ids, the prompt's full blocks are first
    matched against the prefix index and seated by incref — only the
    unmatched remainder draws fresh blocks. `available()` is what
    admission may promise to NEW work. Every operation is O(blocks
    touched); steady-state slot churn is O(1) per block."""

    def __init__(self, num_blocks, block_size, share_prefix=False,
                 host_blocks=0, window=0):
        if num_blocks < 1:
            raise ValueError(
                "num_blocks must be >= 1, got %d" % num_blocks)
        if block_size < 1:
            raise ValueError(
                "block_size must be >= 1, got %d" % block_size)
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.share_prefix = bool(share_prefix)
        # the attention window of the layers whose rows these blocks
        # hold (module docstring, BLOCK CLASSES); 0 = every earlier
        # key, and then nothing below differs from a pool without
        # classes: a sequence is charged its whole length and a table
        # has no hole
        self.window = int(window)
        if self.window and (share_prefix or host_blocks):
            raise ValueError(
                "a window class of blocks releases behind its window: "
                "it can hold no shared or spilled chain")
        self.window_blocks = (
            blocks_for(self.window, self.block_size) + 2
            if self.window else 0)
        # host-spill tier capacity, in blocks (0 = eviction forgets)
        self.host_blocks = int(host_blocks)
        # LIFO: the most recently freed block is reused first (warm
        # reuse; also what the reuse-order tests lock)
        self._free = list(range(self.num_blocks - 1, -1, -1))
        self._tables = {}     # slot -> [block ids], -1 a released one
        # slot -> the table's leading entries released behind the
        # window (a window class; the holes are always a prefix)
        self._holes = {}
        self.released = 0     # monotone: blocks released behind it
        self._committed = {}  # slot -> total blocks promised
        self._cow_credit = {}  # slot -> reserved CoW copies (0 or 1)
        self._reserved = 0    # promised-but-unmaterialized, all slots
        self._refcount = {}   # bid -> live references (allocated only)
        # prefix index: (parent id, block token tuple) -> id; -1 is
        # the root parent. Collision-free: the key IS the content
        # path. Ids >= 0 are device block ids (RESIDENT); ids <= -2
        # are virtual ids of SPILLED entries whose rows live host-side
        # — vids are minted monotonically and never reused, so a
        # recycled device bid can never collide with a spilled key.
        self._index = {}
        self._index_key = {}  # id -> its index key (reverse map)
        self._children = {}   # id -> set of indexed child ids
        # resident indexed children per parent id: the leaf-first
        # device-eviction predicate, maintained incrementally so
        # eviction never scans (a block is device-evictable iff it is
        # cached AND has no resident indexed children)
        self._rkids = {}
        # refcount-0 blocks still indexed, oldest-first (LRU eviction)
        self._cached = collections.OrderedDict()
        # the O(1) eviction frontier: the subset of _cached with no
        # resident indexed children, in the order each block became
        # evictable (a parent promoted by its last child's spill
        # re-enters at the tail — whole cold chains drain bottom-up
        # before a just-promoted parent jumps the line)
        self._evictable = collections.OrderedDict()
        # spilled entries: vid -> None, oldest spill first (host LRU)
        self._spilled = collections.OrderedDict()
        # droppable spilled entries (no indexed children), oldest first
        self._spill_leaves = collections.OrderedDict()
        self._next_vid = -2
        # data-path hooks the PagedKVPool wires: spill copies a dying
        # device block's rows out to the host store, drop discards a
        # host entry. Accounting here, bytes there.
        self._spill_sink = None   # fn(bid, vid)
        self._drop_sink = None    # fn(vid)
        self._revived = []        # [(vid, new bid)] drained by seat
        self.cow_copies = 0        # monotone: CoW faults served
        self.prefix_hits = 0       # monotone: seats that matched
        self.prefix_hit_tokens = 0  # monotone: tokens seated by incref
        self.spills = 0            # monotone: blocks demoted to host
        self.host_drops = 0        # monotone: spilled entries dropped
        self.blocks_revived = 0    # monotone: spilled blocks uploaded

    # ------------------------------------------------------------ queries

    def num_free(self):
        return len(self._free)

    def num_cached(self):
        """Reclaimable blocks: refcount 0 but still in the prefix
        index — revivable by a match, evictable under pressure."""
        return len(self._cached)

    def num_spilled(self):
        """Spilled entries: chains demoted to the host tier, still
        resolvable by the prefix index, revivable by upload."""
        return len(self._spilled)

    def blocks_in_use(self):
        """Blocks pinned by LIVE references (refcount > 0)."""
        return self.num_blocks - len(self._free) - len(self._cached)

    def shared_blocks(self):
        """Blocks currently referenced by more than one table."""
        return sum(1 for c in self._refcount.values() if c > 1)

    def available(self):
        """Blocks admission may promise to NEW work: free plus
        reclaimable, minus the reservations already promised to
        seated slots."""
        return len(self._free) + len(self._cached) - self._reserved

    def can_fit(self, tokens):
        return blocks_for(tokens, self.block_size) <= self.available()

    def table(self, slot):
        return list(self._tables.get(slot, ()))

    def holes(self, slot):
        """The leading table entries of `slot` released behind the
        window (0 without one)."""
        return self._holes.get(slot, 0)

    def charge(self, tokens):
        """Blocks a sequence of `tokens` rows is charged: all of them,
        or at most the window's reach and its slack."""
        n = blocks_for(tokens, self.block_size)
        return min(n, self.window_blocks) if self.window else n

    def live_from(self, pos):
        """The first table slot a row at position `pos` still sees
        (ops.attention.paged_live_blocks' j_lo): 0 without a window."""
        if not self.window:
            return 0
        return max(0, int(pos) - self.window + 1) // self.block_size

    # ----------------------------------------------------- prefix index

    def _full_block_tuples(self, prompt):
        bs = self.block_size
        n = len(prompt) // bs
        return [tuple(int(t) for t in prompt[j * bs:(j + 1) * bs])
                for j in range(n)]

    def match_prefix(self, prompt):
        """Longest resident chain of full blocks covering a prefix of
        `prompt`: the block ids, root-first. Read-only (no refcount
        change) — `alloc(prompt=...)` seats on the result."""
        if not self.share_prefix:
            return []
        chain = []
        parent = -1
        for toks in self._full_block_tuples(prompt):
            bid = self._index.get((parent, toks))
            if bid is None:
                break
            chain.append(bid)
            parent = bid
        return chain

    def plan(self, prompt, tokens, commit_tokens=None):
        """(chain, needed) for seating `prompt` with `tokens` rows now
        and `commit_tokens` promised: the matched shared chain and how
        many blocks the seat would draw from `available()` (fresh
        blocks, the CoW credit for a full-prompt match, the
        RECLAIMABLE chain blocks the seat would revive — reviving pops
        a block out of the cache `available()` counts, so it costs
        capacity exactly like a fresh draw — and one fresh block per
        SPILLED chain entry, whose revival-by-upload materializes a
        new device block). The admission-time answer `can_seat` and
        the seat itself (`alloc`) both run through this, so they
        cannot disagree."""
        chain, needed, _cow = self._plan(prompt, tokens, commit_tokens)
        return chain, needed

    def _plan(self, prompt, tokens, commit_tokens=None):
        # what is materialized now: the blocks the first decode step
        # (position `tokens`) has in reach; all of them without a window
        now = (blocks_for(tokens, self.block_size)
               - self.live_from(tokens))
        commit = max(now, self.charge(commit_tokens or tokens))
        chain = self.match_prefix(prompt) if prompt is not None else []
        chain = chain[:now]
        # full-prompt match: the engine must re-run the last prompt
        # token for its logits, which re-writes that token's row into
        # the shared tail block -> one planned CoW copy, reserved here.
        # EXCEPT when the tail is reclaimable (refcount 0) or SPILLED:
        # the seat revives it as sole owner and the re-write lands in
        # place, so no copy can fault — its cost is the revival/upload
        # charge below, and charging both would refuse a full-budget
        # reseat forever on an idle pool
        cow = 1 if (chain and len(chain) * self.block_size
                    >= int(tokens)
                    and chain[-1] >= 0
                    and chain[-1] not in self._cached) else 0
        # chain blocks at refcount 0 are counted by available(); the
        # seat revives them (incref pops the cache), so they must be
        # charged or reservations can exceed free + reclaimable and
        # a reservation-backed extend could strand mid-decode
        revived = sum(1 for b in chain if b in self._cached)
        # spilled entries (vids < 0) hold no device block: their
        # revival draws a fresh one, charged exactly like an unmatched
        # block — the chain only saves their PREFILL, not their bytes
        spilled = sum(1 for b in chain if b < 0)
        resident = len(chain) - spilled
        return chain, commit - resident + cow + revived, cow

    def can_seat(self, prompt, tokens, commit_tokens=None):
        _chain, needed = self.plan(prompt, tokens, commit_tokens)
        return needed <= self.available()

    def register_prefix(self, slot, prompt):
        """Index `slot`'s FULL prompt blocks so later prompts can seat
        on them. Walks the index: levels already present (this seat's
        own shared chain, or a concurrent duplicate) keep the existing
        block — chains may interleave blocks owned by different slots,
        which is sound because the key path pins the exact content."""
        if not self.share_prefix:
            return
        table = self._tables.get(slot)
        if table is None:
            return
        parent = -1
        for j, toks in enumerate(self._full_block_tuples(prompt)):
            if j >= len(table):
                break
            key = (parent, toks)
            bid = self._index.get(key)
            if bid is None:
                bid = table[j]
                if bid in self._index_key:
                    # already indexed under another path (shouldn't
                    # happen for fresh private blocks) — don't re-key
                    break
                self._index[key] = bid
                self._index_key[bid] = key
                self._children.setdefault(parent, set()).add(bid)
                if parent >= 0:
                    # the parent gained a resident child: it is no
                    # longer a device-eviction leaf
                    self._rkids[parent] = self._rkids.get(parent, 0) + 1
                    self._evictable.pop(parent, None)
            parent = bid

    def flush_index(self):
        """Drop the whole prefix index, BOTH tiers (hot reload: cached
        rows were computed under superseded params — new requests must
        never seat on them, whether the rows are device-resident or
        spilled host-side). Reclaimable blocks return to the free
        list; spilled entries drop their host buffers; live blocks
        just lose their index entry and free normally at refcount 0."""
        for bid in list(self._cached):
            self._free.append(bid)
            self._refcount.pop(bid, None)
        self._cached.clear()
        self._evictable.clear()
        for vid in list(self._spilled):
            if self._drop_sink is not None:
                self._drop_sink(vid)
            self.host_drops += 1
        self._spilled.clear()
        self._spill_leaves.clear()
        self._index.clear()
        self._index_key.clear()
        self._children.clear()
        self._rkids.clear()

    # -------------------------------------------------------- refcounts

    def incref(self, bid):
        """Add a live reference to `bid`, reviving it from the
        reclaimable cache when its refcount was 0. Every incref must
        be settled by a decref/free (edl-lint EDL501 tracks the
        pair)."""
        self._refcount[bid] = self._refcount.get(bid, 0) + 1
        self._cached.pop(bid, None)
        self._evictable.pop(bid, None)

    def decref(self, bid):
        """Drop a live reference; at refcount 0 the block becomes
        reclaimable (still indexed) or free (not indexed). A block is
        never on the free list while any table references it."""
        rc = self._refcount.get(bid, 0) - 1
        if rc > 0:
            self._refcount[bid] = rc
            return
        self._refcount.pop(bid, None)
        if bid in self._index_key:
            self._cached[bid] = None  # newest at the LRU tail
            if not self._rkids.get(bid):
                self._evictable[bid] = None  # leaf: evictable now
        else:
            self._free.append(bid)

    def _dec_resident_kid(self, parent):
        """A resident indexed child of `parent` left the device tier
        (evicted or spilled); at zero resident children a CACHED
        parent becomes device-evictable — leaf-first, incrementally,
        no scan."""
        if parent < 0:
            return
        n = self._rkids.get(parent, 0) - 1
        if n > 0:
            self._rkids[parent] = n
            return
        self._rkids.pop(parent, None)
        if parent in self._cached:
            self._evictable[parent] = None

    def _unindex(self, node):
        """Remove `node` (bid or vid) from the prefix index entirely.
        Only ever called on index leaves (no indexed children), so no
        child re-keying is needed."""
        key = self._index_key.pop(node)
        del self._index[key]
        parent = key[0]
        kids = self._children.get(parent)
        if kids is not None:
            kids.discard(node)
            if not kids:
                del self._children[parent]
                if parent in self._spilled:
                    # the parent just became a host-droppable leaf
                    self._spill_leaves[parent] = None
        self._children.pop(node, None)
        self._rkids.pop(node, None)
        if node >= 0:
            self._dec_resident_kid(parent)

    def _rekey_children(self, old, new):
        """Re-key `old`'s indexed children under id `new` (spill:
        bid -> vid, revive: vid -> bid). The key IS the content path,
        so only the parent-id half moves; the token tuples are
        untouched."""
        sub = self._children.pop(old, None)
        if not sub:
            return False
        self._children[new] = sub
        for child in sub:
            ckey = self._index_key.pop(child)
            del self._index[ckey]
            nkey = (new, ckey[1])
            self._index[nkey] = child
            self._index_key[child] = nkey
        return True

    def _drop_spilled(self):
        """Drop the oldest CHILDLESS spilled entry (leaf-first in the
        host tier too: dropping an interior entry would orphan its
        children's keys). Spilled entries always have a childless
        descendant — device eviction is leaf-first, so a spilled
        node's children are all spilled — hence progress."""
        try:
            vid = next(iter(self._spill_leaves))
        except StopIteration:
            raise OutOfBlocks(
                "no droppable spilled entry (host tier invariant "
                "broken)"
            ) from None
        del self._spill_leaves[vid]
        del self._spilled[vid]
        self._unindex(vid)
        if self._drop_sink is not None:
            self._drop_sink(vid)
        self.host_drops += 1

    def _spill(self, bid):
        """Demote evicted block `bid` to the host tier under a fresh
        virtual id: rows copy out through the spill sink BEFORE the
        device block id is recycled, the trie entry re-keys onto the
        vid (children — all spilled already — re-key under it), and
        the host LRU drops its oldest leaves to stay inside the
        budget."""
        while len(self._spilled) >= self.host_blocks:
            self._drop_spilled()
        vid = self._next_vid
        self._next_vid -= 1
        if self._spill_sink is not None:
            self._spill_sink(bid, vid)
        key = self._index_key.pop(bid)
        self._index[key] = vid
        self._index_key[vid] = key
        parent = key[0]
        kids = self._children.get(parent)
        if kids is not None:
            kids.discard(bid)
            kids.add(vid)
        if not self._rekey_children(bid, vid):
            self._spill_leaves[vid] = None
        self._rkids.pop(bid, None)
        self._spilled[vid] = None
        self._dec_resident_kid(parent)
        self.spills += 1

    def _revive(self, vid, bid):
        """Promote spilled entry `vid` back onto device block `bid`
        (the caller uploads the rows): the trie entry re-keys onto the
        bid, spilled children re-key under it, and the move is logged
        for the pool's batched upload."""
        del self._spilled[vid]
        self._spill_leaves.pop(vid, None)
        key = self._index_key.pop(vid)
        self._index[key] = bid
        self._index_key[bid] = key
        parent = key[0]
        kids = self._children.get(parent)
        if kids is not None:
            kids.discard(vid)
            kids.add(bid)
        self._rekey_children(vid, bid)
        if parent >= 0:
            self._rkids[parent] = self._rkids.get(parent, 0) + 1
            self._evictable.pop(parent, None)
        self._revived.append((vid, bid))
        self.blocks_revived += 1

    def take_revived(self):
        """Drain the (vid, bid) moves the last alloc revived — the
        pool uploads their host rows into the fresh device blocks in
        one batched scatter."""
        out = self._revived
        self._revived = []
        return out

    def _evict_cached(self):
        """Reclaim the oldest device-evictable block — O(1): the
        `_evictable` frontier is maintained on every incref / decref /
        index / spill transition, so eviction never scans the LRU (a
        live block's ancestors are live, so every reclaimable subtree
        has a reclaimable leaf — the frontier is empty iff the cache
        is). With a host tier the block SPILLS (the chain survives,
        demoted); without one it is forgotten outright."""
        try:
            bid = next(iter(self._evictable))
        except StopIteration:
            raise OutOfBlocks(
                "no evictable cached block (allocator invariant "
                "broken)"
            ) from None
        del self._evictable[bid]
        del self._cached[bid]
        if self.host_blocks > 0:
            self._spill(bid)
        else:
            self._unindex(bid)
        return bid

    def _pop_block(self):
        if self._free:
            return self._free.pop()
        return self._evict_cached()

    # ------------------------------------------------------------- churn

    def alloc(self, slot, tokens, commit_tokens=None, prompt=None):
        """Materialize blocks for `tokens` rows under `slot` and
        reserve up to `commit_tokens` total; raises OutOfBlocks when
        the full commitment is not coverable (nothing is taken then).
        With `prompt` (token ids) and share_prefix, the prompt's full
        blocks seat on the prefix index by incref where resident.
        Returns the number of SHARED tokens (0 without a match)."""
        if slot in self._tables:
            raise ValueError("slot %r already holds blocks" % (slot,))
        holes = self.live_from(tokens)
        now = blocks_for(tokens, self.block_size) - holes
        commit = max(now, self.charge(commit_tokens or tokens))
        chain, needed, cow = self._plan(prompt, tokens, commit_tokens)
        if needed > self.available():
            raise OutOfBlocks(
                "need %d new blocks (%d now, %d shared), %d available"
                % (needed, now, len(chain), self.available())
            )
        # seat the chain: resident entries by incref, spilled entries
        # by revival (pop a fresh block, re-key, log the upload). A
        # pop's own spill cascade can drop a not-yet-revived chain
        # entry under host-budget pressure — the chain truncates there
        # and the remainder draws fresh (the plan charged a fresh
        # block for every spilled entry either way, so accounting is
        # unchanged; only the shared-token count shrinks).
        table_ids = [-1] * holes  # behind the window of the prompt's end
        shared_blocks = 0
        for node in chain:
            if node >= 0:
                self.incref(node)
                table_ids.append(node)
                shared_blocks += 1
                continue
            if node not in self._spilled:
                break  # dropped since plan time: rest of chain is gone
            bid = self._pop_block()
            if node in self._spilled:
                self._revive(node, bid)
                self.incref(bid)
                table_ids.append(bid)
                shared_blocks += 1
            else:
                # the pop's cascade dropped THIS entry: the drawn
                # block becomes a plain fresh draw for its position
                self.incref(bid)
                table_ids.append(bid)
                break
        while len(table_ids) < holes + now:
            bid = self._pop_block()
            self.incref(bid)
            table_ids.append(bid)
        self._tables[slot] = table_ids
        if holes:
            self._holes[slot] = holes
        self._committed[slot] = commit
        self._cow_credit[slot] = cow
        self._reserved += (commit - now) + cow
        if shared_blocks:
            self.prefix_hits += 1
            self.prefix_hit_tokens += shared_blocks * self.block_size
        return shared_blocks * self.block_size

    def extend(self, slot, total_tokens):
        """Grow `slot`'s table to cover `total_tokens` rows; growth
        inside the slot's commitment draws reserved blocks (never
        fails), growth beyond it competes with admission and can raise
        OutOfBlocks. Returns the appended block ids."""
        table = self._tables.get(slot)
        if table is None:
            raise ValueError("slot %r holds no blocks" % (slot,))
        need = blocks_for(total_tokens, self.block_size) - len(table)
        if need > 0 and self.window:
            # a window class: the row being written is at
            # total_tokens - 1, and what lies wholly behind its window
            # goes back first, to the reservation it was drawn from
            for j in range(self.holes(slot),
                           min(self.live_from(total_tokens - 1),
                               len(table))):
                self.decref(table[j])
                table[j] = -1
                self._holes[slot] = j + 1
                self._reserved += 1
                self.released += 1
        added = []
        for _ in range(max(0, need)):
            if len(table) - self.holes(slot) < self._committed[slot]:
                self._reserved -= 1  # drawing our own reservation
            elif self.available() < 1:
                raise OutOfBlocks(
                    "slot %r grew past its commitment and no block is "
                    "available" % (slot,)
                )
            else:
                self._committed[slot] += 1
            bid = self._pop_block()
            self.incref(bid)
            table.append(bid)
            added.append(bid)
        return added

    def cow(self, slot, block_index):
        """Copy-on-write fault: `slot` is about to write into its
        table[block_index]. If that block is shared (refcount > 1), a
        fresh block replaces it in the table — drawing the slot's CoW
        credit reserved at seat time (falling back to free capacity
        for an UNPLANNED divergence) — and the shared original is
        decref'd, never freed out from under its other owners.
        Returns (old bid, new bid) when a copy happened, None when the
        block was private (write is safe in place)."""
        table = self._tables.get(slot)
        if table is None:
            raise ValueError("slot %r holds no blocks" % (slot,))
        old = table[block_index]
        if self._refcount.get(old, 0) <= 1:
            return None
        if self._cow_credit.get(slot, 0) > 0:
            self._cow_credit[slot] -= 1
            self._reserved -= 1  # the credit was reserved at seat
        elif self.available() < 1:
            raise OutOfBlocks(
                "CoW fault on slot %r with no block available (no "
                "credit reserved and the pool is dry)" % (slot,)
            )
        new = self._pop_block()
        self.incref(new)
        table[block_index] = new
        self.decref(old)
        self.cow_copies += 1
        return old, new

    def free(self, slot):
        """Release `slot`'s references and its remaining reservation;
        returns how many table entries were dropped. Shared blocks
        survive (decref only) — a block returns to the free list or
        the reclaimable cache strictly at refcount 0. Safe to call for
        a slot that holds nothing (0)."""
        table = self._tables.pop(slot, None)
        if table is None:
            return 0
        held = table[self._holes.pop(slot, 0):]
        self._reserved -= (
            self._committed.pop(slot) - len(held)
            + self._cow_credit.pop(slot, 0)
        )
        # decref'd in table order so a fully-private table lands on the
        # free list with the block allocated LAST on top of the stack
        # (LIFO through the whole alloc -> free -> alloc cycle)
        for bid in held:
            self.decref(bid)
        return len(table)


def build_pools(kv_shapes, cache_len, num_blocks, block_size, kinds=None,
                num_slots=0, leaf_blocks=None):
    """Device arenas from the model's batch-1 decode-cache template
    (api/generation._kv_shapes_for) and its leaves' declared `kinds` (a
    tree alongside; None = the `kv_row_leaf` convention): a ROWS leaf
    `[1, hkv, cache_len, d]` becomes `[num_blocks, block_size, hkv, d]`
    zeros, a STATE leaf `[1, ...]` becomes `[num_slots, ...]` zeros, and
    the position counter stays a zero-d placeholder, so the pool tree
    keeps the cache tree's structure: the model slices its own layer's
    arenas out of it by name. `leaf_blocks` (along the leaves) gives
    each ROWS leaf its own block count: its class's (module docstring,
    BLOCK CLASSES)."""
    if kinds is None:
        kinds = cache_leaf_kinds(None, kv_shapes, cache_len)
    flat, treedef = jax.tree.flatten(kv_shapes)
    if leaf_blocks is None:
        leaf_blocks = [num_blocks] * len(flat)

    def arena(leaf, kind, blocks):
        if kind == ROWS:
            _, hkv, _, d = leaf.shape
            return jnp.zeros((blocks, block_size, hkv, d), leaf.dtype)
        if kind == STATE:
            return jnp.zeros((num_slots,) + leaf.shape[1:], leaf.dtype)
        return jnp.zeros(leaf.shape, leaf.dtype)

    return jax.tree.unflatten(treedef, [
        arena(leaf, kind, blocks) for leaf, kind, blocks in zip(
            flat, jax.tree.leaves(kinds), leaf_blocks)])


def _map_kind(fn, which, kinds, pools, *trees):
    """`pools` with `fn(pool leaf, *the other trees' leaves)` in place
    of each leaf of kind `which`; `kinds` along jax.tree.leaves(pools)."""
    flat, treedef = jax.tree.flatten(pools)
    others = [treedef.flatten_up_to(tree) for tree in trees]
    if len(kinds) != len(flat):
        raise ValueError("%d kinds for a pool of %d leaves"
                         % (len(kinds), len(flat)))
    return jax.tree.unflatten(treedef, [
        fn(pool, *rest) if kind == which else pool
        for pool, kind, *rest in zip(flat, kinds, *others)])


def write_prompt_block(pools, kv, j, bid, block_size, kinds,
                       leaf_class=None, classes=()):
    """Insert block `j` of a freshly prefilled batch-1 cache tree into
    the arenas at block id `bid` — ONE `dynamic_update_slice` per ROWS
    leaf (`kinds`, static, along the pool's leaves) at a TRACED (j,
    bid), so one compiled write serves every (prompt bucket, block,
    slot) combination. Rows past the true prompt length inside the
    last block are prefill junk; the paged attention masks `k_pos <
    length` so they are never read before the decode scatter
    overwrites them. A leaf of another kind is handed back as it is,
    whatever its rank. A pool of several block classes (`leaf_class`,
    static, along the leaves) hands `bid` as a vector, an id a class,
    and names the `classes` this launch writes: a leaf of another
    class is handed back as it is too (a block behind a window class's
    reach has no block there)."""
    def upd(pool, leaf, at):
        rows = jax.lax.dynamic_slice_in_dim(
            leaf[0], j * block_size, block_size, axis=1
        )  # [hkv, block_size, d]
        rows = rows.transpose(1, 0, 2)  # [block_size, hkv, d]
        return jax.lax.dynamic_update_slice(
            pool, rows[None], (at, 0, 0, 0)
        )

    if leaf_class is None:
        return _map_kind(lambda pool, leaf: upd(pool, leaf, bid), ROWS,
                         kinds, pools, kv)
    flat, treedef = jax.tree.flatten(pools)
    return jax.tree.unflatten(treedef, [
        upd(pool, leaf, bid[c]) if kind == ROWS and c in classes else pool
        for pool, leaf, kind, c in zip(
            flat, treedef.flatten_up_to(kv), kinds, leaf_class)])


def write_state(pools, kv, slot, kinds):
    """Seat a freshly prefilled batch-1 cache tree's STATE leaves in
    slot `slot` of their arenas: every state leaf of every layer in
    this ONE program, a `dynamic_update_slice` each at a traced slot.
    The whole of the slot's state is overwritten, so nothing of the
    sequence that sat there before is left to reset."""
    def upd(pool, leaf):
        return jax.lax.dynamic_update_slice(
            pool, leaf.astype(pool.dtype), (slot,) + (0,) * (pool.ndim - 1))

    return _map_kind(upd, STATE, kinds, pools, kv)


def copy_block(pools, src, dst, kinds):
    """Device-side CoW: duplicate arena block `src` into `dst` on
    every ROWS leaf (one gather + dynamic_update_slice per leaf, traced
    indices — one compiled copy serves every fault)."""
    def upd(pool):
        return jax.lax.dynamic_update_slice(
            pool,
            jax.lax.dynamic_slice_in_dim(pool, src, 1, axis=0),
            (dst, 0, 0, 0),
        )

    return _map_kind(upd, ROWS, kinds, pools)


def scatter_rows(pools, rows, bids, offs, leaf_class=None):
    """Write decode rows into the arenas: `rows` is a tree whose
    structure is a SUBSET of `pools` (the model's "kv_out" sown
    collection) with leaves `[..., hkv, d]` — one row per leading
    index; `bids`/`offs` carry matching leading shape (`[S]` for the
    per-slot step, `[S, t]` for the speculative verify tile, `[t]` for
    a suffix prefill). Rows to drop (free lanes, rolled-back draft
    rows, pad rows) carry an out-of-bounds bid and are discarded by the
    scatter — they never touch a block a live sequence owns. Distinct
    live rows target distinct (block, offset) pairs, so the scatter
    indices never collide. A pool of several block classes hands
    `bids` as a list, an array a class, and `leaf_class` (along the
    pool's leaves) says which a leaf takes."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(pools)
    if leaf_class is None:
        bids, leaf_class = [bids], [0] * len(flat)
    rmap = {
        jax.tree_util.keystr(p): leaf
        for p, leaf in jax.tree_util.tree_flatten_with_path(rows)[0]
    }
    out = []
    with jax.named_scope("row_scatter"):  # op_name, for a device trace
        for (path, pool), c in zip(flat, leaf_class):
            row = rmap.get(jax.tree_util.keystr(path))
            if row is None:
                out.append(pool)
            else:
                out.append(pool.at[bids[c], offs].set(row, mode="drop"))
    return jax.tree_util.tree_unflatten(treedef, out)


def _settle_chain_refs(alloc, bids):
    """Drop an import walk's keep-alive references root-first so the
    chain parks refcount-0 cached. Takes ownership of the references
    (the EDL501 settle for import_chain's increfs): called from a
    finally, it must run even when the walk or the upload failed."""
    for bid in bids:
        alloc.decref(bid)


def _pool_tjit(pool, name, fn, **jit_kwargs):
    """jax.jit with recompile-sentry adoption for the pool's compiled
    helpers — lazy like the engine's _tjit, so executables built
    before the server attaches the sentry still count later
    compiles. The pool-updating ones pass `donate_argnums=(0,)`."""
    from elasticdl_tpu.observability.runtime_health import tracked_jit

    return tracked_jit(
        fn, name, lambda: getattr(pool, "sentry", None), **jit_kwargs
    )


class PagedKVPool(object):
    """The device arenas + host tables for one serving engine.

    Owns the BlockAllocator, the `[num_slots, seq_len/block_size]`
    int32 table mirror the compiled step consumes (-1 = unallocated),
    and the jitted block write/copy. `cache_len % block_size == 0` is
    required so prompt blocks slice cleanly out of the prefill cache.

    The mirror is not what the step reads: the engine keeps the
    tables on the device and sends the mirror when `tables_dirty`
    says a row was written, so a decode step that crosses no block
    boundary costs zero host->device table traffic.

    `pools` is the ONE reference to the arenas: every program that
    updates them runs through `update`, which donates the tree and
    rebinds it (module docstring, UPDATED IN PLACE). Never keep
    `pool.pools` in a variable across such a call."""

    def __init__(self, kv_shapes, cache_len, num_slots, num_blocks,
                 block_size, share_prefix=False, host_bytes=0,
                 kinds=None, leaf_windows=None):
        cache_len = int(cache_len)
        block_size = int(block_size)
        if cache_len % block_size:
            raise ValueError(
                "seq_len %d must be a multiple of kv_block_size %d"
                % (cache_len, block_size)
            )
        self.cache_len = cache_len
        self.block_size = block_size
        self.num_blocks = int(num_blocks)
        self.max_blocks_per_slot = cache_len // block_size
        # each leaf's declared kind (module docstring, LEAVES BY KIND),
        # as a tree alongside the template's and, for the programs that
        # take the pool, static, along jax.tree.leaves(self.pools)
        if kinds is None:
            kinds = cache_leaf_kinds(None, kv_shapes, cache_len)
        self.kinds = tuple(jax.tree.leaves(kinds))
        self.has_state = STATE in self.kinds
        # the block CLASSES (module docstring): the ROWS leaves grouped
        # by `leaf_windows` (along the leaves; None = one class that is
        # charged every sequence whole), the whole-length class first.
        # `leaf_class` is static, along the leaves (0 where a leaf is
        # no row leaf); `allocator` is the first class's, the one a
        # pool without classes has
        if leaf_windows is not None and (share_prefix
                                         or int(host_bytes) > 0):
            raise ValueError(
                "block classes by window cannot hold a shared or a "
                "spilled chain: leaf_windows with share_prefix / "
                "host_bytes")
        windows = [int(w) if kind == ROWS else 0 for w, kind in zip(
            leaf_windows or [0] * len(self.kinds), self.kinds)]
        self.class_windows = sorted(
            {w for w, kind in zip(windows, self.kinds) if kind == ROWS}
        ) or [0]
        self.leaf_class = tuple(
            self.class_windows.index(w) if kind == ROWS else 0
            for w, kind in zip(windows, self.kinds))
        # the layers of a class: its row leaves' top-level names
        paths = [path for path, _ in
                 jax.tree_util.tree_flatten_with_path(kv_shapes)[0]]
        self.class_layers = [
            max(1, len({path[0] for path, c, kind in zip(
                paths, self.leaf_class, self.kinds)
                if kind == ROWS and c == mine}))
            for mine in range(len(self.class_windows))]
        self.allocators = [
            BlockAllocator(
                min(self.num_blocks, int(num_slots) * (
                    blocks_for(w, block_size) + 2)) if w
                else self.num_blocks,
                block_size, share_prefix=share_prefix and not w,
                window=w)
            for w in self.class_windows]
        self.allocator = self.allocators[0]
        # whether a table may have holes and a write takes an id a
        # class: any class but the one whole-length one
        self.classed = len(self.allocators) > 1 or bool(
            self.allocator.window)
        m = self.max_blocks_per_slot
        # {top-level name of a layer's leaves: its class's columns of
        # `tables`}, for the model to slice (None: one class, the whole)
        self.table_of = None
        if len(self.allocators) > 1:
            self.table_of = {
                path[0].key: (c * m, (c + 1) * m)
                for path, c, kind in zip(paths, self.leaf_class,
                                         self.kinds) if kind == ROWS}
        if self.has_state and (share_prefix or int(host_bytes) > 0):
            raise ValueError(
                "this model keeps a per-sequence state (a state-space "
                "layer) beside its KV rows, and %s cannot carry one "
                "yet: the state after a prompt's first blocks is not "
                "kept. Start the server without it (%s)" % (
                    "prefix sharing (share_prefix)" if share_prefix
                    else "the host spill tier (host_bytes)",
                    "--kv_shared 0 / EDL_KV_SHARED=0" if share_prefix
                    else "--kv_host_bytes 0 / EDL_KV_HOST_BYTES unset"))
        self.pools = build_pools(
            kv_shapes, cache_len, num_blocks, block_size, kinds,
            int(num_slots),
            leaf_blocks=[self.allocators[c].num_blocks
                         for c in self.leaf_class])
        # a class's table beside the next: class c is columns
        # [c * m, (c + 1) * m)
        self.tables = np.full(
            (int(num_slots), len(self.allocators) * m), -1, np.int32
        )
        # [class, slot]: a lane's leading table entries released
        # behind the window (the engine's counters read them)
        self.holes = np.zeros((len(self.allocators), int(num_slots)),
                              np.int32)
        # a row of `tables` was written since the engine last sent
        # them to the device (it clears this when it does)
        self.tables_dirty = False
        # TRUE arena bytes: summed per leaf at its OWN dtype, so int8
        # arenas count their int8 rows AND f32 scale leaves exactly —
        # never a homogeneous row-dtype assumption. This is what
        # kv_bytes_in_use / bytes-per-generated-token report.
        row_leaves = self._of_kind(ROWS)
        self.bytes_total = sum(_leaf_bytes(leaf) for leaf in row_leaves)
        # one block of every layer of a class, and of all the classes
        # together (what a block of a pool without classes is)
        self.class_block_bytes = [
            sum(_leaf_bytes(leaf) for leaf, k, cls in zip(
                jax.tree.leaves(self.pools), self.kinds, self.leaf_class)
                if k == ROWS and cls == c) // alloc.num_blocks
            for c, alloc in enumerate(self.allocators)]
        self.block_bytes = sum(self.class_block_bytes)
        # the per-slot state arenas beside them: fixed, whatever is
        # seated, and no part of a block
        self.state_bytes = sum(_leaf_bytes(leaf)
                               for leaf in self._of_kind(STATE))
        # one block's row shape and the dtype of every row leaf, in
        # jax.tree.leaves order, recorded here so that no reader needs
        # a buffer for them (a handler thread may ask mid-update)
        self.row_shapes = [tuple(leaf.shape[1:]) for leaf in row_leaves]
        self._leaf_dtypes = [str(leaf.dtype) for leaf in row_leaves]
        # the arenas' storage format, advertised on stats/ServerStatus:
        # any int8 row leaf means the quantized format (its f32 scale
        # leaves ride along)
        self.kv_cache_dtype = (
            "int8" if any(leaf.dtype == jnp.int8 for leaf in row_leaves)
            else ""
        )
        self._write_fn = None
        self._state_fn = None
        self._copy_fn = None
        # ---- tiered host spill (serving the ROADMAP "Tiered KV
        # cache" item): the budget is BYTES, the allocator accounts in
        # BLOCKS — one spilled block costs exactly block_bytes (full
        # blocks only enter the index, and a spill copies every row
        # leaf, scale leaves included)
        self.host_bytes_budget = int(host_bytes)
        host_blocks = (self.host_bytes_budget // self.block_bytes
                       if self.block_bytes else 0)
        self.allocator.host_blocks = int(host_blocks)
        self.allocator._spill_sink = self._spill_block
        self.allocator._drop_sink = self._drop_host_block
        self._host_rows = {}   # vid -> [np rows per ROWS leaf, in order]
        self.host_blocks_peak = 0
        self.revive_uploads = 0  # monotone: batched revival scatters
        # disaggregated handoff economy (serving/disagg.py): chains
        # exported to / imported from sibling replicas, and the prompt
        # tokens imports seated without re-running prefill here
        self.chain_exports = 0
        self.chain_imports = 0
        self.chain_import_tokens = 0
        self._gather_fn = None
        self._upload_fns = {}  # padded batch size -> compiled scatter
        # recompile sentry (runtime health): the engine forwards its
        # sentry so the pool's own executables (spill gather, revival
        # upload buckets, prompt write, CoW copy) count into the same
        # edl_serving_recompiles_total{fn=} family. None = plain jit.
        self.sentry = None

    def _of_kind(self, kind, pools=None):
        """The pool's leaves of one kind, in jax.tree.leaves order."""
        leaves = jax.tree.leaves(self.pools if pools is None else pools)
        return [leaf for leaf, k in zip(leaves, self.kinds) if k == kind]

    def row_arenas(self):
        """The ROWS arenas, in jax.tree.leaves order. Scheduler thread,
        between updates (module docstring)."""
        return self._of_kind(ROWS, self._live_pools())

    # ----------------------------------------------------- in-place update

    def _live_pools(self):
        if self.pools is None:
            raise KVPoolLost(
                "the KV pool was lost to a pool-updating program that "
                "raised after consuming it; this engine serves no more"
            )
        return self.pools

    def update(self, program, *args, **kwargs):
        """Run one pool-updating program over the arenas, in place:
        `program(pools, *args)` donates the tree (run_inplace) and the
        result is bound to `self.pools` before this returns. Returns
        what the program returned beside the pool (None for a program
        that returns the pool alone). Scheduler thread only."""
        try:
            out = run_inplace(program, self._live_pools(), *args,
                              **kwargs)
        except KVPoolLost:
            self.pools = None  # nothing may point at deleted arenas
            raise
        if isinstance(out, tuple):  # a pool tree is a mapping
            self.pools, extra = out
            return extra
        self.pools = out
        return None

    # ----------------------------------------------------------- lifecycle

    def can_seat(self, prompt, prompt_tokens, commit_tokens):
        return all(alloc.can_seat(prompt, prompt_tokens, commit_tokens)
                   for alloc in self.allocators)

    def seat(self, slot, prompt, commit_tokens):
        """Reserve the request's full block budget and materialize the
        prompt's blocks — shared-prefix blocks by incref, spilled
        chain blocks by revival upload, the rest fresh; raises
        OutOfBlocks with nothing taken. Returns the shared token count
        (0 without a match; revived tokens count as shared — they are
        seated without re-running prefill either way)."""
        for alloc in self.allocators[1:]:
            # every class or none: asked before anything is taken
            _chain, needed = alloc.plan(prompt, len(prompt),
                                        commit_tokens)
            if needed > alloc.available():
                raise OutOfBlocks(
                    "the class of blocks of window %d needs %d new "
                    "blocks, %d available"
                    % (alloc.window, needed, alloc.available()))
        shared = self.allocator.alloc(
            slot, len(prompt), commit_tokens=commit_tokens,
            prompt=prompt,
        )
        for c in range(1, len(self.allocators)):  # asked above: it fits
            self.allocators[c].alloc(slot, len(prompt),
                                     commit_tokens=commit_tokens)
        self._apply_revivals()
        self._sync_row(slot)
        return shared

    # ------------------------------------------------- host spill tier

    def _gather_rows(self, bid):
        """One block's rows as host numpy arrays — every ROWS arena
        leaf (int8 rows and f32 scale leaves alike) through ONE
        compiled gather with a traced bid. The spill sink and the
        chain export both read through here, so an exported chain is
        byte-identical to what the host spill tier would hold for the
        same blocks. The gather donates nothing and returns NEW
        arrays, fetched before this returns — safe beside the donating
        programs because it runs on the scheduler thread, between
        them."""
        if self._gather_fn is None:
            def gather(pools, b):
                return [leaf[b] for leaf in self._of_kind(ROWS, pools)]

            self._gather_fn = _pool_tjit(
                self, "kv_spill_gather", gather
            )
        rows = self._gather_fn(self._live_pools(),
                               jnp.asarray(bid, jnp.int32))
        return [np.asarray(r) for r in rows]

    def _spill_block(self, bid, vid):
        """Allocator spill sink: copy device block `bid`'s rows into
        host numpy buffers under `vid`, BEFORE the bid is recycled."""
        self._host_rows[vid] = self._gather_rows(bid)
        self.host_blocks_peak = max(self.host_blocks_peak,
                                    len(self._host_rows))

    def _drop_host_block(self, vid):
        """Allocator drop sink: the host LRU (or a flush) discarded a
        spilled entry — its rows are gone for good."""
        self._host_rows.pop(vid, None)

    def _upload_rows(self, staged):
        """Upload staged `(bid, [np rows per leaf])` row sets into
        their device blocks: ONE launch, its rows padded to a
        power-of-two bucket (the program writes the real lanes only),
        so a handful of executables serve every upload size. Revival
        and chain import both land here — the import path is the
        revival upload pointed at a sibling replica's bytes instead of
        this host's spill store."""
        span = tracing.begin("revive_upload", blocks=len(staged))
        k = len(staged)
        k_pad = 1
        while k_pad < k:
            k_pad *= 2
        bids = np.zeros(k_pad, np.int32)  # lanes past k are not run
        per_leaf = None
        for i, (bid, rows) in enumerate(staged):
            bids[i] = bid
            if per_leaf is None:
                per_leaf = [
                    np.zeros((k_pad,) + r.shape, r.dtype) for r in rows
                ]
            for j, r in enumerate(rows):
                per_leaf[j][i] = r
        self.update(self._upload_program(k_pad),
                    [jnp.asarray(r) for r in per_leaf],
                    jnp.asarray(bids), jnp.asarray(k, jnp.int32))
        self.revive_uploads += 1
        tracing.end(span)

    # the pool's own three updating programs, compiled on first use;
    # each takes the pool first and donates it (module docstring)

    def _upload_program(self, k_pad):
        fn = self._upload_fns.get(k_pad)
        if fn is None:
            def upload(pools, rows_list, b, k):
                # one `dynamic_update_slice` a block and leaf, like
                # the prompt write, over the `k` real lanes only: a
                # scatter over the block axis makes the chip's
                # compiler re-lay the whole arena out, there and back
                flat, treedef = jax.tree_util.tree_flatten(pools)
                at = [i for i, kind in enumerate(self.kinds)
                      if kind == ROWS]

                def one(i, flat):
                    flat = list(flat)
                    for rows, j in zip(rows_list, at):
                        flat[j] = jax.lax.dynamic_update_slice(
                            flat[j],
                            jax.lax.dynamic_index_in_dim(rows, i),
                            (b[i], 0, 0, 0),
                        )
                    return flat

                flat = jax.lax.fori_loop(0, k, one, flat)
                return jax.tree_util.tree_unflatten(treedef, flat)

            fn = self._upload_fns[k_pad] = _pool_tjit(
                self, "kv_revive_upload[%d]" % k_pad, upload,
                donate_argnums=(0,),
            )
        return fn

    def _write_program(self):
        if self._write_fn is None:
            self._write_fn = _pool_tjit(
                self, "kv_prompt_write", write_prompt_block,
                static_argnames=("block_size", "kinds", "leaf_class",
                                 "classes"),
                donate_argnums=(0,),
            )
        return self._write_fn

    def _state_program(self):
        if self._state_fn is None:
            self._state_fn = _pool_tjit(
                self, "kv_state_write", write_state,
                static_argnames=("kinds",), donate_argnums=(0,),
            )
        return self._state_fn

    def _copy_program(self):
        if self._copy_fn is None:
            self._copy_fn = _pool_tjit(
                self, "kv_cow_copy", copy_block,
                static_argnames=("kinds",), donate_argnums=(0,),
            )
        return self._copy_fn

    def _apply_revivals(self):
        """Upload the rows of every chain entry the last seat revived
        into its freshly allocated device block. The host copies are
        consumed — revival is a MOVE, not a copy."""
        moves = self.allocator.take_revived()
        if not moves:
            return
        self._upload_rows(
            [(bid, self._host_rows.pop(vid)) for vid, bid in moves]
        )

    # ------------------------------------------- disaggregated handoff

    def leaf_dtypes(self):
        """Row-leaf dtype names in jax.tree.leaves order — the arena
        format fingerprint a chain transfer carries so an importer can
        refuse a mismatched payload."""
        return list(self._leaf_dtypes)

    def export_chain(self, prompt):
        """Export the longest indexed chain covering `prompt` as a
        dense byte copy: `[(block token tuple, [np rows per leaf])]`
        root-first, resident blocks through the same compiled gather
        the spill tier uses and spilled blocks straight from the host
        store (copied, not consumed). Runs on the scheduler thread, so
        nothing can evict a chain entry mid-gather. Empty list = no
        full prompt block is indexed (nothing to hand off)."""
        if self.has_state:
            raise ValueError(
                "chain export (the disagg handoff) ships a prompt's KV "
                "blocks, and this model keeps a per-sequence state "
                "beside them that no chain carries yet")
        self._refuse_on_classes("chain export (the disagg handoff)")
        alloc = self.allocator
        chain = alloc.match_prefix(prompt)
        tuples = alloc._full_block_tuples(prompt)[:len(chain)]
        blocks = []
        for node, toks in zip(chain, tuples):
            if node >= 0:
                rows = self._gather_rows(node)
            else:
                host = self._host_rows.get(node)
                if host is None:
                    break
                rows = [np.array(r) for r in host]
            blocks.append((toks, rows))
        if blocks:
            self.chain_exports += 1
        return blocks

    def import_chain(self, blocks, leaf_dtypes=None):
        """Import an exported chain into THIS pool: walk the
        `(parent, tokens)` keys root-first, dedup against entries the
        trie already resolves (resident or spilled), allocate a fresh
        block for each missing level and re-key it into the index as a
        refcount-0 reclaimable entry, then land every new block's rows
        in one batched upload. Returns `(blocks_added, tokens_added)`.
        A later prompt seats on the imported chain exactly like any
        prefix hit, so sharing, CoW and spec decode compose unchanged.
        Import stops early (partial chain, still a usable prefix) when
        the pool runs out of blocks."""
        alloc = self.allocator
        if not alloc.share_prefix:
            raise ValueError(
                "chain import requires a prefix-shared pool "
                "(kv_shared=True)"
            )
        if leaf_dtypes is not None:
            mine = self.leaf_dtypes()
            if list(leaf_dtypes) != mine:
                raise ValueError(
                    "chain leaf dtypes %r do not match this pool's %r"
                    % (list(leaf_dtypes), mine)
                )
        # validate the WHOLE payload before allocating anything: a
        # malformed level mid-chain must not leave earlier levels'
        # references un-settled
        blocks = [(tuple(int(t) for t in toks), rows)
                  for toks, rows in blocks]
        for toks, _ in blocks:
            if len(toks) != self.block_size:
                raise ValueError(
                    "chain block carries %d tokens, block_size is %d"
                    % (len(toks), self.block_size)
                )
        parent = -1
        staged = []   # (bid, rows) for the batched upload
        fresh = []    # bids held live until the walk finishes
        try:
            for toks, rows in blocks:
                key = (parent, toks)
                node = alloc._index.get(key)
                if node is not None:
                    # the trie already resolves this level (resident
                    # or spilled) — dedup: keep walking under the
                    # existing id
                    parent = node
                    continue
                if parent < -1:
                    # the chain continues under a SPILLED level this
                    # pool already held: importing a device child
                    # under a vid parent would invert the leaf-first
                    # spill invariant (resident child of a spilled
                    # parent) — stop; the spilled prefix still
                    # resolves and revives normally
                    break
                try:
                    bid = alloc._pop_block()
                except OutOfBlocks:
                    break
                # held live while the walk continues so a later pop's
                # eviction cascade cannot reclaim the chain under us
                alloc.incref(bid)
                alloc._index[key] = bid
                alloc._index_key[bid] = key
                alloc._children.setdefault(parent, set()).add(bid)
                if parent >= 0:
                    alloc._rkids[parent] = (
                        alloc._rkids.get(parent, 0) + 1
                    )
                    alloc._evictable.pop(parent, None)
                staged.append((bid, rows))
                fresh.append(bid)
                parent = bid
            if staged:
                self._upload_rows(staged)
        finally:
            # settle: imported blocks park refcount-0 in the
            # reclaimable cache (root-first, so each non-leaf has
            # resident children and only the chain tail joins the
            # eviction frontier) — in a finally so neither a failed
            # upload nor a mid-walk error can leave the chain pinned
            _settle_chain_refs(alloc, fresh)
        added = len(staged)
        if added:
            self.chain_imports += 1
            self.chain_import_tokens += added * self.block_size
        return added, added * self.block_size

    def _refuse_on_classes(self, what):
        if len(self.allocators) > 1:
            raise ValueError(
                "%s needs every block of every layer, and this pool "
                "keeps its layers' blocks in %d classes by attention "
                "window (%s), a window class only what its window "
                "reaches. Start the server with prefix sharing on "
                "(--kv_shared 1), which keeps one table for every layer"
                % (what, len(self.allocators), self.class_windows))

    def host_bytes_in_use(self):
        """True host-tier bytes: spilled blocks hold every row leaf of
        one block at its own dtype, i.e. exactly block_bytes each."""
        return len(self._host_rows) * self.block_bytes

    def register_prefix(self, slot, prompt):
        """Index the slot's full prompt blocks for future sharing
        (call after their rows are actually resident)."""
        self.allocator.register_prefix(slot, prompt)

    def write_prompt(self, kv, slot, prompt_tokens, start_block=0):
        """Scatter the prefilled cache's blocks [start_block, ...)
        into the slot's allocated blocks — block-granular, no
        whole-slot copy (shared blocks below start_block are already
        resident and must not be re-written) — and, where the model
        keeps a per-sequence state, seat that in the slot: one more
        launch for every state leaf together (`write_state`)."""
        write = self._write_program()
        blocks = range(start_block,
                       blocks_for(prompt_tokens, self.block_size))
        tables = [alloc.table(slot) for alloc in self.allocators]
        launches = skipped = 0
        with tracing.phase("prompt_write", blocks=len(blocks)):
            for j in blocks:
                # `kv` is NOT donated: every block's launch reads it
                if not self.classed:
                    self.update(
                        write, kv, jnp.asarray(j, jnp.int32),
                        jnp.asarray(tables[0][j], jnp.int32),
                        block_size=self.block_size, kinds=self.kinds,
                    )
                    launches += 1
                    continue
                # a block behind a window class's reach of the
                # prompt's end has no block there: the other classes'
                # leaves alone are written
                bids = [table[j] for table in tables]
                classes = tuple(c for c, bid in enumerate(bids)
                                if bid >= 0)
                skipped += sum(layers for layers, bid in zip(
                    self.class_layers, bids) if bid < 0)
                if classes:
                    self.update(
                        write, kv, jnp.asarray(j, jnp.int32),
                        jnp.asarray(np.maximum(bids, 0), jnp.int32),
                        block_size=self.block_size, kinds=self.kinds,
                        leaf_class=self.leaf_class, classes=classes,
                    )
                    launches += 1
        if self.has_state:
            with tracing.phase("state_write", slot=int(slot)):
                self.update(self._state_program(), kv,
                            jnp.asarray(slot, jnp.int32),
                            kinds=self.kinds)
            tracing.count("state_write.launches")
        # work done, counted where it happens: one launch per block,
        # and the prompt tokens those blocks now hold
        tracing.count("prompt_write.launches", launches)
        if skipped:  # a (block, layer) no window class keeps
            tracing.count("prompt_write.blocks_skipped", skipped)
        tracing.count("prompt_write.tokens",
                      prompt_tokens - start_block * self.block_size)
        tracing.count("prompts_prefilled")

    def ensure_blocks(self, slot, pos):
        """Make sure the block covering cache position `pos` exists
        (the decode step writes up to there this iteration); draws the
        slot's reservation, so it cannot fail for a seated request."""
        grew = False
        for layers, alloc in zip(self.class_layers, self.allocators):
            before = alloc.released
            grew = bool(alloc.extend(slot, pos + 1)) or grew
            if alloc.released > before:  # in blocks x layers, as held
                tracing.count("kv.window_blocks_released",
                              layers * (alloc.released - before))
        if grew:
            self._sync_row(slot)

    # back-compat spelling (single position)
    ensure_block = ensure_blocks

    def cow_for_write(self, slot, pos):
        """Copy-on-write guard before `slot` writes cache position
        `pos`: if the covering block is shared, copy it (device) and
        repoint the table. Returns the (old, new) ids or None."""
        self._refuse_on_classes("a copy on write")
        moved = self.allocator.cow(slot, pos // self.block_size)
        if moved is None:
            return None
        old, new = moved
        self.update(self._copy_program(), jnp.asarray(old, jnp.int32),
                    jnp.asarray(new, jnp.int32), kinds=self.kinds)
        self._sync_row(slot)
        return moved

    def release(self, slot):
        """Reclaim a finished/evicted slot's references (O(1) per
        block); private rows are dead the moment the table forgets
        them, shared rows live on under their other owners. A slot's
        per-sequence state needs no device work either: the next
        seating overwrites the whole of it (`write_state`), and until
        then the lane is free and nothing reads it. Host work only,
        and safe while a decode step that still writes these blocks is
        in flight (the engine releases a lane at the LAUNCH of its
        last step): whatever touches the blocks next, for whomever
        (a prompt's block writes, a tile, a copy, a spill's gather, an
        export), is a program that takes the pool that step hands
        back (`update`), so the device runs it after the step; a row
        the step writes into a block that is by then another
        sequence's lies past what that sequence has written and is
        overwritten before it is read. The table row is marked
        written, so the next launch sends the mirror and no later step
        carries the lane."""
        freed = [alloc.free(slot) for alloc in self.allocators][0]
        if freed:
            self.tables[slot, :] = -1
            self.holes[:, slot] = 0
            self.tables_dirty = True
        return freed

    def flush_prefix_cache(self):
        """Hot reload hook: stale-params rows must never seat a new
        request — BOTH tiers flush (BlockAllocator.flush_index drops
        every spilled entry through the drop sink, emptying the host
        store here)."""
        self.allocator.flush_index()

    def _sync_row(self, slot):
        m = self.max_blocks_per_slot
        row = np.full(self.tables.shape[1], -1, np.int32)
        for c, alloc in enumerate(self.allocators):
            table = alloc.table(slot)
            row[c * m: c * m + len(table)] = table
            self.holes[c, slot] = alloc.holes(slot)
        self.tables[slot] = row
        self.tables_dirty = True

    # ------------------------------------------------------------- stats

    def bytes_in_use(self):
        return sum(alloc.blocks_in_use() * each for alloc, each in zip(
            self.allocators, self.class_block_bytes))

    def stats(self):
        return {
            "kv_paged": True,
            "kv_shared": self.allocator.share_prefix,
            "kv_cache_dtype": self.kv_cache_dtype,
            "kv_block_size": self.block_size,
            "kv_blocks_total": sum(a.num_blocks for a in self.allocators),
            # capacity available to new work: free + reclaimable —
            # cached prefixes are not "in use", they are a warm cache
            "kv_blocks_free": sum(a.num_free() + a.num_cached()
                                  for a in self.allocators),
            # the block classes by attention window (one, of window 0,
            # where every layer keeps every block): [window, layers,
            # blocks, blocks free]
            "kv_classes": [
                [a.window, layers, a.num_blocks,
                 a.num_free() + a.num_cached()]
                for a, layers in zip(self.allocators,
                                     self.class_layers)],
            "kv_blocks_cached": self.allocator.num_cached(),
            "kv_blocks_shared": self.allocator.shared_blocks(),
            "kv_bytes_total": self.bytes_total,
            "kv_bytes_in_use": self.bytes_in_use(),
            # the per-slot state arenas beside the row arenas (0 for a
            # model without state layers): fixed, seated or not
            "kv_state_bytes": self.state_bytes,
            "prefix_hit_tokens": self.allocator.prefix_hit_tokens,
            "cow_copies": self.allocator.cow_copies,
            # tiered host spill: current host-tier occupancy (gauges)
            # and the monotone spill economy (counters). Tokens, not
            # blocks, for the revival headline — spilled blocks are
            # always full, so the product is exact.
            "kv_host_blocks": self.allocator.num_spilled(),
            "kv_host_bytes": self.host_bytes_in_use(),
            "kv_host_bytes_budget": self.host_bytes_budget,
            "revive_uploads": self.revive_uploads,
            "prefill_tokens_revived": (
                self.allocator.blocks_revived * self.block_size
            ),
            "host_drops": self.allocator.host_drops,
            # disaggregated handoff economy (serving/disagg.py)
            "chain_exports": self.chain_exports,
            "chain_imports": self.chain_imports,
            "chain_import_tokens": self.chain_import_tokens,
        }
