"""Continuous-batching decode engine over a block-paged KV pool.

The offline decoder (api/generation.py) compiles one program per
(batch, lengths, sampling) combination and runs each request cohort to
completion — fine for batch PREDICTION, fatal for serving, where
requests arrive continuously with mixed lengths and a static cohort
leaves the pool idle while the longest member finishes. This engine
instead runs ONE jit-compiled single-token decode step over a fixed
pool of `num_slots` batch slots, every step, forever:

* the KV rows of every layer live in shared block arenas
  (serving/kv_pool.py); a slot holds a block table and a position, and
  admission works against a token/block budget, so short requests pack
  densely instead of pinning `seq_len` rows each;
* the step `jax.vmap`s the model's decode over the slot axis, so every
  slot advances at its OWN position — the per-slot counter drives each
  layer's RoPE rotation and position-embedding lookup exactly as in
  offline decode, and the new token's rows scatter into the slot's own
  block;
* prompt insertion = one batched prefill (the offline `_run_prefill`,
  bucketed to 64 like offline decode) + block-granular writes of the
  prompt's rows at TRACED block ids — membership changes never
  recompile anything;
* finished/expired slots are marked free host-side and their blocks go
  back to the free list (free slots still ride through the vmapped
  step as masked work — the static-shape price of zero recompiles).

Token parity: a request's output depends only on (params, prompt, seed,
temperature) — never on what else shares the pool. Greedy and sampled
tokens equal the offline `autoregressive_generate(use_cache=True)` on a
batch of one with the same knobs (serving_next_token's contract), which
the serving tests lock against the offline path.

Single-threaded by design: only the scheduler thread may call
insert/step/set_params (jax computations stay serialized; the gRPC
threads touch only the admission queue and event plumbing).

The pool is UPDATED IN PLACE: the decode step, the speculative step,
the suffix / tile prefill and the pool's own block writes all donate
the pool tree they take (kv_pool.py's module docstring has the
contract). The engine holds the arenas only as `self.kv.pools`, the
draft's dense per-slot pool only as `self._d_pool`, and rebinds each
from the call's result in the same statement (`kv.update`,
`run_inplace`); a donating call that raises after consuming the pool
is KVPoolLost, which ends the scheduler like any step that raises.

The tick's LANE STATE lives on the device: each lane's block table,
position, last token, seed and temperature are one small array
(`lane_fields`) that the step takes and hands back advanced — a seated
lane one position on, its last token the token just sampled — and
that never comes to the host. The numpy mirror (`_positions`,
`_last_tokens`, `_seeds`, `_temps`, `kv.tables`) stays the book the
host works from; what the host changes in it (a lane seated or freed,
a table row written) marks the device's copy stale, and the next
launch sends the mirror in one transfer (`_tick_lanes`); a launch
whose lanes the host left alone sends nothing. After a launch that
raised the device's state is not trusted: the next one rebuilds it
from the mirror.

The decode loop keeps ONE STEP IN FLIGHT: `step` launches step n+1
before it fetches step n's tokens, so the device goes from one step to
the next while the host commits, streams, ensures, uploads and
dispatches. That rests on three things. (1) A request ends by length
alone, so the host knows without a token which lanes the next step
has, where each writes and which block it grows into: the mirror's
positions advance when a step is LAUNCHED, and the launch that
produces a lane's last token frees the lane and releases its blocks at
once (the completion stays with that step's commit). (2) The one
thing the host lacks before the fetch is the VALUE of the last tokens,
and only the device needs it: the mirror's token column holds a value
only where the host is the one who knows it (a lane seated since the
last launch: its prefill's first token) and `_KEEP` elsewhere, and a
launch that sends the mirror takes the carried lanes' token wherever
the sent one says `_KEEP` (`_merge_lanes`, run on the launches
that send and on no other). (3) Every program that touches the pool
takes the pool the one before it handed back (`kv.update`, donated), so
the device runs them in the order the host launched them: blocks
released while the step that last writes them is in flight, a prompt
written into them, a tile, a copy, a spill or an export all come after
that step. A launched step updated the pool and the state arenas in
place, so it is never dropped: `_flights` keeps what it needs to be
committed later (the lanes it ran, the weights' version it ran under,
which lanes it finishes), and a lane evicted before its step is
committed (a deadline) is skipped there. The speculative tick stays in
line: its positions advance by what the verify accepts, which only the
fetch tells.

The BLOCK TICK (a model with `block_causal` B > 1: generation by
diffusion over blocks) is the same step program at a tile of B
positions a lane: a lane carries its open block (B tokens, each
position's reveal step) and its pass index beside its position, which
is the block's start. A DENOISING pass (pass s < S, `denoise_steps`)
runs the model over the block against the cache of every earlier
block, with the mask token wherever nothing is revealed yet, caches
nothing, reads at each masked position the greedy token and its
softmax probability from the logits AT that position, and reveals the
B / S of highest probability; pass S, the COMMIT pass, runs the final
tokens, scatters the block's B rows and moves the lane on by B
positions, its next block all masks. S + 1 passes yield B tokens. The
schedule is static, so the host knows every lane's pass without a
fetch and one step stays in flight as ever: the book (`_positions`,
`_passes`) moves at the launch, a lane is freed at the launch of its
last commit pass, and `_collect` hands a request a block's tokens
(with the pass that revealed each, `request.reveal_steps`) at its
commit pass and nothing at the others. The prompt's whole blocks are
prefilled and written at seating, no token comes of it, and the
prompt's last `p mod B` tokens open the first block as given
positions. What cannot hold for such a model refuses to start, by
name (`_refuse_what_a_block_model_cannot_hold`).

The weights are served in the dtype the programs COMPUTE in, made
once a load. The state handed in stays what a checkpoint holds (fp32);
`_load_params` (construction and every hot reload) runs one jitted
program over it and keeps only the result, `_exec_variables`, the tree
every prefill / decode program takes. In it a leaf that those programs
consume only through a cast to one narrower dtype is that cast (bf16
compute over fp32 parameters: every matmul kernel, the MLP biases, the
head and the embedding table — half the bytes a tick streams, and no
cast of the whole table to gather a row a slot); a leaf that any
program reads as it is (LayerNorm scales and biases, an fp32 router)
is kept as handed in, and with fp32 compute nothing is a cast and
`_exec_variables` IS the tree handed in. Which is which comes from the
jaxprs of the engine's own programs, traced over shapes at
construction (serving/exec_weights.py): no option, leaf name or rank
decides. The engine holds no reference to the tree it was handed, so
once the caller lets go of its state the fp32 kernels leave the
device. The draft's tree goes through the same rule.

Weight-only int8 params (api/quantization): by default that same load
program dequantizes first — ONCE per set_params — and the float
weights (cast as above) serve every step: a single-token decode step
that re-dequantized the full weight set every step dominated the step
on the latency-bound path (the decode_kv_int8 bench regression).
EDL_SERVING_FUSED_DEQUANT=1 restores in-jit dequantize (int8 weights
stream HBM->VMEM per step — the right trade when weights dwarf VMEM
and HBM bandwidth, not latency, bounds the step); the tree served is
then the int8 tree as handed in.
"""

import collections
import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.api.generation import (
    STATE,
    _decode_cache,
    _kv_shapes_for,
    _maybe_dequantize,
    _prefill_bucket,
    _require_kv_convention,
    _run_prefill,
    cache_leaf_kinds,
    serving_next_token,
)
from elasticdl_tpu.api.quantization import (
    dequantize_params,
    is_quantized,
)
from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.observability import tracing
from elasticdl_tpu.observability.runtime_health import tracked_jit
from elasticdl_tpu.ops.attention import paged_live_blocks
from elasticdl_tpu.serving.exec_weights import (
    cast_leaves,
    remembered_casts,
    tree_bytes,
)


def kv_shared_default():
    """EDL_KV_SHARED resolves prefix sharing when the config leaves it
    unset. Default ON (sharing is strictly a capacity win under the
    same token-parity contract); EDL_KV_SHARED=0 forces the private
    paged pool — the A/B leg the bench and drills exercise."""
    return os.environ.get("EDL_KV_SHARED", "1") not in ("", "0")


def kv_host_bytes_default():
    """EDL_KV_HOST_BYTES resolves the paged pool's host spill-tier
    budget when the config leaves it unset (0 = eviction forgets, the
    pre-tier behavior) — the env toggle the drills/CI use."""
    try:
        return int(os.environ.get("EDL_KV_HOST_BYTES", "") or 0)
    except ValueError:
        return 0


def _fused_dequant():
    return os.environ.get(
        "EDL_SERVING_FUSED_DEQUANT", "") not in ("", "0")


def prefill_chunk_default():
    """EDL_PREFILL_CHUNK_TOKENS resolves the chunked-prefill tile
    width when the config leaves it unset (0 = off: a prompt prefills
    monolithically, monopolizing its scheduler tick)."""
    try:
        return int(os.environ.get("EDL_PREFILL_CHUNK_TOKENS", "") or 0)
    except ValueError:
        return 0


def prefill_budget_default():
    """EDL_PREFILL_BUDGET_MS resolves the scheduler's per-tick chunked
    prefill budget when the config leaves it unset: the wall-clock ms
    of prefill tiles a tick may run while decode slots are active
    (<= 0 = unbounded). At least one tile always runs per tick, so
    prefill makes progress no matter how small the budget."""
    try:
        return float(
            os.environ.get("EDL_PREFILL_BUDGET_MS", "") or 8.0
        )
    except ValueError:
        return 8.0


def role_default():
    """EDL_SERVING_ROLE resolves the replica's disaggregation role
    when the config leaves it unset: "prefill" | "decode" | "unified"
    (serving/disagg.py). Unified replicas serve both phases — the
    pre-disagg behavior."""
    role = os.environ.get("EDL_SERVING_ROLE", "") or "unified"
    if role not in ("prefill", "decode", "unified"):
        raise ValueError(
            "EDL_SERVING_ROLE must be prefill|decode|unified, got %r"
            % role
        )
    return role


def _tick_counts(sown):
    """{name: int32 scalar} of what a model's layers sowed into
    "counters" in a vmapped decode step: each leaf has the lanes as its
    first axis. A scalar a lane is a count of that lane's rows and is
    summed over the lanes; a vector a lane marks items touched (an
    expert hit) and an item counts once however many lanes touched it.
    Leaves of one name (one a layer) add up."""
    out = {}
    for path, value in jax.tree_util.tree_leaves_with_path(sown):
        name = [k.key for k in path if hasattr(k, "key")][-1]
        n = jnp.sum(value if value.ndim == 1
                    else jnp.max(value, axis=0)).astype(jnp.int32)
        out[name] = out.get(name, 0) + n
    return out


#: the columns of the device's lane state, an int32 [slots, 4 +
#: max_blocks] array with a row a lane; the temperature is kept as its
#: float32 bits. A block engine (tile B > 1) keeps 2 B + 1 more columns
#: behind the table: the open block's tokens, each position's reveal
#: step, and the lane's pass index (`block_fields`)
_LANE_POS, _LANE_TOKEN, _LANE_SEED, _LANE_TEMP, _LANE_TABLE = range(5)

#: a position's reveal step in a block lane: not revealed yet (the
#: model reads the mask token there), or a prompt token that opens the
#: first generated block; else the denoising pass (0 .. S - 1) that
#: revealed it. And a free lane's pass index
MASKED, GIVEN = -1, -2
_NO_PASS = -1

#: in the MIRROR's token column: the host has not seen this lane's last
#: token (its step is in flight, or was when the next was launched);
#: the device holds it. Never a token, which is an index >= 0
_KEEP = -1


Lanes = collections.namedtuple(
    "Lanes", "tables positions last_tokens seeds temps")


def _block_columns(tile):
    return 2 * tile + 1 if tile > 1 else 0


def lane_fields(lanes, tile=1):
    """The lane state array (numpy, or jax inside a program) as its
    named parts: block tables [slots, max_blocks], positions, last
    tokens, seeds (int32) and temperatures (float32). `tile` B > 1: a
    block engine's, whose last 2 B + 1 columns are `block_fields`."""
    temps = lanes[:, _LANE_TEMP]
    return Lanes(
        lanes[:, _LANE_TABLE:lanes.shape[1] - _block_columns(tile)],
        lanes[:, _LANE_POS],
        lanes[:, _LANE_TOKEN], lanes[:, _LANE_SEED],
        temps.view(np.float32) if isinstance(temps, np.ndarray)
        else jax.lax.bitcast_convert_type(temps, jnp.float32),
    )


def block_fields(lanes, tile):
    """A block engine's lane state beyond `lane_fields`: the open
    block's tokens [slots, B], each position's reveal step [slots, B]
    (MASKED, GIVEN, or the pass that revealed it) and the pass index
    [slots] (0 .. S - 1 a denoising pass, S the commit pass, _NO_PASS
    a free lane)."""
    at = lanes.shape[1] - _block_columns(tile)
    return (lanes[:, at:at + tile], lanes[:, at + tile:at + 2 * tile],
            lanes[:, at + 2 * tile])


def _merge_lanes(sent, carried, tile=1):
    """The mirror as sent, with the token the device carries wherever
    the host's says `_KEEP`: a lane whose last token the host has not
    seen (its step is in flight) keeps it. A block lane keeps its open
    block (tokens, reveal steps) the same way: what a pass revealed
    only the device knows. Its pass index is the host's to know (the
    schedule is static) and goes as sent: a lane freed since is free."""
    token = sent[:, _LANE_TOKEN]
    keep = token == _KEEP
    out = sent.at[:, _LANE_TOKEN].set(
        jnp.where(keep, carried[:, _LANE_TOKEN], token))
    if tile > 1:
        at = sent.shape[1] - _block_columns(tile)
        out = out.at[:, at:-1].set(
            jnp.where(keep[:, None], carried[:, at:-1], sent[:, at:-1]))
    return out


def _reveal_by_confidence(prob, masked, count):
    """[slots, B] bool: of each lane's masked positions the `count` of
    highest probability, ties to the lower position (the static
    low-confidence reveal of a block-diffusion model)."""
    tile = prob.shape[1]
    p = jnp.where(masked, prob, -1.0)
    at = jnp.arange(tile)
    # beats[l, i, j]: masked position j goes before position i
    beats = ((p[:, None, :] > p[:, :, None])
             | ((p[:, None, :] == p[:, :, None])
                & (at[None, None, :] < at[None, :, None])))
    rank = jnp.sum(beats & masked[:, None, :], axis=-1)
    return masked & (rank < count)


def _at_path(tree, path):
    """The leaf of a tree of nested dicts at a key path."""
    for key in path:
        tree = tree[key.key]
    return tree


def _set_path(tree, path, leaf):
    """Set the leaf at a key path of a tree of nested dicts, in place,
    making the dicts on the way."""
    for key in path[:-1]:
        tree = tree.setdefault(key.key, {})
    tree[path[-1].key] = leaf


def _trace_id(request):
    """The request's trace id for a phase that serves it ("" for the
    bare requests of tests and benches)."""
    return getattr(request, "trace_id", "") or ""


class _Slot(object):
    __slots__ = ("request", "max_total", "evicted")

    def __init__(self, request, max_total):
        self.request = request
        self.max_total = max_total
        # evicted before its end (a deadline): what its steps in
        # flight produce is no longer its request's
        self.evicted = False


#: a decode step launched and not yet committed: the array its tokens
#: (and the tick's counters behind them) come home in, the lanes it ran
#: as [(slot, _Slot, whether this was the lane's last step)], the
#: weights' version it ran under, and which of those lanes' passes
#: yield tokens: all (None), or, of a block step, the slots at their
#: commit pass
_Flight = collections.namedtuple("_Flight", "tokens ran version commits",
                                 defaults=(None,))


class _PrefillJob(object):
    """One chunked prefill in flight (paged engine): the slot is
    seated — its full block budget reserved — but the prompt's rows
    materialize tile by tile across scheduler ticks via
    advance_prefill(). `first` is the request's first generated token,
    set when the final tile lands; `finished` mirrors the insert()
    contract (a prefill-only or one-token request completes at its
    first token)."""

    __slots__ = ("slot", "request", "pos", "prompt_len", "first",
                 "finished", "tiles")

    def __init__(self, slot, request, pos):
        self.slot = slot
        self.request = request
        self.pos = int(pos)  # next un-prefilled prompt position
        self.prompt_len = len(request.prompt)
        self.first = None
        self.finished = False
        self.tiles = 0

    def done(self):
        return self.first is not None


class PagedContinuousBatchingEngine(object):
    """The decode pool over BLOCK-PAGED KV storage (serving/kv_pool.py).
    `top_k`/`top_p` are server-level static sampling filters (part of
    the compiled step); temperature and seed ride per request as traced
    values.

    * per-layer KV rows live in shared `[num_blocks, block_size, hkv,
      d]` arenas — total KV HBM is the BLOCK BUDGET, decoupled from
      `num_slots x seq_len`, so more concurrent slots fit in the same
      bytes when requests run short of `seq_len`;
    * insert = one batched prefill, then block-granular writes of
      the prompt's blocks into blocks allocated from the free list
      (never a whole-slot copy), with the request's full token budget
      RESERVED so decode growth cannot strand mid-flight;
    * the single jit-compiled vmapped step carries each slot's block
      table and position as DEVICE arrays: churn, growth and table
      contents never recompile. Attention streams the table
      (ops.paged_decode_attention); the new token's k/v rows come back
      sown through "kv_out" and scatter into the arenas — free lanes
      carry an out-of-bounds block id and drop. A lane at position 0
      (free, or its prompt still being written) is handed to the model
      as carrying no sequence (`paged["live"]`): an expert layer reads
      no expert for its sake and counts it nowhere but in `moe.lanes`
      (model_zoo/transformer_lm ExpertFFN);
    * evict returns the slot's blocks to the free list, O(1) per
      block — copy-free slot churn.

    PREFIX SHARING (share_prefix=True): the pool keeps a
    content-addressed index of resident full prompt blocks
    (serving/kv_pool.py). A request whose prompt prefix matches seats
    by INCREF — the shared blocks are never re-prefilled; only the
    unshared suffix runs, as ONE decode tile over the resident prefix
    (paged_decode_attention's verify-k shape). A full-prompt match
    re-runs just the last token for its logits; that row's re-write
    into the shared tail block is the planned COPY-ON-WRITE fault,
    drawing the CoW credit the seat reserved.

    SPECULATIVE DECODE (draft=(trainer, state), draft_k=k): a small
    draft model holds a dense per-slot cache pool beside the paged
    target pool. Each scheduler tick drafts k greedy tokens per slot
    (k vmapped single-token draft steps) and verifies them in ONE
    vmapped target step over a (k+1)-token tile; greedy-exact
    accept/rollback commits 1..k+1 tokens — rolled-back rows are
    simply never scattered into the block table, and the draft's
    rollback is counter-only. Sampled (temperature > 0) slots accept
    nothing and commit exactly the token the plain step would have
    sampled, so token parity holds for every request either way.

    can_seat() answers from the allocator (prefix matches shrink what
    a request needs), turning out-of-blocks into admission-queue
    backpressure instead of a crash. Requires the model's paged-decode
    convention (TransformerLM: `paged` kwarg + "kv_out" sowing).

    TIERED HOST SPILL (host_bytes > 0 / EDL_KV_HOST_BYTES): evicted
    refcount-0 prefix chains demote to bounded host-RAM buffers
    instead of being forgotten; a prompt matching a spilled chain
    seats by UPLOAD (serving/kv_pool.py revival) and then runs only
    the unshared suffix through the same `_insert_shared` tile — the
    engine cannot tell a revived prefix from one that never left the
    device, which is exactly why parity holds. Admission charges one
    fresh block per spilled chain entry, so upload latency replaces
    prefill compute without the planner and the allocator ever
    disagreeing.

    INT8 ARENAS (model kv_cache_dtype="int8"): the arenas store
    symmetric per-row int8 rows plus f32 per-row scale arenas
    `[num_blocks, block_size, hkv, 1]` — the scales are KV row leaves
    too, so the same tree-generic pool machinery (build, prompt write,
    scatter, CoW copy) carries them with zero special cases. Rows are
    quantized at the two insertion points only (the prefill cache
    write and the model's decode-tile sow); every read defers the
    dequantize into the paged attention scan. Halves-or-better
    bytes-per-block ON TOP of prefix sharing at the same block count,
    or buys proportionally more blocks at equal bytes — sharing, CoW
    and speculative decode compose unchanged (the trie is keyed on
    token ids, dtype-blind).

    PER-SLOT STATE (a model with state-space layers, declared by
    `model.cache_leaf_kind`): beside the row arenas of its attention
    layers the pool keeps a `[num_slots, ...]` arena of each state
    leaf, slot i for lane i (kv_pool.py, LEAVES BY KIND). One prefill
    computes the prompt's rows and the state AT the prompt's true
    length; seating writes the blocks and, in one more launch, the
    state of every state layer; the step maps the state arenas over
    the lanes beside the lane state, so lane i reads and writes slot i
    in place (donated like the row arenas); a free lane's STATE is
    updated like a seated one's and nothing reads what it holds (its
    expert layers, above, read nothing); eviction costs
    no device work, the next seating overwrites the slot. Blocks are
    charged for the attention layers' rows alone. What would need a
    SNAPSHOT of the state at some earlier position refuses to start
    with such a model, by name: prefix sharing, the host tier, a draft
    (speculative rollback), chunked prefill tiles, and the disagg
    chain export / a prefill-only role.
    """

    def __init__(self, trainer, state, num_slots, top_k=0, top_p=1.0,
                 block_size=16, num_blocks=0, share_prefix=True,
                 draft=None, draft_k=0, host_bytes=None,
                 prefill_chunk_tokens=None, denoise_steps=0):
        import inspect

        model = trainer.model
        _require_kv_convention(model)
        if not getattr(model, "causal", True):
            raise ValueError("serving needs a causal sequence model")
        if "paged" not in inspect.signature(
                type(model).__call__).parameters:
            raise ValueError(
                "model %r cannot be served: its __call__ must take the "
                "`paged` argument (decode over {'pools', 'table'}: "
                "attend through the block table and sow the new k/v "
                "rows to \"kv_out\", as model_zoo/transformer_lm does)"
                % type(model).__name__
            )
        if getattr(model, "kv_cache_dtype", "") not in ("", "int8"):
            raise ValueError(
                "paged KV supports the plain-dtype and int8 cache "
                "formats (kv_cache_dtype=%r)"
                % (getattr(model, "kv_cache_dtype", ""),)
            )
        if num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        if not 0.0 < top_p <= 1.0:
            raise ValueError("top_p must be in (0, 1], got %r" % (top_p,))
        self.trainer = trainer
        self.model = model
        self.num_slots = int(num_slots)
        self.seq_len = int(model.seq_len)
        # [(window, layers that have it)]: a model may give each layer
        # its own (`layer_windows()`); one that does not has one kind
        windows = (model.layer_windows()
                   if hasattr(model, "layer_windows")
                   else (getattr(model, "attn_window", 0),))
        self._window_kinds = sorted(
            (int(w), windows.count(w)) for w in set(windows))
        self._window_layers = max(1, len(windows))
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.block_size = int(block_size)
        # 0 = the same-bytes budget: the KV rows of num_slots
        # sequences of seq_len tokens
        self.num_blocks = int(num_blocks) or (
            self.num_slots * -(-self.seq_len // self.block_size)
        )
        # host spill tier (None resolves from EDL_KV_HOST_BYTES): the
        # byte budget for chains demoted to host RAM on eviction,
        # revived by upload instead of re-prefill
        self.host_bytes = (
            kv_host_bytes_default() if host_bytes is None
            else int(host_bytes)
        )
        # chunked prefill (None resolves from EDL_PREFILL_CHUNK_TOKENS;
        # 0 = monolithic): long prompts run as fixed-token tiles via
        # begin_insert/advance_prefill so the scheduler can interleave
        # decode ticks between tiles
        self.prefill_chunk_tokens = (
            prefill_chunk_default() if prefill_chunk_tokens is None
            else int(prefill_chunk_tokens)
        )
        # optional ServingTelemetry hook (GenerationServer wires it):
        # the engine reports prefix-share / CoW / draft-accept events
        # it alone can see; None costs nothing (tests, benches)
        self.telemetry = None
        self.draft_proposed = 0
        self.draft_accepted = 0
        # cumulative wall ms this engine has spent inside insert()
        # (prefill / suffix tile / draft prefill) — the scheduler
        # advances it; the servicer stamps it at admission so seating
        # can report how long OTHER requests' prefills held the
        # single-threaded scheduler while this one waited
        # (forensics: prefill_blocked_by_other). Written only by the
        # scheduler thread, read racily by handler threads — a stale
        # read under-reports blocking by at most one prefill, which
        # the attribution tolerates by design.
        self.prefill_busy_ms = 0.0
        # the tile: positions a lane a tick. 1, or the block length of
        # a block-diffusion model (the module docstring, BLOCK TICK),
        # which takes `denoise_steps` S denoising passes a block (0 =
        # one a position) and then the commit pass
        self._tile = max(1, int(getattr(model, "block_causal", 0) or 0))
        self.denoise_steps = int(denoise_steps)
        self._mask_token = int(getattr(model, "mask_token", -1))
        if self._tile > 1:
            self.denoise_steps = self.denoise_steps or self._tile
            self._refuse_what_a_block_model_cannot_hold(
                draft, draft_k, share_prefix)
        elif self.denoise_steps:
            raise ValueError(
                "denoise_steps %d is the number of denoising passes a "
                "block of a block-diffusion model (block_causal > 1) "
                "takes; this model yields a token a step (--denoise_steps "
                "0)" % self.denoise_steps)

        from elasticdl_tpu.serving.kv_pool import PagedKVPool

        # batch-1 cache template (shares the trainer's compile cache
        # so offline callers reuse the shapes) -> the block arenas
        self._kv_shapes = _kv_shapes_for(_decode_cache(trainer), model, 1)
        kinds = cache_leaf_kinds(model, self._kv_shapes, self.seq_len)
        # the layers that keep a per-sequence state (a model may name
        # them by several leaves each: the recurrence's and the
        # convolution's)
        self._state_paths = [
            path for (path, _), kind in zip(
                jax.tree_util.tree_flatten_with_path(self._kv_shapes)[0],
                jax.tree.leaves(kinds)) if kind == STATE]
        self._state_layers = len({path[0].key
                                  for path in self._state_paths})
        if self._state_layers:
            self._refuse_what_needs_a_state_snapshot(draft, draft_k)
            if self._tile > 1:
                raise ValueError(
                    "a model that denoises a block of positions a step "
                    "(block_causal) cannot keep a per-sequence state "
                    "(a state-space layer): a denoising pass would have "
                    "to leave the state as it found it, which nothing "
                    "does yet")
        self.kv = PagedKVPool(
            self._kv_shapes, self.seq_len, self.num_slots,
            self.num_blocks, self.block_size,
            share_prefix=bool(share_prefix),
            host_bytes=self.host_bytes, kinds=kinds,
            leaf_windows=self._leaf_windows(share_prefix, draft, draft_k),
        )
        # [(window, layers that have it)] of the pool's block classes;
        # a pool with one table for every layer has the model's kinds
        # of layer all in the one table
        self._classes = self._window_kinds
        if self.kv.classed:
            self._classes = list(zip(self.kv.class_windows,
                                     self.kv.class_layers))
            logger.info(
                "serving: KV blocks in %d classes by attention window: %s",
                len(self._classes), "; ".join(
                    "window %d: %d layers, %d blocks" % (
                        a.window, n, a.num_blocks) for a, n in zip(
                            self.kv.allocators, self.kv.class_layers)))
        # optional recompile sentry (runtime_health.RecompileSentry;
        # the server attaches it under ServingConfig.runtime_health).
        # Every jit site below compiles through _tjit, which resolves
        # this LAZILY — executables built before the server attaches
        # the sentry still count their later compiles. None = plain
        # jax.jit, zero counting work.
        self.sentry = None
        self._qz = is_quantized(state.params)
        # in-jit dequantize is opt-in (see the module docstring); the
        # default path serves float weights made once by _load_params
        self._exec_qz = self._qz and _fused_dequant()
        self._slots = [None] * self.num_slots  # _Slot or None
        self._prefilling = {}  # slot -> _PrefillJob (chunked, pending)
        self._positions = np.zeros(self.num_slots, np.int32)
        self._last_tokens = np.zeros(self.num_slots, np.int32)
        self._seeds = np.zeros(self.num_slots, np.int32)
        self._temps = np.zeros(self.num_slots, np.float32)
        # a block engine's book of each lane's open block: what the
        # host knows of it (a lane seated since the last launch: the
        # given positions, the rest masked) and the pass the next
        # launch runs, which the host always knows
        self._block_tokens = np.zeros((self.num_slots, self._tile),
                                      np.int32)
        self._block_reveal = np.full((self.num_slots, self._tile),
                                     MASKED, np.int32)
        self._passes = np.full(self.num_slots, _NO_PASS, np.int32)
        # the lane state on the device as the last step launched hands
        # it back (None: not to be trusted, the next launch sends the
        # mirror), and whether the host has written a lane's scalars
        # since
        self._lanes = None
        self._lanes_dirty = False
        # the steps launched and not yet committed, oldest first (one
        # between two calls of step(), two inside one), and how many
        # of their lanes were freed at the launch (their last) and
        # still owe their request its last token
        self._flights = collections.deque()
        self._landing = 0
        self._prefill_fns = {}  # bucket -> compiled prefill
        self._suffix_fns = {}  # suffix bucket -> compiled tile prefill
        self._step_fn = None
        self._merge_fn = None
        self._spec_fn = None
        # the counters the model's layers sow in a decode step, by
        # name, in the order they follow the tokens in the step's
        # result (filled when the step is traced; a model that sows
        # none leaves it empty)
        self._tick_counters = []
        # last-forwarded pool counters: the engine mirrors the pool's
        # monotone spill/revival counters into the closed telemetry
        # set by DELTA, so the event file stays in lockstep with the
        # allocator no matter which path (seat/extend/CoW) spilled
        self._host_counters_seen = {
            "revive_uploads": 0, "prefill_tokens_revived": 0,
            "host_drops": 0,
        }
        d_variables = self._init_draft(draft, draft_k)
        # the weights, last: which leaves are served as a cast is
        # decided from every program that takes them, the draft's too
        variables = {"params": state.params, **state.model_state}
        self._loader = self._weight_loader(
            "load_weights", 0, variables, d_variables,
            dequantize=self._qz and not self._exec_qz,
        )
        self._load_params(state, getattr(state, "version", 0))
        if d_variables is not None:
            # the draft's are served like the target's: the same rule
            # over the programs that take them, once (no hot reload)
            self._d_variables = self._load_weights(
                self._weight_loader("load_draft_weights", 1,
                                    self._exec_variables, d_variables),
                d_variables,
            )

    def _leaf_windows(self, share_prefix, draft, draft_k):
        """Each cache leaf's attention window, along the leaves, for
        the pool to keep its blocks in CLASSES by (kv_pool.py, BLOCK
        CLASSES), or None: one table for every layer, every sequence
        charged whole. Classes need that nothing reads a block behind
        a layer's window: no shared or spilled chain (a later prompt
        would seat on it), no decode tile over earlier positions (the
        draft's verify, a chunked prefill's tiles, a block model's);
        with any of those on the pool is the one it always was, and
        the server says so once, with what it costs
        (`classes_given_up`, `kv_stats()["kv_classes_given_up"]`)."""
        self.classes_given_up = []
        window_of = getattr(self.model, "cache_leaf_window", None)
        if window_of is None:
            return None
        paths = [
            tuple(getattr(k, "key", getattr(k, "name", None)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(
                self._kv_shapes)[0]]
        windows = [int(window_of(path)) for path in paths]
        if not any(windows):
            return None
        self.classes_given_up = [name for name, on in (
            ("prefix sharing (kv_shared)", share_prefix),
            ("the host tier (kv_host_bytes)", self.host_bytes),
            ("chunked prefill (prefill_chunk_tokens)",
             self.prefill_chunk_tokens),
            ("a block model's tile (block_causal)", self._tile > 1),
            ("speculative decode (draft, draft_k)",
             draft is not None and int(draft_k) >= 1)) if on]
        if not self.classes_given_up:
            return windows
        from elasticdl_tpu.serving.kv_pool import blocks_for

        whole = blocks_for(self.seq_len, self.block_size)
        logger.warning(
            "serving: %s keeps the KV pool at ONE table for every layer: "
            "a lane of %d tokens is charged %d blocks in each of the %d "
            "window layers, where a block class of window %d would charge "
            "%d; --kv_num_blocks %d seats %d such lanes",
            ", ".join(self.classes_given_up), self.seq_len, whole,
            len({path[0] for path, w in zip(paths, windows) if w}),
            max(windows),
            min(whole, blocks_for(max(windows), self.block_size) + 2),
            self.num_blocks, self.num_blocks // max(whole, 1))
        return None

    def _refuse_what_needs_a_state_snapshot(self, draft, draft_k):
        """A model with state layers (the pool refuses prefix sharing
        and the host tier itself): no draft, whose rejected proposals
        roll the target back to a position whose state nobody kept,
        and no chunked prefill, whose tiles are decode tiles of
        several tokens."""
        why = ("this model keeps a per-sequence state (a state-space "
               "layer) beside its KV rows, and %s would need the state "
               "of an earlier position kept, which nothing keeps yet. "
               "Start the server without it (%s)")
        if draft is not None and int(draft_k) >= 1:
            raise ValueError(why % (
                "speculative decode (draft, draft_k)",
                "--draft_k 0, no --draft_model_def"))
        if self.prefill_chunk_tokens:
            raise ValueError(why % (
                "chunked prefill (prefill_chunk_tokens)",
                "--prefill_chunk_tokens 0 / EDL_PREFILL_CHUNK_TOKENS "
                "unset"))

    def _refuse_what_a_block_model_cannot_hold(self, draft, draft_k,
                                               share_prefix):
        """A model that generates by diffusion over blocks of B
        positions: S must divide B (the static schedule reveals B / S
        positions a pass), a pool block must hold whole model blocks,
        and what assumes a token a step, or the open block's rows in
        the pool, is refused by name."""
        B, S = self._tile, self.denoise_steps
        if S < 1 or B % S:
            raise ValueError(
                "denoise_steps %d does not divide the model's block of "
                "%d positions: the static reveal takes B / S positions a "
                "pass (--denoise_steps one of %s)"
                % (S, B, [d for d in range(1, B + 1) if B % d == 0]))
        if self.block_size % B or self.seq_len % B:
            raise ValueError(
                "kv_block_size %d and seq_len %d must be whole multiples "
                "of the model's block of %d positions, which is written "
                "to the pool as one" % (self.block_size, self.seq_len, B))
        if self._mask_token < 0:
            raise ValueError(
                "a model that generates by diffusion over blocks "
                "(block_causal) names the id a position not revealed yet "
                "reads: mask_token is not set")
        why = ("this model generates by diffusion over blocks of %d "
               "positions (block_causal), and %s. Start the server "
               "without it (%s)")
        if draft is not None and int(draft_k) >= 1:
            raise ValueError(why % (
                B, "speculative decode (draft, draft_k) verifies tokens "
                "a causal step would have produced one by one",
                "--draft_k 0, no --draft_model_def"))
        if self.prefill_chunk_tokens:
            raise ValueError(why % (
                B, "chunked prefill (prefill_chunk_tokens) samples a "
                "first token from the prompt's last tile, which a block "
                "model does not have", "--prefill_chunk_tokens 0 / "
                "EDL_PREFILL_CHUNK_TOKENS unset"))
        if share_prefix:
            raise ValueError(why % (
                B, "seating on a shared prefix (kv_shared) re-runs the "
                "prompt's tail for a first token, which a block model "
                "does not have; seating the given positions behind a "
                "shared chain is not built yet",
                "--kv_shared 0 / EDL_KV_SHARED=0"))
        if self.host_bytes:
            raise ValueError(why % (
                B, "the host tier (kv_host_bytes) revives the chains "
                "that prefix sharing keeps",
                "--kv_host_bytes 0 / EDL_KV_HOST_BYTES unset"))
        if self.top_k or self.top_p < 1.0:
            raise ValueError(why % (
                B, "the reveal reads each position's greedy token and "
                "its probability: sampling filters (top_k, top_p) and a "
                "temperature have no meaning yet",
                "--top_k 0 --top_p 1.0"))

    def refuse_request(self, request):
        """Why this engine cannot serve `request` at all (None: it
        can): asked at admission, so that a request is turned away
        with INVALID_ARGUMENT and never reaches a lane."""
        if self._tile == 1:
            return None
        if request.temperature > 0.0:
            return ("this model generates by diffusion over blocks and "
                    "is served greedy: temperature %g is refused (0)"
                    % request.temperature)
        if getattr(request, "prefill_only", False):
            return ("this model generates by diffusion over blocks: a "
                    "prefill-only seat (the disagg handoff) is not "
                    "built for it")
        return None

    def _rows_cached(self, request):
        """The KV rows a seated `request` may come to hold: every
        position but the last token's (nobody attends from beyond it),
        or, block by block, up to the end of its last block."""
        total = len(request.prompt) + request.max_new_tokens
        if self._tile == 1:
            return total - 1
        return -(-total // self._tile) * self._tile

    def _init_draft(self, draft, draft_k):
        """Seat the draft model for speculative decode: its own dense
        per-slot cache pool (the draft is small — that is the point)
        beside the paged target pool the reclaimed blocks feed.
        Returns the draft's weights as handed in (None: no draft)."""
        self._draft = None
        self.draft_k = 0  # speculative decode off unless a draft seats
        if draft is None or int(draft_k) < 1:
            return None
        d_trainer, d_state = draft
        d_model = d_trainer.model
        _require_kv_convention(d_model)
        if not getattr(d_model, "causal", True):
            raise ValueError("speculative decode needs a causal draft")
        if getattr(d_model, "vocab_size", None) != getattr(
                self.model, "vocab_size", None):
            raise ValueError(
                "draft and target must share a vocabulary, got %r vs %r"
                % (getattr(d_model, "vocab_size", None),
                   getattr(self.model, "vocab_size", None))
            )
        if int(d_model.seq_len) < self.seq_len:
            raise ValueError(
                "draft seq_len %d must cover the target's %d"
                % (d_model.seq_len, self.seq_len)
            )
        if is_quantized(d_state.params):
            raise ValueError(
                "speculative decode needs float draft params (the "
                "draft is small; quantizing it buys nothing)"
            )
        self.draft_k = int(draft_k)
        self._draft = d_trainer
        self._d_model = d_model
        self._d_kv_shapes = _kv_shapes_for(
            _decode_cache(d_trainer), d_model, 1
        )
        self._d_pool = jax.tree.map(
            lambda sh: jnp.zeros((self.num_slots,) + sh.shape,
                                 sh.dtype),
            self._d_kv_shapes,
        )
        self._d_prefill_fns = {}
        self._d_write_fn = None
        return {"params": d_state.params, **d_state.model_state}

    # ------------------------------------------------------------ params

    @property
    def sentry(self):
        return self._sentry

    @sentry.setter
    def sentry(self, value):
        # the paged pool compiles its own spill gather / revival
        # upload / prompt write / CoW executables — the sentry
        # forwards so those sites count into the same family; the
        # offline decode caches adopt it too (one process, one sentry)
        self._sentry = value
        self.kv.sentry = value
        from elasticdl_tpu.api import generation as _generation

        _generation.set_decode_sentry(value)

    def _weight_loader(self, name, which, variables, d_variables,
                       dequantize=False):
        """The program that turns a weight tree as handed in into the
        tree the serving programs take, for the target's tree
        (`which` 0, `variables`) or the draft's (1, `d_variables`):
        int8 leaves dequantized (the default, non-fused path), then
        every leaf that _weight_programs consume only through a cast
        to one narrower dtype replaced by that cast
        (exec_weights.narrowing_casts: decided from the traced
        programs, here, once, over shapes, and remembered beside the
        compiled programs for the next start). Jitted through _tjit
        and first run at construction, so a hot reload compiles
        nothing. Returns (program, leaves cast, leaves kept); the
        program is None when the programs take the tree as handed in
        (fp32 compute: nothing is a cast)."""
        def prepare(v):
            if dequantize:
                v = dict(v, params=dequantize_params(v["params"]))
            return v

        # what the programs close over, for the remembered decision's
        # key: the models and every setting a program builder reads
        closed_over = (
            name, self.model, getattr(self, "_d_model", None),
            self.trainer.mesh, self.num_slots, self.seq_len,
            self.block_size, self.num_blocks, self.top_k, self.top_p,
            self.draft_k, self._qz, self._exec_qz, dequantize,
        )
        with self.trainer.mesh:
            trees = [variables, d_variables]
            trees[which] = prepared = jax.eval_shape(
                prepare, trees[which])
            plan = remembered_casts(closed_over, prepared, [
                (fn, args, argnums[which])
                for fn, args, argnums in self._weight_programs(*trees)
                if argnums[which] is not None])
        cast = sum(to is not None for to in plan)
        if not dequantize and not cast:
            return None, cast, len(plan)
        fn = self._tjit(name, lambda v: cast_leaves(prepare(v), plan))
        return fn, cast, len(plan) - cast

    def _load_weights(self, loader, variables):
        """`variables` as the programs take them, by `loader` (from
        _weight_loader), and the four `weights.*` counters: the bytes
        handed in and served, the leaves cast and kept."""
        load_fn, cast, kept = loader
        served = variables
        if load_fn is not None:
            with self.trainer.mesh:
                served = load_fn(variables)
        tracing.count("weights.source_bytes", tree_bytes(variables))
        tracing.count("weights.exec_bytes", tree_bytes(served))
        tracing.count("weights.leaves_cast", cast)
        tracing.count("weights.leaves_kept", kept)
        return served

    def _load_params(self, state, version):
        """Bind `state`'s params as the serving weights: ONE program
        (_weight_loader) makes `_exec_variables`, the tree every
        prefill / decode program takes until the next reload replaces
        it here. It holds, for each leaf of the state: the cast to the
        compute dtype where the programs consume the leaf only through
        that cast (bf16 compute over fp32 weights: the matmul kernels,
        the MLP biases, the head, the embedding table), the leaf as
        handed in where any program reads it as it is (LayerNorm
        scales and biases, an fp32 router; every leaf under fp32
        compute, where `_exec_variables` IS the tree handed in), and
        the dequantized float in place of an int8 leaf (non-fused
        path). The engine keeps no reference to the tree it was
        handed: once the caller lets go of `state`, a leaf that was
        replaced is gone from the device."""
        self._exec_variables = self._load_weights(
            self._loader, {"params": state.params, **state.model_state}
        )
        self.model_version = int(version)

    @property
    def variables(self):
        """The weights as served: there is one tree, and this is the
        name it had before it was served as anything but what was
        handed in. In the repo only tests/test_serving_one_engine.py
        reads it; the engine itself reads _exec_variables."""
        return self._exec_variables

    def set_params(self, state, version):
        """Swap the serving params (hot reload). Runs BETWEEN decode
        steps (scheduler thread), so in-flight sequences simply continue
        on the new weights — their KV rows, positions and pending
        tokens are untouched. Shapes/dtypes must match the compiled
        executables; a changed architecture needs a new server.
        Cached prefix rows were computed under the superseded params,
        so the prefix index flushes — a NEW request must never seat on
        stale rows."""
        if is_quantized(state.params) != self._qz:
            raise ValueError(
                "hot reload cannot change quantization (compiled "
                "executables bake the dequantize path)"
            )
        with tracing.phase("reload_swap", version=int(version)):
            self._load_params(state, version)
        self.kv.flush_prefix_cache()

    # ------------------------------------------------------------- slots

    def free_slots(self):
        # a seated-but-still-prefilling slot is occupied: its blocks
        # are reserved and its tiles are mid-flight
        return [i for i, s in enumerate(self._slots)
                if s is None and i not in self._prefilling]

    def active_count(self):
        """The lanes that still owe a token: those seated, and those
        freed at the launch of their last step whose tokens are in
        flight (the scheduler calls step() while this is not 0, or they
        would never be streamed). Read from other threads too (status,
        the watchdog): two plain reads, and never more than the slots
        there are, though a slot freed at a launch may be seated again
        before that launch is committed."""
        return min(self.num_slots, len(self._seated()) + self._landing)

    def seated_count(self):
        """The lanes seated now, those the next launch carries: what a
        tick's root span says was left seated (a lane whose last token
        is in flight holds no slot any more)."""
        return len(self._seated())

    def _seated(self):
        """[(slot, its _Slot)] of the lanes that decode."""
        return [(i, s) for i, s in enumerate(self._slots) if s is not None]

    def active_requests(self):
        reqs = [s.request for _slot, s in self._seated()]
        reqs.extend(j.request for j in self._prefilling.values())
        # freed at their last launch, not yet committed: still owed
        # a `done` or an error
        reqs.extend(st.request for flight in self._flights
                    for _slot, st, last in flight.ran if last)
        return reqs

    def prefilling_count(self):
        return len(self._prefilling)

    def can_seat(self, request):
        """Whether `request` can be seated RIGHT NOW beyond needing a
        free slot (the scheduler checks slots separately): answered
        from the block budget."""
        if (request.max_new_tokens <= 1 and self._tile == 1
                and not getattr(request, "prefill_only", False)):
            return True  # one-token answer; never touches the pool
        return self.kv.can_seat(request.prompt, len(request.prompt),
                                self._rows_cached(request))

    def max_cached_tokens(self):
        """Largest prompt+decode cache footprint a request may ever
        need — the admission queue's never-fits bound: a request must
        fit BOTH one slot's table and the whole pool."""
        return min(self.seq_len, self.num_blocks * self.block_size)

    def kv_stats(self):
        """KV memory accounting for telemetry / ServerStatus."""
        return dict(self.kv.stats(),
                    kv_classes_given_up=list(self.classes_given_up))

    def _sync_host_telemetry(self):
        """Forward the pool's monotone spill-tier counters (revival
        uploads, tokens revived instead of re-prefilled, host LRU
        drops) into the closed telemetry counter set by delta — the
        pool is the single source of truth, the telemetry mirror can
        never drift from it."""
        if self.telemetry is None:
            return
        stats = self.kv.stats()
        for name in ("revive_uploads", "prefill_tokens_revived",
                     "host_drops"):
            delta = stats[name] - self._host_counters_seen[name]
            if delta:
                self.telemetry.count(name, delta)
                self._host_counters_seen[name] = stats[name]

    def insert(self, request):
        """Seat `request` in a free slot: one prefill forward computes
        the prompt's rows and the FIRST generated token (pushed by the
        caller — this is the TTFT boundary), and the rows land in
        allocated blocks. The allocator reserves the FULL cache budget
        (prompt + max_new_tokens - 1 rows) up front — raising
        OutOfBlocks before any compute — so a seated request can
        always extend to completion. A prompt whose prefix matches
        the resident index seats the shared blocks by incref and runs
        ONLY the unshared suffix. A one-token request skips the pool
        entirely (nothing will ever read its rows). Returns (slot_idx,
        first_token, finished); raises RuntimeError when no slot is
        free (callers check free_slots)."""
        free = self.free_slots()
        if not free:
            raise RuntimeError("no free slot")
        slot = free[0]
        p = len(request.prompt)
        total = p + request.max_new_tokens
        if total > self.seq_len:
            raise ValueError(
                "request needs %d positions > seq_len %d"
                % (total, self.seq_len)
            )
        if self._tile > 1:
            return self._insert_block(slot, request)
        prefill_only = getattr(request, "prefill_only", False)
        if prefill_only and self._state_layers:
            raise ValueError(
                "a prefill-only seat (the disagg handoff) parks a "
                "prompt's KV blocks for a sibling to import, and this "
                "model keeps a per-sequence state beside them that no "
                "chain carries yet")
        decoding = request.max_new_tokens > 1 or prefill_only
        shared = 0
        if decoding:
            # reserve-or-raise BEFORE any compute; the scheduler
            # checks can_seat first, so raising here is a bug guard
            revived_before = self.kv.allocator.blocks_revived
            seat_t0 = time.perf_counter()
            shared = self.kv.seat(slot, request.prompt,
                                  self._rows_cached(request))
            revived = (self.kv.allocator.blocks_revived
                       - revived_before)
            if revived and hasattr(request, "trace_event"):
                # the seat revived a spilled chain: the upload IS the
                # seat's cost here, and forensics.attribute() reads
                # this event to split revive_upload out of prefill_own
                request.trace_event(
                    "revive_upload",
                    ms=round((time.perf_counter() - seat_t0)
                             * 1000.0, 3),
                    tokens=revived * self.kv.block_size,
                )
        if decoding and shared:
            first = self._insert_shared(slot, request, shared)
        else:
            p_pad, fn = self._prefill_for(p)
            buf = np.zeros((1, self.seq_len), np.int32)
            buf[0, :p] = request.prompt
            with tracing.phase("prefill", trace_id=_trace_id(request),
                               prompt_tokens=p, bucket=p_pad):
                with self.trainer.mesh:
                    kv, first = fn(
                        self._exec_variables, jnp.asarray(buf),
                        jnp.asarray(p, jnp.int32),
                        jnp.asarray(request.seed, jnp.int32),
                        jnp.asarray(request.temperature, jnp.float32),
                    )
                    if decoding:
                        self.kv.write_prompt(kv, slot, p)
                first = int(first)  # the host waits for the token
            if hasattr(request, "trace_event"):
                request.trace_event("prefill", bucket=p_pad, slot=slot,
                                    paged=True)
        if decoding:
            # make this prompt's full blocks matchable (the shared
            # ones are already indexed; walking is idempotent)
            self.kv.register_prefix(slot, request.prompt)
            if self.draft_k and not prefill_only:
                self._prefill_draft(slot, request)
        request.generated.append(first)
        request.model_version = self.model_version
        self._sync_host_telemetry()
        if prefill_only:
            # cache-warming seat (disagg prefill replica): the chain
            # is registered; release the slot's references NOW so the
            # blocks park refcount-0 in the reclaimable cache —
            # matchable, exportable, and reclaimable under pressure
            self.kv.release(slot)
            return slot, first, True
        if not decoding:
            return slot, first, True
        self._activate(slot, request, first)
        return slot, first, False

    def _prefill_for(self, p):
        """(bucket, its compiled prefill) for a prompt of `p` tokens."""
        p_pad = _prefill_bucket(p, self.seq_len)
        fn = self._prefill_fns.get(p_pad)
        if fn is None:
            fn = self._prefill_fns[p_pad] = self._build_prefill(p_pad)
        return p_pad, fn

    def _insert_block(self, slot, request):
        """Seat a request of a block-diffusion model: the prompt's
        whole blocks are prefilled under the block-causal mask and
        written to the slot's blocks (none for a prompt shorter than a
        block); no token comes of it. Returns (slot, None, False): the
        first tokens come with the first block's commit pass."""
        p = len(request.prompt)
        whole = p // self._tile * self._tile
        self.kv.seat(slot, request.prompt, self._rows_cached(request))
        if whole:
            p_pad, fn = self._prefill_for(whole)
            buf = np.zeros((1, self.seq_len), np.int32)
            buf[0, :whole] = request.prompt[:whole]
            with tracing.phase("prefill", trace_id=_trace_id(request),
                               prompt_tokens=whole, bucket=p_pad):
                with self.trainer.mesh:
                    kv, _first = fn(
                        self._exec_variables, jnp.asarray(buf),
                        jnp.asarray(whole, jnp.int32),
                        jnp.asarray(request.seed, jnp.int32),
                        jnp.asarray(0.0, jnp.float32),
                    )
                    self.kv.write_prompt(kv, slot, whole)
            if hasattr(request, "trace_event"):
                request.trace_event("prefill", bucket=p_pad, slot=slot,
                                    paged=True)
        request.model_version = self.model_version
        self._activate(slot, request, None)
        return slot, None, False

    def _open_block(self, slot, st):
        """Write lane `slot`'s open block into the book as the host
        knows it: at pass 0, the prompt's remainder given where the
        block is the request's first, every other position masked."""
        request = st.request
        p = len(request.prompt)
        given = ([] if request.generated
                 else request.prompt[p // self._tile * self._tile:])
        self._block_tokens[slot] = self._mask_token
        self._block_tokens[slot, :len(given)] = given
        self._block_reveal[slot] = MASKED
        self._block_reveal[slot, :len(given)] = GIVEN
        self._passes[slot] = 0

    def _activate(self, slot, request, first):
        """Start decoding `request` in `slot`, its prompt's rows
        resident and `first` its first generated token: the lane's
        scalars are written in the mirror and owed to the device."""
        p = len(request.prompt)
        self._slots[slot] = _Slot(request, p + request.max_new_tokens)
        self._positions[slot] = p
        self._last_tokens[slot] = first or 0
        self._seeds[slot] = request.seed
        self._temps[slot] = request.temperature
        if self._tile > 1:
            # a block lane sits at its open block's start
            self._positions[slot] = p // self._tile * self._tile
            self._open_block(slot, self._slots[slot])
        self._lanes_dirty = True

    def _insert_shared(self, slot, request, shared):
        """Seat on a prefix match: the shared blocks are resident, so
        only the suffix `prompt[start:]` runs — ONE decode tile over
        the prefix through the slot's table, its rows scattered into
        the slot's fresh blocks, its last logits sampling the first
        token. A full-prompt match re-runs just the last token; that
        row's write into the shared tail block is the planned CoW
        fault (the seat reserved the credit)."""
        p = len(request.prompt)
        if shared >= p:
            if (self.kv.cow_for_write(slot, p - 1) is not None
                    and self.telemetry is not None):
                self.telemetry.count("cow_copies")
            start = p - 1
        else:
            start = shared
        t = p - start
        t_pad = self._suffix_bucket(t)
        fn = self._suffix_fns.get(t_pad)
        if fn is None:
            fn = self._build_suffix_prefill(t_pad)
            self._suffix_fns[t_pad] = fn
        chunk = np.zeros((1, t_pad), np.int32)
        chunk[0, :t] = request.prompt[start:]
        with tracing.phase("suffix_tile", trace_id=_trace_id(request),
                           suffix_tokens=t, bucket=t_pad):
            with self.trainer.mesh:
                first = self.kv.update(
                    fn, self._exec_variables,
                    jnp.asarray(self.kv.tables[slot]),
                    jnp.asarray(chunk),
                    jnp.asarray(start, jnp.int32),
                    jnp.asarray(t, jnp.int32),
                    jnp.asarray(request.seed, jnp.int32),
                    jnp.asarray(request.temperature, jnp.float32),
                )
            first = int(first)  # the host waits for the first token
        if self.telemetry is not None:
            # count the allocator-reported shared tokens so this stays
            # in lockstep with BlockAllocator.prefix_hit_tokens (start
            # is shared - 1 on a full-prompt match: the re-run row)
            self.telemetry.count("prefix_hit_tokens", shared)
        if hasattr(request, "trace_event"):
            request.trace_event("prefix_hit", slot=slot,
                                shared_tokens=start, suffix_tokens=t)
        return first

    # --------------------------------------------------- chunked prefill

    def begin_insert(self, request):
        """Chunked admission: seat `request` — the same full-budget
        reservation as insert() — and return a _PrefillJob whose tiles
        advance_prefill() runs between decode ticks. Prompts that need
        no chunking (chunking off, one-token answers, full-prompt
        prefix matches) complete immediately: job.done() is True and
        job.first/job.finished carry the insert() result, so the
        caller has ONE completion path either way."""
        chunk = self.prefill_chunk_tokens
        prefill_only = getattr(request, "prefill_only", False)
        if not chunk or (request.max_new_tokens <= 1
                         and not prefill_only):
            slot, first, finished = self.insert(request)
            job = _PrefillJob(slot, request, len(request.prompt))
            job.first, job.finished = first, finished
            return job
        free = self.free_slots()
        if not free:
            raise RuntimeError("no free slot")
        slot = free[0]
        p = len(request.prompt)
        total = p + request.max_new_tokens
        if total > self.seq_len:
            raise ValueError(
                "request needs %d positions > seq_len %d"
                % (total, self.seq_len)
            )
        revived_before = self.kv.allocator.blocks_revived
        seat_t0 = time.perf_counter()
        shared = self.kv.seat(slot, request.prompt,
                              p + request.max_new_tokens - 1)
        revived = self.kv.allocator.blocks_revived - revived_before
        if revived and hasattr(request, "trace_event"):
            request.trace_event(
                "revive_upload",
                ms=round((time.perf_counter() - seat_t0) * 1000.0, 3),
                tokens=revived * self.kv.block_size,
            )
        if shared >= p:
            # full-prompt match: the one-token re-run tile IS the
            # whole prefill — nothing left to chunk
            first = self._insert_shared(slot, request, shared)
            job = _PrefillJob(slot, request, p)
            self._finish_prefill(job, first)
            return job
        if shared:
            if self.telemetry is not None:
                self.telemetry.count("prefix_hit_tokens", shared)
            if hasattr(request, "trace_event"):
                request.trace_event(
                    "prefix_hit", slot=slot, shared_tokens=shared,
                    suffix_tokens=p - shared,
                )
        job = _PrefillJob(slot, request, shared)
        self._prefilling[slot] = job
        return job

    def advance_prefill(self, job):
        """Run ONE tile of `job`'s pending prompt: decode up to
        prefill_chunk_tokens prompt tokens at positions
        [pos, pos + t) over the slot's resident blocks and scatter
        their rows — the shared-prefix suffix executable pointed at a
        chunk window, so chunking adds no new compiled surface. The
        FINAL tile's sample (position = prompt length, the monolithic
        prefill's sampling position) is the request's first generated
        token; non-final samples are discarded. Returns True when the
        job completed this call."""
        if job.done():
            return True
        slot, request = job.slot, job.request
        p = job.prompt_len
        t = min(self.prefill_chunk_tokens, p - job.pos)
        final = job.pos + t >= p
        t_pad = self._suffix_bucket(t)
        fn = self._suffix_fns.get(t_pad)
        if fn is None:
            fn = self._build_suffix_prefill(t_pad)
            self._suffix_fns[t_pad] = fn
        chunk = np.zeros((1, t_pad), np.int32)
        chunk[0, :t] = request.prompt[job.pos:job.pos + t]
        with tracing.phase("prefill_tile", trace_id=_trace_id(request),
                           tile_tokens=t, bucket=t_pad), \
                self.trainer.mesh:
            first = self.kv.update(
                fn, self._exec_variables,
                jnp.asarray(self.kv.tables[slot]),
                jnp.asarray(chunk),
                jnp.asarray(job.pos, jnp.int32),
                jnp.asarray(t, jnp.int32),
                jnp.asarray(request.seed, jnp.int32),
                jnp.asarray(request.temperature, jnp.float32),
            )
        job.pos += t
        job.tiles += 1
        if not final:
            return False
        if hasattr(request, "trace_event"):
            request.trace_event(
                "prefill", slot=slot, paged=True, tiles=job.tiles,
                chunk_tokens=self.prefill_chunk_tokens,
            )
        self._finish_prefill(job, int(first))
        return True

    def _finish_prefill(self, job, first):
        """The chunked path's insert() epilogue: index the prompt,
        seat the draft, commit the first token, and either activate
        the slot for decode or (prefill-only) release it with the
        chain parked exportable."""
        slot, request = job.slot, job.request
        self._prefilling.pop(slot, None)
        prefill_only = getattr(request, "prefill_only", False)
        self.kv.register_prefix(slot, request.prompt)
        if self.draft_k and not prefill_only:
            self._prefill_draft(slot, request)
        request.generated.append(first)
        request.model_version = self.model_version
        self._sync_host_telemetry()
        job.first = first
        if prefill_only or request.max_new_tokens <= 1:
            self.kv.release(slot)
            job.finished = True
            return
        self._activate(slot, request, first)

    def abort_prefill(self, job):
        """Abandon a pending chunked prefill (deadline expiry between
        tiles): release the seat — rows already scattered die with
        their blocks' refcounts; shared ancestors survive under their
        other owners."""
        if self._prefilling.pop(job.slot, None) is None:
            return
        job.finished = True
        self.kv.release(job.slot)

    def _prefill_draft(self, slot, request):
        """Fill the draft's dense cache for this prompt (the draft has
        no paged pool, so it always prefills the full prompt — it is
        small enough that this is noise next to the target)."""
        p = len(request.prompt)
        p_pad = _prefill_bucket(p, self.seq_len)
        fn = self._d_prefill_fns.get(p_pad)
        if fn is None:
            fn = self._build_draft_prefill(p_pad)
            self._d_prefill_fns[p_pad] = fn
        buf = np.zeros((1, self.seq_len), np.int32)
        buf[0, :p] = request.prompt
        with tracing.phase("draft", trace_id=_trace_id(request),
                           prompt_tokens=p, bucket=p_pad), \
                self.trainer.mesh:
            d_kv = fn(self._d_variables, jnp.asarray(buf),
                      jnp.asarray(p, jnp.int32))
            self._write_draft_slot(d_kv, slot)

    def _suffix_bucket(self, t):
        """Static tile widths for the suffix prefill, in steps of 8 so
        nearby suffix lengths share one executable."""
        return min(self.seq_len, -(-int(t) // 8) * 8)

    def evict(self, slot):
        """Evict the slot's request before its end (a deadline): free
        the lane, and skip what its steps in flight produce when they
        are committed."""
        if self._slots[slot] is not None:
            self._slots[slot].evicted = True
        self._free(slot)

    def _free(self, slot):
        """Free the lane (the launch of its last step, or an eviction)
        AND drop its block references; private rows are dead the
        moment the table forgets them, shared rows live on under their
        other owners (copy-free churn — nothing is zeroed or moved).
        Host work only, and safe while a step that writes the slot's
        blocks is in flight: the mirror is owed to the device, so no
        later launch carries the lane, and whatever next touches those
        blocks takes the pool that step hands back (kv_pool.py,
        `release`)."""
        self._slots[slot] = None
        self._positions[slot] = 0
        self._passes[slot] = _NO_PASS
        self._lanes_dirty = True
        self.kv.release(slot)

    def evict_expired(self, now):
        """Evict every active request whose deadline has passed;
        returns the evicted requests (the scheduler fails them with
        DEADLINE_EXCEEDED — partial tokens already streamed stand)."""
        out = []
        for i, st in enumerate(self._slots):
            if st is not None and st.request.expired(now):
                self.evict(i)
                out.append(st.request)
        return out

    def _count_paged_stream(self):
        """Count what this tick's paged decode streams, a layer: the
        table slots in reach of each lane's sequence (the kernel's own
        live range; a free lane sits at position 0 and has none) of
        all the table slots the lanes have. Layers may differ in their
        window (`model.layer_windows()`), so the reach is taken a kind
        of layer and averaged over the layers (whole blocks); with one
        window for every layer that is the one layer's count. And what
        the pool holds, in blocks x layers: `kv.blocks_whole`, what one
        table for every layer holds of the seated lanes' rows written
        so far (every block, in every layer); `kv.blocks_held`, what
        the pool's classes do hold of them (a window class has released
        what fell behind its window: kv_pool.py, BLOCK CLASSES; with
        one table the two are equal); and `kv.window_dead_blocks`, the
        held blocks wholly behind their layer's window, which nothing
        reads again."""
        m = self.kv.max_blocks_per_slot
        whole = held = streamed = dead = 0
        for c, (window, layers) in enumerate(self._classes):
            j_lo, j_hi = paged_live_blocks(
                self._positions, window or None, self.block_size, m,
                xp=np,
            )
            # a lane's leading table entries released behind the window
            gone = (np.minimum(self.kv.holes[c], j_hi)
                    if self.kv.classed else 0)
            whole += layers * int(j_hi.sum())
            held += layers * int((j_hi - gone).sum())
            dead += layers * int(np.maximum(j_lo - gone, 0).sum())
            streamed += layers * int((j_hi - j_lo).sum())
        tracing.count("paged.blocks_streamed",
                      streamed // self._window_layers)
        tracing.count("paged.table_slots", self.num_slots * m)
        tracing.count("kv.window_dead_blocks", dead)
        tracing.count("kv.blocks_held", held)
        tracing.count("kv.blocks_whole", whole)

    def _tick_lanes(self, budgets=None):
        """The lane state this launch's program takes, on the device,
        inside `tick.upload`. When the host wrote no lane and no table
        row since the last launch it is the array the last step handed
        back and nothing is sent. Otherwise (a lane seated or freed, a
        row grown or rewritten, no trusted state on the device: the
        first launch, or one that raised) the mirror goes whole, in
        ONE transfer, without waiting for any token: where its token
        column says `_KEEP` the lane keeps the token the device
        carries (one small program, run on the launches that send).
        With no trusted state there is nothing to keep: whatever was
        launched has been committed by then (`step`), and the token
        column is rebuilt from what each seated request was last
        given. The speculative tick hands no state back and sends the
        mirror every tick, its `budgets` one more column. Counts
        `tick.transfers` (0 or 1). The state is taken: the launch puts
        back what its program returned, and one that raises leaves
        none."""
        lanes, self._lanes = self._lanes, None
        send = (lanes is None or budgets is not None
                or self._lanes_dirty or self.kv.tables_dirty)
        self._lanes_dirty = self.kv.tables_dirty = False
        if send:
            if lanes is None:
                self._last_tokens[:] = 0
                for slot, st in self._seated():
                    if self._tile > 1:
                        # what its passes revealed is lost with the
                        # device's state: the open block starts anew
                        self._open_block(slot, st)
                    else:
                        self._last_tokens[slot] = st.request.generated[-1]
            sent = jax.device_put(np.column_stack(
                [self._positions, self._last_tokens, self._seeds,
                 self._temps.view(np.int32), self.kv.tables]
                + ([self._block_tokens, self._block_reveal, self._passes]
                   if self._tile > 1 else [])
                + ([] if budgets is None else [budgets])))
            if budgets is None:
                sent = self._merge_fn(sent, sent if lanes is None else lanes)
            lanes = sent
        tracing.count("tick.transfers", int(send))
        return lanes

    def step(self):
        """One call a scheduler tick: keep one decode step in flight,
        and hand back the tokens of the oldest. With a step in flight
        (the steady state) it LAUNCHES THE NEXT and then fetches and
        commits the older, so the device starts step n+1 the moment
        step n ends, and the host's share of a tick runs while a step
        computes; with none in flight it launches, launches the next
        ahead, and collects the first. Returns [(slot, request,
        tokens, finished)] for the lanes of the step it committed —
        `tokens` is the LIST of tokens the step gave that request, the
        tail of `request.generated` (one here; the speculative step
        commits 1..k+1); `finished` lanes were freed when that step was
        launched. A lane seated since the last launch joins the next
        one, so its second token comes one call after its seating;
        sampling is keyed by seed and position, so the tokens are
        those of any other order. After a launch that raised nothing
        runs ahead of tokens the host has not seen: the step in
        flight, if any, is committed first and the next launch sends
        every lane's real token. With a draft seated the call is the
        speculative draft-verify tick instead, in line."""
        if self.draft_k:
            active = self._seated()
            return self._spec_step(active) if active else []
        if self._flights and self._lanes is None:
            out = self._collect()
            self._launch()
            return out
        if not self._flights and not self._launch():
            return []
        self._launch()
        return self._collect()

    def _launch(self):
        """Launch one vmapped decode step over the WHOLE pool for the
        lanes seated now (False: none is): block tables and positions
        enter as device arrays, each seated lane attends over its own
        table and its row scatters into its own block. Free lanes ride
        along masked (stale tokens, all-(-1) tables, out-of-bounds
        scatter ids, no expert chosen): static shape, zero recompiles.
        Nothing here
        waits for the device. Once the step is dispatched the book
        moves on with it: each lane is one position on, its token the
        device's to know (`_KEEP`), and a lane whose LAST token this
        step produces (prompt + generated + in flight reaches its
        total) is freed and its blocks released now, so the next
        launch does not carry it and the next admission can seat into
        it; its request completes when this step is committed.
        `tick.ahead` counts whether an older step's tokens were still
        unfetched."""
        active = self._seated()
        if not active:
            return False
        tile, commit_pass = self._tile, self.denoise_steps
        with tracing.phase("tick.ensure"):
            for i, _st in active:
                # the block this step writes (position = the slot's
                # pos; a block lane's B rows, at its commit pass, lie
                # in one pool block); drawn from the slot's
                # reservation, so it cannot fail
                if tile == 1 or self._passes[i] == commit_pass:
                    self.kv.ensure_blocks(i, int(self._positions[i]))
            # an extend's pop can spill under pressure: keep the
            # telemetry mirror current even on decode-only ticks
            self._sync_host_telemetry()
            self._count_paged_stream()
            if self._state_layers:
                # every lane's state is updated, a free lane's too
                tracing.count("ssm.lanes",
                              self.num_slots * self._state_layers)
                tracing.count("ssm.lanes_live",
                              len(active) * self._state_layers)
        if self._step_fn is None:
            self._step_fn = self._build_paged_step()
        with self.trainer.mesh:
            with tracing.phase("tick.upload"):
                lanes = self._tick_lanes()
            with tracing.phase("tick.dispatch"):
                tracing.count("tick.ahead", int(bool(self._flights)))
                self._lanes, tokens = self.kv.update(
                    self._step_fn, self._exec_variables, lanes
                )
                ran, commits = [], (None if tile == 1 else set())
                for slot, st in active:
                    self._last_tokens[slot] = _KEEP
                    # a block lane moves on at its commit pass alone
                    commit = tile == 1 or self._passes[slot] == commit_pass
                    if tile > 1:
                        self._passes[slot] = (
                            0 if commit else self._passes[slot] + 1)
                    if commit:
                        self._positions[slot] += tile
                        if tile > 1:
                            commits.add(slot)
                    # the first token came with the seating, one more
                    # with every step launched since (a block model's
                    # tokens all come of its commit passes)
                    last = commit and bool(
                        self._positions[slot] + (tile == 1)
                        >= st.max_total)
                    if last:
                        self._free(slot)
                        self._landing += 1
                    ran.append((slot, st, last))
                self._flights.append(_Flight(
                    tokens, ran, self.model_version, commits))
        return True

    def _collect(self):
        """Fetch and commit the oldest step in flight: the one wait
        for the device a tick has. A lane evicted since the launch (a
        deadline) is skipped; the tokens carry the version of the
        weights that made them, whatever has been loaded since. A
        fetch that raises leaves the step in flight, and raises again
        at the next call: its pool was updated in place and cannot be
        run again."""
        flight = self._flights[0]
        with tracing.phase("tick.fetch"):
            nxt = np.asarray(flight.tokens)  # the host waits here
        self._flights.popleft()
        out = []
        tile = self._tile
        # a block step's tokens: every lane's block, then every
        # position's reveal step
        n_tokens = self.num_slots * (2 * tile if tile > 1 else 1)
        with tracing.phase("tick.commit"):
            for name, n in zip(self._tick_counters, nxt[n_tokens:]):
                tracing.count(name, int(n))
            for slot, st, last in flight.ran:
                if last:
                    self._landing -= 1
                if st.evicted:
                    continue
                request = st.request
                if tile == 1:
                    tokens = [int(nxt[slot])]
                elif slot in flight.commits:
                    tokens = self._block_committed(request, nxt, slot)
                else:
                    tokens = []  # a denoising pass yields none
                request.generated.extend(tokens)
                request.model_version = flight.version
                out.append((slot, request, tokens, last))
        return out

    def _block_committed(self, request, fetched, slot):
        """The tokens a block lane's commit pass hands its request: the
        block's, less the positions the prompt gave and whatever lies
        beyond `max_new_tokens` (the last block is trimmed); each
        token's reveal step goes to `request.reveal_steps` beside
        it."""
        tile = self._tile
        block = fetched[slot * tile:(slot + 1) * tile]
        reveal = fetched[(self.num_slots + slot) * tile:
                         (self.num_slots + slot + 1) * tile]
        room = request.max_new_tokens - len(request.generated)
        new = np.flatnonzero(reveal != GIVEN)[:room]
        steps = getattr(request, "reveal_steps", None)
        if steps is not None:
            steps.extend(int(x) for x in reveal[new])
        tracing.count("diffusion.blocks_committed")
        return [int(x) for x in block[new]]

    def _spec_step(self, active):
        """One speculative tick: k drafted tokens per slot, verified
        in ONE vmapped target step, greedy-exact accept/rollback.
        Rolled-back rows are never committed to the block table
        (their scatter ids are masked out-of-bounds inside the step);
        the draft's rollback is counter-only."""
        k = self.draft_k
        budgets = np.ones(self.num_slots, np.int32)
        with tracing.phase("tick.ensure"):
            for i, st in active:
                pos = int(self._positions[i])
                # materialize every block this tick MIGHT write (rows
                # pos..pos+k, capped at the slot's last needed row) —
                # reservation-backed, cannot fail for a seated request
                self.kv.ensure_blocks(i, min(pos + k, st.max_total - 2))
                budgets[i] = st.max_total - (
                    len(st.request.prompt) + len(st.request.generated)
                )
            self._sync_host_telemetry()  # ensure_blocks pops can spill
            self._count_paged_stream()
        if self._spec_fn is None:
            self._spec_fn = self._build_spec_step()
        with self.trainer.mesh:
            with tracing.phase("tick.upload"):
                # positions advance by what the tick accepts, which
                # this program does not carry on the device (D14): it
                # hands no state back, so every tick sends the mirror
                lanes = self._tick_lanes(budgets)
            with tracing.phase("tick.dispatch"):
                self._d_pool, toks, counts = self.kv.update(
                    self._spec_fn, self._d_pool,
                    self._exec_variables, self._d_variables, lanes
                )
            with tracing.phase("tick.fetch"):
                toks = np.asarray(toks)
                counts = np.asarray(counts)
        out = []
        accepted = 0
        with tracing.phase("tick.commit"):
            for slot, st in active:
                c = int(counts[slot])
                committed = [int(x) for x in toks[slot, :c]]
                st.request.generated.extend(committed)
                st.request.model_version = self.model_version
                self._positions[slot] += c
                self._last_tokens[slot] = committed[-1]
                accepted += c - 1
                finished = (
                    len(st.request.prompt) + len(st.request.generated)
                    >= st.max_total
                )
                if finished:
                    self._free(slot)
                out.append((slot, st.request, committed, finished))
            self.draft_proposed += k * len(active)
            self.draft_accepted += accepted
            if self.telemetry is not None:
                self.telemetry.count("draft_proposed", k * len(active))
                if accepted:
                    self.telemetry.count("draft_accepted", accepted)
        return out

    # ------------------------------------------------------- compiled fns

    def _weight_programs(self, variables, d_variables):
        """EVERY program that takes a weight tree, as narrowing_casts
        traces it: [(program, its arguments over shapes, (where the
        target's tree is among them, where the draft's; None: not
        taken))]. The model's prompt prefill and its decode tile (the
        suffix / chunked-prefill program), each at its smallest
        bucket — a bucket changes a program's widths, not what it
        does with a weight — and the tick's own program: the paged
        step or, with a draft seated (`d_variables`), the speculative
        step and the draft's prefill. A model may do with a weight in
        a one-token call what it does in no other, so no program
        stands in for another; a start with the decision remembered
        traces none of them."""
        spec = jax.ShapeDtypeStruct
        i32, f32 = spec((), jnp.int32), spec((), jnp.float32)
        tile = self._suffix_bucket(1)
        prompt = spec((1, self.seq_len), jnp.int32)
        lanes = self._lanes_spec()
        programs = [
            (self._prefill_program(_prefill_bucket(1, self.seq_len)),
             (variables, prompt, i32, i32, f32), (0, None)),
        ]
        if not self._state_layers:  # else no decode tile ever runs
            programs.append(
                (self._suffix_prefill_program(tile),
                 (self.kv.pools, variables,
                  spec(self.kv.tables.shape[1:], jnp.int32),
                  spec((1, tile), jnp.int32), i32, i32, i32, f32),
                 (1, None)))
        if d_variables is None:
            return programs + [
                (self._paged_step_program(),
                 (self.kv.pools, variables, lanes), (1, None))]
        return programs + [
            (self._spec_step_program(),
             (self.kv.pools, self._d_pool, variables, d_variables,
              self._lanes_spec(budgets=True)), (2, 3)),
            (self._draft_prefill_program(
                _prefill_bucket(1, self.seq_len)),
             (d_variables, prompt, i32), (None, 0)),
        ]

    def _lanes_spec(self, budgets=False):
        """The shape of what _tick_lanes hands a tick's program, for
        who traces one without running it."""
        width = (_LANE_TABLE + self.kv.tables.shape[1]
                 + _block_columns(self._tile) + bool(budgets))
        return jax.ShapeDtypeStruct((self.num_slots, width), jnp.int32)

    def _tjit(self, name, fn, **jit_kwargs):
        """jax.jit with recompile-sentry adoption: one fixed NAME per
        call site (buckets included), so a second compile of any name
        is, by construction, the churn-recompiles failure the sentry
        exists to catch."""
        return tracked_jit(fn, name, lambda: self.sentry, **jit_kwargs)

    def _prefill_program(self, p_pad):
        model, kv_shapes = self.model, self._kv_shapes
        top_k, top_p, qz = self.top_k, self.top_p, self._exec_qz

        def prefill(variables, buf, p_len, seed, temperature):
            variables = _maybe_dequantize(variables, qz)
            kv, last = _run_prefill(
                model, variables, kv_shapes, buf, p_len, p_pad
            )
            first = serving_next_token(
                last[0], seed, p_len, temperature, top_k, top_p
            )
            return kv, first

        return prefill

    def _build_prefill(self, p_pad):
        logger.info("serving: compiling prefill for bucket %d", p_pad)
        return self._tjit("prefill[%d]" % p_pad,
                          self._prefill_program(p_pad))

    def _paged_step_program(self):
        from elasticdl_tpu.serving.kv_pool import scatter_rows

        model = self.model
        top_k, top_p, qz = self.top_k, self.top_p, self._exec_qz
        block_size, num_blocks = self.block_size, self.num_blocks
        tick_counters = self._tick_counters  # named when traced
        state_paths = self._state_paths
        state_at = [i for i, kind in enumerate(self.kv.kinds)
                    if kind == STATE]
        # the tile: 1, or a block-diffusion model's block (BLOCK TICK)
        tile, n_passes = self._tile, self.denoise_steps
        mask_token = self._mask_token
        max_blocks = self.kv.max_blocks_per_slot
        # the pool's block classes (one, but for a model whose layers
        # differ in window served without sharing): each class's first
        # column of the tables and its arenas' blocks, and which class
        # a leaf of the pool is of
        table_of, leaf_class = self.kv.table_of, self.kv.leaf_class
        classes = [(c * max_blocks, alloc.num_blocks)
                   for c, alloc in enumerate(self.kv.allocators)]

        def step(pools, variables, lanes):
            variables = _maybe_dequantize(variables, qz)
            tables, positions, last_tokens, seeds, temps = lane_fields(
                lanes, tile)
            if tile > 1:
                # a block lane reads its open block, the mask token
                # wherever nothing is revealed yet; its pass rides
                # beside the tokens and says whether it is seated
                block, reveal, passes = block_fields(lanes, tile)
                last_tokens = jnp.concatenate(
                    [jnp.where(reveal == MASKED, mask_token, block),
                     passes[:, None]], axis=1)
            # the per-slot state arenas ride the lanes' axis: lane i
            # reads and writes slot i (none for a model without state
            # layers)
            flat, treedef = jax.tree.flatten(pools)
            states = [flat[i] for i in state_at]

            def one(table, pos, tok, seed, temp, state):
                # pre-advance counter: this token's k/v rows belong
                # at `pos`, the sampled token lands at pos + 1 (the
                # offline loop's `_next_token(..., i + 1)`). The cache
                # collection carries
                # ONLY the counter — the rows live in the shared
                # arenas, read through this slot's table and written
                # back via the sown "kv_out" rows. The scope is the
                # per-slot body's op_name in a device trace.
                cache = {"pos": pos}
                for path, leaf in zip(state_paths, state):
                    _set_path(cache, path, leaf[None])  # a batch of one
                if tile == 1:
                    # a lane at position 0 (free, or its prompt still
                    # being written) carries no sequence
                    tokens, live = tok[None, None], (pos > 0)[None]
                else:
                    # a free block lane has no pass (its first block
                    # may start at position 0)
                    tokens, live = tok[None, :tile], (tok[tile] >= 0)[None]
                with jax.named_scope("paged_slot"):
                    logits, aux = model.apply(
                        dict(variables, cache=cache),
                        {"tokens": tokens},
                        training=False, decode=True,
                        mutable=["cache", "kv_out", "counters"],
                        paged=dict(
                            {"pools": pools, "table": table[None],
                             "live": live},
                            **({"table_of": table_of} if table_of else {})),
                    )
                if tile == 1:
                    nxt = serving_next_token(
                        logits[0, 0], seed, pos + 1, temp, top_k, top_p
                    )
                    rows = jax.tree.map(
                        lambda t: t[0][0, :, 0, :], aux.get("kv_out", {}),
                        is_leaf=lambda x: isinstance(x, tuple),
                    )  # sown [1, hkv, 1, d] -> [hkv, d]
                else:
                    # each position's greedy token and its softmax
                    # probability, from the logits AT that position
                    z = logits[0] - jnp.max(logits[0], -1, keepdims=True)
                    nxt = (jnp.argmax(logits[0], -1).astype(jnp.int32),
                           1.0 / jnp.sum(jnp.exp(z), -1))
                    rows = jax.tree.map(
                        lambda t: t[0][0].transpose(1, 0, 2),
                        aux.get("kv_out", {}),
                        is_leaf=lambda x: isinstance(x, tuple),
                    )  # sown [1, hkv, B, d] -> [B, hkv, d]
                state = [_at_path(aux["cache"], path)[0]
                         for path in state_paths]
                return nxt, rows, aux.get("counters", {}), state

            nxt, rows, sown, states = jax.vmap(one)(
                tables, positions, last_tokens, seeds, temps, states
            )
            for i, leaf in zip(state_at, states):
                flat[i] = leaf
            pools = jax.tree.unflatten(treedef, flat)
            # what the model's layers counted this tick rides home
            # behind the tokens, in the one array the host fetches
            counted = _tick_counts(sown)
            if tile > 1:
                greedy, prob = nxt
                seated = passes >= 0
                denoise = seated & (passes < n_passes)
                commit = seated & (passes == n_passes)
                # a denoising pass reveals its B / S most confident
                # masked positions; a revealed token never changes
                now = denoise[:, None] & _reveal_by_confidence(
                    prob, reveal == MASKED, tile // n_passes)
                block = jnp.where(now, greedy, block)
                reveal = jnp.where(now, passes[:, None], reveal)
                counted.update({
                    "diffusion.lane_passes": jnp.sum(seated),
                    "diffusion.commit_passes": jnp.sum(commit),
                    "diffusion.tokens_revealed": jnp.sum(now),
                })
                tick_counters[:] = sorted(counted)
                # what the host fetches: every lane's block and reveal
                # steps (read at a lane's commit pass, when they are
                # final), then the counters
                fetched = jnp.concatenate(
                    [block.reshape(-1), reveal.reshape(-1)]
                    + [counted[name][None] for name in tick_counters]
                ).astype(jnp.int32)
                # the commit pass writes the block's rows and moves
                # the lane on, its next block all masks
                at = lanes.shape[1] - _block_columns(tile)
                lanes = lanes.at[:, _LANE_POS].add(
                    jnp.where(commit, tile, 0)
                ).at[:, at:].set(jnp.concatenate([
                    jnp.where(commit[:, None], mask_token, block),
                    jnp.where(commit[:, None], MASKED, reveal),
                    jnp.where(commit, 0, passes + denoise)[:, None],
                ], axis=1))
                wpos = positions[:, None] + jnp.arange(tile)[None, :]
                bids = jnp.take_along_axis(
                    tables, jnp.minimum(wpos // block_size, max_blocks - 1),
                    axis=1)
                bids = jnp.where(commit[:, None] & (bids >= 0), bids,
                                 num_blocks)
                pools = scatter_rows(pools, rows, bids, wpos % block_size)
                return pools, (lanes, fetched)
            tick_counters[:] = sorted(counted)
            # the state the next tick starts from: a seated lane is
            # one position on and its last token is the one just
            # sampled; a lane at position 0 is free (or its prompt is
            # still being written) and stays as it is
            seated = positions > 0
            lanes = lanes.at[:, _LANE_POS].add(
                seated.astype(jnp.int32)
            ).at[:, _LANE_TOKEN].set(
                jnp.where(seated, nxt.astype(jnp.int32), last_tokens))
            nxt = jnp.concatenate(
                [nxt] + [counted[name][None] for name in tick_counters]
            ).astype(jnp.int32)
            if table_of is None:
                bids = jnp.take_along_axis(
                    tables, (positions // block_size)[:, None], axis=1
                )[:, 0]
                # free lanes (table row -1), and a lane whose prompt is
                # still being written tile by tile (its row is there,
                # its position 0): point past the arena so the
                # scatter's mode="drop" discards them
                bids = jnp.where(seated & (bids >= 0), bids, num_blocks)
                pools = scatter_rows(pools, rows, bids,
                                     positions % block_size)
                return pools, (lanes, nxt)
            # the same a class: its own table columns, its own arenas
            bids = [
                jnp.take_along_axis(
                    tables, (at + positions // block_size)[:, None],
                    axis=1)[:, 0] for at, _blocks in classes]
            bids = [jnp.where(seated & (b >= 0), b, blocks)
                    for b, (_at, blocks) in zip(bids, classes)]
            pools = scatter_rows(pools, rows, bids,
                                 positions % block_size, leaf_class)
            return pools, (lanes, nxt)

        return step

    def _build_paged_step(self):
        logger.info(
            "serving: compiling paged decode step for %d slots over "
            "%d x %d-token blocks", self.num_slots, self.num_blocks,
            self.block_size,
        )
        # with it the small program of the launches that send the
        # mirror: every such launch runs it, the first one too, so it
        # is compiled with the step and never later
        self._merge_fn = self._tjit(
            "merge_lanes", _merge_lanes if self._tile == 1
            else functools.partial(_merge_lanes, tile=self._tile))
        return self._tjit("paged_step", self._paged_step_program(),
                          donate_argnums=(0,))

    def _suffix_prefill_program(self, t_pad):
        """The shared-prefix suffix prefill: decode a tile of up
        to `t_pad` prompt tokens at positions [start, start + t) over
        the resident prefix blocks, scatter the tile's rows into the
        slot's blocks (pad rows dropped via out-of-bounds ids), and
        sample the first generated token from the last REAL row's
        logits. One executable per tile bucket."""
        from elasticdl_tpu.serving.kv_pool import scatter_rows

        model = self.model
        top_k, top_p, qz = self.top_k, self.top_p, self._exec_qz
        block_size, num_blocks = self.block_size, self.num_blocks
        max_blocks = self.kv.max_blocks_per_slot

        def fn(pools, variables, table, chunk, start, t_real, seed,
               temp):
            variables = _maybe_dequantize(variables, qz)
            logits, aux = model.apply(
                dict(variables, cache={"pos": start}),
                {"tokens": chunk},
                training=False, decode=True,
                mutable=["cache", "kv_out"],
                paged={"pools": pools, "table": table[None]},
            )  # logits [1, t_pad, V]
            rows = jax.tree.map(
                lambda s: s[0][0].transpose(1, 0, 2), aux["kv_out"],
                is_leaf=lambda x: isinstance(x, tuple),
            )  # sown [1, hkv, t_pad, d] -> [t_pad, hkv, d]
            pos = start + jnp.arange(t_pad)
            bids = jnp.take(
                table, jnp.minimum(pos // block_size, max_blocks - 1)
            )
            keep = (jnp.arange(t_pad) < t_real) & (bids >= 0)
            bids = jnp.where(keep, bids, num_blocks)
            pools = scatter_rows(pools, rows, bids, pos % block_size)
            step_logits = jnp.take(logits[0], t_real - 1, axis=0)
            first = serving_next_token(
                step_logits, seed, start + t_real, temp, top_k, top_p
            )
            return pools, first

        return fn

    def _build_suffix_prefill(self, t_pad):
        logger.info(
            "serving: compiling shared-prefix suffix prefill for "
            "tile %d", t_pad,
        )
        return self._tjit("suffix_prefill[%d]" % t_pad,
                          self._suffix_prefill_program(t_pad),
                          donate_argnums=(0,))

    def _draft_prefill_program(self, p_pad):
        d_model, d_kv_shapes = self._d_model, self._d_kv_shapes

        def prefill(d_variables, buf, p_len):
            kv, _last = _run_prefill(
                d_model, d_variables, d_kv_shapes, buf, p_len, p_pad
            )
            return kv

        return prefill

    def _build_draft_prefill(self, p_pad):
        logger.info(
            "serving: compiling draft prefill for bucket %d", p_pad
        )
        return self._tjit("draft_prefill[%d]" % p_pad,
                          self._draft_prefill_program(p_pad))

    def _write_draft_slot(self, kv, slot):
        from elasticdl_tpu.serving.kv_pool import run_inplace

        if self._d_write_fn is None:
            def write(pool, kv, idx):
                def upd(p, n):
                    start = (idx,) + (0,) * n.ndim
                    return jax.lax.dynamic_update_slice(
                        p, n[None], start
                    )

                return jax.tree.map(upd, pool, kv)

            self._d_write_fn = self._tjit("draft_slot_write", write,
                                          donate_argnums=(0,))
        self._d_pool = run_inplace(
            self._d_write_fn, self._d_pool, kv,
            jnp.asarray(slot, jnp.int32),
        )

    def _spec_step_program(self):
        """The speculative tick as ONE program: k vmapped
        draft steps (a lax.scan of single-token greedy proposals),
        then the target verifying the whole [last, d_1..d_k] tile in
        one vmapped (k+1)-wide paged decode. Acceptance is the longest
        greedy-matching proposal prefix (0 for sampled slots, whose
        committed token is exactly the plain step's sample); commit
        c = min(accepted + 1, remaining budget) tokens — row scatters
        for j >= c are masked to out-of-bounds ids, so rolled-back
        rows never reach the block table, and the draft rolls back by
        counter only (its pos is forced from `positions` each tick)."""
        from elasticdl_tpu.serving.kv_pool import scatter_rows

        model, d_model = self.model, self._d_model
        top_k, top_p, qz = self.top_k, self.top_p, self._exec_qz
        block_size, num_blocks = self.block_size, self.num_blocks
        max_blocks = self.kv.max_blocks_per_slot
        k = self.draft_k

        def step(pools, d_pool, variables, d_variables, lanes):
            variables = _maybe_dequantize(variables, qz)
            # the mirror as sent this tick, the budgets its last column
            budgets = lanes[:, -1]
            tables, positions, last_tokens, seeds, temps = lane_fields(
                lanes[:, :-1])
            # force the draft counters to the committed truth — the
            # rollback contract: rows past the counter are masked junk
            d_pool_f = dict(d_pool, pos=positions)

            def d_one(cache, tok):
                lg, upd = d_model.apply(
                    dict(d_variables, cache=cache),
                    {"tokens": tok[None, None]},
                    training=False, decode=True, mutable=["cache"],
                )
                nxt = jnp.argmax(lg[0, 0], axis=-1).astype(jnp.int32)
                return upd["cache"], nxt

            def d_scan(carry, _):
                cache, tok = carry
                cache, nxt = jax.vmap(d_one)(cache, tok)
                return (cache, nxt), nxt

            (d_pool_out, _), d_seq = jax.lax.scan(
                d_scan, (d_pool_f, last_tokens), None, length=k
            )
            d_toks = jnp.moveaxis(d_seq, 0, 1)  # [S, k]
            chunk = jnp.concatenate(
                [last_tokens[:, None], d_toks], axis=1
            )  # [S, k+1]; row j = the token at stream position pos+j

            def v_one(table, pos, toks):
                with jax.named_scope("paged_slot"):
                    logits, aux = model.apply(
                        dict(variables, cache={"pos": pos}),
                        {"tokens": toks[None]},
                        training=False, decode=True,
                        mutable=["cache", "kv_out"],
                        paged={"pools": pools, "table": table[None],
                               "live": (pos > 0)[None]},
                    )  # logits [1, k+1, V]: row j predicts pos + j + 1
                g = jnp.argmax(logits[0], axis=-1).astype(jnp.int32)
                rows = jax.tree.map(
                    lambda s: s[0][0].transpose(1, 0, 2),
                    aux["kv_out"],
                    is_leaf=lambda x: isinstance(x, tuple),
                )  # [k+1, hkv, d]
                return logits[0], g, rows

            logits, g, rows = jax.vmap(v_one)(tables, positions, chunk)
            # longest greedy-matching proposal prefix, per slot;
            # sampled slots accept nothing (their committed token is
            # the sampled one below — exactly the plain step's)
            match = jnp.cumprod(
                (d_toks == g[:, :k]).astype(jnp.int32), axis=1
            )
            a = jnp.where(temps > 0.0, 0, match.sum(axis=1))  # [S]
            c = jnp.minimum(a + 1, jnp.maximum(budgets, 1))
            # committed token j < a: the greedy target (== proposal);
            # j == a: the correction/bonus, sampled exactly like the
            # plain step at position pos + 1 + a
            def pick(lg, aa, seed, pos, temp):
                return serving_next_token(
                    lg[aa], seed, pos + 1 + aa, temp, top_k, top_p
                )

            bonus = jax.vmap(pick)(logits, a, seeds, positions, temps)
            out_toks = jnp.where(
                jnp.arange(k + 1)[None, :] == a[:, None],
                bonus[:, None], g,
            )  # [S, k+1]; entries past c-1 are dead
            # scatter ONLY the committed rows j < c (free lanes carry
            # -1 tables; both mask to the out-of-bounds drop id)
            wpos = positions[:, None] + jnp.arange(k + 1)[None, :]
            bids = jnp.take_along_axis(
                tables, jnp.minimum(wpos // block_size, max_blocks - 1),
                axis=1,
            )
            # (nor a lane at position 0, whose prompt is still being
            # written tile by tile: its row is there, it decodes nothing)
            keep = ((jnp.arange(k + 1)[None, :] < c[:, None])
                    & (bids >= 0) & (positions[:, None] > 0))
            bids = jnp.where(keep, bids, num_blocks)
            pools = scatter_rows(pools, rows, bids, wpos % block_size)
            return pools, (d_pool_out, out_toks, c)

        return step

    def _build_spec_step(self):
        logger.info(
            "serving: compiling speculative draft-verify step "
            "(k=%d) for %d slots", self.draft_k, self.num_slots,
        )
        return self._tjit("spec_step", self._spec_step_program(),
                          donate_argnums=(0, 1))
