"""Batched serving engine: device-resident continuous batching.

The privacy story of the paper means the *client* runs inference; this engine
is the server-side counterpart used for (a) the e2e batched-serving example
mandated for a serving-kind paper, and (b) the throughput benchmark behind
the paper's claim that eq. 1 trajectory generation is fast enough for
interactive risk prediction.

Design — one jitted ``decode_and_sample`` step per engine tick:

* the batched ``decode_step`` runs across **all slots at once** with per-slot
  absolute positions (vector ``step`` plumbing in ``repro.models``), instead
  of a ``vmap`` of single-slot decodes;
* eq. 1 competing-exponential sampling happens **in-graph** right after the
  logits head — ``sample_next_event`` (jnp reference, default) or the fused
  Pallas kernel ``repro.kernels.tte_sample`` (``sampler="pallas"``).  Generic
  LMs sample the Gumbel-max categorical from the same uniforms;
* per-slot age / step / emitted-count / active state advances as device
  arrays inside the tick (``advance_trajectory_state`` — the same censoring
  semantics as the SDK: an event past ``max_age`` terminates BEFORE being
  emitted); the host sees exactly ONE packed (4, slots) transfer per tick;
* admissions run a **bucketed-padding batched prefill**: prompt lengths are
  right-padded to power-of-two buckets and admission groups to power-of-two
  batch buckets, so a request stream compiles a small fixed set of
  (batch, seq) shapes instead of one jit per prompt length.  Padded cache
  positions are invalidated (``pos = -1``) so decode never attends garbage;
  bootstrap logits are gathered at each prompt's true last token
  (``forward(..., last_index=...)``).

Each phase of a step runs inside a span (``repro.serve.spans``): a
``jax.profiler`` annotation on the device's clock plus a per-name aggregate
that ``health_stats()`` reports beside the engine's counters.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import threading
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import base as cb
from repro.configs.base import ModelConfig
from repro.core.sampler import advance_trajectory_state, sample_next_event
from repro.kernels import tte_sample
from repro.models import attention as attn_lib
from repro.models import (decode_step, forward, forward_suffix,
                          make_decode_cache, make_paged_decode_cache,
                          mask_padded_positions)
from repro.serve.prefix import PrefixIndex, SharedBlockPool
from repro.serve.spans import Spans

# Module-level so tests can monkeypatch/count device->host transfers: this is
# the ONLY way the engine moves data off-device.
_to_host = np.asarray


@dataclasses.dataclass(eq=False)        # identity, not ndarray comparison
class Request:
    tokens: np.ndarray                  # (S,) prompt
    ages: Optional[np.ndarray] = None   # (S,) for Delphi-style models
    max_new: int = 64
    # optional pre-drawn U(0,1) of shape (max_new, V): injected for
    # SDK/engine bit-parity tests (claims C2/C3).  Row i is consumed by the
    # i-th sampled event (row 0 at admission, from the prefill logits).
    uniforms: Optional[np.ndarray] = None
    # streaming hooks (repro.api.EngineBackend.stream): invoked on the host
    # side of the tick sync — on_event(token, age_or_None) per emitted event,
    # on_done(request) once at termination
    on_event: Optional[Callable[[int, Optional[float]], None]] = None
    on_done: Optional[Callable[["Request"], None]] = None
    # address for Engine.cancel / the wire's POST /v1/cancel; autogenerated
    # at submit when unset
    request_id: Optional[str] = None
    # prefill-only parking: the request is admitted (prompt KV inserted,
    # bootstrap logits stashed) but samples nothing and holds its slot until
    # Engine.fork clones it into N decode slots — the Monte-Carlo futures
    # parent.  Held slots never decode; their otherwise-unavoidable batched
    # tick write is redirected to the trash block (paged) or masked out of
    # the fork copy (ring), so shared prefix blocks stay immutable.
    hold: bool = False
    # filled by the engine:
    out_tokens: Optional[List[int]] = None
    out_ages: Optional[List[float]] = None
    done: bool = False
    # set (before on_done fires) if the engine loop failed this request —
    # waiters must check it rather than trusting out_tokens
    error: Optional[BaseException] = None
    # time.monotonic() at submit (or fork), first admission to a slot, first
    # emitted event and termination; None until the engine gets there
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    # engine bookkeeping: admission order (pool-exhaustion preemption picks
    # the youngest) and the wall-clock deadline stamped at submit when the
    # engine enforces request timeouts
    _seq: int = dataclasses.field(default=0, repr=False)
    _deadline: Optional[float] = dataclasses.field(default=None, repr=False)
    # memoized prefix-index digests of the effective prompt, keyed by its
    # length (recomputed after a preemption resume appends emitted events):
    # admission probes hash-match repeatedly under the engine lock and a
    # long history re-hash per probe would serialize submitters
    _pfx: Optional[tuple] = dataclasses.field(default=None, repr=False)


@dataclasses.dataclass(eq=False)
class _PrefillProgress:
    """Host-side state of one chunked prefill in progress.

    While a slot appears here its device table row stays fully masked (-1):
    the batched tick keeps writing the slot's discarded token to the trash
    block and nothing can read half-written prompt blocks.  ``cursor``
    counts prompt tokens already in the cache — it starts at the matched
    prefix boundary (full blocks lent by the prefix index), so only the
    unmatched suffix is ever recomputed."""
    req: Request
    tokens: np.ndarray                  # effective prompt (history + resumed)
    ages: Optional[np.ndarray]
    max_new: int                        # remaining budget after a resume
    age0: float
    S: int                              # effective prompt length
    cursor: int                         # tokens already written (block-aligned
                                        # except when == S)


class BlockAllocator:
    """Host-side free list over the paged KV block pool.

    Block 0 is reserved as the **trash block** — the destination for writes
    of slots with no allocated block (idle engine rows); allocatable ids are
    ``1 .. num_blocks - 1``.  ``peak_used`` is the watermark the serve
    benchmark reports; ``used`` must return to 0 whenever the engine drains
    — the zero-leak invariant ``scripts/paged_parity.py`` asserts after its
    cancel/preempt/timeout storm.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("paged pool needs >= 2 blocks "
                             "(block 0 is the reserved trash block)")
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, 0, -1))
        self.peak_used = 0

    @property
    def capacity(self) -> int:
        return self.num_blocks - 1

    @property
    def free(self) -> int:
        return len(self._free)

    @property
    def used(self) -> int:
        return self.capacity - len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n block ids, or None (never partial) when the pool can't serve."""
        if n > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(n)]
        self.peak_used = max(self.peak_used, self.used)
        return ids

    def release(self, ids: List[int]) -> None:
        for i in ids:
            if not 0 < i < self.num_blocks:
                raise ValueError(f"release of invalid block id {i}")
        self._free.extend(ids)
        if len(self._free) > self.capacity:
            raise RuntimeError("double free in the paged block allocator")


def _current_device() -> jax.Device:
    """The device ``jax.default_device`` names in this thread, else the
    first device."""
    d = jax.config.jax_default_device
    if isinstance(d, str):
        return jax.devices(d)[0]
    return d if d is not None else jax.devices()[0]


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 1).bit_length() if n > 1 else 1


class _Knobs(NamedTuple):
    """Hashable static engine parameters for the shared module-level jits.

    The jitted tick/prefill functions live at module level with
    ``(cfg, knobs)`` as static arguments, so every engine instance with the
    same configuration shares ONE compiled executable per shape — a second
    engine (or a restarted serving process within one interpreter) pays no
    recompilation."""
    slots: int
    max_context: int
    is_delphi: bool
    use_pallas: bool
    inv_temp: float
    max_age: float
    death_token: int
    vocab: int


def _sample_evt(lg, u, kn: _Knobs):
    """(B, V) logits + uniforms -> (event (B,), waiting time (B,))."""
    if kn.is_delphi:
        if kn.use_pallas:
            return tte_sample(lg, u)
        return sample_next_event(lg, u)
    g = -jnp.log(-jnp.log(jnp.clip(u, 1e-12, 1.0 - 1e-12)))
    evt = jnp.argmax(lg * kn.inv_temp + g, axis=-1).astype(jnp.int32)
    return evt, jnp.zeros(evt.shape, jnp.float32)


@jax.named_scope("sample")
def _advance(lg, u, age, n_emitted, max_new, next_pos, active, kn: _Knobs):
    evt, tmin = _sample_evt(lg, u, kn)
    return advance_trajectory_state(
        evt, tmin, age, n_emitted, max_new, next_pos, active,
        max_age=kn.max_age if kn.is_delphi else np.inf,
        death_token=kn.death_token if kn.is_delphi else -1,
        max_context=kn.max_context)


def _pack(adv):
    return jnp.stack([adv["evt"].astype(jnp.float32), adv["age"],
                      adv["emit"].astype(jnp.float32),
                      adv["finished"].astype(jnp.float32)])


#: MoE dispatch (``repro.models.moe``) per program: grouped matmuls for the
#: prefills, whose thousands of tokens spread over every expert, and for the
#: decode tick the scan over held experts, which the chip ran faster at 32
#: slots (106.0 against 109.4 ms a Moonlight tick on a TPU v5e; PERF.md)
_MOE_PREFILL = "ragged"
_MOE_DECODE = "dense_scan"
#: ring rows (batch bucket x max_context) one admission prefill may return:
#: a group that would pass it splits, which bounds the prefill's cache rows
#: and its activations
PREFILL_ROWS = 16384


def _tick_core(params, cache, state, u, cfg: ModelConfig, kn: _Knobs):
    """Decode every slot one event, sample, advance: -> (cache, state,
    packed host columns, the (slots, V) float32 logits sampled from)."""
    batch = {"tokens": state["last"][:, None]}
    if cfg.age_encoding:
        batch["ages"] = state["age"][:, None]
    d = decode_step(params, cfg, cache, batch, state["step"],
                    moe_impl=_MOE_DECODE)
    lg = d["logits"][:, 0].astype(jnp.float32)
    next_step = jnp.where(state["active"], state["step"] + 1, state["step"])
    adv = _advance(lg, u, state["age"], state["n_emitted"], state["max_new"],
                   next_step, state["active"], kn)
    new_state = {
        "last": jnp.where(adv["emit"], adv["evt"], state["last"]),
        "age": adv["age"],
        "step": next_step,
        "n_emitted": adv["n_emitted"],
        "max_new": state["max_new"],
        "active": state["active"] & ~adv["finished"],
    }
    packed = _pack(adv)
    if cfg.arch_type == cb.MOE:
        # a fifth row, in the same fetch: each active slot's assignments
        # to the held experts, summed over MoE layers
        held = jnp.where(state["active"], d["expert_tokens"], 0)
        packed = jnp.concatenate([packed, held.astype(jnp.float32)[None]])
    return d["cache"], new_state, packed, lg


@functools.partial(jax.jit, static_argnames=("cfg", "kn"),
                   donate_argnums=(1, 2))
def _tick_u_jit(params, cache, state, u, *, cfg, kn):
    return _tick_core(params, cache, state, u, cfg, kn)


@functools.partial(jax.jit, static_argnames=("cfg", "kn"),
                   donate_argnums=(1, 2))
def _tick_rng_jit(params, cache, state, key, *, cfg, kn):
    key, sub = jax.random.split(key)
    u = jax.random.uniform(sub, (kn.slots, kn.vocab))
    return (*_tick_core(params, cache, state, u, cfg, kn), key)


@jax.named_scope("prefill")
def _prefill_core(params, tokens, ages, last_idx, age0, lengths, max_new, u,
                  cfg: ModelConfig, kn: _Knobs):
    batch: Dict[str, Any] = {"tokens": tokens}
    if cfg.age_encoding:
        batch["ages"] = ages
    out = forward(params, cfg, batch, mode="prefill",
                  cache_width=kn.max_context, last_index=last_idx,
                  moe_impl=_MOE_PREFILL)
    cache_rows = mask_padded_positions(out["cache"], last_idx)
    lg = out["logits"][:, 0].astype(jnp.float32)
    nb = tokens.shape[0]
    active = jnp.ones((nb,), bool)
    adv = _advance(lg, u, age0, jnp.zeros((nb,), jnp.int32), max_new,
                   lengths, active, kn)
    rows = {
        "last": jnp.where(adv["emit"], adv["evt"], 0),
        "age": adv["age"],
        "step": lengths,
        "n_emitted": adv["n_emitted"],
        "max_new": max_new,
        "active": active & ~adv["finished"],
    }
    # lg rides along so hold admissions can stash per-prompt bootstrap
    # logits (fork / prefix-cache admission samples event 0 from them)
    return cache_rows, rows, _pack(adv), lg


@functools.partial(jax.jit, static_argnames=("cfg", "kn"))
def _prefill_u_jit(params, tokens, ages, last_idx, age0, lengths, max_new, u,
                   *, cfg, kn):
    return _prefill_core(params, tokens, ages, last_idx, age0, lengths,
                         max_new, u, cfg, kn)


@functools.partial(jax.jit, static_argnames=("cfg", "kn"))
def _prefill_rng_jit(params, tokens, ages, last_idx, age0, lengths, max_new,
                     key, *, cfg, kn):
    key, sub = jax.random.split(key)
    u = jax.random.uniform(sub, (tokens.shape[0], kn.vocab))
    cache_rows, rows, packed, lg = _prefill_core(
        params, tokens, ages, last_idx, age0, lengths, max_new, u, cfg, kn)
    return cache_rows, rows, packed, lg, key


def _prefill_inputs(prompts, nb: int, sb: int):
    """Right-padded (nb, sb) prefill arrays for ``prompts`` — a list of
    (tokens, ages or None, events still to sample) — as (tokens, ages,
    last_idx, age0, lengths, max_new).  Batch rows past the prompts clone
    row 0: computed and discarded."""
    tokens = np.zeros((nb, sb), np.int32)
    ages = np.zeros((nb, sb), np.float32)
    age0 = np.zeros((nb,), np.float32)
    lengths = np.full((nb,), len(prompts[0][0]), np.int32)
    max_new = np.full((nb,), 1, np.int32)
    for j, (toks, ags, remaining) in enumerate(prompts):
        S = len(toks)
        tokens[j, :S] = toks
        if ags is not None:
            ages[j, :S] = ags
            ages[j, S:] = ags[-1]
            age0[j] = np.float32(ags[-1])
        lengths[j] = S
        max_new[j] = remaining
    tokens[len(prompts):] = tokens[0]
    ages[len(prompts):] = ages[0]
    return tokens, ages, lengths - 1, age0, lengths, max_new


def _fork_rows_core(lg, u, age0, lengths, max_new, kn: _Knobs):
    """Bootstrap slot-state rows for forked futures: sample each child's
    FIRST event from the shared parent's prefill logits — the exact tail of
    ``_prefill_core`` with the forward pass factored out."""
    nb = u.shape[0]
    active = jnp.ones((nb,), bool)
    adv = _advance(lg, u, age0, jnp.zeros((nb,), jnp.int32), max_new,
                   lengths, active, kn)
    rows = {
        "last": jnp.where(adv["emit"], adv["evt"], 0),
        "age": adv["age"],
        "step": lengths,
        "n_emitted": adv["n_emitted"],
        "max_new": max_new,
        "active": active & ~adv["finished"],
    }
    return rows, _pack(adv)


@functools.partial(jax.jit, static_argnames=("kn",))
def _fork_rows_jit(lg, u, age0, lengths, max_new, *, kn):
    return _fork_rows_core(lg, u, age0, lengths, max_new, kn)


@functools.partial(jax.jit, static_argnames=("kn",))
def _fork_rows_rng_jit(lg, key, age0, lengths, max_new, *, kn):
    key, sub = jax.random.split(key)
    u = jax.random.uniform(sub, lg.shape)
    rows, packed = _fork_rows_core(lg, u, age0, lengths, max_new, kn)
    return rows, packed, key


@functools.partial(jax.jit, donate_argnums=(0,))
def _fork_copy_rows_jit(cache, src, dst, max_pos):
    """Ring fork: replicate the parent slot's cache row into child slots.

    Positions past ``max_pos`` (the prompt's last index) are invalidated in
    the copies — a held parent's parked ticks scribble a harmless garbage
    token at its next write position, which must not surface as valid
    context in a child.  ``dst`` is padded with ``src`` (self-copy) to keep
    the shape static."""
    def cp(buf):
        row = buf[:, src]
        if buf.ndim == 3:               # pos plane (L, B, W)
            row = jnp.where((row >= 0) & (row <= max_pos), row, -1)
        tiled = jnp.broadcast_to(row[:, None],
                                 (buf.shape[0], dst.shape[0]) + row.shape[1:])
        return buf.at[:, dst].set(tiled)
    return jax.tree_util.tree_map(cp, cache)


@functools.partial(jax.jit, donate_argnums=(0,))
def _cow_block_jit(cache, src, dst):
    """Copy-on-write: duplicate one pool block — its row in every layer of
    the held ``(L, NB, bs * Hkv * hd)`` planes and its position row — in
    place, before a slot's first write into a block it does not exclusively
    own."""
    pc = cache["self"]

    def row(a):             # block ``src`` of every layer: (L, 1, R)
        return jax.lax.dynamic_slice_in_dim(a, src, 1, axis=1)

    return {"self": attn_lib.paged_put_rows(pc, jnp.reshape(dst, (1,)),
                                            row(pc.k), row(pc.v),
                                            pc.pos[src][None])}


@functools.partial(jax.jit, donate_argnums=(0,))
def _insert_rows_jit(cache, rows_cache, slot_ids):
    """One scatter writes all admitted prefill rows into the big cache.

    cache leaves are (L, B, ...) with the slot axis at 1; rows_cache leaves
    (L, n, ...) land at batch indices ``slot_ids`` (n,) — a single jitted
    dispatch per admission batch instead of one whole-cache update per slot.
    """
    return jax.tree_util.tree_map(
        lambda buf, new: buf.at[:, slot_ids].set(new.astype(buf.dtype)),
        cache, rows_cache)


@functools.partial(jax.jit, donate_argnums=(0,))
def _commit_jit(state, slot_ids, rows):
    return {k: state[k].at[slot_ids].set(rows[k].astype(state[k].dtype))
            for k in state}


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("nblk",))
def _insert_blocks_jit(cache, rows_cache, dst, *, nblk):
    """Copy-on-admit: bucketed ring prefill rows -> paged pool blocks.

    ``rows_cache`` leaves are the prefill's ring rows (L, n, Hkv, W, hd);
    the first ``nblk`` blocks of each row become pool rows (token-major,
    ``bs * Hkv * hd`` wide) at pool ids ``dst`` (n, nblk), written in place
    in one jitted dispatch per admission batch.  Rows whose prompt needs
    fewer blocks point their tail at the trash block 0 — copied padding
    (pos = -1) that is never referenced by a table.
    """
    pc = cache["self"]
    rows = rows_cache["self"]
    bs = pc.pos.shape[1]
    n = dst.shape[0]

    def blocks(a):          # (L, n, Hkv, W, hd) -> (L, n*nblk, bs*Hkv*hd)
        r = attn_lib.paged_rows(a[:, :, :, :nblk * bs].swapaxes(2, 3), bs)
        return r.reshape(r.shape[0], n * nblk, r.shape[-1])

    # ring pos is identical across layers: copy layer 0's view
    pos = rows.pos[0, :, :nblk * bs].reshape(n * nblk, bs)
    return {"self": attn_lib.paged_put_rows(
        pc, dst.reshape(-1), blocks(rows.k), blocks(rows.v), pos)}


@functools.partial(jax.jit, donate_argnums=(0,))
def _reset_pos_jit(cache, ids):
    """Invalidate positions of freshly allocated decode-growth blocks: a
    reused block still carries its previous owner's positions, which would
    otherwise read as valid context for the new slot.  ``ids`` is padded
    with 0 (resetting the trash block is harmless) to keep the shape
    static."""
    pc = cache["self"]
    return {"self": pc._replace(pos=pc.pos.at[ids].set(-1))}


@functools.partial(jax.jit, donate_argnums=(0,))
def _keep_active_jit(state, keep):
    """Deactivate evicted slots (cancel / timeout / preemption) in-graph."""
    return {**state, "active": state["active"] & keep}


def _chunk_len(S: int, cursor: int, budget: int, bs: int) -> int:
    """Tokens the budget scheduler prefills next for one slot: the whole
    remaining suffix when it fits the budget, otherwise the largest
    block-aligned chunk the budget covers (chunk boundaries stay on block
    edges so every chunk scatter writes whole pool blocks)."""
    n = min(budget, S - cursor)
    if cursor + n < S:
        n = (n // bs) * bs
    return n


def _chunk_arrays(toks, ags, start: int, n: int, bs: int, table_row):
    """Bucketed host arrays for ONE suffix-prefill chunk.

    Shared by the engine tick and the straight-line oracle
    (``repro.serve.prefix.chunked_reference_trajectory``): identical shapes
    mean identical executables, which is what makes chunked engine
    trajectories bit-equal to the oracle's.  ``start`` is block-aligned
    (chunks advance in whole blocks; only the final chunk may end
    unaligned).  Token/position tails past ``n`` pad with pos = -1 (masked
    everywhere), context ids pad with the trash block 0.
    """
    nblk = -(-n // bs)
    nblk_pad = _next_pow2(nblk)
    sc = nblk_pad * bs
    tokens = np.zeros((1, sc), np.int32)
    tokens[0, :n] = toks[start:start + n]
    ages = np.zeros((1, sc), np.float32)
    if ags is not None:
        ages[0, :n] = ags[start:start + n]
        ages[0, n:] = ags[start + n - 1]
    positions = np.full((1, sc), -1, np.int32)
    positions[0, :n] = np.arange(start, start + n, dtype=np.int32)
    # a chunk starting at the prompt head carries a zero-width context:
    # beyond saving work, identical KV widths keep the budget-infinity
    # chunk at the monolithic prefill's softmax reduction widths (a
    # masked-out pad block would reassociate them)
    first = start // bs
    nctx = _next_pow2(first) if first else 0
    ctx_ids = np.zeros((1, nctx), np.int32)
    ctx_ids[0, :first] = table_row[:first]
    dst = np.zeros((1, nblk_pad), np.int32)
    dst[0, :nblk] = table_row[first:first + nblk]
    last_idx = np.asarray([n - 1], np.int32)
    return tokens, ages, positions, ctx_ids, dst, last_idx


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def _suffix_chunk_jit(params, cache, tokens, ages, positions, ctx_ids, dst,
                      last_idx, *, cfg):
    """One chunked-prefill step: gather the slot's already-written context
    blocks from the pool, run the suffix forward over the chunk, write the
    chunk's K/V (+ position planes) into its destination blocks.

    tokens/ages/positions: (B, Sc) bucketed chunk rows (pos -1 = padding);
    ctx_ids: (B, C) pool ids of the blocks before the chunk (0 = trash
    padding — its positions are masked, never trusted); dst: (B, nblk) pool
    ids the chunk writes (0 = trash padding).  Returns (cache, (B, V) fp32
    logits at ``last_idx`` — the final chunk's bootstrap logits).
    """
    pc = cache["self"]
    bs = pc.pos.shape[1]
    B, C = ctx_ids.shape
    safe = jnp.maximum(ctx_ids, 0)
    heads = attn_lib.cache_rows(cfg)[0]

    def gather(pool):       # (L, NB, bs*Hkv*hd) -> (L, B, C*bs, Hkv, hd)
        # rows by (layer, block), as the decode reads them: a gather of
        # whole (L, 1, R) windows would lay the pool out block-major first
        rows = pool[jnp.arange(pool.shape[0])[:, None, None], safe[None]]
        return attn_lib.paged_tokens(rows, bs, heads)

    # the trash block's position plane receives REAL positions from idle
    # slots' discarded tick writes — mask padded context unconditionally
    ctx_pos = jnp.where(ctx_ids[:, :, None] > 0, pc.pos[safe], -1)
    ctx_pos = ctx_pos.reshape(B, C * bs)
    batch: Dict[str, Any] = {"tokens": tokens, "positions": positions}
    if cfg.age_encoding:
        batch["ages"] = ages
    out = forward_suffix(params, cfg, batch,
                         {"k": gather(pc.k), "v": gather(pc.v),
                          "pos": ctx_pos}, last_index=last_idx,
                         moe_impl=_MOE_PREFILL)
    Sc = tokens.shape[1]
    nblk = Sc // bs

    def blocks(a):          # (L, B, Sc, Hkv, hd) -> (L, B*nblk, bs*Hkv*hd)
        r = attn_lib.paged_rows(a, bs)
        return r.reshape(r.shape[0], B * nblk, r.shape[-1])

    # full position planes land with the data: the padded tail of a partial
    # final block carries -1 (decode writes reclaim it a token at a time),
    # so stale positions from a block's previous owner can never leak
    pc = attn_lib.paged_put_rows(pc, dst.reshape(-1), blocks(out["k"]),
                                 blocks(out["v"]),
                                 positions.reshape(B * nblk, bs))
    lg = out["logits"][:, 0].astype(jnp.float32)
    return {"self": pc}, lg


class BatchedEngine:
    """Slot-based continuous batching, fully device-resident between syncs.

    Two cache layouts:

    * ``cache="ring"`` (default) — one dense per-slot ring, memory =
      ``slots x max_context`` worst case;
    * ``cache="paged"`` — a shared block pool + per-slot block tables
      (``repro.models.attention.PagedCache``): admission is budgeted by
      *free blocks* rather than the dense worst case, decode grows a slot
      one block at a time, and pool exhaustion preempts the youngest
      request (blocks freed, request requeued for vLLM-style recompute
      resume) instead of deadlocking.  Trajectories are bit-identical to
      the ring layout under injected uniforms — the paged read path
      reconstructs the exact ring view through the block table.

    Requests can be cancelled mid-flight (:meth:`cancel`, addressed by
    ``Request.request_id`` — the engine half of the wire's ``/v1/cancel``)
    and expire after ``request_timeout`` seconds; both paths free the
    slot's blocks immediately.

    Paged blocks are **refcounted** (``repro.serve.prefix.SharedBlockPool``)
    so requests with identical history prefixes can share them: the
    :meth:`fork` primitive clones a prefill-only held parent into N decode
    slots referencing all of its blocks (the Monte-Carlo futures workload,
    :meth:`sample_futures`), a write into a shared block copy-on-writes it
    first, and ``prefix_cache=True`` additionally indexes admitted prompts
    so identical prefixes admit by reference — eviction, preemption and
    cancellation drop references, never shared bytes, and the zero-leak
    invariant extends to refcounts (drained engine +
    :meth:`drop_prefix_cache` -> ``used == 0``).
    """

    def __init__(self, params, cfg: ModelConfig, *, slots: int = 8,
                 max_context: int = 512, temperature: float = 1.0,
                 seed: int = 0, sampler: str = "jnp",
                 min_seq_bucket: int = 8, cache: str = "ring",
                 blocks: Optional[int] = None, block_size: int = 16,
                 request_timeout: Optional[float] = None,
                 prefix_cache: bool = False,
                 prefill_chunk_tokens: Optional[int] = None):
        if cfg.frontend is not None or cfg.arch_type in (cb.AUDIO, cb.ENC_DEC):
            raise ValueError("engine serves token-only architectures")
        if sampler not in ("jnp", "pallas"):
            raise ValueError(f"sampler must be 'jnp' or 'pallas': {sampler!r}")
        if cache not in ("ring", "paged"):
            raise ValueError(f"cache must be 'ring' or 'paged': {cache!r}")
        self.params = params
        self.cfg = cfg
        self.slots = slots
        self.max_context = max_context
        self.temperature = temperature
        self.is_delphi = cfg.age_encoding
        self.sampler = sampler
        self.min_seq_bucket = min_seq_bucket
        self.request_timeout = request_timeout
        # right-padding a prefill is only sound when padded positions can be
        # masked out of the state — true for KV-cache attention (pos = -1),
        # false for recurrent SSM/hybrid state; those admit unbucketed.
        self.bucketed = cfg.arch_type in (cb.DENSE, cb.MOE, cb.VLM)
        self.paged = cache == "paged"
        self.block_size = block_size
        if prefill_chunk_tokens is not None:
            if cache != "paged":
                raise ValueError(
                    "prefill_chunk_tokens requires the paged KV cache: "
                    "chunked prefill writes prompt KV through the paged "
                    "insert path — build with cache='paged'")
            if (prefill_chunk_tokens < block_size
                    or prefill_chunk_tokens % block_size != 0):
                raise ValueError(
                    f"prefill_chunk_tokens={prefill_chunk_tokens} must be a "
                    f"positive multiple of block_size={block_size}")
        self.prefill_chunk_tokens = prefill_chunk_tokens

        self._rng = jax.random.PRNGKey(seed)
        if prefix_cache and not self.paged:
            raise ValueError(
                "prefix_cache requires the paged KV cache: the ring layout "
                "has no shareable blocks — build with cache='paged'")
        if self.paged:
            if not self.bucketed:
                raise ValueError("paged KV cache needs an attention-cache "
                                 "architecture (dense/moe/vlm)")
            if max_context % block_size != 0:
                raise ValueError(f"max_context={max_context} must be a "
                                 f"multiple of block_size={block_size}")
            self.blocks_per_slot = max_context // block_size
            if blocks is None:
                # dense-equivalent pool: same bytes as the ring layout
                blocks = slots * self.blocks_per_slot + 1
            if blocks < self.blocks_per_slot + 1:
                raise ValueError(
                    f"pool of {blocks} blocks cannot hold one full slot "
                    f"(needs >= {self.blocks_per_slot + 1} including the "
                    f"trash block)")
            self.allocator: Optional[BlockAllocator] = BlockAllocator(blocks)
            self.pool: Optional[SharedBlockPool] = \
                SharedBlockPool(self.allocator)
            self.prefix: Optional[PrefixIndex] = (
                PrefixIndex(self.pool, block_size) if prefix_cache else None)
            self.cache = make_paged_decode_cache(
                cfg, slots, max_context, num_blocks=blocks,
                block_size=block_size)
            self._table = np.full((slots, self.blocks_per_slot), -1,
                                  np.int32)                # guarded-by: engine-thread
            self._table_dirty = False                      # guarded-by: engine-thread
            self._fresh_blocks: List[int] = []             # guarded-by: engine-thread
            self._slot_blocks: List[List[int]] = \
                [[] for _ in range(slots)]                 # guarded-by: engine-thread
        else:
            self.allocator = None
            self.pool = None
            self.prefix = None
            self.cache = make_decode_cache(params, cfg, slots, max_context)
        # host mirror of each slot's next decode-write position (the paged
        # scheduler allocates that block BEFORE the tick writes it)
        self._slot_pos = np.zeros(slots, np.int64)         # guarded-by: engine-thread
        self._seq_counter = itertools.count(1)
        self._by_id: Dict[str, Request] = {}               # guarded-by: _lock
        self._cancel_ids: set = set()                      # guarded-by: _lock
        self._deactivate: List[int] = []                   # guarded-by: engine-thread
        # hold/fork machinery: slot -> (V,) bootstrap logits of a parked
        # parent; queued fork ops applied on the engine thread each tick
        self._held_logits: Dict[int, jax.Array] = {}       # guarded-by: engine-thread
        # chunked prefill: slot -> in-progress prompt cursor state; slots
        # here occupy a table row but never tick until the prefill finishes
        self._prefills: Dict[int, _PrefillProgress] = {}   # guarded-by: _lock
        self._fork_ops: List[Tuple["Request", List[Request]]] = []  # guarded-by: _lock
        self._state: Dict[str, jax.Array] = {
            "last": jnp.zeros((slots,), jnp.int32),
            "age": jnp.zeros((slots,), jnp.float32),
            "step": jnp.zeros((slots,), jnp.int32),
            "n_emitted": jnp.zeros((slots,), jnp.int32),
            "max_new": jnp.ones((slots,), jnp.int32),
            "active": jnp.zeros((slots,), bool),
        }
        # one device holds this engine's params, pool and state: the one
        # current at construction (``jax.default_device``), so replicas
        # built under different defaults run on different chips.  Every
        # jitted call follows its committed inputs to that device.
        self.device = _current_device()
        self.params, self.cache, self._state, self._rng = jax.device_put(
            (params, self.cache, self._state, self._rng), self.device)
        # (slots, V) float32 logits the last tick sampled from, left on the
        # device: what checks against a reference read (never fetched here)
        self.last_logits: Optional[jax.Array] = None       # guarded-by: engine-thread
        self.slot_req: List[Optional[Request]] = [None] * slots  # guarded-by: engine-thread
        self.pending: List[Request] = []                   # guarded-by: _lock
        self.completed: List[Request] = []
        # foreground run() returns completed; background start() defaults
        # this off so a long-lived server doesn't retain every request
        self.retain_completed = True
        # cross-thread submission (the HTTP front-end submits from handler
        # threads while a background thread ticks): `_lock` guards `pending`,
        # `_wake` cuts the idle backoff short on new work.  Slot/device state
        # is touched only by whichever single thread drives step().  The
        # `# guarded-by:` field annotations and `# repro-lint:` method
        # markers make this contract machine-checked (RL001, tools/analyze).
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._stop_flag = False
        # instrumentation (asserted on by tests, read by the benchmark and
        # /v1/healthz): bumped on the engine thread, snapshotted under
        # `_lock` by the cross-thread stats readers (pool_stats /
        # health_stats)
        self.ticks = 0                                     # guarded-by: _lock
        # active slots summed over ticks; events emitted by ticks and by
        # admission bootstraps; first admissions to a slot and their
        # summed submit-to-slot seconds
        self.slot_ticks = 0                                # guarded-by: _lock
        self.events_tick = 0                               # guarded-by: _lock
        self.events_admit = 0                              # guarded-by: _lock
        self.admitted = 0                                  # guarded-by: _lock
        self.queue_wait_s = 0.0                            # guarded-by: _lock
        self.host_syncs = 0                                # guarded-by: _lock
        self.admit_batches = 0                             # guarded-by: _lock
        self.preemptions = 0                               # guarded-by: _lock
        self.peak_active = 0                               # guarded-by: _lock
        self.forks = 0                                     # guarded-by: _lock
        self.chunked_prefills = 0                          # guarded-by: _lock
        self.prefill_chunks = 0                            # guarded-by: _lock
        self.suffix_tokens_saved = 0                       # guarded-by: _lock
        # MoE stacks: token->expert assignments that landed on the held
        # experts, summed over MoE layers and ticks (the tick's fifth row)
        self.expert_tokens = 0                             # guarded-by: _lock
        self.prefill_shapes: set = set()                   # guarded-by: engine-thread
        # phase spans of the engine's steps and its callers' requests
        self.spans = Spans()
        self._kn = _Knobs(
            slots=slots, max_context=max_context,
            is_delphi=self.is_delphi, use_pallas=sampler == "pallas",
            inv_temp=1.0 / max(temperature, 1e-6),
            max_age=cfg.max_age, death_token=cfg.death_token,
            vocab=cfg.vocab_size)

    # -- device->host boundary (the only one) -------------------------------
    def _fetch(self, x) -> np.ndarray:  # repro-lint: engine-thread-only
        self.host_syncs += 1
        # repro-lint: disable=RL006 this IS the one budgeted packed sync;
        # every tick/admission funnels its device reads through here
        return _to_host(x)

    # -- public API ----------------------------------------------------------
    def submit(self, req: Request):
        """Thread-safe: handler threads enqueue while the loop thread ticks."""
        if len(req.tokens) == 0:
            raise ValueError("empty prompt")
        req.out_tokens, req.out_ages = [], []
        req._seq = next(self._seq_counter)
        req.t_submit = time.monotonic()
        if req.request_id is None:
            req.request_id = f"req-{id(self):x}-{req._seq}"
        if self.request_timeout is not None and req._deadline is None:
            req._deadline = time.monotonic() + self.request_timeout
        with self._lock:
            if req.request_id in self._by_id:
                # a silent clobber would make the OLDER entry's completion
                # pop the registry and strand cancels aimed at the newer one
                from repro.api.errors import InvalidRequestError
                raise InvalidRequestError(
                    f"request_id {req.request_id!r} is already in flight "
                    f"on this engine")
            self._by_id[req.request_id] = req
            self.pending.append(req)
        self._wake.set()

    def cancel(self, request_id: str) -> bool:
        """Flag a pending or in-flight request for cancellation (thread-safe:
        the wire front-end cancels from handler threads while the loop
        ticks).  The next control pass drops it from the queue or evicts it
        from its slot — freeing its cache blocks in paged mode — and waiters
        unblock with a structured ``request_cancelled`` error.  Returns
        False for unknown or already-finished ids."""
        with self._lock:
            req = self._by_id.get(request_id)
            if req is None or req.done:
                return False
            self._cancel_ids.add(request_id)
        self._wake.set()
        return True

    # -- fork: the Monte-Carlo futures primitive ------------------------------
    def fork(self, request_id: str, n: Optional[int] = None, *,
             uniforms=None, max_new: Optional[int] = None,
             children: Optional[List[Request]] = None) -> List[Request]:
        """Clone a held (``Request.hold=True``) prefilled parent into N
        decode slots.  Thread-safe: the op is queued and applied on the
        engine thread between ticks.

        Each child shares ALL of the parent's prefix blocks by reference
        (paged: refcounts, first write copy-on-writes the partial tail
        block; ring: one masked row copy) and samples its OWN first event
        from the parent's prefill logits — so N futures cost ~1 prefill and
        ~1 prefix's worth of KV bytes instead of N.  Children that outnumber
        the free slots queue as ordinary pending requests (they re-acquire
        the shared prefix through the prefix index when it is enabled).
        The parent is consumed: its slot is released once the fork applies.

        Pass ``children`` (pre-built, e.g. with completion hooks attached)
        OR ``n`` [+ per-child ``uniforms`` (n, max_new, V)].  Returns the
        child requests; wait on them like any submitted request.
        """
        from repro.api.errors import InvalidRequestError
        with self._lock:
            parent = self._by_id.get(request_id)
        if parent is None or parent.done:
            raise InvalidRequestError(
                f"fork of unknown or finished request {request_id!r}")
        if not parent.hold:
            raise InvalidRequestError(
                "fork requires a hold=True parent (prefill-only parked "
                "request): submit(Request(..., hold=True)) first")
        if children is None:
            children = self._build_fork_children(parent, n, uniforms,
                                                 max_new)
        if not children:
            raise InvalidRequestError("fork of zero children")
        injected = children[0].uniforms is not None
        if any((c.uniforms is not None) != injected for c in children):
            raise InvalidRequestError(
                "fork children must be uniformly injected or uniformly "
                "RNG-sampled (one tick draws from one uniform source)")
        now = time.monotonic()
        for c in children:
            c.out_tokens, c.out_ages = [], []
            c._seq = next(self._seq_counter)
            c.t_submit = now
            if c.request_id is None:
                c.request_id = f"req-{id(self):x}-{c._seq}"
            if self.request_timeout is not None and c._deadline is None:
                c._deadline = time.monotonic() + self.request_timeout
        with self._lock:
            # validate EVERY id before registering ANY: a duplicate found
            # halfway would otherwise strand the already-registered
            # children in _by_id forever (never finalized, ids unusable)
            ids = [c.request_id for c in children]
            clashes = [i for i in ids if i in self._by_id]
            if clashes or len(set(ids)) != len(ids):
                raise InvalidRequestError(
                    f"fork child request_id(s) already in flight or "
                    f"duplicated: {clashes or ids!r}")
            for c in children:
                self._by_id[c.request_id] = c
            # the op holds the parent OBJECT: its done/error state must stay
            # readable after completion pops it from the id registry
            self._fork_ops.append((parent, list(children)))
        self._wake.set()
        return children

    def _build_fork_children(self, parent: Request, n: Optional[int],
                             uniforms, max_new: Optional[int]
                             ) -> List[Request]:
        """The ONE construction of fork children (fork() and
        sample_futures): validates shapes up front so a bad request raises
        a structured error before any engine state changes."""
        from repro.api.errors import InvalidRequestError
        if n is None or n < 1:
            raise InvalidRequestError("fork needs n >= 1 or children=[...]")
        mn = parent.max_new if max_new is None else max_new
        if uniforms is not None:
            uniforms = np.asarray(uniforms)
            if uniforms.ndim != 3 or uniforms.shape[0] < n:
                raise InvalidRequestError(
                    f"fork uniforms must be (>= n, max_new, V); got "
                    f"{tuple(uniforms.shape)}")
        return [
            Request(tokens=np.asarray(parent.tokens),
                    ages=(np.asarray(parent.ages)
                          if parent.ages is not None else None),
                    max_new=mn,
                    uniforms=(None if uniforms is None
                              else np.asarray(uniforms[i])),
                    request_id=f"{parent.request_id}/fork-{i}")
            for i in range(n)]

    def sample_futures(self, tokens, ages=None, *, n: int,
                       max_new: int = 48, uniforms=None,
                       request_id: Optional[str] = None,
                       wait_timeout: float = 300.0) -> List[Request]:
        """N stochastic futures of one patient history via hold + fork.

        Submits a prefill-only parent, forks it into ``n`` children sharing
        the prefix KV, and drives the engine (foreground) or waits on the
        children's completion hooks (when the background loop is running).
        Returns the completed child requests in fork order; callers check
        ``Request.error`` per child.
        """
        parent = Request(tokens=np.asarray(tokens),
                         ages=(np.asarray(ages) if ages is not None
                               else None),
                         max_new=max_new, hold=True, request_id=request_id)
        running = self.running
        # validate + build BEFORE submit (a bad uniforms shape must not
        # leave an orphaned parent parked in a slot) and attach completion
        # hooks BEFORE fork (the background loop may apply the op at once);
        # when submit() autogenerates the parent id, rebuild so the child
        # ids derive from the real one
        children = self._build_fork_children(parent, n, uniforms, max_new)
        with self.spans.span("api.futures.submit") as sp:
            self.submit(parent)
            sp.stat(request_id=parent.request_id)
            if request_id is None:
                children = self._build_fork_children(parent, n, uniforms,
                                                     max_new)
            waits: List[threading.Event] = []
            if running:
                for c in children:
                    evt = threading.Event()
                    c.on_done = lambda _r, _evt=evt: _evt.set()
                    waits.append(evt)
            self.fork(parent.request_id, children=children)
        with self.spans.span("api.futures.wait",
                             request_id=parent.request_id):
            if running:
                from repro.api.errors import RequestTimeoutError
                for evt in waits:
                    if not evt.wait(wait_timeout):
                        raise RequestTimeoutError(
                            f"engine did not complete the forked futures "
                            f"within {wait_timeout}s")
            else:
                self.run()
        return children

    def drop_prefix_cache(self) -> int:
        """Evict every prefix-index entry; returns blocks actually freed.
        With the engine drained this restores ``allocator.used == 0`` (the
        zero-leak invariant extended to refcounts).  Refcounts and the
        index are engine-thread state, so this refuses while the
        background loop is running — ``stop()`` first."""
        if self.prefix is None:
            return 0
        if self.running:
            raise RuntimeError(
                "drop_prefix_cache() mutates engine-thread state (index + "
                "refcounts): stop() the background loop first")
        return self.prefix.clear()

    # -- background run loop (the HTTP front-end's async admission) ----------
    def start(self, *, idle_min: float = 0.001, idle_max: float = 0.05,
              retain_completed: bool = False) -> "BatchedEngine":
        """Tick on a daemon thread until :meth:`stop`.

        When no slot is active the loop backs off exponentially from
        ``idle_min`` to ``idle_max`` seconds between polls; ``submit`` wakes
        it immediately, so admission latency stays ~0 under load and the
        idle engine costs no busy spin.

        ``retain_completed=False`` (the default here, unlike foreground
        ``run()``) stops appending finished requests to ``self.completed``:
        a long-running server would otherwise leak every request's prompt,
        outputs and uniforms forever — callers observe completion through
        the per-request ``on_event``/``on_done`` hooks instead.
        """
        if self.running:
            return self
        self.retain_completed = retain_completed
        self._stop_flag = False
        self._wake.clear()
        self._thread = threading.Thread(
            target=self._loop, args=(idle_min, idle_max),
            name="repro-engine-loop", daemon=True)
        self._thread.start()
        return self

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    @property
    def cache_bytes(self) -> int:
        """Resident KV-cache bytes (block pool or dense rings) — the
        fixed-memory axis of the paged-vs-ring serve benchmark."""
        return sum(int(a.size) * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(self.cache))

    def pool_stats(self) -> Dict[str, Any]:
        """Allocator watermarks + scheduler counters (healthz / benchmark).

        Called from HTTP handler threads while the loop ticks, so the
        counter snapshot is taken under ``_lock`` (RL001: the engine
        thread bumps them lock-free; cross-thread readers serialize here).
        """
        with self._lock:
            stats: Dict[str, Any] = {
                "cache": "paged" if self.paged else "ring",
                "cache_bytes": self.cache_bytes,
                "peak_active": self.peak_active,
                "preemptions": self.preemptions,
                "forks": self.forks,
            }
            if self.paged:
                a = self.allocator
                # every count is of PHYSICAL blocks: a block shared by k
                # owners appears once in used/free, with sharing surfaced
                # separately
                stats.update(
                    block_size=self.block_size, blocks=a.num_blocks,
                    blocks_free=a.free, blocks_used=a.used,
                    blocks_peak_used=a.peak_used,
                    utilization=a.used / max(a.capacity, 1),
                    shared_blocks=self.pool.shared_blocks,
                    shared_blocks_peak=self.pool.peak_shared,
                    cow_copies=self.pool.cow_copies,
                    prefill_chunk_tokens=self.prefill_chunk_tokens,
                    chunked_prefills=self.chunked_prefills,
                    prefill_chunks=self.prefill_chunks,
                    prefill_in_progress=len(self._prefills),
                    suffix_tokens_saved=self.suffix_tokens_saved)
                stats["prefix_cache"] = (self.prefix.stats()
                                         if self.prefix is not None else None)
        return stats

    def health_stats(self) -> Dict[str, Any]:
        """Queue/slot/counter snapshot for ``/v1/healthz``.

        The single locked accessor HTTP handler threads are allowed to use
        instead of reaching into engine fields directly (RL001 flags the
        latter as a foreign access).  ``spans`` holds the per-name span
        aggregates (``repro.serve.spans``).
        """
        with self._lock:
            pending = len(self.pending)
            counters = {
                "ticks": self.ticks, "slot_ticks": self.slot_ticks,
                "events_tick": self.events_tick,
                "events_admit": self.events_admit,
                "admitted": self.admitted,
                "queue_wait_s": self.queue_wait_s,
                "expert_tokens": self.expert_tokens}
        # repro-lint: disable=RL001 GIL-atomic scan of the slot list for a
        # monitoring count; entries may be mid-swap but never torn
        active = sum(r is not None for r in self.slot_req)
        return {
            "running": self.running,
            **counters,
            "pending": pending,
            "active_slots": active,
            "slots": self.slots,
            "memory": self.pool_stats(),
            "spans": self.spans.snapshot(),
        }

    def stop(self, join: bool = True, timeout: float = 60.0) -> None:
        was_running = self.running
        self._stop_flag = True
        self._wake.set()
        t = self._thread
        if join and t is not None:
            t.join(timeout=timeout)
            if t.is_alive():
                # mid-compile ticks can outlive the timeout: leave _thread
                # set (running stays True) rather than race a zombie loop
                # over slot state — the caller can retry stop()
                raise RuntimeError(
                    f"engine loop still ticking after {timeout}s "
                    f"(jit compile in flight?) — retry stop()")
        self._thread = None
        # waiters parked on background-mode completion hooks must get an
        # immediate error, not a request_timeout-later 504.  The queue
        # snapshot takes the lock: submit() may still be racing us from a
        # handler thread even though the loop itself has exited.
        with self._lock:
            queued = bool(self.pending or self._fork_ops)
        # repro-lint: disable=RL001 loop thread is joined (or was never
        # started): this thread is the only one left touching slot state
        inflight = any(r is not None for r in self.slot_req)
        if was_running and (queued or inflight):
            self._fail_inflight(
                RuntimeError("engine stopped with the request in flight"))

    def _loop(self, idle_min: float, idle_max: float) -> None:
        idle = idle_min
        while not self._stop_flag:
            try:
                progressed = self.step()
            except Exception as e:          # fail loudly per-request, keep
                self._fail_inflight(e)      # the loop alive for new work
                progressed = False
            if progressed:
                idle = idle_min
            else:
                with self.spans.span("engine.idle"):
                    self._wake.wait(idle)
                self._wake.clear()
                idle = min(idle * 2.0, idle_max)

    def _fail_inflight(self, exc: Exception) -> None:  # repro-lint: engine-thread-only
        """A tick blew up: every in-flight request gets the error (waiters
        unblock via on_done) and slot/pool state resets so serving
        continues."""
        with self._lock:
            victims = self.pending[:]
            self.pending.clear()
            for _pid, kids in self._fork_ops:
                victims += kids
            self._fork_ops.clear()
        victims += [r for r in self.slot_req if r is not None]
        for slot in range(self.slots):
            # also release slots whose request never landed (_admit_group
            # crashed after allocating): blocks must return to the pool
            if self.slot_req[slot] is not None or (
                    self.paged and self._slot_blocks[slot]):
                self._release_slot(slot)
        self._state = {k: jnp.zeros_like(v) for k, v in self._state.items()}
        self._slot_pos[:] = 0
        self._deactivate.clear()
        self._prefills.clear()
        for req in victims:
            self._finalize(req, exc)

    # -- request lifecycle ----------------------------------------------------
    def _finalize(self, req: Request, error: Optional[BaseException] = None
                  ) -> None:
        """Terminal bookkeeping shared by completion, failure, cancellation
        and timeout: deregister, mark done, unblock waiters."""
        if error is not None:
            req.error = error
        req.t_done = time.monotonic()
        req.done = True
        with self._lock:
            if req.request_id is not None:
                self._by_id.pop(req.request_id, None)
                self._cancel_ids.discard(req.request_id)
        if req.error is None and self.retain_completed:
            self.completed.append(req)
        if req.on_done is not None:
            req.on_done(req)

    def _release_slot(self, slot: int) -> None:
        """Detach the slot's request and drop its block references (a block
        frees only when no other fork/index owner still holds it).  No
        marker needed: every caller is engine-thread-only, so repro-lint
        derives it through the call graph."""
        self.slot_req[slot] = None
        self._held_logits.pop(slot, None)
        # a mid-prefill eviction (preempt / cancel / timeout / engine stop)
        # drops the cursor with the blocks: partially-written prompt blocks
        # release here, so chunked prefill cannot leak pool blocks
        self._prefills.pop(slot, None)
        if self.paged:
            ids = self._slot_blocks[slot]
            if ids:
                self.pool.release(ids)
            self._slot_blocks[slot] = []
            self._table[slot, :] = -1
            self._table_dirty = True

    def _evict(self, slot: int, *, requeue: bool,  # repro-lint: engine-thread-only
               error: Optional[BaseException] = None) -> None:
        """Remove an in-flight request from its slot: frees blocks, queues a
        device-side deactivation, and either requeues at the FRONT of
        pending (preemption — progress so far is kept and resumed by
        recompute) or finalizes with ``error`` (cancel / timeout)."""
        req = self.slot_req[slot]
        self._release_slot(slot)
        self._deactivate.append(slot)
        if requeue:
            self.preemptions += 1
            with self._lock:
                self.pending.insert(0, req)
        else:
            self._finalize(req, error)

    def _control_error(self, why: str):
        from repro.api.errors import (RequestCancelledError,
                                      RequestTimeoutError)
        if why == "cancel":
            return RequestCancelledError("request cancelled")
        return RequestTimeoutError(
            "request exceeded its engine deadline")

    def _apply_control(self) -> None:  # repro-lint: engine-thread-only
        """Tick-start control pass: apply cancellations and expire
        deadlines, for both queued and in-flight requests."""
        if not self._cancel_ids and not self._by_id:
            return
        now = time.monotonic()
        with self._lock:
            ids = set(self._cancel_ids)
            drop, keep = [], []
            for r in self.pending:
                if r.request_id in ids:
                    drop.append((r, "cancel"))
                elif r._deadline is not None and now > r._deadline:
                    drop.append((r, "timeout"))
                else:
                    keep.append(r)
            self.pending[:] = keep
            # fork-op children can be cancelled/expired before they ever
            # reach a slot or the pending queue
            for _pid, kids in self._fork_ops:
                still = []
                for r in kids:
                    if r.request_id in ids:
                        drop.append((r, "cancel"))
                    elif r._deadline is not None and now > r._deadline:
                        drop.append((r, "timeout"))
                    else:
                        still.append(r)
                kids[:] = still
        for r, why in drop:
            self._finalize(r, self._control_error(why))
        for slot, r in enumerate(self.slot_req):
            if r is None:
                continue
            if r.request_id in ids:
                self._evict(slot, requeue=False,
                            error=self._control_error("cancel"))
            elif r._deadline is not None and now > r._deadline:
                self._evict(slot, requeue=False,
                            error=self._control_error("timeout"))
        with self._lock:
            self._cancel_ids -= ids       # stale ids (finished racing) drop

    # -- paged-pool scheduling ------------------------------------------------
    def _eff_len(self, r: Request) -> int:
        """Effective prompt length: original history plus events already
        emitted before a preemption (recompute resume re-prefills both)."""
        return len(r.tokens) + len(r.out_tokens or ())

    def _prompt_digests(self, r: Request):
        """Memoized prefix-index digests of the effective prompt (None when
        the index is off or the prompt wraps the ring — over-width
        histories bypass the index: their block layout is the wrapped
        window, not the prompt's chunk sequence)."""
        if self.prefix is None or self._eff_len(r) > self.max_context:
            return None
        n = self._eff_len(r)
        if r._pfx is None or r._pfx[0] != n:
            toks, ags, _ = self._prompt_state(r)
            full, key = self.prefix.digests(toks, ags)
            r._pfx = (n, full, key)
        return r._pfx

    def _prefix_hits_for(self, r: Request) -> List[int]:
        """Resident full-block run the prefix index can lend this request."""
        pfx = self._prompt_digests(r)
        return [] if pfx is None else self.prefix.match_run(pfx[1])

    def _full_entry_for(self, r: Request):
        """Complete prefix-index entry exactly matching this request's
        whole (effective) prompt — admission needs no prefill at all."""
        pfx = self._prompt_digests(r)
        return None if pfx is None else self.prefix.lookup_key(pfx[2])

    def _chunked_for(self, r: Request) -> bool:
        """Whether this request admits through the chunked-prefill path.
        Over-width prompts keep the monolithic S>W ring pack: their block
        layout is the wrapped window, which the chunk cursor cannot build
        incrementally."""
        return (self.prefill_chunk_tokens is not None
                and self._eff_len(r) <= self.max_context)

    def _admission_plan(self, r: Request) -> Tuple[str, int, List[int]]:
        """(kind, fresh blocks needed, index blocks this admission would
        PIN by sharing).  ``needed`` counts only blocks that must come from
        the free list or a genuine eviction: index-lent blocks are acquired
        by reference (a shared block is counted once, globally) — and they
        must be excluded from the evictable side of the budget, since
        sharing them pins their entries.  Prefill admissions additionally
        reserve the growth block a block-aligned (non-wrapped) prompt's
        FIRST decode write needs in the same tick, otherwise a request
        could be admitted, fully prefilled, and immediately preempted by
        _ensure_blocks every tick under pool pressure; a complete-entry
        (no-prefill) admission reserves the single COW/growth headroom
        block the same way."""
        if not self.paged:
            return "prefill", 0, []
        entry = self._full_entry_for(r)
        if entry is not None:
            return "ref", 1, list(entry.blocks)
        m = min(self._eff_len(r), self.max_context)
        hits = self._prefix_hits_for(r)
        if self._chunked_for(r) and hits \
                and len(hits) * self.block_size >= m:
            # chunked admission needs >= 1 suffix token for its bootstrap
            # logits: _admit_chunked trims an all-covering run the same way,
            # and the budget here must agree with what it will allocate
            hits = hits[:(m - 1) // self.block_size]
        needed = (-(-m // self.block_size) - len(hits)
                  + (1 if m % self.block_size == 0 and m < self.max_context
                     else 0))
        return "prefill", needed, hits

    def _prompt_state(self, r: Request):
        """(tokens, ages, remaining_max_new) for admission — identical to
        the raw request unless it was preempted mid-decode."""
        if not r.out_tokens:
            return np.asarray(r.tokens), r.ages, r.max_new
        toks = np.concatenate([np.asarray(r.tokens, np.int64),
                               np.asarray(r.out_tokens, np.int64)])
        ages = None
        if r.ages is not None:
            ages = np.concatenate([np.asarray(r.ages, np.float64),
                                   np.asarray(r.out_ages, np.float64)])
        return toks, ages, r.max_new - len(r.out_tokens)

    def _ensure_blocks(self) -> None:  # repro-lint: engine-thread-only
        """Every active slot must EXCLUSIVELY own the block its next decode
        write lands in: unallocated destinations get a fresh block, and a
        destination the slot shares with other owners (forked siblings, the
        prefix index) is **copied-on-write** first — one jitted block copy,
        the old reference dropped — so shared prefixes are immutable.  On
        pool exhaustion the **youngest** request is preempted (references
        dropped, requeued at the queue front) until the rest fit; held
        parents never decode-write real data and are skipped."""
        W, bs = self.max_context, self.block_size
        while True:
            # mid-prefill slots never decode-write this tick (their blocks
            # were allocated whole at admission), so only genuinely ticking
            # slots can be needy — but they DO hold pool blocks, so they
            # stay on the preemption-victim side below
            active = [i for i, r in enumerate(self.slot_req)
                      if r is not None and not r.hold
                      and i not in self._prefills]
            needy = []
            for slot in active:
                jb = int(self._slot_pos[slot] % W) // bs
                bid = int(self._table[slot, jb])
                if bid < 0:
                    needy.append((slot, jb, None))
                elif self.pool.refcount(bid) > 1:
                    needy.append((slot, jb, bid))        # COW before write
            if not needy:
                return
            exhausted = False
            for slot, jb, old in needy:
                if old is not None and self.pool.refcount(old) == 1:
                    # the other sharers COWed away earlier in this pass:
                    # this slot is now the sole owner — write in place
                    continue
                got = self.pool.alloc(1)
                if got is None:
                    exhausted = True
                    break
                new = got[0]
                if old is None:
                    self._slot_blocks[slot].append(new)
                    self._fresh_blocks.append(new)
                else:
                    # the copy carries valid positions — do NOT reset them
                    try:
                        self.cache = _cow_block_jit(
                            self.cache, jnp.int32(old), jnp.int32(new))
                    except BaseException:
                        self.pool.release([new])   # undo: copy never landed
                        raise
                    self._slot_blocks[slot][
                        self._slot_blocks[slot].index(old)] = new
                    self.pool.release([old])
                    self.pool.cow_copies += 1
                self._table[slot, jb] = new
                self._table_dirty = True
            if not exhausted:
                return
            victims = [i for i, r in enumerate(self.slot_req)
                       if r is not None and not r.hold]
            victim = max(victims, key=lambda s: self.slot_req[s]._seq)
            self._evict(victim, requeue=True)

    def _flush_slot_updates(self) -> None:  # repro-lint: engine-thread-only
        """Push queued host-side slot bookkeeping to the device: evicted
        slots deactivate, fresh decode-growth blocks invalidate their
        stale positions, and the block table syncs (at most one small
        host->device transfer per tick each)."""
        if self._deactivate:
            keep = np.ones(self.slots, bool)
            keep[self._deactivate] = False
            self._state = _keep_active_jit(self._state, jnp.asarray(keep))
            self._deactivate.clear()
        if self.paged:
            if self._fresh_blocks:
                npad = _next_pow2(max(len(self._fresh_blocks), self.slots))
                ids = np.zeros(npad, np.int32)          # pad with trash 0
                ids[:len(self._fresh_blocks)] = self._fresh_blocks
                self.cache = _reset_pos_jit(self.cache, jnp.asarray(ids))
                self._fresh_blocks.clear()
            if self._table_dirty:
                tab = self._table
                if self._held_logits or self._prefills:
                    # a held parent's slot still rides the batched tick; its
                    # (discarded) decode write must NOT land in the shared
                    # tail block — mask that column so the write goes to the
                    # trash block, keeping indexed/forked prefixes immutable
                    tab = tab.copy()
                    W, bs = self.max_context, self.block_size
                    for slot in self._held_logits:
                        tab[slot, int(self._slot_pos[slot] % W) // bs] = -1
                    # a mid-prefill slot's row stays FULLY masked: nothing
                    # may read (or tick-write) half-written prompt blocks
                    for slot in self._prefills:
                        tab[slot, :] = -1
                pc = self.cache["self"]
                self.cache = {"self": pc._replace(
                    table=jax.device_put(tab, self.device))}
                self._table_dirty = False

    # -- admission: bucketed batched prefill --------------------------------
    def _seq_bucket(self, n: int) -> int:
        return max(_next_pow2(n), self.min_seq_bucket)

    def _admit(self):  # repro-lint: engine-thread-only
        """Admit pending requests: one ``engine.admit`` span over the
        selection and every group it admits, with the groups' count, the
        requests admitted, the first one's id and the prefill shapes."""
        groups = requests = 0
        first, shapes = None, []
        with self.spans.span("engine.admit") as sp:
            while True:
                with self._lock:
                    sel = self._select_admission()
                if sel is None:
                    break
                kind, group, slot_ids, injected = sel
                groups += 1
                requests += len(group)
                if first is None:
                    first = group[0].request_id
                if kind == "ref":
                    self._admit_ref(group[0], slot_ids[0], injected)
                elif self._chunked_for(group[0]):
                    # chunked admissions only stage cursor state: the
                    # forward work is metered out by _run_prefill_chunks
                    # between ticks.  Cohort-mates all satisfy _chunked_for
                    # (over-width prompts admit solo, so a chunk-eligible
                    # head implies an eligible group).
                    for req, slot in zip(group, slot_ids):
                        self._admit_chunked(req, slot)
                else:
                    shapes.append("%dx%d" % self._prefill_shape(
                        [self._eff_len(r) for r in group]))
                    self._admit_group(group, slot_ids, injected)
            if groups:
                sp.stat(groups=groups, requests=requests, first=first,
                        shapes=",".join(shapes))

    def _landed(self, req: Request, now: float) -> None:
        """A request took a slot: its first admission stamps ``t_admit``
        and counts its queue wait (a preempted request's re-admission
        does not)."""
        if req.t_admit is None:
            req.t_admit = now
            self.admitted += 1
            self.queue_wait_s += now - req.t_submit

    def _select_admission(self) -> Optional[  # repro-lint: engine-thread-only holds=_lock
            Tuple[str, List[Request], List[int], bool]]:
        """Pop the next admission off ``pending`` (lock held by the caller;
        the jitted prefill itself runs outside the lock so submitters never
        block on device work).  Returns (kind, group, slots, injected):

        * ``"ref"`` — the head request's whole prompt hit a complete
          prefix-index entry: admit by reference, no prefill (solo; the
          loop drains consecutive hits one by one).
        * ``"prefill"`` — a bucketed prefill cohort, budgeted in paged mode
          by AVAILABLE blocks (free + index-evictable; blocks lent by the
          prefix index cost nothing).  ``hold`` parents admit solo.

        Admission stays FIFO, so a big request waits for blocks rather
        than being starved by later small ones."""
        if not self.pending:
            return None
        free = [i for i, r in enumerate(self.slot_req) if r is None]
        if not free:
            return None
        head = self.pending[0]
        injected = head.uniforms is not None
        # one tick samples all slots from ONE uniform source: defer
        # requests whose injectedness differs from the active cohort
        # until it drains (they are admitted on a later tick).  Held
        # parents sample nothing and are compatible with either.
        occupied = [r for r in self.slot_req
                    if r is not None and not r.hold]
        if occupied and (occupied[0].uniforms is not None) != injected \
                and not head.hold:
            return None
        # pin-aware block budget: blocks the cohort will SHARE from the
        # index stop being evictable the moment the shares are taken, so
        # they are excluded from the evictable side — otherwise a hit
        # could be double-counted as both lent and evictable, admitting a
        # cohort the pool then cannot actually serve
        pinned: set = set()
        fresh_taken = 0

        def fits(needed: int, pins: List[int]) -> bool:
            if not self.paged:
                return True
            if self.pool.free - fresh_taken >= needed:
                return True         # free list covers it: skip the O(index)
            return (self.pool.available(pinned | set(pins)) - fresh_taken
                    >= needed)      # evictable scan only under pressure

        def admissible(r: Request):
            """The request's plan, demoted if its pins don't fit: sharing
            hits strictly reduces fresh demand, so when the pin-aware
            budget fails, a fully-fresh budget (eviction allowed to take
            the hit blocks) is a sound sufficient condition — admission
            itself still shares whatever run is resident then."""
            kind, needed, pins = self._admission_plan(r)
            if fits(needed, pins):
                return kind, needed, pins
            if self.paged and pins:
                m = min(self._eff_len(r), self.max_context)
                fresh = -(-m // self.block_size) + (
                    1 if m % self.block_size == 0
                    and m < self.max_context else 0)
                if fits(fresh, []):
                    return "prefill", fresh, []
            return None

        plan = admissible(head)
        if plan is None:
            return None
        if plan[0] == "ref":
            return "ref", [self.pending.pop(0)], free[:1], injected
        group: List[Request] = []
        limit = len(free) if self.bucketed else 1
        if head.hold or self._eff_len(head) > self.max_context:
            # hold parents park without sampling (their cohort-mates would
            # waste a uniforms row each) and over-width prompts need an
            # exact-shape S>W ring pack: both admit solo
            limit = 1
        while self.pending and len(group) < limit \
                and (self.pending[0].uniforms is not None) == injected:
            cand = self.pending[0]
            if group and (self._eff_len(cand) > self.max_context
                          or cand.hold):
                break
            if group and min(_next_pow2(len(group) + 1), self.slots) \
                    * self.max_context > PREFILL_ROWS:
                break                      # the rest prefill as a next group
            plan = admissible(cand)
            if plan is None or (group and plan[0] == "ref"):
                break                      # complete hits admit solo next
            pinned |= set(plan[2])
            fresh_taken += plan[1]
            group.append(self.pending.pop(0))
        if not group:
            return None
        return "prefill", group, free[:len(group)], injected

    def _admit_group(self, group: List[Request], slot_ids: List[int],  # repro-lint: engine-thread-only
                     injected: bool):
        try:
            self._admit_group_inner(group, slot_ids, injected)
        except Exception:
            # a crashed admission (device error mid-prefill/insert) must not
            # leak its cohort: return allocated blocks to the pool and put
            # un-slotted requests back on the queue so the loop's
            # _fail_inflight (or the foreground caller) sees them
            if self.paged:
                for slot in slot_ids:
                    if self.slot_req[slot] is None and self._slot_blocks[slot]:
                        self._release_slot(slot)
            with self._lock:
                self.pending[:0] = [r for r in group
                                    if not r.done and r not in self.slot_req]
            raise

    def _prefill_shape(self, lens: List[int]) -> Tuple[int, int]:
        """(batch, seq) an admission group with prompt lengths ``lens``
        prefills at."""
        n = len(lens)
        if max(lens) > self.max_context:
            return n, max(lens)              # solo over-width admission
        if self.bucketed:
            # never bucket past the ring width: a pad-rounded S > W would
            # evict valid prompt context via the S>W ring pack
            return (min(_next_pow2(n), self.slots),
                    min(self._seq_bucket(max(lens)), self.max_context))
        return n, max(lens)

    def prefill_logits(self, tokens, ages=None) -> np.ndarray:
        """(V,) float32 logits one prompt's admission samples its first
        event from, computed by the engine's own prefill executable (its
        shape bucket, dtype and device) — for checks against a reference.
        Touches no slot, block or queue."""
        toks = np.asarray(tokens, np.int32)
        ags = None if ages is None else np.asarray(ages, np.float32)
        nb, sb = self._prefill_shape([len(toks)])
        u = np.full((nb, self.cfg.vocab_size), 0.5, np.float32)
        *_, lg = _prefill_u_jit(
            self.params, *map(jnp.asarray, _prefill_inputs(
                [(toks, ags, 1)], nb, sb)),
            jnp.asarray(u), cfg=self.cfg, kn=self._kn)
        return np.asarray(lg[0])

    def _admit_group_inner(self, group: List[Request], slot_ids: List[int],
                           injected: bool):
        n = len(group)
        prompts = [self._prompt_state(r) for r in group]
        lens = [len(p[0]) for p in prompts]
        nb, sb = self._prefill_shape(lens)
        self.prefill_shapes.add((nb, sb))

        hold = group[0].hold            # hold parents admit solo
        inputs = _prefill_inputs(prompts, nb, sb)
        age0 = inputs[3]
        args = (self.params, *map(jnp.asarray, inputs))
        if injected or hold:
            # a hold admission samples nothing: filler uniforms keep the
            # prefill on the shared injected executable, the sampled row is
            # discarded (the same call an rng-cohort would otherwise make)
            u = np.full((nb, self.cfg.vocab_size), 0.5, np.float32)
            if not hold:
                for j, r in enumerate(group):
                    # a resumed (preempted) request has consumed its first
                    # len(out_tokens) rows already
                    u[j] = r.uniforms[len(r.out_tokens)]
            cache_rows, rows, packed, lg = _prefill_u_jit(
                *args, jnp.asarray(u), cfg=self.cfg, kn=self._kn)
        else:
            cache_rows, rows, packed, lg, self._rng = _prefill_rng_jit(
                *args, self._rng, cfg=self.cfg, kn=self._kn)

        ids = jnp.asarray(np.asarray(slot_ids, np.int32))
        row_slice = jax.tree_util.tree_map(lambda a: a[:, :n], cache_rows)
        if self.paged:
            W, bs = self.max_context, self.block_size
            nblk = -(-min(sb, W) // bs)
            dst = np.zeros((n, nblk), np.int32)      # unneeded tail -> trash
            for j, (req, slot) in enumerate(zip(group, slot_ids)):
                nb_j = -(-min(lens[j], W) // bs)
                # prefix-index hits are acquired by REFERENCE: their prefill
                # rows go to the trash and only the unmatched suffix gets
                # fresh blocks — how N identical histories share KV and how
                # a preempt-resumed fork re-acquires its prefix.  Shares are
                # taken BEFORE the alloc: alloc may evict index entries for
                # room, and an unshared hit block could otherwise be freed
                # and re-handed out by that very eviction.
                hits = self._prefix_hits_for(req)[:nb_j]
                if hits:
                    self.pool.share(hits)
                    self.prefix.partial_hits += 1
                    # park the shared refs on the slot immediately: if the
                    # alloc below fails (or raises), _admit_group's cleanup
                    # releases them with the rest of the cohort
                    self._slot_blocks[slot] = list(hits)
                alloc = self.pool.alloc(nb_j - len(hits))
                if alloc is None:                    # _select budgeted this
                    raise RuntimeError("admission outran the block budget")
                blocks_j = hits + alloc
                self._slot_blocks[slot] = blocks_j
                self._table[slot, :] = -1
                self._table[slot, :nb_j] = blocks_j
                dst[j, len(hits):nb_j] = alloc
            self._table_dirty = True
            self.cache = _insert_blocks_jit(
                self.cache, row_slice, jnp.asarray(dst), nblk=nblk)
            self._flush_slot_updates()
        else:
            self.cache = _insert_rows_jit(self.cache, row_slice, ids)
        self._state = _commit_jit(
            self._state, ids, jax.tree_util.tree_map(lambda a: a[:n], rows))

        self.admit_batches += 1
        arr = self._fetch(packed)    # ONE sync per admission batch
        now = time.monotonic()
        for j, (req, slot) in enumerate(zip(group, slot_ids)):
            self.slot_req[slot] = req
            self._slot_pos[slot] = lens[j]
            self._landed(req, now)
            if self.prefix is not None and lens[j] <= self.max_context \
                    and not req.out_tokens:
                # register this prompt's blocks; hold parents also index
                # their partial tail + bootstrap logits (a COMPLETE entry:
                # identical later prompts admit with no prefill at all).
                # Decoding requests register full blocks only — sharing
                # their written-into tail would force a COW per admission.
                full = lens[j] // self.block_size
                prompts_j = prompts[j]
                pfx = self._prompt_digests(req)   # memoized: no re-hash
                if req.hold:
                    self.prefix.register(
                        prompts_j[0], prompts_j[1],
                        list(self._slot_blocks[slot]),
                        S=lens[j], age0=float(age0[j]), logits=lg[j],
                        digests=(pfx[1], pfx[2]))
                elif full:
                    self.prefix.register(
                        prompts_j[0][:full * self.block_size],
                        (None if prompts_j[1] is None
                         else prompts_j[1][:full * self.block_size]),
                        self._slot_blocks[slot][:full],
                        S=full * self.block_size, age0=float(age0[j]),
                        digests=(pfx[1][:full],
                                 self.prefix.aligned_key(pfx[1], full)))
                self.prefix.misses += 1
            if req.hold:
                # park: stash bootstrap logits, deactivate the device row
                # (its discarded tick writes go to the trash via the masked
                # table column), and emit nothing until fork
                self._held_logits[slot] = lg[j]
                self._deactivate.append(slot)
            else:
                self.events_admit += self._apply_host(req, slot, arr[:, j])

    def _admit_ref(self, req: Request, slot: int, injected: bool) -> None:  # repro-lint: engine-thread-only
        """Admission by pure reference: the whole prompt matched a complete
        prefix-index entry, so the request takes shared references on the
        entry's blocks and samples its first event from the entry's cached
        bootstrap logits — NO prefill runs.  A ``hold`` parent parks on the
        shared blocks the same way (the second ``sample_futures`` for a
        patient costs no forward pass at all)."""
        entry = self._full_entry_for(req)
        if entry is None:               # evicted since selection: re-queue
            with self._lock:
                self.pending.insert(0, req)
            return
        self.prefix.hits += 1
        self.prefix.touch(entry)        # probes are side-effect-free; the
        self.pool.share(entry.blocks)   # landed admission bumps MRU/hits
        self._slot_blocks[slot] = list(entry.blocks)
        self._table[slot, :] = -1
        self._table[slot, :len(entry.blocks)] = entry.blocks
        self._table_dirty = True
        self.slot_req[slot] = req
        self._slot_pos[slot] = entry.S
        self._landed(req, time.monotonic())
        if req.hold:
            self._held_logits[slot] = entry.logits
            self._deactivate.append(slot)
            return
        lg_b = jnp.broadcast_to(entry.logits[None], (1, self._kn.vocab))
        age0 = jnp.full((1,), entry.age0, jnp.float32)
        lengths = jnp.full((1,), entry.S, jnp.int32)
        mn = jnp.full((1,), req.max_new - len(req.out_tokens), jnp.int32)
        if injected:
            u = jnp.asarray(req.uniforms[len(req.out_tokens)][None])
            rows, packed = _fork_rows_jit(lg_b, u, age0, lengths, mn,
                                          kn=self._kn)
        else:
            rows, packed, self._rng = _fork_rows_rng_jit(
                lg_b, self._rng, age0, lengths, mn, kn=self._kn)
        self._state = _commit_jit(
            self._state, jnp.asarray([slot], jnp.int32), rows)
        self.admit_batches += 1
        arr = self._fetch(packed)       # ONE sync, like any admission batch
        self.events_admit += self._apply_host(req, slot, arr[:, 0])

    # -- chunked prefill (prefill/decode interleaving) ------------------------
    def _admit_chunked(self, req: Request, slot: int) -> None:  # repro-lint: engine-thread-only
        """Stage a chunked prefill: allocate the prompt's blocks, acquire
        any prefix-index hit run by reference, and park the cursor at the
        matched boundary — NO forward pass runs here.  The per-tick budget
        scheduler (:meth:`_run_prefill_chunks`) meters the unmatched suffix
        through ``prefill_chunk_tokens`` between decode ticks."""
        toks, ags, remaining = self._prompt_state(req)
        S = len(toks)
        bs = self.block_size
        nb = -(-S // bs)
        hits = self._prefix_hits_for(req)[:nb]
        if hits and len(hits) * bs >= S:
            # keep >= 1 suffix token: the final chunk's last-token logits
            # bootstrap the first sampled event (same trim _admission_plan
            # budgeted — a complete-entry hit admits via "ref" instead)
            hits = hits[:(S - 1) // bs]
        try:
            if hits:
                self.pool.share(hits)
                self.prefix.partial_hits += 1
                # park the shared refs on the slot immediately so a failed
                # alloc below releases them with everything else
                self._slot_blocks[slot] = list(hits)
            alloc = self.pool.alloc(nb - len(hits))
            if alloc is None:                    # _select budgeted this
                raise RuntimeError("admission outran the block budget")
        except Exception:
            if self._slot_blocks[slot]:
                self._release_slot(slot)
            with self._lock:
                if not req.done:
                    self.pending.insert(0, req)
            raise
        self._slot_blocks[slot] = hits + alloc
        self._table[slot, :] = -1
        self._table[slot, :nb] = self._slot_blocks[slot]
        self._table_dirty = True
        self.slot_req[slot] = req
        self._slot_pos[slot] = 0
        self._landed(req, time.monotonic())
        cursor = len(hits) * bs
        self.suffix_tokens_saved += cursor
        self.chunked_prefills += 1
        self._prefills[slot] = _PrefillProgress(
            req=req, tokens=np.asarray(toks), ages=ags, max_new=remaining,
            age0=float(ags[-1]) if ags is not None else 0.0, S=S,
            cursor=cursor)

    def _run_prefill_chunks(self) -> bool:  # repro-lint: engine-thread-only hot-path
        """Spend this tick's chunk-token budget across in-progress
        prefills, oldest request first.  A prefill whose last chunk lands
        bootstraps its first event at once (or parks, for a hold parent)
        and joins the batched decode this very tick."""
        budget = self.prefill_chunk_tokens
        bs = self.block_size
        progressed = False
        for slot in sorted(self._prefills,
                           key=lambda s: self._prefills[s].req._seq):
            st = self._prefills.get(slot)
            if st is None:
                continue
            if budget < min(bs, st.S - st.cursor):
                break                   # budget spent: FIFO, no backfill
            n = _chunk_len(st.S, st.cursor, budget, bs)
            self._run_one_chunk(slot, st, n)
            budget -= n
            progressed = True
        return progressed

    def _run_one_chunk(self, slot: int, st: _PrefillProgress, n: int) -> None:  # repro-lint: engine-thread-only hot-path
        tokens, ages, positions, ctx_ids, dst, last_idx = _chunk_arrays(
            st.tokens, st.ages, st.cursor, n, self.block_size,
            self._table[slot])
        self.prefill_shapes.add(("chunk", ctx_ids.shape[1], tokens.shape[1]))
        self.cache, lg = _suffix_chunk_jit(
            self.params, self.cache, jnp.asarray(tokens), jnp.asarray(ages),
            jnp.asarray(positions), jnp.asarray(ctx_ids), jnp.asarray(dst),
            jnp.asarray(last_idx), cfg=self.cfg)
        st.cursor += n
        self.prefill_chunks += 1
        if st.cursor >= st.S:
            self._finish_prefill(slot, st, lg[0])

    def _finish_prefill(self, slot: int, st: _PrefillProgress,
                        logits) -> None:  # repro-lint: engine-thread-only
        """The last chunk landed: unmask the slot's table row, register the
        prompt with the prefix index, then either park (hold parent) or
        bootstrap the first sampled event from the final chunk's logits —
        the exact tail of a monolithic admission, spread over ticks."""
        req = st.req
        del self._prefills[slot]
        self._slot_pos[slot] = st.S
        self._table_dirty = True        # unmask: the row is fully written
        if self.prefix is not None and not req.out_tokens:
            full = st.S // self.block_size
            pfx = self._prompt_digests(req)   # memoized: no re-hash
            if req.hold:
                self.prefix.register(
                    st.tokens, st.ages, list(self._slot_blocks[slot]),
                    S=st.S, age0=st.age0, logits=logits,
                    digests=(pfx[1], pfx[2]))
            elif full:
                self.prefix.register(
                    st.tokens[:full * self.block_size],
                    (None if st.ages is None
                     else st.ages[:full * self.block_size]),
                    self._slot_blocks[slot][:full],
                    S=full * self.block_size, age0=st.age0,
                    digests=(pfx[1][:full],
                             self.prefix.aligned_key(pfx[1], full)))
            self.prefix.misses += 1
        if req.hold:
            # park on the written blocks; the device row was never
            # activated, so no deactivation needs queueing
            self._held_logits[slot] = logits
            return
        lg_b = jnp.broadcast_to(logits[None], (1, self._kn.vocab))
        age0 = jnp.full((1,), st.age0, jnp.float32)
        lengths = jnp.full((1,), st.S, jnp.int32)
        mn = jnp.full((1,), st.max_new, jnp.int32)
        if req.uniforms is not None:
            u = jnp.asarray(req.uniforms[len(req.out_tokens)][None])
            rows, packed = _fork_rows_jit(lg_b, u, age0, lengths, mn,
                                          kn=self._kn)
        else:
            rows, packed, self._rng = _fork_rows_rng_jit(
                lg_b, self._rng, age0, lengths, mn, kn=self._kn)
        self._state = _commit_jit(
            self._state, jnp.asarray([slot], jnp.int32), rows)
        self.admit_batches += 1
        arr = self._fetch(packed)       # ONE sync, like any admission batch
        self.events_admit += self._apply_host(req, slot, arr[:, 0])

    # -- fork application (engine thread) ------------------------------------
    def _apply_forks(self) -> bool:  # repro-lint: engine-thread-only
        """Apply queued fork ops whose parent is parked in a slot: children
        acquire the parent's blocks by reference (paged) or a masked row
        copy (ring), bootstrap their own first event from the parent's
        prefill logits in ONE jitted call per wave, and start decoding this
        very tick.  Children beyond the free slots join ``pending`` at the
        front (re-acquiring the prefix through the index when enabled).
        The parent is consumed."""
        if not self._fork_ops:
            return False
        with self.spans.span("engine.fork") as sp:
            progressed, children = self._apply_fork_ops()
            sp.stat(children=children)
        return progressed

    def _apply_fork_ops(self) -> Tuple[bool, int]:  # repro-lint: engine-thread-only
        """:meth:`_apply_forks`' work: -> (progressed, children of the
        forks applied)."""
        with self._lock:
            ops = self._fork_ops[:]
            self._fork_ops.clear()
        deferred: List[Tuple[Request, List[Request]]] = []
        children = 0
        progressed = False
        for parent, kids in ops:
            if parent.done:
                from repro.api.errors import InvalidRequestError
                err = (parent.error if parent.error is not None else
                       InvalidRequestError(
                           f"fork parent {parent.request_id!r} is gone "
                           f"(cancelled, expired or failed before the fork "
                           f"applied)"))
                for c in kids:
                    self._finalize(c, err)
                progressed = True
                children += len(kids)
                continue
            try:
                pslot = self.slot_req.index(parent)
            except ValueError:
                deferred.append((parent, kids))     # parent still pending
                continue
            if pslot in self._prefills:
                # the parent is mid-chunked-prefill: no bootstrap logits
                # yet — the fork applies once its last chunk lands
                deferred.append((parent, kids))
                continue
            injected = bool(kids) and kids[0].uniforms is not None
            occupied = [r for r in self.slot_req
                        if r is not None and not r.hold]
            if kids and occupied \
                    and (occupied[0].uniforms is not None) != injected:
                deferred.append((parent, kids))     # uniform-source mismatch
                continue
            # snapshot the parent's resources, then consume it.  The temp
            # reference keeps the blocks alive across the parent's release
            # and MUST be dropped on every exit path — a user on_done hook
            # or a device error mid-bootstrap would otherwise leak it and
            # break the refcounts-drain-to-zero invariant for good.
            logits = self._held_logits[pslot]
            S = int(self._slot_pos[pslot])
            age0 = (float(parent.ages[-1])
                    if parent.ages is not None else 0.0)
            blocks: List[int] = []
            if self.paged:
                blocks = list(self._slot_blocks[pslot])
                tab = self._table[pslot].copy()
                self.pool.share(blocks)   # temp ref across the release
            try:
                self._apply_one_fork(parent, kids, pslot, logits, S, age0,
                                     blocks,
                                     tab if self.paged else None, injected)
            finally:
                if self.paged:
                    self.pool.release(blocks)        # drop the temp ref
            progressed = True
            children += len(kids)
        if deferred:
            with self._lock:
                self._fork_ops[:0] = deferred
        return progressed, children

    def _apply_one_fork(self, parent: Request, kids: List[Request],  # repro-lint: engine-thread-only
                        pslot: int, logits, S: int, age0: float,
                        blocks: List[int], tab, injected: bool) -> None:
        """Consume one held parent: release its slot, land as many children
        as there are free slots (shared blocks / copied row + one jitted
        bootstrap), queue the rest.  The caller holds the temp block
        reference and releases it in a ``finally``."""
        self._release_slot(pslot)
        self._finalize(parent)
        self.forks += 1
        free = [i for i, r in enumerate(self.slot_req) if r is None]
        k = min(len(kids), len(free))
        wave, rest = kids[:k], kids[k:]
        if rest:
            with self._lock:
                self.pending[:0] = rest
        if wave:
            wave_slots = free[:k]
            # a hold admitted THIS step may have queued its own slot for
            # deactivation; a child landing there must stay active
            self._deactivate = [s for s in self._deactivate
                                if s not in wave_slots]
            if self.paged:
                for c, s in zip(wave, wave_slots):
                    self.pool.share(blocks)
                    self._slot_blocks[s] = list(blocks)
                    self._table[s] = tab
                self._table_dirty = True
            else:
                kb = _next_pow2(k)
                dstp = np.full((kb,), pslot, np.int32)  # pad: self-copy
                dstp[:k] = wave_slots
                self.cache = _fork_copy_rows_jit(
                    self.cache, jnp.int32(pslot), jnp.asarray(dstp),
                    jnp.int32(S - 1))
            kb = _next_pow2(k)
            lg_b = jnp.broadcast_to(logits[None], (kb, self._kn.vocab))
            age0_a = jnp.full((kb,), age0, jnp.float32)
            lens_a = jnp.full((kb,), S, jnp.int32)
            mn = np.full((kb,), 1, np.int32)
            for j, c in enumerate(wave):
                mn[j] = c.max_new
            if injected:
                u = np.full((kb, self._kn.vocab), 0.5, np.float32)
                for j, c in enumerate(wave):
                    u[j] = c.uniforms[0]
                rows, packed = _fork_rows_jit(
                    lg_b, jnp.asarray(u), age0_a, lens_a,
                    jnp.asarray(mn), kn=self._kn)
            else:
                rows, packed, self._rng = _fork_rows_rng_jit(
                    lg_b, self._rng, age0_a, lens_a, jnp.asarray(mn),
                    kn=self._kn)
            ids = jnp.asarray(np.asarray(wave_slots, np.int32))
            self._state = _commit_jit(
                self._state, ids,
                jax.tree_util.tree_map(lambda a: a[:k], rows))
            self.admit_batches += 1
            arr = self._fetch(packed)
            now = time.monotonic()
            for j, (c, s) in enumerate(zip(wave, wave_slots)):
                self.slot_req[s] = c
                self._slot_pos[s] = S
                self._landed(c, now)
                self.events_admit += self._apply_host(c, s, arr[:, j])

    def _apply_host(self, req: Request, slot: int, col: np.ndarray) -> int:
        """One slot's column of a packed sync onto its request: -> the
        number of events emitted (0 or 1)."""
        evt, age, emit, finished = col
        emitted = int(emit >= 0.5)
        if emitted:
            if not req.out_tokens:
                req.t_first = time.monotonic()
            req.out_tokens.append(int(evt))
            if self.is_delphi:
                req.out_ages.append(float(age))
            if req.on_event is not None:
                req.on_event(int(evt), float(age) if self.is_delphi else None)
        if finished >= 0.5:
            self._release_slot(slot)     # returns paged blocks to the pool
            self._finalize(req)
        return emitted

    # -- the tick ------------------------------------------------------------
    def step(self) -> bool:  # repro-lint: engine-thread-only hot-path
        """One engine tick: control pass (cancel/timeout), admit pending,
        apply fork ops, grow/COW/preempt paged blocks, decode+sample all
        non-held slots in-graph.  The whole is one ``engine.step`` span,
        each phase a span inside it."""
        with self.spans.span("engine.step"):
            return self._step()

    def _step(self) -> bool:  # repro-lint: engine-thread-only hot-path
        span = self.spans.span
        with span("engine.control"):
            self._apply_control()
            self._flush_slot_updates()   # deactivations BEFORE slots reuse
        self._admit()
        forked = self._apply_forks()  # parents admitted above fork NOW
        chunked = False
        if self._prefills:
            with span("engine.prefill_chunks"):
                chunked = self._run_prefill_chunks()  # budgeted
        if self.paged:
            with span("engine.blocks"):
                self._ensure_blocks()    # may COW shared blocks or preempt
        with span("engine.control"):
            self._flush_slot_updates()
        active = [i for i, r in enumerate(self.slot_req)
                  if r is not None and not r.hold
                  and i not in self._prefills]
        if not active:
            return forked or chunked
        self.ticks += 1
        self.slot_ticks += len(active)
        self.peak_active = max(self.peak_active, len(active))
        injected = [i for i in active if self.slot_req[i].uniforms is not None]
        if injected and len(injected) != len(active):
            raise ValueError("cannot mix uniform-injected and RNG-sampled "
                             "requests in one tick")
        if injected:
            with span("engine.uniforms"):
                u = np.full((self.slots, self.cfg.vocab_size), 0.5,
                            np.float32)
                for i in active:
                    r = self.slot_req[i]
                    u[i] = r.uniforms[len(r.out_tokens)]
                u = jax.device_put(u, self.device)
            with span("engine.tick.dispatch", program=_tick_u_jit.__name__):
                (self.cache, self._state, packed,
                 self.last_logits) = _tick_u_jit(
                    self.params, self.cache, self._state, u,
                    cfg=self.cfg, kn=self._kn)
        else:
            with span("engine.tick.dispatch", program=_tick_rng_jit.__name__):
                (self.cache, self._state, packed, self.last_logits,
                 self._rng) = _tick_rng_jit(
                    self.params, self.cache, self._state, self._rng,
                    cfg=self.cfg, kn=self._kn)
        self._slot_pos[active] += 1  # mirror the in-graph step advance
        with span("engine.tick.wait"):
            arr = self._fetch(packed)    # ONE sync per tick
        with span("engine.apply_host"):
            emitted = 0
            for slot in active:
                emitted += self._apply_host(self.slot_req[slot], slot,
                                            arr[:4, slot])
            self.events_tick += emitted
            if len(arr) > 4:
                self.expert_tokens += int(arr[4, active].sum())
        return True

    def run(self, max_ticks: int = 10_000) -> List[Request]:  # repro-lint: engine-thread-only
        if self.running:
            raise RuntimeError(
                "engine is ticking on its background thread (start() was "
                "called): submit() and wait on the request instead of run()")
        ticks = 0
        while (self.pending or any(r is not None for r in self.slot_req)) \
                and ticks < max_ticks:
            self.step()
            ticks += 1
        return self.completed

