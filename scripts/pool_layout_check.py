#!/usr/bin/env python3
"""Byte check of the paged pool's row layout, at the served cells' shapes.

    python3 scripts/pool_layout_check.py [--cells longctx,decode,cohort]
        [--small] [--out FILE]

The engine holds each plane of its paged KV pool as one token-major row
per (layer, block), ``(L, NB, bs * Hkv * hd)`` (``repro.models.attention.
PagedCache``).  For each cell's pool shape this script starts two pools
from the same random contents: one held per head, ``(L, NB, Hkv, bs, hd)``,
driven through this file's copies of the per-head pool functions (one
scatter per write, as that layout was served), and one held as rows,
driven through the engine's own functions.  Both go through the same
sequence:

* 64 ticks' writes for 32 slots: positions crossing block edges, a wrapped
  ring, block ids up to NB - 1, a forked slot writing its own block past
  its parent's shared ones, and idle slots writing the trash block 0;
* an admission insert of the cell's longest prompt (3,583 tokens at
  ``longctx``), its bucket padded into the trash block;
* a copy-on-write of the fork's shared block;
* a reset of freshly allocated blocks' positions.

Then every layer's rows, and every layer's gathered ring view of all 32
slots (the dense layer 0 of Moonlight included), must be equal bit for
bit.  One JSON line per cell, with mismatch counts, goes to standard
output and to ``--out``; the exit code is 1 if any count is not 0.

The shapes are the cells' own (3.06 GB a pool at ``longctx``): run on the
chip.  ``--small`` runs a cut of every shape (any backend, the CPU tests).
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if __name__ == "__main__":          # run as a script: this checkout's package
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.models.attention import (LayerCache, PagedCache,  # noqa: E402
                                    PagedLayerView, cache_write_stacked,
                                    paged_gather_layer, paged_ring_index)
from repro.serve.engine import (_cow_block_jit, _insert_blocks_jit,  # noqa: E402
                                _reset_pos_jit)

BS = 16
SLOTS = 32
TICKS = 64

#: each cell's pool: layers, blocks, KV heads, key and value widths, the
#: context each slot's table spans, and the cell's longest prompt
CELLS = {
    "longctx": dict(L=27, NB=6145, heads=1, dk=576, dv=0, ctx=4096,
                    prompt=3583),
    "decode": dict(L=24, NB=2049, heads=8, dk=80, dv=80, ctx=1024,
                   prompt=767),
    "cohort": dict(L=12, NB=4097, heads=12, dk=10, dv=10, ctx=256,
                   prompt=70),
}


def small(shape: dict) -> dict:
    """The same row widths over few layers, blocks and positions."""
    return dict(shape, L=2, NB=161, ctx=128, prompt=100)


# ---------------------------------------------------------------------------
# The per-head layout's functions: the oracle (``tests/test_attention.py``
# holds the engine's functions to them too)
# ---------------------------------------------------------------------------
@jax.jit
def head_rows(a):
    """(L, NB, Hkv, bs, hd) per-head blocks -> (L, NB, bs * Hkv * hd)."""
    L, NB, H, bs, hd = a.shape
    return jax.lax.map(
        lambda x: jnp.swapaxes(x, 1, 2).reshape(NB, bs * H * hd), a)


def head_write(k, v, pos, table, k_news, v_news, step):
    """The tick's write into per-head planes: one scatter."""
    bs = k.shape[3]
    jb = (step % (table.shape[1] * bs)) // bs
    blk = jnp.take_along_axis(table, jb[:, None], axis=1)[:, 0]
    dst = jnp.where(blk >= 0, blk, 0)
    off = step % bs
    k = k.at[:, dst, :, off, :].set(k_news[:, :, 0].transpose(1, 0, 2, 3))
    v = v.at[:, dst, :, off, :].set(v_news[:, :, 0].transpose(1, 0, 2, 3))
    return k, v, pos.at[dst, off].set(step)


def head_insert(k, v, pos, rows: LayerCache, dst, *, nblk):
    """Ring prefill rows (L, n, Hkv, W, hd) into per-head pool blocks."""
    bs = k.shape[3]
    n = dst.shape[0]
    d = dst.reshape(-1)

    def blocks(a):
        L, _, H, _, hd = a.shape
        a = a[:, :, :, :nblk * bs, :].reshape(L, n, H, nblk, bs, hd)
        return a.transpose(0, 1, 3, 2, 4, 5).reshape(L, n * nblk, H, bs, hd)
    return (k.at[:, d].set(blocks(rows.k)), v.at[:, d].set(blocks(rows.v)),
            pos.at[d].set(rows.pos[0, :, :nblk * bs].reshape(n * nblk, bs)))


def head_cow(k, v, pos, src, dst):
    """Copy-on-write of block ``src`` into ``dst``, every layer."""
    return (k.at[:, dst].set(k[:, src]), v.at[:, dst].set(v[:, src]),
            pos.at[dst].set(pos[src]))


def head_reset(pos, ids):
    """Fresh blocks' positions emptied."""
    return pos.at[ids].set(-1)


#: the oracle as the check runs it, the per-head planes donated
_donated = functools.partial(jax.jit, donate_argnums=(0, 1, 2))
write_heads = _donated(head_write)
insert_heads = _donated(head_insert, static_argnames=("nblk",))
cow_heads = _donated(head_cow)
reset_heads = jax.jit(head_reset, donate_argnums=(0,))


@jax.jit
def head_view(k, v, layer, blocks, ring_pos) -> LayerCache:
    """One layer's ring view, gathered by whole per-head blocks."""
    def ring(plane):
        pool = plane[layer]
        NB, H, bs, hd = pool.shape
        B, nbs = blocks.shape
        rows = pool.reshape(NB, H, bs * hd)[blocks]
        return rows.transpose(0, 2, 1, 3).reshape(B, H, nbs * bs, hd)
    return LayerCache(k=ring(k), v=ring(v), pos=ring_pos)


# ---------------------------------------------------------------------------
# The check
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=(1, 2))
def random_pool(key, shape, dtype):
    """Per-layer random blocks, made one layer at a time."""
    L = shape[0]
    return jax.lax.map(lambda i: jax.random.normal(
        jax.random.fold_in(key, i), shape[1:], dtype), jnp.arange(L))


@functools.partial(jax.jit, static_argnums=(4,))
def row_view(k, v, layer, index, heads: int) -> LayerCache:
    return paged_gather_layer(PagedLayerView(k, v, layer, *index, heads))


@jax.jit
def differ(a, b):
    """How many elements of ``a`` and ``b`` differ in their bits."""
    if a.dtype == jnp.bfloat16:
        a = jax.lax.bitcast_convert_type(a, jnp.uint16)
        b = jax.lax.bitcast_convert_type(b, jnp.uint16)
    return jnp.sum(a != b)


@jax.jit
def rows_differ(rows, heads, layer):
    """Elements of one layer's rows that differ from its per-head blocks."""
    NB, R = rows.shape[1:]
    return differ(rows[layer],
                  jnp.swapaxes(heads[layer], 1, 2).reshape(NB, R))


def tables(rng, sh: dict):
    """Block tables and first positions for SLOTS slots and TICKS ticks."""
    NB, nbs, bs = sh["NB"], sh["ctx"] // BS, BS
    W = nbs * bs
    table = np.full((SLOTS, nbs), -1, np.int32)
    free = list(rng.permutation(np.arange(1, NB - 1)))
    p0 = rng.integers(0, W - TICKS, SLOTS)
    p0[0] = W - TICKS - 3               # slot 0 ends in its last block
    p0[2] = W - TICKS // 2              # slot 2 wraps the ring
    idle = [3, 4, 5, 6]                 # no table at all: trash writes
    # slot 0 holds its whole prefix, and its last block is pool block NB - 1
    table[0, :] = [free.pop() for _ in range(nbs)]
    table[0, nbs - 1] = NB - 1
    # slot 1 forks slot 0: shares its first blocks, writes its own after
    share = (p0[0] // bs) // 2
    table[1, :share] = table[0, :share]
    p0[1] = share * bs
    for b in range(1, SLOTS):
        if b in idle:
            continue
        for p in range(p0[b], p0[b] + TICKS):
            jb = (p % W) // bs
            if table[b, jb] < 0:
                table[b, jb] = free.pop()
    # slot 7 has a table, but its write column is unallocated: trash
    table[7, (p0[7] % W) // bs:] = -1
    table[7, :(p0[7] % W) // bs] = [free.pop()
                                    for _ in range((p0[7] % W) // bs)]
    return table, p0, free


def check_cell(name: str, sh: dict, seed: int) -> dict:
    t0 = time.monotonic()
    L, NB, H, dk, dv = sh["L"], sh["NB"], sh["heads"], sh["dk"], sh["dv"]
    nbs = sh["ctx"] // BS
    W = nbs * BS
    bf16 = jnp.bfloat16
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    table, p0, free = tables(rng, sh)

    hk = random_pool(jax.random.fold_in(key, 1), (L, NB, H, BS, dk), bf16)
    hv = random_pool(jax.random.fold_in(key, 2), (L, NB, H, BS, dv), bf16)
    pos0 = rng.integers(0, 4 * W, (NB, BS)).astype(np.int32)
    pos0[rng.random((NB, BS)) < 0.2] = -1
    hpos = jnp.asarray(pos0)
    pc = PagedCache(k=head_rows(hk), v=head_rows(hv), pos=jnp.asarray(pos0),
                    table=jnp.asarray(table))

    tab = jnp.asarray(table)
    for t in range(TICKS):
        step = jnp.asarray(p0 + t, jnp.int32)
        kk = jax.random.fold_in(key, 100 + t)
        kn = jax.random.normal(kk, (L, SLOTS, 1, H, dk), bf16)
        vn = jax.random.normal(jax.random.fold_in(kk, 1),
                               (L, SLOTS, 1, H, dv), bf16)
        hk, hv, hpos = write_heads(hk, hv, hpos, tab, kn, vn, step)
        pc = cache_write_stacked(pc, kn, vn, step)

    # admission: the longest prompt into fresh blocks, bucket padded with
    # the trash block; slot 3 (idle so far) then holds it
    P = sh["prompt"]
    nblk = -(-P // BS)
    nblk_pad = min(1 << (nblk - 1).bit_length(), nbs)
    ids = [free.pop() for _ in range(nblk)]
    dst = np.zeros((1, nblk_pad), np.int32)
    dst[0, :nblk] = ids
    kr = jax.random.fold_in(key, 7)
    ring = LayerCache(
        k=jax.random.normal(kr, (L, 1, H, W, dk), bf16),
        v=jax.random.normal(jax.random.fold_in(kr, 1), (L, 1, H, W, dv),
                            bf16),
        pos=jnp.broadcast_to(jnp.where(jnp.arange(W) < P, jnp.arange(W), -1),
                             (L, 1, W)).astype(jnp.int32))
    hk, hv, hpos = insert_heads(hk, hv, hpos, ring, jnp.asarray(dst),
                                nblk=nblk_pad)
    cache = _insert_blocks_jit({"self": pc}, {"self": ring},
                               jnp.asarray(dst), nblk=nblk_pad)
    table[3, :] = -1
    table[3, :nblk] = ids

    # copy-on-write: the fork's first shared block into a fresh one
    src, new = int(table[1, 0]), free.pop()
    hk, hv, hpos = cow_heads(hk, hv, hpos, jnp.int32(src), jnp.int32(new))
    cache = _cow_block_jit(cache, jnp.int32(src), jnp.int32(new))
    table[1, 0] = new

    # decode growth: fresh blocks' positions reset, padded with the trash
    grow = [free.pop() for _ in range(3)]
    rid = jnp.asarray(grow + [0] * 5, jnp.int32)
    hpos = reset_heads(hpos, rid)
    cache = _reset_pos_jit(cache, rid)
    for b, g in zip((8, 9, 10), grow):
        col = int(np.argmax(table[b] < 0)) if (table[b] < 0).any() else 0
        table[b, col] = g

    pc = cache["self"]
    index = paged_ring_index(pc.pos, jnp.asarray(table))
    hindex = paged_ring_index(hpos, jnp.asarray(table))
    out = {"rows_k": 0, "rows_v": 0, "pos": int(differ(pc.pos, hpos)),
           "view_k": 0, "view_v": 0, "view_pos": 0}
    for layer in range(L):
        i = jnp.int32(layer)
        out["rows_k"] += int(rows_differ(pc.k, hk, i))
        out["rows_v"] += int(rows_differ(pc.v, hv, i))
        got = row_view(pc.k, pc.v, jnp.int32(layer), index, H)
        want = head_view(hk, hv, jnp.int32(layer), *hindex)
        for part in ("k", "v", "pos"):
            out["view_" + part] += int(differ(getattr(got, part),
                                              getattr(want, part)))
    jax.block_until_ready(pc)
    dev = jax.devices()[0]
    return {"cell": name, "pool": [L, NB, BS * H * dk],
            "values": [L, NB, BS * H * dv], "layers_compared": L,
            "slots": SLOTS, "ticks": TICKS, "prompt": P,
            "pool_block_ids_max": int(table.max()), "mismatched": out,
            "equal": not any(out.values()),
            "device": {"platform": dev.platform, "kind": dev.device_kind},
            "seconds": round(time.monotonic() - t0, 1)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--seed", type=int, default=2147483911)
    ap.add_argument("--out", default=None)
    o = ap.parse_args(argv)
    if not o.small and jax.default_backend() == "cpu":
        print("the cells' pools are sized for the chip: pass --small on the "
              "CPU", file=sys.stderr)
        return 2
    rows = []
    for name in o.cells.split(","):
        sh = small(CELLS[name]) if o.small else CELLS[name]
        rows.append(check_cell(name, sh, o.seed))
        print(json.dumps(rows[-1]), flush=True)
    if o.out:
        os.makedirs(os.path.dirname(os.path.abspath(o.out)), exist_ok=True)
        with open(o.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0 if all(r["equal"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
