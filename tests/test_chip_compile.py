"""Compile rehearsals for the TPU v5e: the served programs at published
widths, compiled for a described (not attached) chip.

Nothing runs, so these say nothing about results or times; they catch what
the chip's compiler refuses (block shapes off the tiling, too much fast
memory) before a chip run does.  The topology is described inside a
fixture, never at import: only one process may load the TPU library, and
under several test workers only the worker given this file does.  Every
kernel is compiled with ``interpret=False`` passed explicitly, and each
program where a kernel is meant to be must hold a ``tpu_custom_call``.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import init_params, make_decode_cache, make_paged_decode_cache
from repro.serve.engine import _Knobs, _prefill_u_jit, _tick_u_jit

SLOTS, CONTEXT, BLOCK = 8, 512, 16       # repro-serve's defaults, paged


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a rehearsal's executable cannot be read back without a chip: keep
    # them out of any persistent cache the environment turned on
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def delphi():
    cfg = get_config("delphi-2m")
    assert cfg.dtype == "bfloat16"            # the served dtype on the chip
    return cfg


def _on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(fn, *args, **kw):
    return jax.jit(fn, static_argnames=tuple(kw)).lower(*args, **kw).compile()


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _engine_args(cfg, sharding, paged: bool):
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    if paged:
        cache = jax.eval_shape(lambda: make_paged_decode_cache(
            cfg, SLOTS, CONTEXT, num_blocks=SLOTS * CONTEXT // BLOCK + 1,
            block_size=BLOCK))
    else:
        cache = jax.eval_shape(
            lambda p: make_decode_cache(p, cfg, SLOTS, CONTEXT), params)
    i32, f32 = jnp.int32, jnp.float32
    state = {"last": (SLOTS,), "age": (SLOTS,), "step": (SLOTS,),
             "n_emitted": (SLOTS,), "max_new": (SLOTS,), "active": (SLOTS,)}
    dtypes = {"last": i32, "age": f32, "step": i32, "n_emitted": i32,
              "max_new": i32, "active": jnp.bool_}
    state = {k: jax.ShapeDtypeStruct(v, dtypes[k]) for k, v in state.items()}
    u = jax.ShapeDtypeStruct((SLOTS, cfg.vocab_size), f32)
    return _on((params, cache, state, u), sharding)


def _knobs(cfg, sampler: str):
    return _Knobs(slots=SLOTS, max_context=CONTEXT, is_delphi=True,
                  use_pallas=sampler == "pallas", inv_temp=1.0,
                  max_age=cfg.max_age, death_token=cfg.death_token,
                  vocab=cfg.vocab_size)


@pytest.mark.parametrize("paged", [False, True], ids=["ring", "paged"])
@pytest.mark.parametrize("sampler", ["jnp", "pallas"])
def test_engine_tick_compiles(one_chip, delphi, paged, sampler):
    args = _engine_args(delphi, one_chip, paged)
    c = _tick_u_jit.lower(*args, cfg=delphi,
                          kn=_knobs(delphi, sampler)).compile()
    # the fused sampler is the Mosaic kernel, not the interpreter
    assert (_kernels(c) > 0) == (sampler == "pallas")


@pytest.mark.parametrize("sampler", ["jnp", "pallas"])
def test_engine_prefill_compiles(one_chip, delphi, sampler):
    nb, sb = 4, 64                          # an admission bucket
    params = jax.eval_shape(lambda k: init_params(delphi, k),
                            jax.random.PRNGKey(0))
    i32, f32 = jnp.int32, jnp.float32
    args = (params, jax.ShapeDtypeStruct((nb, sb), i32),
            jax.ShapeDtypeStruct((nb, sb), f32),
            *(jax.ShapeDtypeStruct((nb,), d) for d in (i32, f32, i32, i32)),
            jax.ShapeDtypeStruct((nb, delphi.vocab_size), f32))
    c = _prefill_u_jit.lower(*_on(args, one_chip), cfg=delphi,
                             kn=_knobs(delphi, sampler)).compile()
    assert (_kernels(c) > 0) == (sampler == "pallas")


@pytest.mark.parametrize("batch", [1, 4, 8, 16])
def test_tte_sample_compiles(one_chip, delphi, batch):
    x = jax.ShapeDtypeStruct((batch, delphi.vocab_size), jnp.float32,
                             sharding=one_chip)
    c = _compile(ops.tte_sample, x, x, interpret=False)
    assert _kernels(c) == 1


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_decode_attention_compiles(one_chip, delphi, dtype):
    H, hd = delphi.n_heads, delphi.head_dim
    NB, nbs = SLOTS * CONTEXT // BLOCK + 1, CONTEXT // BLOCK
    i32 = jnp.int32
    args = (jax.ShapeDtypeStruct((SLOTS, H, hd), dtype),
            jax.ShapeDtypeStruct((NB, BLOCK * H * hd), dtype),
            jax.ShapeDtypeStruct((NB, BLOCK * H * hd), dtype),
            jax.ShapeDtypeStruct((SLOTS, nbs), i32),
            jax.ShapeDtypeStruct((NB, BLOCK), i32),
            jax.ShapeDtypeStruct((SLOTS,), i32))
    c = _compile(ops.paged_decode_attention, *_on(args, one_chip),
                 interpret=False)
    assert _kernels(c) == 1


@pytest.mark.parametrize("seq", [256, 512])
def test_flash_attention_compiles(one_chip, delphi, seq):
    x = jax.ShapeDtypeStruct((4, delphi.n_heads, seq, delphi.head_dim),
                             jnp.bfloat16, sharding=one_chip)
    c = _compile(ops.flash_attention, x, x, x, interpret=False)
    assert _kernels(c) == 1


def _moonlight():
    """Moonlight-16B-A3B as its cell serves it: 8 of 64 experts held,
    bfloat16 weights."""
    return get_config("moonlight-16b-a3b").replace(
        n_experts=8, n_router_experts=64, param_dtype="bfloat16")


def _cell_tick(cfg, slots, ctx, blocks, sharding, *, delphi):
    """The engine tick compiled for a described v5e at a cell's own slots,
    context and pool blocks; -> (compiled, the pool's key plane)."""
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: make_paged_decode_cache(
        cfg, slots, ctx, num_blocks=blocks, block_size=BLOCK))
    i32, f32 = jnp.int32, jnp.float32
    dtypes = {"last": i32, "age": f32, "step": i32, "n_emitted": i32,
              "max_new": i32, "active": jnp.bool_}
    state = {k: jax.ShapeDtypeStruct((slots,), d) for k, d in dtypes.items()}
    u = jax.ShapeDtypeStruct((slots, cfg.vocab_size), f32)
    kn = _Knobs(slots=slots, max_context=ctx, is_delphi=delphi,
                use_pallas=False, inv_temp=1.0 if delphi else 1e6,
                max_age=cfg.max_age, death_token=cfg.death_token,
                vocab=cfg.vocab_size)
    c = _tick_u_jit.lower(*_on((params, cache, state, u), sharding),
                          cfg=cfg, kn=kn).compile()
    return c, cache["self"].k


def test_moonlight_tick_compiles_and_fits_one_chip(one_chip):
    """Moonlight-16B-A3B's decode tick serving long documents (32 slots x
    4,096, a latent pool of 6,145 blocks, bfloat16 weights with 8 of 64
    experts held): the compiler takes it, and weights, pool and the
    tick's temporaries fit in one v5e's 16 GiB with room to spare."""
    c, plane = _cell_tick(_moonlight(), 32, 4096, 6145, one_chip,
                          delphi=False)
    assert plane.shape == (27, 6145, BLOCK * 576)
    mem = c.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 15 * 2**30, used


#: the one-chip cells' ticks: configuration, slots, context, pool blocks
_CELL_TICKS = {
    "delphi-2m.cohort": ("delphi-2m", 256, 256, 4097),
    "h2o-danube-1.8b.decode": ("h2o-danube-1.8b", 32, 1024, 2049),
    "moonlight-16b-a3b.longctx": ("moonlight-16b-a3b", 32, 4096, 6145),
}

#: ops that may have a whole pool plane as their output: the tick's
#: in-place writes and the program's plumbing, never a copy of the pool
_PLANE_OPS = {"parameter", "get-tuple-element", "tuple", "bitcast",
              "dynamic-update-slice"}


def _plane_copies(compiled, plane):
    """Ops of ``compiled`` whose output is a whole pool plane, other than
    in-place writes and plumbing."""
    dims = ",".join(map(str, plane.shape))
    found = re.findall(r"%([\w.\-]+) = \w+\[" + dims + r"\]\S* ([\w\-]+)\(",
                       compiled.as_text())
    assert found, f"no op holds the {plane.shape} pool plane"
    return [(n, op) for n, op in found if op not in _PLANE_OPS]


@pytest.mark.parametrize("cell", list(_CELL_TICKS))
def test_tick_holds_the_pool_in_place(one_chip, cell):
    """At each one-chip cell's own shapes the tick neither copies nor
    transposes a whole pool plane (the write updates the donated pool in
    place, the gathers read it as held), and its temporaries stay below one
    plane.  Danube holds float32 weights that the tick casts to bfloat16,
    a temporary larger than its pool plane, so its bound adds them."""
    name, slots, ctx, blocks = _CELL_TICKS[cell]
    cfg = _moonlight() if name == "moonlight-16b-a3b" else get_config(name)
    c, plane = _cell_tick(cfg, slots, ctx, blocks, one_chip,
                          delphi=name == "delphi-2m")
    copies = _plane_copies(c, plane)
    assert not copies, f"whole-plane ops in the tick: {copies}"
    plane_bytes = plane.size * plane.dtype.itemsize
    cast = 0
    if cfg.param_dtype == "float32":
        params = jax.eval_shape(lambda k: init_params(cfg, k),
                                jax.random.PRNGKey(0))
        cast = sum(a.size for a in jax.tree_util.tree_leaves(params)) * 2
    temp = c.memory_analysis().temp_size_in_bytes
    assert temp < plane_bytes + cast, (temp, plane_bytes, cast)


@pytest.mark.parametrize("op", ["insert", "cow", "suffix"])
def test_block_ops_hold_the_pool_in_place(one_chip, delphi, op):
    """The engine's other pool writers at the cohort cell's pool (4,097
    blocks of Delphi-2M rows): admission's block insert, copy-on-write and
    a chunked-prefill step update the donated pool in place and read it as
    held — no whole-plane copy, temporaries below one plane."""
    from repro.models import LayerCache
    from repro.serve.engine import (_cow_block_jit, _insert_blocks_jit,
                                    _suffix_chunk_jit)
    slots, ctx, blocks = 256, 256, 4097
    cache = jax.eval_shape(lambda: make_paged_decode_cache(
        delphi, slots, ctx, num_blocks=blocks, block_size=BLOCK))
    plane = cache["self"].k
    i32, f32 = jnp.int32, jnp.float32
    L, H, hd = delphi.n_layers, delphi.n_kv_heads, delphi.head_dim
    cache = _on(cache, one_chip)
    if op == "insert":
        rows = LayerCache(*(jax.ShapeDtypeStruct(s, d) for s, d in (
            ((L, 4, H, ctx, hd), plane.dtype), ((L, 4, H, ctx, hd),
                                                plane.dtype),
            ((L, 4, ctx), i32))))
        c = _insert_blocks_jit.lower(
            cache, _on({"self": rows}, one_chip),
            jax.ShapeDtypeStruct((4, 8), i32, sharding=one_chip),
            nblk=8).compile()
    elif op == "cow":
        one = jax.ShapeDtypeStruct((), i32, sharding=one_chip)
        c = _cow_block_jit.lower(cache, one, one).compile()
    else:
        params = jax.eval_shape(lambda k: init_params(delphi, k),
                                jax.random.PRNGKey(0))
        sc, ctx_blocks = 64, 8
        args = (params, cache, *(jax.ShapeDtypeStruct(s, d) for s, d in (
            ((1, sc), i32), ((1, sc), f32), ((1, sc), i32),
            ((1, ctx_blocks), i32), ((1, sc // BLOCK), i32), ((1,), i32))))
        c = _suffix_chunk_jit.lower(*_on(args, one_chip),
                                    cfg=delphi).compile()
    copies = _plane_copies(c, plane)
    assert not copies, f"whole-plane ops in {op}: {copies}"
    temp = c.memory_analysis().temp_size_in_bytes
    assert temp < plane.size * plane.dtype.itemsize, temp
