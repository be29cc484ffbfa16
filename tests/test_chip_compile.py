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
            jax.ShapeDtypeStruct((NB, H, BLOCK, hd), dtype),
            jax.ShapeDtypeStruct((NB, H, BLOCK, hd), dtype),
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


def test_moonlight_tick_compiles_and_fits_one_chip(one_chip):
    """Moonlight-16B-A3B's decode tick serving long documents (32 slots x
    4,096, a latent pool of 6,145 blocks, bfloat16 weights with 8 of 64
    experts held): the compiler takes it, and weights, pool and the
    tick's temporaries (among them the scatter's copy of the whole pool)
    fit in one v5e's 16 GiB with room to spare."""
    cfg = get_config("moonlight-16b-a3b").replace(
        n_experts=8, n_router_experts=64, param_dtype="bfloat16")
    slots, ctx, blocks = 32, 4096, 6145
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: make_paged_decode_cache(
        cfg, slots, ctx, num_blocks=blocks, block_size=BLOCK))
    assert cache["self"].k.shape == (27, blocks, 1, BLOCK, 576)
    i32, f32 = jnp.int32, jnp.float32
    dtypes = {"last": i32, "age": f32, "step": i32, "n_emitted": i32,
              "max_new": i32, "active": jnp.bool_}
    state = {k: jax.ShapeDtypeStruct((slots,), d) for k, d in dtypes.items()}
    u = jax.ShapeDtypeStruct((slots, cfg.vocab_size), f32)
    kn = _Knobs(slots=slots, max_context=ctx, is_delphi=False,
                use_pallas=False, inv_temp=1e6, max_age=cfg.max_age,
                death_token=cfg.death_token, vocab=cfg.vocab_size)
    c = _tick_u_jit.lower(*_on((params, cache, state, u), one_chip),
                          cfg=cfg, kn=kn).compile()
    mem = c.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 15 * 2**30, used
