"""The traffic generator: a seed fixes the inputs, every seed offers the
same sizes and arrival gaps in another order, and a mix's parts are files
found by name."""
import json
import os

import numpy as np
import pytest

import small
from harness import byname, traffic

MIXES = ["clinic", "decode", "cohort"]


def mix(name):
    with open(os.path.join(small.ROOT, "traffic", name + ".json")) as f:
        return json.load(f)


def sizes(m, seed, n=200):
    specs = traffic.specs(m, seed, 4.0, 20.0)[:n]
    return [(len(traffic.prompt(m, s, seed)[0]), s.max_new) for s in specs]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_inputs(name):
    m = mix(name)
    a, b = traffic.specs(m, 7, 4.0, 20.0), traffic.specs(m, 7, 4.0, 20.0)
    assert a == b
    for s in a[:20]:
        ta, aa = traffic.prompt(m, s, 7)
        tb, ab = traffic.prompt(m, s, 7)
        assert np.array_equal(ta, tb)
        assert (aa is None and ab is None) or np.array_equal(aa, ab)


def test_open_loop_seeds_share_arrivals():
    m = mix("clinic")
    rate = m["arrivals"]["rate"]
    for seed in (1, 2**31 + 5):
        due = np.array([s.due for s in traffic.specs(m, seed, 4.0, 20.0)])
        assert np.sum((due >= 4.0) & (due < 24.0)) == round(rate * 20)
        assert np.all(np.diff(due) >= 0)
    a = sorted(np.diff([s.due for s in traffic.specs(m, 1, 4.0, 20.0)]))
    b = sorted(np.diff([s.due for s in traffic.specs(m, 9, 4.0, 20.0)]))
    assert np.allclose(a[:50], b[:50])


@pytest.mark.parametrize("name", ["clinic", "decode", "cohort"])
def test_seeds_permute_one_pool_of_sizes(name):
    m = mix(name)
    a = traffic.specs(m, 3, 4.0, 20.0)
    b = traffic.specs(m, 2**33 + 1, 4.0, 20.0)
    key = (lambda s: (s.patient, s.cut, s.length, s.max_new))
    n = (len(a) if m["arrivals"]["kind"] == "poisson"
         else int(m["arrivals"]["pool"]))
    for head in (n, len(a) // n * n):
        assert sorted(map(key, a[:head])) == sorted(map(key, b[:head]))
    assert [key(s) for s in a] != [key(s) for s in b]


def test_closed_stream_repeats_sizes_on_fresh_histories():
    m = mix("cohort")
    pool = int(m["arrivals"]["pool"])
    s = traffic.specs(m, 5, 4.0, 20.0)
    assert len({x.patient for x in s}) == len(s)
    assert sorted(x.cut for x in s[:pool]) == \
        sorted(x.cut for x in s[pool:2 * pool])


def test_uniforms_are_a_function_of_seed_and_index():
    u = traffic.uniforms(5, 3, 4, 10)
    assert u.shape == (4, 10) and u.dtype == np.float32
    assert np.array_equal(u, traffic.uniforms(5, 3, 4, 10))
    assert not np.array_equal(u, traffic.uniforms(5, 4, 4, 10))
    assert traffic.uniforms(5, 3, 4, 10, n=2).shape == (2, 4, 10)


@pytest.mark.parametrize("name", MIXES)
def test_each_part_of_a_mix_is_a_file_found_by_name(name):
    m = mix(name)
    for kind, part in (("sources", m["source"]["kind"]),
                       ("arrivals", m["arrivals"]["kind"]),
                       ("drivers", m["driver"])):
        assert os.path.isfile(os.path.join(small.ROOT, kind, part + ".py"))
        assert byname.load(kind, part) is byname.load(kind, part)


def test_a_new_source_is_a_new_file(tmp_path, monkeypatch):
    (tmp_path / "sources").mkdir()
    (tmp_path / "sources" / "ramp.py").write_text(
        "import numpy as np\n"
        "def prompt(src, spec, seed):\n"
        "    return np.arange(spec.length, dtype=np.int32), None\n"
        "def span(src):\n"
        "    return 1, 9\n")
    monkeypatch.setattr(byname, "ROOT", str(tmp_path))
    monkeypatch.setattr(byname, "_LOADED", {})
    m = {"source": {"kind": "ramp"}}
    spec = traffic.Spec(index=0, patient=-1, cut=0.0, length=5, max_new=1)
    assert traffic.prompt(m, spec, 1)[0].tolist() == [0, 1, 2, 3, 4]
    assert traffic.length_span(m) == (1, 9)
    with pytest.raises(ValueError, match="no sources named 'gone'"):
        traffic.prompt({"source": {"kind": "gone"}}, spec, 1)


def test_server_settings_reach_the_engine():
    import jax
    from harness import program
    from repro.models import init_params
    _, _, run = small.small_run("delphi-2m.cohort")
    mcfg = program.model_config(run.cfg)
    params = init_params(mcfg, jax.random.PRNGKey(0))
    be = program.engine_backend(
        params, mcfg, {"slots": 2, "max_context": 64,
                       "prefill_chunk_tokens": 32},
        run.cfg["served"], seed=3)
    eng = be.engine
    assert (eng.slots, eng.max_context, eng.block_size) == (2, 64, 16)
    assert eng.prefill_chunk_tokens == 32
