"""repro-lint (tools/analyze): per-rule fixtures + repo self-run.

Each rule gets a fixture it MUST flag (positive) and a near-identical one
it must NOT flag (negative), plus suppression/baseline semantics, a
self-run over ``src/repro`` asserting the tree is clean modulo the
committed baseline, and seeded-mutation checks that re-introduce the
exact bug classes RL005/RL006/RL007 exist to catch and assert each
yields exactly one finding.  Fixtures are parsed, never imported, so
they don't need to be runnable.
"""
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:       # tests run with PYTHONPATH=src;
    sys.path.insert(0, str(REPO_ROOT))   # `tools` lives at the repo root

from tools.analyze import baseline as baseline_mod  # noqa: E402
from tools.analyze import callgraph as callgraph_mod  # noqa: E402
from tools.analyze.cli import main as cli_main, run_lint  # noqa: E402
from tools.analyze.core import Project  # noqa: E402
from tools.analyze.wire import FROZEN_WIRE_V1  # noqa: E402


def make_project(tmp_path, files):
    """Write {relpath: source} and lint it (no baseline)."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return run_lint(tmp_path)


def rules_of(res):
    return [f.rule for f in res.new]


# ---------------------------------------------------------------------------
# RL001 lock discipline
# ---------------------------------------------------------------------------
RL001_POSITIVE = """
    import threading

    class Eng:
        def __init__(self):
            self._lock = threading.Lock()
            self.pending = []        # guarded-by: _lock
            self.slot_req = []       # guarded-by: engine-thread

        def submit(self, r):
            with self._lock:
                self.pending.append(r)      # locked: ok

        def bad_queue_peek(self):
            return len(self.pending)        # RL001: unlocked read

        def bad_slot_peek(self):
            return self.slot_req            # RL001: wrong thread

        def step(self):  # repro-lint: engine-thread-only
            return self.pending, self.slot_req   # both exempt

        def holds(self):  # repro-lint: holds=_lock
            return self.pending[0]          # caller owns the lock: ok
"""


def test_rl001_flags_unguarded_access(tmp_path):
    res = make_project(tmp_path, {"src/repro/serve/eng.py": RL001_POSITIVE})
    assert rules_of(res) == ["RL001", "RL001"]
    msgs = " ".join(f.message for f in res.new)
    assert "bad_queue_peek" in msgs and "bad_slot_peek" in msgs
    # the disciplined accesses stay silent
    assert "submit" not in msgs and "`Eng.step`" not in msgs


def test_rl001_foreign_access(tmp_path):
    res = make_project(tmp_path, {
        "src/repro/serve/eng.py": RL001_POSITIVE,
        "src/repro/serve/web.py": """
            class Handler:
                def healthz(self, eng):
                    return len(eng.pending)     # RL001: foreign access
        """,
        "src/repro/serve/other.py": """
            class RefEngine:
                def __init__(self):
                    self.pending = []           # its own field, unguarded

                def drain(self):
                    return self.pending         # not a foreign access
        """,
    })
    foreign = [f for f in res.new if "foreign access" in f.message]
    assert len(foreign) == 1
    assert foreign[0].path.endswith("web.py")


def test_rl001_negative_all_locked(tmp_path):
    res = make_project(tmp_path, {"src/repro/serve/eng.py": """
        import threading

        class Eng:
            def __init__(self):
                self._lock = threading.Lock()
                self.pending = []    # guarded-by: _lock

            def submit(self, r):
                with self._lock:
                    self.pending.append(r)
    """})
    assert res.new == []


# ---------------------------------------------------------------------------
# RL002 trace purity
# ---------------------------------------------------------------------------
def test_rl002_flags_host_syncs(tmp_path):
    res = make_project(tmp_path, {"src/repro/core/fn.py": """
        import functools
        import jax
        import numpy as np

        @jax.jit
        def bad(x):
            if x > 0:            # tracer-dependent control flow
                x = x + 1
            y = float(x)         # host cast
            z = np.abs(x)        # numpy on a tracer
            x.item()             # explicit sync
            return x
    """})
    assert rules_of(res) == ["RL002"] * 4
    msgs = " ".join(f.message for f in res.new)
    for needle in ("`if` on a traced value", "host cast `float()`",
                   "`np.abs` call on a traced value", "host sync `.item()`"):
        assert needle in msgs, needle


def test_rl002_static_args_and_helpers_are_clean(tmp_path):
    res = make_project(tmp_path, {"src/repro/core/fn.py": """
        import functools
        import jax
        import jax.numpy as jnp
        import numpy as np

        @functools.partial(jax.jit, static_argnames=("cfg", "n"))
        def good(x, *, cfg, n):
            if cfg.age_encoding:        # static arg attribute: ok
                x = x + 1
            B, S = x.shape              # shape math is trace-static
            pad = (-S) % max(n, 1)
            if pad == 0:                # derived static local: ok
                x = x * 2
            lim = cfg.max_age if n is not None else np.inf   # np attr: ok
            return _helper(x, cfg), lim

        def _helper(x, cfg):
            if cfg.age_encoding:        # static-ness propagates into helpers
                x = x - 1
            return {k: x[k] for k in x}   # pytree-key iteration: ok

        def untraced(x):
            return float(x) if x > 0 else x.item()   # host code: not scanned
    """})
    assert res.new == []


def test_rl002_closure_mutation(tmp_path):
    res = make_project(tmp_path, {"src/repro/core/fn.py": """
        import jax

        @jax.jit
        def leaky(x):
            acc = []
            def body(i, s):
                acc.append(i)       # escapes the trace body
                return s
            return jax.lax.fori_loop(0, 3, body, x)
    """})
    assert rules_of(res) == ["RL002"]
    assert "mutation `.append()`" in res.new[0].message


# ---------------------------------------------------------------------------
# RL003 kernel <-> oracle pairing
# ---------------------------------------------------------------------------
RL003_KERNEL = """
    def fused_scan(x):
        return x

    def _private_helper(x):
        return x
"""


def test_rl003_missing_oracle_and_test(tmp_path):
    res = make_project(tmp_path, {
        "src/repro/kernels/fused.py": RL003_KERNEL,
        "src/repro/kernels/ref.py": "def other_ref(x):\n    return x\n",
        "tests/test_none.py": "def test_nothing():\n    pass\n",
    })
    assert rules_of(res) == ["RL003"]
    assert "no `fused_scan_ref` oracle" in res.new[0].message

    # oracle present but no parity test naming both sides
    res = make_project(tmp_path, {
        "src/repro/kernels/ref.py":
            "def fused_scan_ref(x):\n    return x\n",
    })
    assert rules_of(res) == ["RL003"]
    assert "parity test missing" in res.new[0].message


def test_rl003_paired_is_clean(tmp_path):
    res = make_project(tmp_path, {
        "src/repro/kernels/fused.py": RL003_KERNEL,
        "src/repro/kernels/ref.py":
            "def fused_scan_ref(x):\n    return x\n",
        "tests/test_fused.py": """
            def test_parity():
                assert fused_scan(1) == fused_scan_ref(1)
        """,
    })
    assert res.new == []


def test_rl003_signature_parity(tmp_path):
    files = {
        "src/repro/kernels/fused.py":
            "def fused_scan(q, k, v):\n    return q\n",
        "src/repro/kernels/ref.py":
            "def fused_scan_ref(q, v, k):\n    return q\n",   # k/v swapped
        "tests/test_fused.py": """
            def test_parity():
                assert fused_scan(1, 2, 3) == fused_scan_ref(1, 2, 3)
        """,
    }
    res = make_project(tmp_path, files)
    assert rules_of(res) == ["RL003"]
    assert res.new[0].symbol == "kernels.fused_scan.signature-parity"
    assert "(q, v, k)" in res.new[0].message

    # matching order (trailing defaults don't count) is clean
    files["src/repro/kernels/ref.py"] = \
        "def fused_scan_ref(q, k, v, eps=1e-6):\n    return q\n"
    res = make_project(tmp_path, files)
    assert res.new == []

    # an ops.py wrapper overrides the raw kernel def as the canonical
    # signature source
    files["src/repro/kernels/ops.py"] = \
        "def fused_scan(a, b):\n    return a\n"
    files["src/repro/kernels/ref.py"] = \
        "def fused_scan_ref(a, b):\n    return a\n"
    res = make_project(tmp_path, files)
    assert res.new == []


# ---------------------------------------------------------------------------
# call graph: resolution + marker propagation (backs RL001/RL005/RL006)
# ---------------------------------------------------------------------------
CG_POOL = """
    class Pool:
        def alloc(self, n):
            return list(range(n))

        def release(self, ids):
            pass
"""

CG_ENG = """
    import threading
    from .pool import Pool

    class Eng:
        def __init__(self):
            self._lock = threading.Lock()
            self.pool = Pool()
            self.pending = []     # guarded-by: _lock
            self.slots = []       # guarded-by: engine-thread

        def step(self):  # repro-lint: engine-thread-only
            return self._inner()

        def _inner(self):
            return self.slots       # marker derived from the only caller

        def submit(self):
            with self._lock:
                return self._locked_pop()

        def _locked_pop(self):
            return self.pending.pop()   # holder derived from lock context

        def grab(self):
            ids = self.pool.alloc(1)
            self.pool.release(ids)
            return ids
"""


def _write(tmp_path, files):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))


def test_callgraph_resolves_self_field_methods(tmp_path):
    _write(tmp_path, {"src/repro/serve/pool.py": CG_POOL,
                      "src/repro/serve/eng.py": CG_ENG})
    g = callgraph_mod.build(Project(tmp_path))
    grab = next(f for f in g.functions if f.qualname == "Eng.grab")
    callees = {(s.callee.cls, s.callee.name) for s in grab.calls}
    # `self.pool.alloc` resolves through the __init__ field type,
    # across the relative import, to the Pool class
    assert ("Pool", "alloc") in callees and ("Pool", "release") in callees


def test_callgraph_marker_and_holder_propagation(tmp_path):
    _write(tmp_path, {"src/repro/serve/pool.py": CG_POOL,
                      "src/repro/serve/eng.py": CG_ENG})
    g = callgraph_mod.build(Project(tmp_path))
    fid = {f.qualname: f.fid for f in g.functions}
    eng_derived = callgraph_mod.propagate_all_callers(g, "engine-thread-only")
    assert fid["Eng._inner"] in eng_derived
    assert fid["Eng.submit"] not in eng_derived      # unmarked entry point
    holders = callgraph_mod.propagate_holds(g)
    assert fid["Eng._locked_pop"] in holders
    assert fid["Eng._inner"] not in holders


def test_rl001_accepts_derived_markers(tmp_path):
    """The fixture's guarded accesses live in UNANNOTATED helpers reached
    only through annotated (or locked) callers: propagation must keep the
    tree clean end to end."""
    res = make_project(tmp_path, {"src/repro/serve/pool.py": CG_POOL,
                                  "src/repro/serve/eng.py": CG_ENG})
    assert res.new == []
    # sever the propagation path: an unmarked second caller taints _inner
    extra = textwrap.dedent(CG_ENG) + (
        "\n"
        "    def poke(self):\n"
        "        return self._inner()\n")
    (tmp_path / "src/repro/serve/eng.py").write_text(extra)
    res = run_lint(tmp_path)
    assert rules_of(res) == ["RL001"]
    assert "Eng._inner" in res.new[0].message


# ---------------------------------------------------------------------------
# RL004 wire stability
# ---------------------------------------------------------------------------
def errors_src(table):
    lines = ["class ApiError(ValueError):",
             "    code = 'bad_request'",
             "    http_status = 400",
             ""]
    for i, (code, status) in enumerate(sorted(table.items())):
        lines += [f"class E{i}(ApiError):",
                  f"    code = {code!r}",
                  f"    http_status = {status}",
                  ""]
    return "\n".join(lines)


def test_rl004_frozen_table_round_trip(tmp_path):
    res = make_project(
        tmp_path, {"src/repro/api/errors.py": errors_src(FROZEN_WIRE_V1)})
    assert res.new == []


def test_rl004_status_drift_new_code_and_removal(tmp_path):
    drifted = dict(FROZEN_WIRE_V1)
    drifted["timeout"] = 500                 # drift
    drifted["brand_new"] = 418               # unfrozen addition
    del drifted["internal"]                  # removal
    res = make_project(
        tmp_path, {"src/repro/api/errors.py": errors_src(drifted)})
    msgs = " ".join(f.message for f in res.new)
    assert rules_of(res) == ["RL004"] * 3
    assert "frozen v1 table says 504" in msgs
    assert "new wire code `brand_new`" in msgs
    assert "`internal` has no ApiError subclass" in msgs


def test_rl004_duplicate_code(tmp_path):
    src = errors_src(FROZEN_WIRE_V1) + (
        "class Dup(ApiError):\n"
        "    code = 'timeout'\n"
        "    http_status = 504\n")
    res = make_project(tmp_path, {"src/repro/api/errors.py": src})
    assert rules_of(res) == ["RL004"]
    assert "registered by both" in res.new[0].message


SCHEMAS_SRC = """
    import dataclasses

    def check_protocol(d):
        pass

    @dataclasses.dataclass
    class Req:
        a: int
        b: int = 0

        def to_json(self):
            return {"a": self.a{MAYBE_B}}

        @classmethod
        def from_json(cls, d):
            check_protocol(d)
            return cls(a=d["a"], b=d.get("b", 0))
"""


def test_rl004_schema_field_must_round_trip(tmp_path):
    src = SCHEMAS_SRC.replace("{MAYBE_B}", "")
    res = make_project(tmp_path, {"src/repro/api/schemas.py": src})
    assert rules_of(res) == ["RL004"]
    assert "`Req.b` does not appear in `to_json`" in res.new[0].message

    src = SCHEMAS_SRC.replace("{MAYBE_B}", ", 'b': self.b")
    res = make_project(tmp_path, {"src/repro/api/schemas.py": src})
    assert res.new == []


def test_rl004_handler_protocol_check(tmp_path):
    res = make_project(tmp_path, {
        "src/repro/api/schemas.py":
            SCHEMAS_SRC.replace("{MAYBE_B}", ", 'b': self.b"),
        "src/repro/serve/server.py": """
            class Handler:
                def do_POST(self):
                    path = self.path
                    if path == "/v1/via_schema":
                        req = Req.from_json(self._read())   # checks inside
                    elif path == "/v1/via_helper":
                        self.helper(self._read())
                    elif path == "/v1/naked":
                        self._send(self._read())            # RL004

                def helper(self, d):
                    check_protocol(d)
        """,
    })
    assert rules_of(res) == ["RL004"]
    assert "`/v1/naked`" in res.new[0].message


# ---------------------------------------------------------------------------
# RL005 resource discipline
# ---------------------------------------------------------------------------
RL005_SRC = """
    class Eng:
        def __init__(self, pool):
            self.pool = pool
            self._slots = {}

        def leaky_admit(self, n, slot):
            blocks = self.pool.alloc(n)
            if blocks is None:
                raise RuntimeError("budget")
            self._prep(blocks)              # may raise: handle still live
            self._slots[slot] = blocks

        def guarded_admit(self, n, slot):
            blocks = self.pool.alloc(n)
            if blocks is None:
                raise RuntimeError("budget")
            try:
                self._prep(blocks)
            except BaseException:
                self.pool.release(blocks)
                raise
            self._slots[slot] = blocks

        def finally_admit(self, n):
            blocks = self.pool.alloc(n)
            if blocks is None:
                return 0
            try:
                self._prep(blocks)
            finally:
                self.pool.release(blocks)
            return 1

        def handoff(self, n):
            blocks = self.pool.alloc(n)
            self._consume(blocks)

        def _consume(self, blocks):  # repro-lint: transfers-ownership
            self._slots[0] = blocks

        def conditional_share(self, blocks, flag):
            if self.paged:
                self.pool.share(blocks)
            try:
                self._prep(blocks)
            finally:
                if self.paged:
                    self.pool.release(blocks)

        def _prep(self, blocks):
            pass
"""


def test_rl005_leak_on_raise_only(tmp_path):
    """One leak-on-raise positive; the finally/handler/marker/path-fact
    variants of the same shape stay silent."""
    res = make_project(tmp_path, {"src/repro/serve/eng.py": RL005_SRC})
    assert rules_of(res) == ["RL005"]
    f = res.new[0]
    assert "Eng.leaky_admit" in f.message and "raising path" in f.message
    assert f.symbol == "Eng.leaky_admit.leak.blocks"


def test_rl005_missing_release_on_exit(tmp_path):
    res = make_project(tmp_path, {"src/repro/serve/eng.py": """
        class Idx:
            def __init__(self, pool):
                self.pool = pool
                self._entries = {}

            def evict(self, key):
                e = self._entries.pop(key)
                self.evictions += 1         # popped entry's refs never drop
                return self.evictions

            def evict_ok(self, key):
                e = self._entries.pop(key)
                self.pool.release(e.blocks)
                self.evictions += 1
                return self.evictions
    """})
    assert rules_of(res) == ["RL005"]
    assert "Idx.evict" in res.new[0].message
    assert "every exit path" in res.new[0].message


# ---------------------------------------------------------------------------
# RL006 hot-path host syncs
# ---------------------------------------------------------------------------
RL006_SRC = """
    import jax
    import numpy as np

    @jax.jit
    def _fwd(x):
        return x

    class Eng:
        def tick(self):  # repro-lint: hot-path
            out = _fwd(1)
            return self._drain(out)

        def _drain(self, out):
            n = out.sum().item()            # sync in a hot transitive callee
            host = np.asarray(out)          # np on a device value
            meta = np.zeros((4,))           # host-only numpy: fine
            return n, host, meta

        def offline_stats(self, out):
            return out.item()               # not hot-reachable: fine
"""


def test_rl006_transitive_hot_path_syncs(tmp_path):
    res = make_project(tmp_path, {"src/repro/serve/eng.py": RL006_SRC})
    assert rules_of(res) == ["RL006", "RL006"]
    whats = sorted(f.symbol.rsplit(".hotsync.", 1)[1] for f in res.new)
    assert whats == [".item()", "np.asarray"]
    msgs = " ".join(f.message for f in res.new)
    assert "Eng._drain" in msgs and "hot path `Eng.tick`" in msgs
    assert "offline_stats" not in msgs


def test_rl006_annotated_packed_sync_allowed(tmp_path):
    src = RL006_SRC.replace(
        "n = out.sum().item()            # sync in a hot transitive callee",
        "n = out.sum().item()  # repro-lint: disable=RL006 the packed sync"
    ).replace(
        "host = np.asarray(out)          # np on a device value",
        "host = np.asarray(n)")
    res = make_project(tmp_path, {"src/repro/serve/eng.py": src})
    assert res.new == [] and res.suppressed == 1


def test_rl006_silent_without_hot_seed(tmp_path):
    src = RL006_SRC.replace("  # repro-lint: hot-path", "")
    res = make_project(tmp_path, {"src/repro/serve/eng.py": src})
    assert res.new == []


# ---------------------------------------------------------------------------
# RL007 Pallas geometry
# ---------------------------------------------------------------------------
RL007_SRC = """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def _copy_kernel(x_ref, o_ref, acc):
        o_ref[...] = x_ref[...]

    def _bad_arity(x):
        grid = (4, 2)
        return pl.pallas_call(
            _copy_kernel,
            grid=grid,
            in_specs=[pl.BlockSpec((1, 8), lambda i, j, k: (i, 0))],
            out_specs=[pl.BlockSpec((1, 8), lambda i, j: (i, 0))],
            scratch_shapes=[pltpu.VMEM((8,), jnp.float32)],
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        )(x)

    def _good(x):
        return pl.pallas_call(
            _copy_kernel,
            grid=(4, 2),
            in_specs=[pl.BlockSpec((1, 8), lambda i, j: (i, 0))],
            out_specs=[pl.BlockSpec((1, 8), lambda i, j: (i, 0))],
            scratch_shapes=[pltpu.VMEM((8,), jnp.float32)],
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        )(x)
"""


def test_rl007_index_map_arity(tmp_path):
    res = make_project(tmp_path, {"src/repro/kernels/toy.py": RL007_SRC})
    assert rules_of(res) == ["RL007"]
    f = res.new[0]
    assert "takes 3 args, expected 2" in f.message
    assert f.symbol == "kernels._copy_kernel.index-map-arity.3"


def test_rl007_kernel_signature_and_scratch_dtype(tmp_path):
    src = textwrap.dedent(RL007_SRC).replace(
        "def _copy_kernel(x_ref, o_ref, acc):",
        "def _copy_kernel(x_ref, o_ref):").replace(
        "lambda i, j, k: (i, 0)", "lambda i, j: (i, 0)").replace(
        "pltpu.VMEM((8,), jnp.float32)", "pltpu.VMEM((8,))")
    res = make_project(tmp_path, {"src/repro/kernels/toy.py": src})
    syms = sorted(f.symbol for f in res.new)
    assert syms == ["kernels._copy_kernel.scratch-dtype",
                    "kernels._copy_kernel.signature"]
    msgs = " ".join(f.message for f in res.new)
    assert "takes 2 positional refs, expected 3" in msgs
    assert "explicit dotted dtype" in msgs


RL007_PREFETCH = """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def _unguarded_kernel(tbl_ref, x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def _guarded_kernel(tbl_ref, x_ref, o_ref):
        blk = tbl_ref[0]

        @pl.when(blk >= 0)
        def _():
            o_ref[...] = x_ref[...]

    def _paged(x, tbl):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(4,),
            in_specs=[pl.BlockSpec((1, 8), lambda i, tbl: (tbl[i], 0))],
            out_specs=[pl.BlockSpec((1, 8), lambda i, tbl: (i, 0))],
        )
        return pl.pallas_call(
            _unguarded_kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(tbl, x)
"""


def test_rl007_prefetch_guard(tmp_path):
    res = make_project(tmp_path,
                       {"src/repro/kernels/paged.py": RL007_PREFETCH})
    assert rules_of(res) == ["RL007"]
    assert res.new[0].symbol == "kernels._unguarded_kernel.prefetch-guard"
    assert "no `pl.when` guard" in res.new[0].message

    guarded = RL007_PREFETCH.replace("_unguarded_kernel, grid_spec",
                                     "_guarded_kernel, grid_spec")
    res = make_project(tmp_path,
                       {"src/repro/kernels/paged.py": guarded})
    assert res.new == []


# ---------------------------------------------------------------------------
# suppression + baseline semantics
# ---------------------------------------------------------------------------
def test_inline_suppression(tmp_path):
    files = {"src/repro/serve/eng.py": """
        import threading

        class Eng:
            def __init__(self):
                self._lock = threading.Lock()
                self.pending = []    # guarded-by: _lock

            def peek(self):
                # post-join snapshot, documented single-threaded
                return len(self.pending)  # repro-lint: disable=RL001 drained
    """}
    res = make_project(tmp_path, files)
    assert res.new == [] and res.suppressed == 1

    # a disable= for a DIFFERENT rule does not silence the finding
    files["src/repro/serve/eng.py"] = files[
        "src/repro/serve/eng.py"].replace("disable=RL001", "disable=RL002")
    res = make_project(tmp_path, files)
    assert rules_of(res) == ["RL001"] and res.suppressed == 0


def test_baseline_grandfathers_but_catches_new(tmp_path):
    src = {"src/repro/serve/eng.py": RL001_POSITIVE}
    res = make_project(tmp_path, src)
    assert len(res.new) == 2

    base = tmp_path / "baseline.json"
    baseline_mod.save(base, res.new)
    res2 = run_lint(tmp_path, baseline_path=base)
    assert res2.new == [] and len(res2.grandfathered) == 2
    assert res2.exit_code == 0

    # introduce a NEW violation: only it fails the run
    (tmp_path / "src/repro/serve/eng.py").write_text(
        textwrap.dedent(RL001_POSITIVE) + textwrap.dedent("""
            def sneak(self):
                return self.pending.pop()
        """).replace("\n", "\n    ").rstrip() + "\n")
    res3 = run_lint(tmp_path, baseline_path=base)
    assert len(res3.grandfathered) == 2
    assert [f.rule for f in res3.new] == ["RL001"]
    assert "sneak" in res3.new[0].message
    assert res3.exit_code == 1

    # fixing everything leaves stale baseline entries, not failures
    (tmp_path / "src/repro/serve/eng.py").write_text("x = 1\n")
    res4 = run_lint(tmp_path, baseline_path=base)
    assert res4.new == [] and len(res4.stale_baseline) == 2


def test_fingerprint_survives_line_churn(tmp_path):
    res = make_project(tmp_path, {"src/repro/serve/eng.py": RL001_POSITIVE})
    fp = {f.fingerprint for f in res.new}
    shifted = "\n\n# a comment\n" + textwrap.dedent(RL001_POSITIVE)
    (tmp_path / "src/repro/serve/eng.py").write_text(shifted)
    res2 = run_lint(tmp_path)
    assert {f.fingerprint for f in res2.new} == fp


# ---------------------------------------------------------------------------
# the repo itself
# ---------------------------------------------------------------------------
def test_self_run_src_repro_is_clean():
    """The committed tree must lint clean modulo the committed baseline —
    the same gate CI runs."""
    res = run_lint(REPO_ROOT,
                   baseline_path=REPO_ROOT / "tools/analyze/baseline.json")
    assert res.new == [], "\n".join(f.format_text() for f in res.new)


def test_cli_exit_codes(tmp_path, capsys):
    assert cli_main(["--list-rules"]) == 0
    assert cli_main(["--root", str(REPO_ROOT)]) == 0
    out = capsys.readouterr()
    assert "RL001" in out.out          # --list-rules table
    # a dirty fixture tree exits 1 and renders GitHub annotations
    p = tmp_path / "src/repro/serve/eng.py"
    p.parent.mkdir(parents=True)
    p.write_text(textwrap.dedent(RL001_POSITIVE))
    assert cli_main(["--root", str(tmp_path), "--format=github"]) == 1
    out = capsys.readouterr()
    assert "::error file=" in out.out


def test_fix_baseline_prints_fingerprint_diff(tmp_path, capsys):
    p = tmp_path / "src/repro/serve/eng.py"
    p.parent.mkdir(parents=True)
    p.write_text(textwrap.dedent(RL001_POSITIVE))
    bl = tmp_path / "bl.json"
    common = ["--root", str(tmp_path), "--baseline", str(bl)]
    assert cli_main(common + ["--fix-baseline"]) == 0
    out = capsys.readouterr().out
    added = [l for l in out.splitlines() if l.startswith("+ ")]
    assert len(added) == 2 and all("RL001" in l for l in added)
    # the rewritten baseline greens the tree
    assert cli_main(common) == 0
    capsys.readouterr()
    # fixing the sources: the next --fix-baseline prunes and prints `-` lines
    p.write_text("x = 1\n")
    assert cli_main(common + ["--fix-baseline"]) == 0
    out = capsys.readouterr().out
    removed = [l for l in out.splitlines() if l.startswith("- ")]
    assert len(removed) == 2
    assert json.loads(bl.read_text())["findings"] == {}


def test_analyzer_output_is_byte_deterministic(tmp_path):
    """Same tree in, same bytes out — across interpreter runs with
    different hash seeds (the CI artifact must be diffable)."""
    for rel, src in {"src/repro/serve/eng.py": RL001_POSITIVE,
                     "src/repro/serve/res.py": RL005_SRC,
                     "src/repro/serve/hot.py": RL006_SRC,
                     "src/repro/kernels/toy.py": RL007_SRC}.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    outs = []
    for seed in ("0", "31337"):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.analyze", "--root", str(tmp_path),
             "--no-baseline", "--format=json"],
            cwd=REPO_ROOT, capture_output=True,
            env=dict(os.environ, PYTHONHASHSEED=seed))
        assert proc.returncode == 1, proc.stderr.decode()
        outs.append(proc.stdout)
    assert outs[0] == outs[1]
    assert len(json.loads(outs[0])) >= 4     # all four rule families fired


# ---------------------------------------------------------------------------
# seeded mutations: re-introduce the exact bug classes the new rules catch
# in the REAL tree and assert each yields exactly one finding
# ---------------------------------------------------------------------------
def _mutated_src(tmp_path, rel, old, new):
    shutil.copytree(REPO_ROOT / "src/repro", tmp_path / "src/repro")
    p = tmp_path / "src/repro" / rel
    text = p.read_text()
    assert old in text, f"mutation anchor drifted in {rel}: {old!r}"
    p.write_text(text.replace(old, new, 1))
    return run_lint(tmp_path)


def test_mutation_deleted_release_is_exactly_one_rl005(tmp_path):
    res = _mutated_src(
        tmp_path, "serve/prefix.py",
        "        self.pool.release(e.blocks)\n", "")
    assert [f.rule for f in res.new] == ["RL005"]
    assert "PrefixIndex._evict_entry" in res.new[0].message


def test_mutation_sync_under_tick_is_exactly_one_rl006(tmp_path):
    anchor = "        arr = self._fetch(packed)    # ONE sync per tick\n"
    res = _mutated_src(
        tmp_path, "serve/engine.py",
        anchor, anchor + "        _dbg = arr.sum().item()\n")
    assert [f.rule for f in res.new] == ["RL006"]
    assert ".item()" in res.new[0].message
    assert "BatchedEngine.step" in res.new[0].message


def test_mutation_index_map_arity_is_exactly_one_rl007(tmp_path):
    res = _mutated_src(
        tmp_path, "kernels/paged_attention.py",
        "lambda b, i, tbl, stp: (b, 0, 0)",
        "lambda b, i, tbl: (b, 0, 0)")
    assert [f.rule for f in res.new] == ["RL007"]
    assert res.new[0].symbol == "kernels._paged_kernel.index-map-arity.3"
