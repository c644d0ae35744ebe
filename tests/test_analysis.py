"""repro-lint (src/repro/analysis): per-rule fixture snippets
(positive + suppressed + clean, including minimized reproductions of
the PR 5 mesh-dependent-RNG bug, the PR 6 poll-aliasing bug, the PR 8
partial-psum bug, and the PR 9 half-committed-slot bug), the
suppression syntax (including the interprocedural related-location
form), the baseline / GitHub-annotation CLI modes, the runtime guards,
and a self-run over src/repro pinning the tree clean."""
import pathlib
import textwrap
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from repro.analysis import run_paths
from repro.analysis.core import RULE_DOCS

REPO = pathlib.Path(__file__).resolve().parents[1]


def lint_snippet(tmp_path, source, name="snippet.py", rules=None):
    path = tmp_path / name
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    findings, suppressed = run_paths([str(path)], rules=rules,
                                     root=tmp_path)
    return findings, suppressed


def codes(findings):
    return [f.code for f in findings]


# ---------------------------------------------------------------------------
# RPL001 — jit hazards
# ---------------------------------------------------------------------------

def test_rpl001_fires_on_tracer_branch_and_coercion(tmp_path):
    findings, _ = lint_snippet(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            if x > 0:                 # tracer branch
                return x
            return -x

        @jax.jit
        def g(x):
            for i in range(x):        # tracer loop bound
                pass
            return float(x)           # tracer coercion
    """)
    assert codes(findings).count("RPL001") == 3


def test_rpl001_fires_on_name_passed_to_jit_and_item(tmp_path):
    # the AsrEngine pattern: a nested def jitted BY NAME, not decorator
    findings, _ = lint_snippet(tmp_path, """
        import jax

        def build():
            def step(state, x):
                s = x.sum()
                return s.item()       # coercion inside the traced fn
            return jax.jit(step)
    """)
    assert codes(findings) == ["RPL001"]


def test_rpl001_fires_on_mutable_static_default(tmp_path):
    findings, _ = lint_snippet(tmp_path, """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("opts",))
        def f(x, opts=[]):
            return x
    """)
    assert codes(findings) == ["RPL001"]


def test_rpl001_clean_on_shape_branches_static_args_and_none_checks(
        tmp_path):
    findings, _ = lint_snippet(tmp_path, """
        import functools
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("mode",))
        def f(x, bias=None, *, mode="ref"):
            T, D = x.shape
            pad = (-T) % 4
            if pad:                      # shape-derived: static
                x = jnp.pad(x, ((0, pad), (0, 0)))
            if mode == "ref":            # static arg
                x = x * 2
            if bias is not None:         # structural None check
                x = x + bias
            if len(x.shape) == 2:        # len() of static
                x = x[None]
            return x
    """)
    assert findings == []


def test_rpl001_suppressed(tmp_path):
    findings, suppressed = lint_snippet(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            if x > 0:  # repro-lint: disable=RPL001
                return x
            return -x
    """)
    assert findings == []
    assert codes(suppressed) == ["RPL001"]


# ---------------------------------------------------------------------------
# RPL002 — kernel contract
# ---------------------------------------------------------------------------

def _kernel_tree(tmp_path, registry_body, kernel_body=None):
    kdir = tmp_path / "kernels"
    kdir.mkdir(exist_ok=True)
    (kdir / "ref.py").write_text("def foo(x):\n    return x\n")
    (kdir / "policy.py").write_text(registry_body)
    (kdir / "foo.py").write_text(kernel_body or textwrap.dedent("""
        from jax.experimental import pallas as pl

        def run(x, bt=8):
            T = x.shape[0]
            assert T % bt == 0
            return pl.pallas_call(lambda r, o: None, grid=(T // bt,))(x)
    """))
    (tmp_path / "tests").mkdir(exist_ok=True)
    (tmp_path / "tests" / "test_foo.py").write_text(
        "from kernels import foo  # parity: foo vs ref\n")
    findings, suppressed = run_paths([str(kdir)], rules=["RPL002"],
                                     root=tmp_path)
    return findings, suppressed


def test_rpl002_fires_on_unregistered_pallas_call(tmp_path):
    findings, _ = _kernel_tree(tmp_path, "KERNEL_REGISTRY = {}\n")
    assert codes(findings) == ["RPL002"]
    assert "no KERNEL_REGISTRY entry" in findings[0].message


def test_rpl002_fires_on_missing_ref_twin_and_guard(tmp_path):
    findings, _ = _kernel_tree(tmp_path, textwrap.dedent("""
        KERNEL_REGISTRY = {
            "foo": {"ref": "nope", "test": "tests/test_foo.py",
                    "shape_guard": "checked"},
        }
    """))
    assert "not defined in kernels/ref.py" in findings[0].message

    findings, _ = _kernel_tree(tmp_path, textwrap.dedent("""
        KERNEL_REGISTRY = {
            "foo": {"ref": "foo", "test": "tests/test_foo.py",
                    "shape_guard": "checked"},
        }
    """), kernel_body=textwrap.dedent("""
        from jax.experimental import pallas as pl

        def run(x):
            return pl.pallas_call(lambda r, o: None, grid=(4,))(x)
    """))
    assert codes(findings) == ["RPL002"]
    assert "divisibility" in findings[0].message


def test_rpl002_clean_with_full_contract(tmp_path):
    findings, _ = _kernel_tree(tmp_path, textwrap.dedent("""
        KERNEL_REGISTRY = {
            "foo": {"ref": "foo", "test": "tests/test_foo.py",
                    "shape_guard": "checked"},
        }
    """))
    assert findings == []


def test_rpl002_live_registry_covers_every_kernel_module():
    """The real KERNEL_REGISTRY names every pallas_call module, its ref
    twins exist, and its parity tests reference it — i.e. RPL002 is
    green on the tree it was built for."""
    findings, _ = run_paths([str(REPO / "src" / "repro" / "kernels")],
                            rules=["RPL002"], root=REPO)
    assert findings == []


# ---------------------------------------------------------------------------
# RPL003 — aliasing (minimized PR 6 bug)
# ---------------------------------------------------------------------------

PR6_BUG = """
    class Eng:
        def _poll(self, session):
            if session.admitted:
                res = self.slot_best(session.slot)
                res["steps"] = 1
                return res
            return {"steps": 0}
"""


def test_rpl003_fires_on_pr6_poll_aliasing_repro(tmp_path):
    findings, _ = lint_snippet(tmp_path, PR6_BUG)
    assert codes(findings) == ["RPL003"]


def test_rpl003_fires_on_state_attr_in_dict_and_set_result(tmp_path):
    findings, _ = lint_snippet(tmp_path, """
        class Eng:
            def snapshot(self, slot):
                return {"beam": self._beam, "n": 3}

            def resolve(self, fut, sess):
                fut.set_result(sess.result)
    """)
    assert codes(findings) == ["RPL003", "RPL003"]


def test_rpl003_clean_when_routed_through_copy_result(tmp_path):
    findings, _ = lint_snippet(tmp_path, """
        from repro.serving.engine import copy_result

        class Eng:
            def _poll(self, session):
                res = self.slot_best(session.slot)
                res["steps"] = 1
                return copy_result(res)

            def tokens(self, slot):
                return list(self._gen[slot])
    """)
    assert findings == []


def test_rpl003_suppressed_file_wide(tmp_path):
    findings, suppressed = lint_snippet(
        tmp_path, "# repro-lint: disable-file=RPL003\n"
        + textwrap.dedent(PR6_BUG))
    assert findings == []
    assert codes(suppressed) == ["RPL003"]


# ---------------------------------------------------------------------------
# RPL004 — thread discipline
# ---------------------------------------------------------------------------

THREADED = """
    def worker_only(fn):
        return fn

    class Eng:
        @worker_only
        def _advance_pool(self):
            pass

    async def handler(eng, worker):
        {call}
"""


def test_rpl004_fires_on_direct_async_call(tmp_path):
    findings, _ = lint_snippet(
        tmp_path, THREADED.format(call="eng._advance_pool()"))
    assert codes(findings) == ["RPL004"]


def test_rpl004_clean_through_worker_thunk(tmp_path):
    findings, _ = lint_snippet(
        tmp_path,
        THREADED.format(call="await worker.call("
                             "lambda eng: eng._advance_pool())"))
    assert findings == []


def test_rpl004_suppressed(tmp_path):
    findings, suppressed = lint_snippet(
        tmp_path, THREADED.format(
            call="eng._advance_pool()  # repro-lint: disable=RPL004"))
    assert findings == []
    assert codes(suppressed) == ["RPL004"]


SUPERVISED = """
    def worker_only(fn):
        return fn

    class Eng:
        @worker_only
        def _fail_all(self, exc):
            pass

    class Server:
        def {name}(self, eng, worker, exc):
            {call}
"""


def test_rpl004_fires_in_sync_watchdog_entry_point(tmp_path):
    """Supervisor/watchdog restart paths are sync defs running on the
    event-loop thread; a direct @worker_only call there is the same
    cross-thread race as one in an async handler."""
    findings, _ = lint_snippet(
        tmp_path, SUPERVISED.format(name="_watchdog_restart",
                                    call="eng._fail_all(exc)"))
    assert codes(findings) == ["RPL004"]
    assert "supervisor/watchdog" in findings[0].message


def test_rpl004_clean_watchdog_through_worker_thunk(tmp_path):
    """The blessed restart idiom — submitting the quarantine as a thunk
    the NEW worker runs — stays clean (lambdas are exempt)."""
    findings, _ = lint_snippet(
        tmp_path, SUPERVISED.format(
            name="_supervise_restart",
            call="worker.submit(lambda e: e._fail_all(exc))"))
    assert findings == []


def test_rpl004_ignores_unrelated_sync_functions(tmp_path):
    """Plain sync helpers (in-process drivers, tests) may call
    @worker_only methods directly — only supervisor/watchdog-named
    entry points are loop-side by contract."""
    findings, _ = lint_snippet(
        tmp_path, SUPERVISED.format(name="drive_inprocess",
                                    call="eng._fail_all(exc)"))
    assert findings == []


# ---------------------------------------------------------------------------
# RPL005 — RNG discipline (minimized PR 5 bug)
# ---------------------------------------------------------------------------

PR5_BUG = """
    import jax

    def init_params(mesh, spec):
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (8, 8))
        place = jax.jit(lambda x: x, out_shardings=spec)
        return place(w)
"""


def test_rpl005_fires_on_pr5_mesh_dependent_init_repro(tmp_path):
    findings, _ = lint_snippet(tmp_path, PR5_BUG)
    assert codes(findings) == ["RPL005"]
    assert "mesh_invariant_rng" in findings[0].message


def test_rpl005_clean_with_mesh_invariant_rng(tmp_path):
    findings, _ = lint_snippet(tmp_path, """
        import jax
        from repro.runtime.elastic import mesh_invariant_rng

        def init_params(mesh, spec):
            with mesh_invariant_rng():
                key = jax.random.PRNGKey(0)
                w = jax.random.normal(key, (8, 8))
            place = jax.jit(lambda x: x, out_shardings=spec)
            return place(w)
    """)
    assert findings == []


def test_rpl005_clean_without_sharded_jit(tmp_path):
    findings, _ = lint_snippet(tmp_path, """
        import jax

        def make_key():
            return jax.random.PRNGKey(0)
    """)
    assert findings == []


def test_rpl005_fires_on_shard_map_module(tmp_path):
    # the 2D ('data','model') serving-mesh class: shard_map compute
    # plus PRNGKey init — mesh-dependent RNG would fork per data shard
    findings, _ = lint_snippet(tmp_path, """
        import jax
        from jax.sharding import PartitionSpec as P

        def build(mesh):
            key = jax.random.PRNGKey(0)
            w = jax.random.normal(key, (8, 8))
            step = jax.shard_map(lambda x: x, mesh=mesh,
                                 in_specs=(P(),), out_specs=P())
            return step(w)
    """)
    assert codes(findings) == ["RPL005"]
    assert "shard_map" in findings[0].message


def test_rpl005_clean_shard_map_with_mesh_invariant_rng(tmp_path):
    findings, _ = lint_snippet(tmp_path, """
        import jax
        from repro.runtime.elastic import mesh_invariant_rng
        from jax.sharding import PartitionSpec as P

        def build(mesh):
            with mesh_invariant_rng():
                key = jax.random.PRNGKey(0)
                w = jax.random.normal(key, (8, 8))
            step = jax.shard_map(lambda x: x, mesh=mesh,
                                 in_specs=(P(),), out_specs=P())
            return step(w)
    """)
    assert findings == []


# ---------------------------------------------------------------------------
# RPL006 — collective/axis discipline (interprocedural; minimized PR 8 bug)
# ---------------------------------------------------------------------------

def test_rpl006_fires_on_undeclared_collective_axis(tmp_path):
    # psum over "model" inside a function traced by a shard_map whose
    # PartitionSpecs only declare "data" — fails at trace time on the
    # real mesh, and the finding carries the binder as a related site
    findings, _ = lint_snippet(tmp_path, """
        import jax
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P

        def step(x):
            return jax.lax.psum(x, "model")

        def build(mesh):
            return shard_map(step, mesh=mesh,
                             in_specs=(P("data"),), out_specs=P("data"))
    """, rules=["RPL006"])
    assert codes(findings) == ["RPL006"]
    assert "psum" in findings[0].message and "'model'" in findings[0].message
    assert findings[0].related            # binder call site attached


def test_rpl006_clean_on_declared_axis(tmp_path):
    findings, _ = lint_snippet(tmp_path, """
        import jax
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P

        def step(x):
            return jax.lax.psum(x, "data")

        def build(mesh):
            return shard_map(step, mesh=mesh,
                             in_specs=(P("data"),), out_specs=P("data"))
    """, rules=["RPL006"])
    assert findings == []


PR8_BUG = """
    import jax
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    W = None

    def shard_cols(w):
        i = jax.lax.axis_index("model")
        return jax.lax.dynamic_slice(w, (0, i * 4), (8, 4))

    def step(x):
        wl = shard_cols(W)
        return {ret}

    def build(mesh):
        return shard_map(step, mesh=mesh,
                         in_specs=(P("model"),), out_specs=P())
"""


def test_rpl006_fires_on_pr8_partial_matmul_repro(tmp_path):
    # the PR 8 silent-wrong-numerics class: each shard returns its
    # DIFFERENT partial product because the psum is missing
    findings, _ = lint_snippet(
        tmp_path, PR8_BUG.format(ret="x @ wl"), rules=["RPL006"])
    assert codes(findings) == ["RPL006"]
    assert "partial sum" in findings[0].message


def test_rpl006_clean_with_dominating_psum(tmp_path):
    findings, _ = lint_snippet(
        tmp_path, PR8_BUG.format(ret='jax.lax.psum(x @ wl, "model")'),
        rules=["RPL006"])
    assert findings == []


def test_rpl006_fires_on_unguarded_mesh_shape_lookup(tmp_path):
    findings, _ = lint_snippet(tmp_path, """
        def param_spec(mesh, size):
            return size // mesh.shape["model"]
    """, rules=["RPL006"])
    assert codes(findings) == ["RPL006"]
    assert "axis_names" in findings[0].message


def test_rpl006_clean_on_guarded_mesh_shape_lookup(tmp_path):
    # regression fixture for the sharding.py fix: the guarded helper
    # form (membership test before the lookup) is clean, and callers
    # that route through it never touch mesh.shape directly
    findings, _ = lint_snippet(tmp_path, """
        def axis_size(mesh, name):
            return mesh.shape[name] if name in mesh.axis_names else None

        def param_spec(mesh, size):
            nm = axis_size(mesh, "model")
            return size // nm if nm and size % nm == 0 else size
    """, rules=["RPL006"])
    assert findings == []


def test_rpl006_suppressed_at_collective_line(tmp_path):
    findings, suppressed = lint_snippet(tmp_path, """
        import jax
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P

        def step(x):
            return jax.lax.psum(x, "model")  # repro-lint: disable=RPL006

        def build(mesh):
            return shard_map(step, mesh=mesh,
                             in_specs=(P("data"),), out_specs=P("data"))
    """, rules=["RPL006"])
    assert findings == []
    assert codes(suppressed) == ["RPL006"]


def test_rpl006_suppressed_at_related_binder_line(tmp_path):
    """Interprocedural findings carry related locations: a disable at
    the shard_map BINDER call silences the finding inside the root
    function too (the binder owns the axis declaration)."""
    findings, suppressed = lint_snippet(tmp_path, """
        import jax
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P

        def step(x):
            return jax.lax.psum(x, "model")

        def build(mesh):
            # repro-lint: disable=RPL006
            return shard_map(step, mesh=mesh,
                             in_specs=(P("data"),), out_specs=P("data"))
    """, rules=["RPL006"])
    assert findings == []
    assert codes(suppressed) == ["RPL006"]


# ---------------------------------------------------------------------------
# RPL007 — Pallas block contract
# ---------------------------------------------------------------------------

REGISTRY_FULL = """
    KERNEL_REGISTRY = {{
        "foo": {{"ref": "foo", "test": "tests/test_foo.py",
                "shape_guard": "checked"{extra}}},
    }}
"""

KERNEL_OK = """
    from jax.experimental import pallas as pl

    def run(x, bt=8):
        T = x.shape[0]
        assert T % bt == 0
        return pl.pallas_call(lambda r, o: None, grid=(T // bt,))(x)
"""


def _rpl007_tree(tmp_path, registry_body, kernel_body=KERNEL_OK,
                 ref_body="def foo(x):\n    return x\n"):
    kdir = tmp_path / "kernels"
    kdir.mkdir(exist_ok=True)
    (kdir / "ref.py").write_text(ref_body)
    (kdir / "policy.py").write_text(textwrap.dedent(registry_body))
    (kdir / "foo.py").write_text(textwrap.dedent(kernel_body))
    return run_paths([str(kdir)], rules=["RPL007"], root=tmp_path)


def test_rpl007_fires_on_missing_entry_metadata(tmp_path):
    findings, _ = _rpl007_tree(
        tmp_path, REGISTRY_FULL.format(extra=""))
    assert codes(findings) == ["RPL007"]
    assert "'entry'" in findings[0].message


def test_rpl007_fires_on_undefined_entry_wrapper(tmp_path):
    findings, _ = _rpl007_tree(
        tmp_path, REGISTRY_FULL.format(extra=', "entry": "nope"'))
    assert codes(findings) == ["RPL007"]
    assert "not defined" in findings[0].message


def test_rpl007_fires_on_signature_parity_break(tmp_path):
    # ref twin requires (x, scale); the entry wrapper only takes (x):
    # policy dispatch between kernel and ref would TypeError
    findings, _ = _rpl007_tree(
        tmp_path, REGISTRY_FULL.format(extra=', "entry": "run"'),
        ref_body="def foo(x, scale):\n    return x * scale\n")
    assert codes(findings) == ["RPL007"]
    assert "scale" in findings[0].message


def test_rpl007_fires_on_index_map_closure(tmp_path):
    findings, _ = _rpl007_tree(
        tmp_path, REGISTRY_FULL.format(extra=', "entry": "run"'),
        kernel_body="""
            from jax.experimental import pallas as pl

            OFFSET = 3

            def run(x, bt=8):
                T = x.shape[0]
                assert T % bt == 0
                spec = pl.BlockSpec((bt,),
                                    index_map=lambda i: (i + OFFSET,))
                return pl.pallas_call(lambda r, o: None, grid=(T // bt,),
                                      in_specs=[spec])(x)
        """)
    assert codes(findings) == ["RPL007"]
    assert "closes over `OFFSET`" in findings[0].message


def test_rpl007_fires_on_unenforced_shape_guard(tmp_path):
    findings, _ = _rpl007_tree(
        tmp_path, REGISTRY_FULL.format(extra=', "entry": "run"'),
        kernel_body="""
            from jax.experimental import pallas as pl

            def run(x, bt=8):
                return pl.pallas_call(lambda r, o: None, grid=(4,))(x)
        """)
    assert codes(findings) == ["RPL007"]
    assert "divisibility" in findings[0].message


def test_rpl007_clean_with_full_contract(tmp_path):
    findings, _ = _rpl007_tree(
        tmp_path, REGISTRY_FULL.format(extra=', "entry": "run"'))
    assert findings == []


def test_rpl007_suppressed_above_decorated_entry(tmp_path):
    """The parity finding anchors on the `def` line; a disable comment
    ABOVE the decorator stack must still reach it (comment suppression
    propagates through decorator lines)."""
    findings, suppressed = _rpl007_tree(
        tmp_path, REGISTRY_FULL.format(extra=', "entry": "run"'),
        kernel_body="""
            import functools
            from jax.experimental import pallas as pl

            # repro-lint: disable=RPL007
            @functools.lru_cache(maxsize=None)
            def run(x, bt=8):
                T = x.shape[0]
                assert T % bt == 0
                return pl.pallas_call(lambda r, o: None,
                                      grid=(T // bt,))(x)
        """,
        ref_body="def foo(x, scale):\n    return x * scale\n")
    assert findings == []
    assert codes(suppressed) == ["RPL007"]


# ---------------------------------------------------------------------------
# RPL008 — commit discipline (minimized PR 9 bug)
# ---------------------------------------------------------------------------

PR9_BUG = """
    class Eng:
        def reset_slot(self, slot):
            self._slot_bufs[slot] = None
            self._stream_state = self._jit_reset(self._stream_state, slot)
"""


def test_rpl008_fires_on_pr9_half_committed_reset_repro(tmp_path):
    findings, _ = lint_snippet(tmp_path, PR9_BUG, rules=["RPL008"])
    assert codes(findings) == ["RPL008"]
    assert "_slot_bufs" in findings[0].message
    assert findings[0].related            # mutation line attached


def test_rpl008_clean_dispatch_then_commit(tmp_path):
    # regression fixture for the asr.py reset_slot fix: run the
    # may-raise jit dispatch FIRST, commit engine state only after
    findings, _ = lint_snippet(tmp_path, """
        class Eng:
            def reset_slot(self, slot):
                new_state = self._jit_reset(self._stream_state, slot)
                self._stream_state = new_state
                self._slot_bufs[slot] = None
    """, rules=["RPL008"])
    assert findings == []


def test_rpl008_clean_with_restoring_handler(tmp_path):
    findings, _ = lint_snippet(tmp_path, """
        class Eng:
            def reset_slot(self, slot):
                saved = self._slot_bufs[slot]
                self._slot_bufs[slot] = None
                try:
                    self._jit_reset(slot)
                except Exception:
                    self._slot_bufs[slot] = saved
                    raise
    """, rules=["RPL008"])
    assert findings == []


def test_rpl008_fires_on_mutator_method_before_fault_probe(tmp_path):
    findings, _ = lint_snippet(tmp_path, """
        class Eng:
            def admit(self, sess):
                self._beam.append(sess)
                self._faults.check("admit")
    """, rules=["RPL008"])
    assert codes(findings) == ["RPL008"]
    assert "fault injector" in findings[0].message


def test_rpl008_suppressed_at_related_callee_hazard_line(tmp_path):
    """The hazard sits two files away: eng.py mutates state and calls
    disp.dispatch(), whose body dispatches a jitted step.  A disable at
    the CALLEE hazard line suppresses the caller-side finding (the
    callee owns the raise contract)."""
    (tmp_path / "disp.py").write_text(textwrap.dedent("""
        def dispatch(eng, slot):
            return eng._jit_step(slot)  # repro-lint: disable=RPL008
    """))
    (tmp_path / "eng.py").write_text(textwrap.dedent("""
        from disp import dispatch

        class Eng:
            def reset(self, slot):
                self._slot_bufs[slot] = None
                dispatch(self, slot)
    """))
    findings, suppressed = run_paths(
        [str(tmp_path / "eng.py"), str(tmp_path / "disp.py")],
        rules=["RPL008"], root=tmp_path)
    assert findings == []
    assert codes(suppressed) == ["RPL008"]


def test_rpl008_fires_through_unsuppressed_callee_hazard(tmp_path):
    # same two-file shape without the disable: the interprocedural
    # propagation itself must fire, and related must point at both the
    # mutation line and the callee hazard line
    (tmp_path / "disp.py").write_text(textwrap.dedent("""
        def dispatch(eng, slot):
            return eng._jit_step(slot)
    """))
    (tmp_path / "eng.py").write_text(textwrap.dedent("""
        from disp import dispatch

        class Eng:
            def reset(self, slot):
                self._slot_bufs[slot] = None
                dispatch(self, slot)
    """))
    findings, _ = run_paths(
        [str(tmp_path / "eng.py"), str(tmp_path / "disp.py")],
        rules=["RPL008"], root=tmp_path)
    assert codes(findings) == ["RPL008"]
    rel_paths = {p for p, _ in findings[0].related}
    assert "eng.py" in rel_paths and "disp.py" in rel_paths


# ---------------------------------------------------------------------------
# driver mechanics + self-run
# ---------------------------------------------------------------------------

def test_rule_docs_cover_all_eight_rules():
    assert sorted(RULE_DOCS) == ["RPL001", "RPL002", "RPL003",
                                 "RPL004", "RPL005", "RPL006",
                                 "RPL007", "RPL008"]


def test_preceding_line_suppression(tmp_path):
    findings, suppressed = lint_snippet(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            # repro-lint: disable=RPL001
            if x > 0:
                return x
            return -x
    """)
    assert findings == []
    assert codes(suppressed) == ["RPL001"]


def test_cli_exit_codes(tmp_path):
    from repro.analysis.__main__ import main
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\n\n@jax.jit\ndef f(x):\n"
                   "    return float(x)\n")
    assert main([str(bad)]) == 1
    good = tmp_path / "good.py"
    good.write_text("x = 1\n")
    assert main([str(good)]) == 0
    assert main(["--list-rules"]) == 0


BAD_SNIPPET = ("import jax\n\n@jax.jit\ndef f(x):\n"
               "    return float(x)\n")


def test_cli_file_wide_disable_with_show_suppressed(tmp_path, capsys):
    from repro.analysis.__main__ import main
    bad = tmp_path / "bad.py"
    bad.write_text("# repro-lint: disable-file=RPL001\n" + BAD_SNIPPET)
    assert main([str(bad)]) == 0
    # without the flag only the summary counts it; no finding line
    assert "[suppressed] " not in capsys.readouterr().out
    assert main([str(bad), "--show-suppressed"]) == 0
    out = capsys.readouterr().out
    assert "[suppressed] " in out and "RPL001" in out
    assert "1 suppressed" in out


def test_cli_github_format(tmp_path, capsys):
    from repro.analysis.__main__ import main
    bad = tmp_path / "bad.py"
    bad.write_text(BAD_SNIPPET)
    assert main([str(bad), "--format", "github"]) == 1
    out = capsys.readouterr().out
    assert "::error file=" in out
    assert "title=repro-lint RPL001" in out

    sup = tmp_path / "sup.py"
    sup.write_text("# repro-lint: disable-file=RPL001\n" + BAD_SNIPPET)
    assert main([str(sup), "--format", "github",
                 "--show-suppressed"]) == 0
    out = capsys.readouterr().out
    assert "::notice file=" in out          # suppressed demoted
    assert "::error" not in out


def test_cli_baseline_round_trip(tmp_path, capsys):
    from repro.analysis.__main__ import main
    bad = tmp_path / "bad.py"
    bad.write_text(BAD_SNIPPET)
    baseline = tmp_path / "baseline.json"

    # recording the current findings turns the run green...
    assert main([str(bad), "--baseline", str(baseline),
                 "--update-baseline"]) == 0
    assert baseline.exists()
    assert main([str(bad), "--baseline", str(baseline)]) == 0
    out = capsys.readouterr().out
    assert "1 baselined" in out and "[baseline] " in out

    # ...but a NEW finding (second float() coercion, distinct message
    # context) still gates: the baseline is a per-key count budget
    bad.write_text(BAD_SNIPPET +
                   "\n@jax.jit\ndef g(y):\n    return int(y)\n")
    assert main([str(bad), "--baseline", str(baseline)]) == 1
    assert main([str(bad), "--baseline", str(baseline),
                 "--update-baseline"]) == 0
    assert main([str(bad), "--baseline", str(baseline)]) == 0


def test_self_run_over_src_repro_is_clean():
    """The gating CI contract: zero unsuppressed findings on the tree."""
    findings, _ = run_paths([str(REPO / "src" / "repro")], root=REPO)
    assert findings == [], "\n".join(f.format() for f in findings)


def test_config_registry_has_no_dead_modules():
    """Every config module is either imported outside archs.py's blanket
    registration or named by a test/launcher (the import-graph check
    that cleared deepseek_coder_33b for deletion)."""
    from repro.analysis.imports import config_usage
    dead = [u.module for u in config_usage(REPO) if u.dead]
    assert dead == [], dead


# ---------------------------------------------------------------------------
# runtime guards
# ---------------------------------------------------------------------------

def test_worker_only_runtime_guard():
    from repro.serving.engine import Engine
    eng = Engine(SimpleNamespace(n_slots=1, max_queue=None))
    assert eng._admit() is False          # unowned engine: any thread

    eng._owner_thread = threading.Thread(name="fake-worker")
    with pytest.raises(RuntimeError, match="owned by worker thread"):
        eng._admit()
    eng._owner_thread = None
    assert eng._admit() is False


def test_compilation_budget_counts_and_raises():
    import jax
    import jax.numpy as jnp

    from repro.analysis.guards import compilation_budget, count_compilations

    @jax.jit
    def f(x):
        return x * 2 + 1

    x7, x7b, x9 = jnp.arange(7.0), jnp.arange(7.0) + 1, jnp.arange(9.0)
    with count_compilations() as c:
        jax.block_until_ready(f(x7))
    assert c.count >= 1                   # fresh shape: really compiled

    with compilation_budget(0, "warmed f"):
        jax.block_until_ready(f(x7b))

    with pytest.raises(AssertionError, match="compilation budget"):
        with compilation_budget(0, "cold shape"):
            jax.block_until_ready(f(x9))


def test_no_implicit_transfers_blocks_scalar_readback():
    import jax
    import jax.numpy as jnp

    from repro.analysis.guards import no_implicit_transfers

    x = jnp.arange(4.0)
    with no_implicit_transfers():
        y = x + x                         # device-only work: fine
    with pytest.raises(jax.errors.JaxRuntimeError, match="[Dd]isallow"):
        with no_implicit_transfers():
            float(x[0])                   # implicit device->host readback


# ---------------------------------------------------------------------------
# regression tests for the true positives fixed in this PR
# ---------------------------------------------------------------------------

def test_asr_poll_results_are_owned_writable_copies():
    """PR 6 follow-up (found by RPL003): mid-stream poll results were
    zero-copy READ-ONLY views over the engine's jitted readout buffers.
    Callers must receive owned, writable arrays, and mutating them must
    not leak into later polls."""
    from repro.launch.serve import asr_demo_engine
    from repro.data.pipeline import SyntheticASR

    engine, words = asr_demo_engine(1)
    audio = SyntheticASR(words).utterance(0)["audio"]
    sess = engine.open().push(audio)
    res = sess.poll()
    assert sess.admitted and engine.n_steps > 0
    for key in ("words", "tokens"):
        arr = res[key]
        assert isinstance(arr, np.ndarray) and arr.flags.writeable, key
        arr.fill(-1)                      # caller scribbles on its copy
    res2 = sess.poll()                    # ...and the engine never sees it
    assert not (len(res2["tokens"]) and (res2["tokens"] == -1).all())
    final = sess.finish()
    assert final["words"].flags.writeable
    assert final["tokens"].flags.writeable
