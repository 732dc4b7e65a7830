"""quadlint (``python -m repro.analysis``) tests.

Per-rule bad/good fixtures (each bad snippet must produce its rule,
each good twin must not), the suppression contract (reasons are
mandatory, QL000 is unsuppressable), CLI exit codes and output format,
the QL001 mutation checks (an unthreaded QuadState field and a
dropped registry entry must both fail the scan), and the tier-1 pin
that the repo's own tree is clean.
"""
import collections
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import repro.core.solver as solver_mod
from repro.analysis import run_paths
from repro.analysis.engine import main

REPO = Path(__file__).resolve().parent.parent


def _write(tmp_path, rel_parts, code):
    p = tmp_path.joinpath(*rel_parts)
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(code), encoding="utf-8")
    return p


def _lint(tmp_path, rel_parts, code):
    p = _write(tmp_path, rel_parts, code)
    return run_paths([str(p)], project_checks=False)


def _rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# QL002: tracer leaks


def test_ql002_if_on_traced_value_in_jit(tmp_path):
    findings = _lint(tmp_path, ("m.py",), """
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
        """)
    assert _rules(findings) == ["QL002"]
    assert findings[0].line == 6


def test_ql002_concretization_in_while_loop_body(tmp_path):
    findings = _lint(tmp_path, ("m.py",), """
        import jax

        def run(x0):
            def body(c):
                y = float(c)
                return y + 1.0
            return jax.lax.while_loop(lambda c: c < 3.0, body, x0)
        """)
    assert _rules(findings) == ["QL002"]
    assert "float()" in findings[0].message


def test_ql002_static_shapes_and_none_checks_are_fine(tmp_path):
    findings = _lint(tmp_path, ("m.py",), """
        import jax
        import jax.numpy as jnp
        from functools import partial

        @partial(jax.jit, static_argnames=("n",))
        def f(x, n, probe=None):
            if n > 2:
                x = x * 2
            if probe is not None:
                x = x + probe
            if x.ndim == 2:
                x = x.sum(axis=-1)
            return jnp.where(x > 0, x, -x)
        """)
    assert findings == []


# ---------------------------------------------------------------------------
# QL003: jit discipline


def test_ql003_serve_jit_without_trace_counter(tmp_path):
    findings = _lint(tmp_path, ("src", "repro", "serve", "m.py"), """
        import jax

        @jax.jit
        def _run(x):
            return x * 2
        """)
    assert _rules(findings) == ["QL003"]
    assert "trace counter" in findings[0].message


def test_ql003_serve_jit_with_trace_counter_is_fine(tmp_path):
    findings = _lint(tmp_path, ("src", "repro", "serve", "m.py"), """
        import jax

        _RUN_TRACES = [0]

        @jax.jit
        def _run(x):
            _RUN_TRACES[0] += 1
            return x * 2
        """)
    assert findings == []


def test_ql003_jit_inside_function_body(tmp_path):
    findings = _lint(tmp_path, ("src", "repro", "pkg", "m.py"), """
        import jax

        def make(f):
            return jax.jit(f)
        """)
    assert _rules(findings) == ["QL003"]
    assert "function body" in findings[0].message


def test_ql003_only_applies_to_library_code(tmp_path):
    findings = _lint(tmp_path, ("scripts", "m.py"), """
        import jax

        def make(f):
            return jax.jit(f)
        """)
    assert findings == []


# ---------------------------------------------------------------------------
# QL004: collective pairing under shard_map


_QL004_BAD = """
    import jax
    import jax.numpy as jnp
    from jax.experimental.shard_map import shard_map

    def drive(mesh, xs):
        def local_fn(x):
            def cond(c):
                return c[1] < 3

            def body(c):
                g = jax.lax.all_gather(c[0], "lanes")
                return (g.sum(axis=0), c[1] + 1)

            return jax.lax.while_loop(cond, body, (x, 0))

        return shard_map(local_fn, mesh=mesh, in_specs=None,
                         out_specs=None)(xs)
    """


def test_ql004_unguarded_collective_in_while_loop(tmp_path):
    findings = _lint(tmp_path, ("m.py",), _QL004_BAD)
    assert "QL004" in _rules(findings)
    msg = [f for f in findings if f.rule == "QL004"][0].message
    assert "all_gather" in msg


def test_ql004_psum_continue_flag_is_fine(tmp_path):
    findings = _lint(tmp_path, ("m.py",), """
        import jax
        import jax.numpy as jnp
        from jax.experimental.shard_map import shard_map

        def drive(mesh, xs):
            def local_fn(x):
                def cond(c):
                    nm = c[1] < 3
                    return jax.lax.psum(
                        jnp.any(nm).astype(jnp.int32), "lanes") > 0

                def body(c):
                    g = jax.lax.all_gather(c[0], "lanes")
                    return (g.sum(axis=0), c[1] + 1)

                return jax.lax.while_loop(cond, body, (x, 0))

            return shard_map(local_fn, mesh=mesh, in_specs=None,
                             out_specs=None)(xs)
        """)
    assert "QL004" not in _rules(findings)


# ---------------------------------------------------------------------------
# QL007: collective cadence in core/ loop bodies


_QL007_BAD = """
    import jax
    import jax.numpy as jnp

    def drive(xs):
        def cond(c):
            return c[1] < 3

        def body(c):
            g = jax.lax.all_gather(c[0], "lanes")
            f = jax.lax.psum(jnp.any(g).astype(jnp.int32), "lanes")
            return (g.sum(axis=0), c[1] + f)

        return jax.lax.while_loop(cond, body, (xs, 0))
    """


def test_ql007_raw_collectives_in_core_loop_body(tmp_path):
    findings = _lint(tmp_path, ("src", "repro", "core", "m.py"),
                     _QL007_BAD)
    ql7 = [f for f in findings if f.rule == "QL007"]
    # one finding PER collective call site, anchored at its own line
    assert sorted(f.message.split()[1] for f in ql7) == \
        ["all_gather", "psum"]


def test_ql007_transitive_through_module_helper(tmp_path):
    # QL004's same-scope walk cannot see a module-level helper; QL007's
    # module-wide walk must
    findings = _lint(tmp_path, ("src", "repro", "core", "m.py"), """
        import jax

        def helper(x):
            return jax.lax.all_gather(x, "lanes")

        def drive(xs):
            def cond(c):
                return c[1] < 3

            def body(c):
                g = helper(c[0])
                return (g.sum(axis=0), c[1] + 1)

            return jax.lax.while_loop(cond, body, (xs, 0))
        """)
    ql7 = [f for f in findings if f.rule == "QL007"]
    assert len(ql7) == 1 and "all_gather" in ql7[0].message
    # anchored at the helper's gather line, where a suppression lives
    assert ql7[0].line == 5


def test_ql007_suppressed_cadence_helper_is_fine(tmp_path):
    findings = _lint(tmp_path, ("src", "repro", "core", "m.py"), """
        import jax

        def _round_gather(x):
            return jax.lax.all_gather(x, "lanes", tiled=True)  # quadlint: disable=QL007 -- the sanctioned per-round collective

        def drive(xs):
            def cond(c):
                return c[1] < 3

            def body(c):
                g = _round_gather(c[0])
                return (g.sum(axis=0), c[1] + 1)

            return jax.lax.while_loop(cond, body, (xs, 0))
        """)
    assert "QL007" not in _rules(findings)


def test_ql007_collective_outside_the_loop_is_fine(tmp_path):
    findings = _lint(tmp_path, ("src", "repro", "core", "m.py"), """
        import jax

        def boundary(x):
            return jax.lax.all_gather(x, "lanes")

        def drive(xs):
            def cond(c):
                return c[1] < 3

            def body(c):
                return (c[0] * 2.0, c[1] + 1)

            out = jax.lax.while_loop(cond, body, (xs, 0))
            return boundary(out[0])
        """)
    assert "QL007" not in _rules(findings)


def test_ql007_only_applies_to_core(tmp_path):
    findings = _lint(tmp_path, ("src", "repro", "serve", "m.py"),
                     _QL007_BAD)
    assert "QL007" not in _rules(findings)


# ---------------------------------------------------------------------------
# QL005: removed-shim imports stay removed


def test_ql005_shim_function_and_module_imports(tmp_path):
    findings = _lint(tmp_path, ("src", "repro", "pkg", "m.py"), """
        from repro.core import bif_bounds
        from repro.core.judge import judge_threshold
        """)
    assert [f.rule for f in findings] == ["QL005", "QL005"]


def test_ql005_solver_imports_are_fine(tmp_path):
    findings = _lint(tmp_path, ("src", "repro", "pkg", "m.py"), """
        from repro.core import BIFSolver, bif_bounds_trace
        """)
    assert findings == []


# ---------------------------------------------------------------------------
# QL006: unkeyed randomness


def test_ql006_legacy_and_unseeded_randomness(tmp_path):
    findings = _lint(tmp_path, ("src", "repro", "pkg", "m.py"), """
        import random
        import numpy as np

        x = np.random.rand(3)
        rng = np.random.default_rng()
        """)
    assert [f.rule for f in findings] == ["QL006", "QL006", "QL006"]


def test_ql006_seeded_rng_is_fine(tmp_path):
    findings = _lint(tmp_path, ("src", "repro", "pkg", "m.py"), """
        import numpy as np

        rng = np.random.default_rng(0)
        x = rng.standard_normal(3)
        """)
    assert findings == []


# ---------------------------------------------------------------------------
# QL008: telemetry is host-side only


def test_ql008_metrics_call_and_print_in_traced_scope(tmp_path):
    findings = _lint(tmp_path, ("src", "repro", "pkg", "m.py"), """
        import jax
        from repro.obs import metrics as obs_metrics

        def run(x):
            def body(c):
                obs_metrics.counter("steps").inc()
                print(c)
                return c - 1
            return jax.lax.while_loop(lambda c: c > 0, body, x)
        """)
    assert [f.rule for f in findings] == ["QL008", "QL008"]
    assert "host-side-only" in findings[0].message
    assert "trace time" in findings[1].message


def test_ql008_span_through_module_helper(tmp_path):
    findings = _lint(tmp_path, ("src", "repro", "pkg", "m.py"), """
        import jax
        from repro.obs.spans import span

        def _tick():
            with span("step"):
                return None

        def run(x):
            def body(c):
                _tick()
                return c - 1
            return jax.lax.while_loop(lambda c: c > 0, body, x)
        """)
    assert _rules(findings) == ["QL008"]


def test_ql008_obs_package_attribute_path_in_jit(tmp_path):
    findings = _lint(tmp_path, ("src", "repro", "pkg", "m.py"), """
        import jax
        from repro import obs

        @jax.jit
        def _run(x):
            obs.spans.span("step")
            return x * 2
        """)
    assert "QL008" in _rules(findings)


def test_ql008_host_side_and_registry_probe_are_fine(tmp_path):
    # obs.registry.count is the sanctioned trace-time probe — and it
    # satisfies QL003's trace-counter requirement on serve jits
    findings = _lint(tmp_path, ("src", "repro", "serve", "m.py"), """
        import jax
        from repro.obs import metrics as obs_metrics
        from repro.obs import registry as obs_registry

        @jax.jit
        def _run(x):
            obs_registry.count("serve.m.run")
            return x * 2

        def drive(x):
            y = _run(x)
            obs_metrics.counter("calls").inc()
            return y
        """)
    assert findings == []


def test_ql008_suppression_and_non_library_code(tmp_path):
    findings = _lint(tmp_path, ("src", "repro", "pkg", "m.py"), """
        import jax

        def run(x):
            def body(c):
                # quadlint: disable=QL008 -- trace-time dump, dev only
                print(c)
                return c - 1
            return jax.lax.while_loop(lambda c: c > 0, body, x)
        """)
    assert findings == []
    findings = _lint(tmp_path, ("benchmarks", "m.py"), """
        import jax
        from repro.obs import metrics as obs_metrics

        def run(x):
            def body(c):
                obs_metrics.counter("steps").inc()
                return c - 1
            return jax.lax.while_loop(lambda c: c > 0, body, x)
        """)
    assert findings == []  # QL008 is a library-code contract


# ---------------------------------------------------------------------------
# Suppressions


def test_suppression_with_reason_silences_rule(tmp_path):
    findings = _lint(tmp_path, ("src", "repro", "pkg", "m.py"), """
        import numpy as np

        # quadlint: disable=QL006 -- fixture generator, determinism n/a
        x = np.random.rand(3)
        """)
    assert findings == []


def test_suppression_without_reason_is_a_finding(tmp_path):
    findings = _lint(tmp_path, ("src", "repro", "pkg", "m.py"), """
        import numpy as np

        x = np.random.rand(3)  # quadlint: disable=QL006
        """)
    # the bare directive does NOT suppress, and is itself QL000
    assert _rules(findings) == ["QL000", "QL006"]


def test_ql000_cannot_be_suppressed(tmp_path):
    findings = _lint(tmp_path, ("m.py",), """
        # quadlint: disable=QL000 -- nice try
        # quadlint: enable-everything
        """)
    assert [f.rule for f in findings] == ["QL000"]
    assert "malformed" in findings[0].message


# ---------------------------------------------------------------------------
# QL001: state-threading mutation checks (the tentpole's teeth)


def _ql001(paths=None):
    findings = run_paths(paths or [str(REPO / "src" / "repro")])
    return [f for f in findings if f.rule == "QL001"]


def test_ql001_unthreaded_quadstate_field_is_caught(monkeypatch):
    mutant = collections.namedtuple(
        "QuadState", solver_mod.QuadState._fields + ("block_basis",))
    monkeypatch.setattr(solver_mod, "QuadState", mutant)
    findings = _ql001()
    msgs = [f.message for f in findings]
    # unclaimed by the registries ...
    assert any("block_basis" in m and "not claimed" in m for m in msgs)
    # ... and every construction site now under-threads it
    assert any("omits field 'block_basis'" in m for m in msgs)


def test_ql001_dropped_registry_entry_is_caught(monkeypatch):
    monkeypatch.setattr(solver_mod, "QUADSTATE_PER_LANE", ("st", "basis"))
    findings = _ql001()
    assert any("'coeffs'" in f.message and "not claimed" in f.message
               for f in findings)


def test_ql001_coeffhistory_mutations_are_caught(monkeypatch):
    import dataclasses

    import repro.core.matfun as matfun_mod

    # dropping the writer-exclusion registry: fnidx is now unhandled
    monkeypatch.setattr(matfun_mod, "COEFF_REPLACE_EXCLUDED", ())
    findings = _ql001()
    assert any("update_coeffs" in f.message and "'fnidx'" in f.message
               for f in findings)

    # a new CoeffHistory field missing from the pytree registration
    mutant = dataclasses.make_dataclass(
        "CoeffHistory", [f.name for f in
                         dataclasses.fields(matfun_mod.CoeffHistory)]
        + ["block_buf"])
    monkeypatch.setattr(matfun_mod, "CoeffHistory", mutant)
    findings = _ql001()
    assert any("block_buf" in f.message and "register_dataclass"
               in f.message for f in findings)


def test_ql001_excluded_field_registry_is_live(monkeypatch):
    import repro.core.sharded as sharded_mod
    monkeypatch.setattr(sharded_mod, "SHARDED_STATE_EXCLUDED", ())
    findings = _ql001()
    assert any("_drive_sharded" in f.message and "'basis'" in f.message
               for f in findings)


def test_ql001_chainfactor_mutations_are_caught(monkeypatch):
    import dataclasses

    import repro.core.update as update_mod

    # dropping the writer-exclusion registry: `n` becomes an unhandled
    # field of `downdate` (which rewrites via dataclasses.replace and
    # deliberately never touches n; `extend` constructs a full
    # ChainFactor so it writes every field either way)
    monkeypatch.setattr(update_mod, "FACTOR_REPLACE_EXCLUDED", ())
    findings = _ql001()
    assert any("downdate" in f.message and "'n'" in f.message
               for f in findings)

    # a new ChainFactor field (say a rank-update cache) missing from the
    # pytree registration AND from the carry writers
    mutant = dataclasses.make_dataclass(
        "ChainFactor", [f.name for f in
                        dataclasses.fields(update_mod.ChainFactor)]
        + ["rank_cache"])
    monkeypatch.setattr(update_mod, "ChainFactor", mutant)
    findings = _ql001()
    msgs = [f.message for f in findings]
    assert any("rank_cache" in m and "register_dataclass" in m
               for m in msgs)
    assert any("rank_cache" in m and "extend" in m for m in msgs)
    assert any("rank_cache" in m and "downdate" in m for m in msgs)


def test_ql001_blockstate_mutations_are_caught(monkeypatch):
    import dataclasses

    import repro.core.block as block_mod

    # a new BlockState field (say a reorth buffer) missing from the
    # pytree registration AND from the step writer
    mutant = dataclasses.make_dataclass(
        "BlockState", [f.name for f in
                       dataclasses.fields(block_mod.BlockState)]
        + ["reorth_buf"])
    monkeypatch.setattr(block_mod, "BlockState", mutant)
    findings = _ql001()
    msgs = [f.message for f in findings]
    assert any("reorth_buf" in m and "register_dataclass" in m
               for m in msgs)
    assert any("reorth_buf" in m and "block_step" in m for m in msgs)


def test_ql001_blockstate_dropped_registry_entry_is_caught(monkeypatch):
    import repro.core.block as block_mod

    # dropping the writer-exclusion registry: r0/fnidx (init-constant
    # fields block_step deliberately never rewrites) become unhandled
    monkeypatch.setattr(block_mod, "BLOCK_REPLACE_EXCLUDED", ())
    findings = _ql001()
    msgs = [f.message for f in findings]
    assert any("block_step" in m and "'r0'" in m for m in msgs)
    assert any("block_step" in m and "'fnidx'" in m for m in msgs)


def test_ql001_round_body_delegation_credit():
    """PR 7 moved the per-substep freeze into ``_round_body``; a handler
    inherits that freeze coverage ONLY if it actually references the
    round driver — a handler that skips it must freeze for itself."""
    import ast as _ast

    from repro.analysis.contracts import _round_body_frozen

    tree = _ast.parse(textwrap.dedent("""
        def _round_body(op, stepfn):
            def substep(i, carry):
                st = tree_freeze(st1, st, frozen)
                coeffs = tree_freeze(coeffs1, coeffs, frozen)
                return carry
            return substep

        def delegating(self, state):
            round_fn = self._round_body(op, stepfn)
            return round_fn

        def freeloading(self, state):
            return state
    """))
    defs = {n.name: n for n in tree.body}
    credited = _round_body_frozen(defs["delegating"], tree)
    assert {"st", "coeffs"} <= credited
    assert _round_body_frozen(defs["freeloading"], tree) == set()


# ---------------------------------------------------------------------------
# CLI + the repo's own cleanliness (tier-1)


def test_cli_exit_codes_and_output_format(tmp_path, capsys):
    bad = _write(tmp_path, ("src", "repro", "pkg", "m.py"),
                 "import random\n")
    good = _write(tmp_path, ("src", "repro", "pkg", "ok.py"),
                  "X = 1\n")
    assert main([str(bad)]) == 1
    out = capsys.readouterr().out
    assert re.search(r"^.+:1 QL006 ", out, re.M)
    assert "1 finding(s)" in out
    assert main([str(good)]) == 0
    assert capsys.readouterr().out == ""


def test_cli_module_entrypoint(tmp_path):
    bad = _write(tmp_path, ("src", "repro", "pkg", "m.py"),
                 "import random\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", str(bad),
         "--no-project-checks"],
        capture_output=True, text=True, env=env, cwd=str(REPO))
    assert proc.returncode == 1
    assert re.search(r":1 QL006 ", proc.stdout)


def test_repo_tree_is_clean():
    """The merged tree carries zero findings (the CI `static` job)."""
    findings = run_paths([str(REPO / "src"), str(REPO / "tests"),
                          str(REPO / "benchmarks")])
    assert findings == [], "\n".join(f.render() for f in findings)
