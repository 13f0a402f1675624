"""Tests for tools/repo_lint.py: each rule fires on a seeded fixture,
the pragma escape works, and the repo itself lints clean."""

import os
import sys
import textwrap

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import repo_lint  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_src(tmp_path, src, name="m.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    return repo_lint.lint_file(str(p), str(tmp_path))


def fired(findings):
    return [f["rule"] for f in findings]


def test_expr_eq_fires(tmp_path):
    fs = lint_src(tmp_path, """\
        def f(expr, other):
            if expr == other:
                return True
    """)
    assert fired(fs) == ["EXPR-EQ"]


def test_expr_ne_and_attr_operand(tmp_path):
    fs = lint_src(tmp_path, """\
        def f(eq, node):
            return eq.lhs != node
    """)
    assert fired(fs) == ["EXPR-NE"]


def test_expr_key_subscript_and_dict_literal(tmp_path):
    fs = lint_src(tmp_path, """\
        def f(memo, expr, rhs):
            memo[expr] = 1
            return {rhs: 2}
    """)
    assert fired(fs) == ["EXPR-KEY", "EXPR-KEY"]


def test_pragma_escapes(tmp_path):
    fs = lint_src(tmp_path, """\
        import jax

        def f(expr, other, memo):
            a = expr == other  # lint: expr-eq-ok
            memo[expr] = 1  # lint: expr-key-ok
            return jax.devices()   # a plain device query is fine
    """)
    assert fs == []


def test_clean_code_not_flagged(tmp_path):
    fs = lint_src(tmp_path, """\
        def f(expr, other, count):
            if expr.same(other) and count == 3:
                return expr.skey()
            table = {expr.skey(): 1}
            return table
    """)
    assert fs == []


def test_mesh_direct_fires_outside_factory(tmp_path):
    fs = lint_src(tmp_path, """\
        from jax.sharding import Mesh

        def build(devs):
            return Mesh(devs, axis_names=("x",))
    """)
    assert fired(fs) == ["MESH-DIRECT"]


def test_mesh_direct_exempt_in_factory_and_pragma(tmp_path):
    import os
    (tmp_path / "yask_tpu" / "parallel").mkdir(parents=True)
    fs = lint_src(tmp_path, """\
        from jax.sharding import Mesh

        def make_mesh(devs, axes):
            return Mesh(devs, axis_names=axes)
    """, name=os.path.join("yask_tpu", "parallel", "mesh.py"))
    assert fs == []
    fs = lint_src(tmp_path, """\
        import jax.sharding as shd

        def probe(devs):
            return shd.Mesh(devs, ("x",))  # lint: mesh-ok
    """)
    assert fs == []


def test_ordinary_eq_in_expr_suffix_name_only(tmp_path):
    # names NOT in the suspect set stay un-flagged
    fs = lint_src(tmp_path, """\
        def f(value, mode, cond):
            return value == 1 and mode != "jit" and cond == True
    """)
    assert fs == []


def lint_tool(tmp_path, src, name="tools/t.py"):
    p = tmp_path / name
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    return repo_lint.lint_file(str(p), str(tmp_path))


def test_bare_device_call_fires_in_driver_scope(tmp_path):
    src = """\
        def main(ctx):
            ctx.run_solution(0, 9)
    """
    assert fired(lint_tool(tmp_path, src)) == ["BARE-DEVICE-CALL"]
    assert fired(lint_tool(tmp_path, src, name="yask_tpu/serve/x.py")) \
        == ["BARE-DEVICE-CALL"]
    # library / test code is out of scope: the rule is about driver
    # artifacts that run unattended against the device
    assert fired(lint_tool(tmp_path, src, name="yask_tpu/x.py")) == []


def test_bare_device_call_sanctioned_via_guarded_name(tmp_path):
    fs = lint_tool(tmp_path, """\
        def measure(ctx):
            ctx.run_solution(0, 9)
            return ctx.compare_data(ctx)

        def main(ctx):
            return guarded_call(measure, ctx, site="bench.measure")
    """)
    assert fs == []


def test_bare_device_call_transitive_closure(tmp_path):
    # the guarded root calls a helper; the helper's device work is
    # sanctioned through the call-graph closure
    fs = lint_tool(tmp_path, """\
        def helper(ctx):
            ctx.run_solution(0, 9)

        def sect(ctx):
            helper(ctx)

        def main(ctx):
            guarded_call(sect, ctx, site="suite.sect")
    """)
    assert fs == []


def test_bare_device_call_factory_arg(tmp_path):
    # guarded_call(make_body(...)): the factory's nested body runs
    # under the guard
    fs = lint_tool(tmp_path, """\
        def make_body(ctx):
            def body():
                ctx.run_solution(0, 9)
            return body

        def main(ctx):
            guarded_call(make_body(ctx), site="suite.validate")
    """)
    assert fs == []


def test_bare_device_call_unguarded_sibling_still_fires(tmp_path):
    fs = lint_tool(tmp_path, """\
        def guarded_fn(ctx):
            ctx.run_solution(0, 9)

        def bare_fn(ctx):
            ctx.run_solution(0, 9)

        def main(ctx):
            guarded_call(guarded_fn, ctx, site="bench.x")
            bare_fn(ctx)
    """)
    assert fired(fs) == ["BARE-DEVICE-CALL"]
    assert fs[0]["line"] == 5


def test_bare_device_call_pragma(tmp_path):
    fs = lint_tool(tmp_path, """\
        def main(ctx):
            ctx.run_solution(0, 9)  # lint: bare-device-call-ok
    """)
    assert fs == []


def test_ckpt_unguarded_fires_in_driver_scope(tmp_path):
    src = """\
        def main(ctx, path):
            save_checkpoint(ctx, path)
    """
    assert fired(lint_tool(tmp_path, src)) == ["CKPT-UNGUARDED"]
    assert fired(lint_tool(tmp_path, src, name="yask_tpu/serve/x.py")) \
        == ["CKPT-UNGUARDED"]
    # library / test code is out of scope, same as BARE-DEVICE-CALL
    assert fired(lint_tool(tmp_path, src, name="yask_tpu/x.py")) == []


def test_ckpt_unguarded_sanctioned_via_guard(tmp_path):
    # passing the checkpoint fn INTO guarded_call is the sanctioned
    # shape; a helper invoked from a guard root rides the closure
    fs = lint_tool(tmp_path, """\
        def resume(ctx, path):
            return restore_checkpoint(ctx, path)

        def main(ctx, path):
            guarded_call(save_checkpoint, ctx, path, site="ckpt.save")
            guarded_call(resume, ctx, path, site="ckpt.restore")
    """)
    assert fs == []


def test_ckpt_unguarded_pragma(tmp_path):
    fs = lint_tool(tmp_path, """\
        def main(ctx, path):
            restore_checkpoint(ctx, path)  # lint: ckpt-unguarded-ok
    """)
    assert fs == []


def test_compile_direct_fires_on_chain(tmp_path):
    fs = lint_src(tmp_path, """\
        import jax

        def build(fn, state):
            return jax.jit(fn).lower(state, 0).compile()
    """)
    assert fired(fs) == ["COMPILE-DIRECT"]


def test_compile_direct_fires_on_prejitted_chain(tmp_path):
    # the shard builders return jax.jit objects; chaining off them
    # directly is the same bypass
    fs = lint_src(tmp_path, """\
        def build(jitted, state):
            return jitted.lower(state, 0).compile()
    """)
    assert fired(fs) == ["COMPILE-DIRECT"]


def test_compile_direct_not_fooled_by_str_lower_or_frontend(tmp_path):
    fs = lint_src(tmp_path, """\
        def f(soln, kind):
            csol = soln.compile(dtype="float32")
            low = kind.lower()
            lowered = jax.jit(g).lower(state, 0)   # no .compile(): ok
            return csol, low, lowered
    """)
    assert fs == []


def test_compile_direct_serialize_import(tmp_path):
    fs = lint_src(tmp_path, """\
        from jax.experimental.serialize_executable import serialize
        import jax.experimental.serialize_executable as se
    """)
    assert fired(fs) == ["COMPILE-DIRECT", "COMPILE-DIRECT"]


def test_compile_direct_exempt_in_cache_and_pragma(tmp_path):
    (tmp_path / "yask_tpu" / "cache").mkdir(parents=True)
    fs = lint_src(tmp_path, """\
        from jax.experimental.serialize_executable import serialize

        def fresh(fn, args):
            return jax.jit(fn).lower(*args).compile()
    """, name=os.path.join("yask_tpu", "cache", "compile_cache.py"))
    assert fs == []
    fs = lint_src(tmp_path, """\
        def view(fn, state):
            return jax.jit(fn).lower(state, 0).compile()  # lint: compile-direct-ok
    """)
    assert fs == []


def test_trace_id_fires_on_unstamped_jsonl_append(tmp_path):
    fs = lint_src(tmp_path, """\
        import json

        def bank(path, row):
            with open(path, "a") as f:
                f.write(json.dumps(row) + "\\n")
    """)
    assert fired(fs) == ["TRACE-ID"]
    assert fs[0]["line"] == 4


def test_trace_id_satisfied_by_stamp_or_explicit_field(tmp_path):
    fs = lint_src(tmp_path, """\
        import json
        from yask_tpu.obs.tracer import stamp_trace

        def bank(path, row):
            stamp_trace(row)
            with open(path, "a") as f:
                f.write(json.dumps(row) + "\\n")

        def bank2(path, row, trace_id=""):
            if trace_id:
                row["trace_id"] = trace_id
            with open(path, "a") as f:
                f.write(json.dumps(row) + "\\n")
    """)
    assert fs == []


def test_trace_id_ignores_non_jsonl_appends(tmp_path):
    # a plain text log appender (no json.dumps) is not a journal
    fs = lint_src(tmp_path, """\
        def log(path, line):
            with open(path, "a") as f:
                f.write(line + "\\n")
    """)
    assert fs == []


def test_trace_id_pragma_and_tests_scope(tmp_path):
    src = """\
        import json

        def bank(path, row):
            with open(path, "a") as f:  # lint: trace-id-ok
                f.write(json.dumps(row) + "\\n")
    """
    assert lint_src(tmp_path, src) == []
    bare = src.replace("  # lint: trace-id-ok", "")
    assert fired(lint_src(tmp_path, bare)) == ["TRACE-ID"]
    # tests/ fixture writers are out of scope
    assert lint_tool(tmp_path, bare,
                     name=os.path.join("tests", "t.py")) == []


def test_phase_site_fires_on_unmapped_literal(tmp_path):
    # a site the tracer's phase table maps to the "guard" catch-all is
    # invisible in the per-phase breakdown — new sites must land on a
    # real phase prefix (or extend the table)
    fs = lint_src(tmp_path, """\
        def f(x):
            fault_point("mystery.site")
            return maybe_corrupt("unmapped.thing", x)
    """)
    assert sorted(fired(fs)) == ["PHASE-SITE", "PHASE-SITE"]


def test_phase_site_mapped_and_dynamic_sites_pass(tmp_path):
    fs = lint_src(tmp_path, """\
        def f(fn, x, name):
            fault_point("ckpt.save")
            guarded_call(fn, x, site="bench.measure")
            fault_point(f"suite.{name}")        # mapped f-string head
            guarded_call(fn, x, site=name)      # dynamic: not checkable
    """)
    assert fs == []


def test_phase_site_fires_on_unmapped_fstring_head(tmp_path):
    fs = lint_src(tmp_path, """\
        def f(name):
            fault_point(f"mystery.{name}")
    """)
    assert fired(fs) == ["PHASE-SITE"]


def test_phase_site_pragma_and_tests_scope(tmp_path):
    src = """\
        def f():
            fault_point("mystery.site")
    """
    ok = src.replace('"mystery.site")',
                     '"mystery.site")  # lint: phase-site-ok')
    assert lint_src(tmp_path, ok) == []
    # tests/ fixtures invent sites freely
    assert lint_tool(tmp_path, src,
                     name=os.path.join("tests", "t.py")) == []


def lint_scoped(tmp_path, src, name):
    p = tmp_path / name
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    return repo_lint.lint_file(str(p), str(tmp_path))


CAP_SCOPE = os.path.join("yask_tpu", "compiler", "lowering.py")


def test_cap_const_fires_on_each_literal_class(tmp_path):
    # all four re-baked-constant shapes: raw lane 128, sublane
    # alignment arithmetic, constant-MiB byte value, itemsize→sublane
    # dict map
    fs = lint_scoped(tmp_path, """\
        def geom(total, off, itemsize):
            lanes = 128
            ok = off % 8 == 0 and total // 16 > 1
            budget = 64 * 2 ** 20
            folds = {4: 8, 2: 16, 1: 32}
            return lanes, ok, budget, folds[itemsize]
    """, CAP_SCOPE)
    assert sorted(fired(fs)) == ["CAP-CONST"] * 5
    assert all("capability" in f["message"] for f in fs)


def test_cap_const_scope_is_the_drift_perimeter(tmp_path):
    # same source: flagged in the planner/checker perimeter, legal in
    # the capability table itself (the sanctioned home) and anywhere
    # outside the single-source-of-truth modules
    src = """\
        def f(off):
            return off % 8 == 0 and 128
    """
    for name in (CAP_SCOPE,
                 os.path.join("yask_tpu", "ops", "tile_planner.py"),
                 os.path.join("yask_tpu", "checker", "vmem.py")):
        assert "CAP-CONST" in fired(lint_scoped(tmp_path, src, name)), name
    for name in (os.path.join("yask_tpu", "backend", "capability.py"),
                 os.path.join("yask_tpu", "runtime", "context.py"),
                 "tools/t.py"):
        assert "CAP-CONST" not in fired(lint_scoped(tmp_path, src, name)), \
            name


def test_cap_const_dict_keys_and_plain_ints_exempt(tmp_path):
    # itemsize→X maps KEY on byte sizes; a bare 8 outside alignment
    # arithmetic is a loop bound, not a layout fact
    fs = lint_scoped(tmp_path, """\
        def f(xs):
            table = {128: "lane", 8: "sub"}
            n = 8
            halo = 16 + n
            return table, halo, xs[:8]
    """, CAP_SCOPE)
    assert fs == []


def test_cap_const_pragma(tmp_path):
    fs = lint_scoped(tmp_path, """\
        def f(n):
            return n * 2 ** 20  # lint: cap-const-ok
    """, CAP_SCOPE)
    assert fs == []


LEDGER_SRC = """\
    import os

    def default_path(root):
        return os.path.join(root, "PERF_LEDGER.jsonl")
"""


@pytest.mark.parametrize("name", [
    "tools/t.py", "yask_tpu/serve/x.py", "chip_smoke.py",
    "examples/e.py"])
def test_ledger_write_fires_outside_benchmark_and_tests(tmp_path, name):
    fs = lint_tool(tmp_path, LEDGER_SRC, name=name)
    assert fired(fs) == ["LEDGER-WRITE"]
    assert fs[0]["line"] == 4


@pytest.mark.parametrize("name", [
    "benchmark/run.py", "tests/test_x.py", "tests/benchmark/test_y.py",
    os.path.join("tools", "repo_lint.py")])
def test_ledger_write_scope_leaves_the_benchmark_and_tests(tmp_path,
                                                           name):
    assert fired(lint_tool(tmp_path, LEDGER_SRC, name=name)) == []


def test_ledger_write_has_no_pragma(tmp_path):
    fs = lint_tool(tmp_path, """\
        LEDGER = "PERF_LEDGER.jsonl"  # lint: ledger-write-ok
    """)
    assert fired(fs) == ["LEDGER-WRITE"]


def test_repo_is_clean():
    findings = repo_lint.run_lint([ROOT], root=ROOT)
    assert findings == [], findings
