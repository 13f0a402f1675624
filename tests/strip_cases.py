"""The strip evaluator held to the whole-tile evaluator, bit for bit.

Both are built from one program with the same arguments
(``build_pallas_chunk(_tile_eval=)``), run in interpret mode on one
seeded state, and every array a launch writes is compared exactly.
The comparison runs in a child process whose XLA may not contract a
multiply and an add into one fused operation (``--xla_cpu_max_isa=AVX``:
no FMA): the two evaluators hand XLA's CPU compiler the same operations
a point in the same order, but in loops of different shapes, and where
it fuses them differently it contracts differently -- tti and awp then
differ in the last bit or two, with contraction off in none.  The test
files run ``python tests/strip_cases.py <case> ...`` once a file and
read one JSON line a case.
"""

import json
import os
import sys

#: name -> (stencil, radius, options, fuse depth, build arguments)
CASES = {
    # uniform shrink, K = 1, 2, and 4 with the shorter last group a
    # 10-step call makes (4 + 4 + 2: the K=2 chunk on pads planned
    # for K=4)
    "uniform-k1": ("iso3dfd", 2, "-g 32", 1, {"block": (8, 16)}),
    "uniform-k2": ("iso3dfd", 2, "-g 32", 2,
                   {"block": (8, 16), "skew": False}),
    "cube-k4": ("cube", 1, "-g 32", 4, {"block": (8, 16)}),
    "cube-k4-last-group-k2": ("cube", 1, "-g 32", 4,
                              {"block": (8, 16), "fuse": 2}),
    # stages, scratch vars, conditions, partial-dim vars
    "ssg-two-stages": ("ssg", 2, "-g 16", 1, {"block": (8, 8)}),
    "awp-abc-four-stages": ("awp_abc", None, "-g 16", 1,
                            {"block": (8, 8)}),
    "tti-scratch-vars": ("tti", 2, "-g 16", 1, {"block": (8, 8)}),
    "conditioned-equation": ("test_boundary_3d", None, "-g 24", 2,
                             {"block": (8, 8)}),
    "misc-value-2d": ("test_misc_value_2d", None, "-g 32", 1, {}),
    "swe2d-scratch-2d": ("swe2d", None, "-g 32", 2, {}),
    "partial-dim-var": ("partial_written_cond", None, "-g 32", 2, {}),
    "misc-written-var": ("box", None, "-g 24", 1, {}),
    "three-lead-dims": ("test_4d", None, "-g 16", 1, {}),
    # 801 x 801 x 187 cut down: no block divides its extent
    "ragged-block": ("iso3dfd_sponge", 2,
                     "-g_x 50 -g_y 50 -g_z 27 -b_x 26 -b_y 8", 2,
                     {"block": (26, 8)}),
    # a strip shape that does not divide the region: a remainder strip
    # in the lead rows and in the sublane rows
    "remainder-lead-rows": ("iso3dfd", 2, "-g 32", 2,
                            {"block": (8, 16), "skew": False,
                             "_strip": (3, 16)}),
    "remainder-sublane-rows": ("iso3dfd", 2, "-g 32", 2,
                               {"block": (8, 16), "skew": False,
                                "_strip": (2, 24)}),
    "output-staging": ("iso3dfd", 2, "-g 32", 2,
                       {"block": (8, 8), "pipeline_dmas": True}),
    # one shard's arms
    "shard-core": ("iso3dfd", 2, "-g 32", 2,
                   {"block": (8, 8), "distributed": True,
                    "region": {"x": (4, 28)}, "arm": "core"}),
    "shard-shell": ("iso3dfd", 2, "-g 32", 2,
                    {"block": (8, 8), "distributed": True,
                     "region": {"x": (0, 4)}, "arm": "shell"}),
    # the skewed wavefront (tests/test_skew.py)
    "yskew-k2-r8": ("iso3dfd", 8, "-g 48", 2, {}),
    "yskew-k4-r2": ("iso3dfd", 2, "-g 32", 4,
                    {"block": (8, 16), "skew": True}),
    "yskew-k2-misaligned": ("iso3dfd", 2, "-g 32", 2,
                            {"block": (8, 16), "skew": True}),
    "yskew-multi-stage": ("ssg", 2, "-g 32", 2, {"skew": True}),
    "yskew-shard": ("iso3dfd", 8, "-g 48", 2,
                    {"distributed": True, "stream_unsharded": True}),
}

PALLAS_CASES = [c for c in CASES if "skew" not in c]
SKEW_CASES = [c for c in CASES if "skew" in c]


def partial_written_cond():
    """A var without the lead dim, written under a condition, and read
    by a full-dim equation (``tests/test_pallas.py``'s)."""
    from yask_tpu.compiler.solution import yc_factory
    soln = yc_factory().new_solution("partial_written_cond")
    t = soln.new_step_index("t")
    x = soln.new_domain_index("x")
    y = soln.new_domain_index("y")
    a = soln.new_var("A", [t, x, y])
    p = soln.new_var("P", [t, y])
    p(t + 1, y).EQUALS(p(t, y) * 0.8 + 0.1).IF_DOMAIN(y >= 4)
    a(t + 1, x, y).EQUALS(a(t, x, y) * 0.5 + p(t, y) * 0.3)
    return soln


def run_case(name):
    import numpy as np
    import jax.numpy as jnp
    from yask_tpu import yk_factory
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk
    from yask_tpu.runtime.init_utils import init_solution_vars
    stencil, radius, opts, wf, kw = CASES[name]
    kw = dict(kw)
    fac = yk_factory()
    if stencil == "partial_written_cond":
        ctx = fac.new_solution(fac.new_env(), partial_written_cond())
    else:
        ctx = fac.new_solution(fac.new_env(), stencil=stencil,
                               radius=radius)
    ctx.apply_command_line_options(opts)
    ctx.get_settings().mode = "pallas"
    ctx.get_settings().wf_steps = wf
    ctx.prepare_solution()
    init_solution_vars(ctx)
    ctx._refresh_derived()      # a hoisted scratch var's array (tti)
    prog = ctx._program
    fuse = kw.pop("fuse", wf)
    strip = kw.pop("_strip", None)
    args = ()
    if kw.get("distributed"):
        args = (jnp.zeros((len(prog.ana.domain_dims),), jnp.int32),)
    outs, tilings = {}, {}
    for tile in (True, False):
        chunk, _tb = build_pallas_chunk(
            prog, fuse_steps=fuse, interpret=True, _tile_eval=tile,
            _strip=None if tile else strip, **kw)
        state = {k: list(v) for k, v in ctx._state.items()}
        outs[tile] = chunk(state, 0, *args)
        tilings[tile] = chunk.tiling
    def written(n, a):
        """What a launch answers for: under ``region=`` the cells of
        the region alone (the scheduler patches the rest)."""
        g = prog.geoms[n]
        idx = [slice(None)] * np.ndim(a)
        for d, (lo, hi) in (kw.get("region") or {}).items():
            idx[g.axis_of(d)] = slice(g.origin[d] + lo, g.origin[d] + hi)
        return np.asarray(a)[tuple(idx)]

    differ = [n for n in outs[True]
              for a, b in zip(outs[True][n], outs[False][n])
              if not np.array_equal(written(n, a), written(n, b))]
    til = tilings[False]
    return {"case": name, "differ": sorted(set(differ)),
            "arrays": sum(len(v) for v in outs[True].values()),
            "evals": [tilings[True]["eval"], til["eval"]],
            # (a class priced by what the strip kernel declares counts
            # no result tile for a var written in place; the whole-tile
            # evaluator, which holds it as a value, still does)
            "same_plan": all(tilings[True][k] == til[k] for k in (
                "block", "grid", "vinstr_est",
                "skew_dims", "pipeline_dmas", "pipeline_out"))
            and 0 <= tilings[True]["tile_bytes"] - til["tile_bytes"]
            <= til["result_bytes"],
            **{k: til[k] for k in ("block", "grid", "skew_dims",
                                   "stages", "strip", "strips",
                                   "strip_vregs", "pipeline_out")}}


def run_child(names):
    """Every case of ``names`` in ONE child process whose XLA keeps a
    multiply and an add apart: ``{name: result}``."""
    import subprocess
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root,
               XLA_FLAGS="--xla_cpu_max_isa=AVX")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)] + list(names),
        env=env, capture_output=True, text=True, timeout=900)
    rows = [json.loads(line) for line in proc.stdout.splitlines()
            if line.startswith("{")]
    assert proc.returncode == 0 and len(rows) == len(names), \
        proc.stdout[-2000:] + proc.stderr[-4000:]
    return {r["case"]: r for r in rows}


if __name__ == "__main__":
    for case in sys.argv[1:]:
        print(json.dumps(run_case(case)), flush=True)
