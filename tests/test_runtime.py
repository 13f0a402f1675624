"""Kernel runtime tests: var API, lifecycle, stats, validation — the analog
of the reference's kernel API tests (``src/kernel/tests/yask_kernel_api_test
.py:84-327``: slice get/set via numpy, fixed-size vars, reductions, steps)."""

import numpy as np
import pytest

from yask_tpu import yk_factory, YaskException
from yask_tpu.compiler.solution import yc_factory


@pytest.fixture(scope="module")
def env():
    return yk_factory().new_env()


def make_heat(env, g=16, mode=None, **opts):
    ctx = yk_factory().new_solution(env, stencil="3axis", radius=1)
    ctx.apply_command_line_options(f"-g {g}")
    if mode:
        ctx.get_settings().mode = mode
    for k, v in opts.items():
        setattr(ctx.get_settings(), k, v)
    ctx.prepare_solution()
    return ctx


def test_lifecycle_and_var_geometry(env):
    ctx = make_heat(env)
    assert ctx.is_prepared()
    assert ctx.get_step_dim_name() == "t"
    assert ctx.get_domain_dim_names() == ["x", "y", "z"]
    v = ctx.get_var("A")
    assert v.get_dim_names() == ["t", "x", "y", "z"]
    assert v.get_halo_size("x") == 1
    assert v.get_left_pad_size("x") >= 1
    assert v.get_alloc_size("x") >= 16 + 2
    assert v.get_alloc_size("t") == 2
    assert v.is_storage_allocated()


def test_element_and_slice_access(env):
    ctx = make_heat(env)
    v = ctx.get_var("A")
    v.set_element(3.5, [0, 5, 6, 7])
    assert v.get_element([0, 5, 6, 7]) == pytest.approx(3.5)
    v.add_to_element(1.0, [0, 5, 6, 7])
    assert v.get_element([0, 5, 6, 7]) == pytest.approx(4.5)

    data = np.arange(4 * 4 * 4, dtype=np.float32).reshape(4, 4, 4)
    n = v.set_elements_in_slice(data, [0, 2, 2, 2], [0, 5, 5, 5])
    assert n == 64
    back = v.get_elements_in_slice([0, 2, 2, 2], [0, 5, 5, 5])
    np.testing.assert_allclose(back, data)

    assert v.reduce_elements_in_slice(
        "sum", [0, 2, 2, 2], [0, 5, 5, 5]) == pytest.approx(float(data.sum()))
    assert v.reduce_elements_in_slice(
        "max", [0, 2, 2, 2], [0, 5, 5, 5]) == pytest.approx(63.0)
    with pytest.raises(YaskException):
        v.reduce_elements_in_slice("bogus", [0, 2, 2, 2], [0, 5, 5, 5])


def test_run_and_oracle_match(env):
    ctx = make_heat(env)
    ctx.get_var("A").set_elements_in_seq(0.1)
    ctx.run_solution(0, 4)
    ref = make_heat(env, mode="ref")
    ref.get_var("A").set_elements_in_seq(0.1)
    ref.run_solution(0, 4)
    assert ctx.compare_data(ref) == 0
    st = ctx.get_stats()
    assert st.get_num_steps_done() == 5
    assert st.get_num_elements() == 16 ** 3
    assert st.get_elapsed_secs() > 0
    assert st.get_pts_per_sec() > 0
    assert "throughput" in st.format()


def test_step_indexing_after_run(env):
    ctx = make_heat(env)
    ctx.get_var("A").set_all_elements_same(1.0)
    ctx.run_solution(0, 2)
    v = ctx.get_var("A")
    # after 3 steps, steps 2 (older) and 3 (newest) are retained
    v.get_element([3, 0, 0, 0])
    v.get_element([2, 0, 0, 0])
    with pytest.raises(YaskException):
        v.get_element([0, 0, 0, 0])   # evicted step


def test_reverse_time_step_index_ordering(env):
    """ADVICE r3: for step_dir=-1 the oldest slot has the LARGER step
    index; first/last must stay numerically ordered so
    are_indices_local range checks hold."""
    ctx = yk_factory().new_solution(env, stencil="test_reverse_2d")
    ctx.apply_command_line_options("-g 8")
    ctx.prepare_solution()
    ctx.get_vars()[0].set_elements_in_seq(0.1)  # non-zero: sums differ
    ctx.run_solution(0, 2)   # reverse: cur_step walks downward
    v = ctx.get_vars()[0]
    first = v.get_first_valid_step_index()
    last = v.get_last_valid_step_index()
    assert first <= last
    assert v.are_indices_local([first, 0, 0])
    assert v.are_indices_local([last, 0, 0])
    assert not v.are_indices_local([last + 1, 0, 0])
    # reductions must cover the NEWEST step (cur_step, numerically the
    # SMALLER index under reverse time), not the numeric max
    import numpy as np
    cur = first  # 3 reverse steps from 0 → newest = -3 = min
    newest = v.get_elements_in_slice([cur, 0, 0], [cur, 7, 7]) \
        .astype(np.float64)
    assert v.get_sum() == pytest.approx(newest.sum(), rel=1e-5)


def test_end_solution_reports_clear_error(env):
    """ADVICE r3: after end_solution, accessors must say so (not the
    misleading 'state was lost' / AttributeError)."""
    ctx = make_heat(env, g=8)
    ctx.get_var("A").set_all_elements_same(1.0)
    ctx.run_solution(0, 1)
    v = ctx.get_var("A")
    ctx.end_solution()
    with pytest.raises(YaskException, match="end_solution was called"):
        ctx.run_solution(2, 3)
    with pytest.raises(YaskException, match="end_solution was called"):
        v.get_element([2, 0, 0, 0])
    # re-prepare brings the solution back to life
    ctx.prepare_solution()
    ctx.get_var("A").set_all_elements_same(1.0)
    ctx.run_solution(0, 1)


def test_wf_chunking_equivalence(env):
    a = make_heat(env)
    a.get_var("A").set_elements_in_seq(0.1)
    a.run_solution(0, 5)
    b = make_heat(env, wf_steps=2)
    b.get_var("A").set_elements_in_seq(0.1)
    b.run_solution(0, 5)
    assert a.compare_data(b) == 0


def test_boundary_ghosts_are_zero(env):
    ctx = make_heat(env, g=8)
    v = ctx.get_var("A")
    v.set_all_elements_same(2.0)
    # pads are excluded from fills: reading just outside the domain gives 0
    assert v.get_element([0, -1, 0, 0]) == 0.0
    assert v.get_element([0, 8, 3, 3]) == 0.0


def test_hooks(env):
    calls = []
    ctx = yk_factory().new_solution(env, stencil="3axis", radius=1)
    ctx.apply_command_line_options("-g 8")
    ctx.call_before_prepare_solution(lambda c: calls.append("bp"))
    ctx.call_after_prepare_solution(lambda c: calls.append("ap"))
    ctx.call_before_run_solution(lambda c: calls.append("br"))
    ctx.call_after_run_solution(lambda c: calls.append("ar"))
    ctx.prepare_solution()
    ctx.run_solution(0, 0)
    assert calls == ["bp", "ap", "br", "ar"]


def test_cli_help_and_env(env):
    ctx = yk_factory().new_solution(env, stencil="3axis", radius=1)
    h = ctx.get_command_line_help()
    assert "-g <val>" in h and "-mode <val>" in h
    assert env.get_num_ranks() >= 1
    env.global_barrier()
    assert env.sum_over_ranks(3) == 3
    assert yk_factory().get_version_string()


def test_custom_solution_object(env):
    soln = yc_factory().new_solution("custom")
    t = soln.new_step_index("t")
    x = soln.new_domain_index("x")
    u = soln.new_var("u", [t, x])
    u(t + 1, x).EQUALS(0.5 * (u(t, x - 1) + u(t, x + 1)))
    ctx = yk_factory().new_solution(env, soln)
    ctx.apply_command_line_options("-g 32")
    ctx.prepare_solution()
    arr = np.sin(np.arange(32, dtype=np.float32))
    ctx.get_var("u").set_elements_in_slice(arr, [0, 0], [0, 31])
    ctx.run_solution(0, 0)
    got = ctx.get_var("u").get_elements_in_slice([1, 0], [1, 31])
    pad = np.pad(arr, 1)
    want = 0.5 * (pad[:-2] + pad[2:])
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_auto_tune_preserves_run_semantics(env):
    """Online tuning must not replay step indices or skew stats: a tuned
    run of a t-dependent stencil (IF_STEP) must equal the untuned oracle,
    with step bookkeeping identical (ADVICE r1: tuner step replay)."""
    def build(**opts):
        ctx = yk_factory().new_solution(env, stencil="test_step_cond_1d")
        ctx.apply_command_line_options("-g 24")
        for k, v in opts.items():
            setattr(ctx.get_settings(), k, v)
        ctx.prepare_solution()
        ctx.get_var("A").set_elements_in_seq(0.1)
        return ctx

    tuned = build(do_auto_tune=True, auto_tune_trial_secs=0.02)
    tuned.run_solution(0, 5)
    oracle = build(force_scalar=True)
    oracle.run_solution(0, 5)

    assert tuned.compare_data(oracle) == 0
    assert tuned._cur_step == oracle._cur_step == 6
    assert tuned.get_stats().get_num_steps_done() == 6


def test_checkpoint_extensionless_path(env, tmp_path):
    """save/load round trip with a path missing '.npz' (ADVICE r1)."""
    ctx = make_heat(env, g=12)
    ctx.get_var("A").set_elements_in_seq(0.2)
    ctx.run_solution(0, 1)
    ck = str(tmp_path / "snap")  # no extension
    ctx.save_checkpoint(ck)
    other = make_heat(env, g=12)
    other.load_checkpoint(ck)
    assert other._cur_step == ctx._cur_step
    assert other.compare_data(ctx) == 0


def test_shard_map_cache_keyed_on_overlap(env):
    """Toggling -overlap_comms between equal-length runs must not reuse
    the other strategy's compiled body (ADVICE r1: stale jit cache)."""
    ctx = yk_factory().new_solution(env, stencil="3axis", radius=1)
    ctx.apply_command_line_options("-g 16")
    ctx.get_settings().mode = "shard_map"
    ctx.set_num_ranks("x", 2)
    ctx.prepare_solution()
    ctx.get_var("A").set_elements_in_seq(0.1)
    ctx.get_settings().overlap_comms = False
    ctx.run_solution(0, 1)
    ctx.get_settings().overlap_comms = True
    ctx.run_solution(2, 3)
    keys = [k for k in ctx._jit_cache if k[0] == "shard_map"]
    assert len(keys) == 2 and len({k[2] for k in keys}) == 2


@pytest.fixture(scope="module")
def shard_map_x4(env):
    """3axis r1 at 64^3, ``shard_map`` over four ranks, overlap off,
    eight steps: the context and its stats."""
    ctx = yk_factory().new_solution(env, stencil="3axis", radius=1)
    ctx.apply_command_line_options("-g 64 -no-overlap_comms")
    ctx.get_settings().mode = "shard_map"
    ctx.set_num_ranks("x", 4)
    ctx.prepare_solution()
    ctx.get_var("A").set_elements_in_seq(0.1)
    ctx.run_solution(0, 7)
    return ctx, ctx.get_stats()


def test_shard_map_on_four_ranks_equals_the_oracle(env, shard_map_x4):
    ctx, st = shard_map_x4
    # modeled HBM traffic: 3axis has 1 var x 2 slots read + 1 written
    # (write-back) -> 12 B/pt at f32; the model reports pad-inclusive
    # array bytes so it must be at least that
    assert st.get_hbm_bytes_per_point() >= 12.0
    assert "hbm-bytes-per-point" in st.format()
    assert 0.0 < st.get_elapsed_secs()

    oracle = yk_factory().new_solution(env, stencil="3axis", radius=1)
    oracle.apply_command_line_options("-g 64")
    oracle.get_settings().force_scalar = True
    oracle.prepare_solution()
    oracle.get_var("A").set_elements_in_seq(0.1)
    oracle.run_solution(0, 7)
    assert ctx.compare_data(oracle) == 0


def test_a_shard_runs_stats_print_no_halo_line_and_scrape(shard_map_x4):
    """What an exchange costs is the device trace's and the launch
    span's to say: the stats print no ``halo-`` line, every line they
    print is one ``log_to_csv`` knows, and the launch's attrs hold the
    counts."""
    from yask_tpu.tools.log_to_csv import KEYS, scrape
    ctx, st = shard_map_x4
    text = st.format()
    assert "halo" not in text
    assert not [m for m in dir(st) if m.startswith("get_halo")]
    assert not [k for k in KEYS if "halo" in k]
    scraped = scrape(text)
    # all of it but the one line that is no column ("throughput (GPts/s)")
    assert len(scraped) == len(text.splitlines()) - 1
    assert float(scraped["elapsed-time (sec)"]) > 0.0
    assert int(scraped["num-steps-done"]) == 8
    (attrs,) = ctx._launch_attrs.values()
    assert attrs["xrounds"] == 9 and attrs["xslabs"] > 0 < attrs["xbytes"]


def test_shard_state_stays_device_resident(env):
    """Repeated shard-mode runs hand interiors over directly — no
    per-call strip/re-pad (VERDICT r1 item 9); host var access
    materializes lazily and stays correct."""
    def build(mode):
        ctx = yk_factory().new_solution(env, stencil="3axis", radius=1)
        ctx.apply_command_line_options("-g 32")
        ctx.get_settings().mode = mode
        ctx.set_num_ranks("x", 4)
        ctx.prepare_solution()
        ctx.get_var("A").set_elements_in_seq(0.1)
        return ctx

    for mode in ("shard_map", "shard_pallas"):
        ctx = build(mode)
        ctx.run_solution(0, 1)
        # interiors parked on device, padded state not rebuilt
        assert ctx._resident is not None and ctx._state is None
        ctx.run_solution(2, 3)   # second run consumes the resident set
        assert ctx._resident is not None

        oracle = yk_factory().new_solution(env, stencil="3axis", radius=1)
        oracle.apply_command_line_options("-g 32")
        oracle.get_settings().force_scalar = True
        oracle.prepare_solution()
        oracle.get_var("A").set_elements_in_seq(0.1)
        oracle.run_solution(0, 3)
        # compare_data materializes the resident interiors lazily
        assert ctx.compare_data(
            oracle, epsilon=1e-3, abs_epsilon=1e-4) == 0
        assert ctx._resident is None and ctx._state is not None
        # and a var write after materialization still round-trips
        ctx.get_var("A").set_element(2.5, [4, 7, 7, 7])
        assert ctx.get_var("A").get_element([4, 7, 7, 7]) == 2.5


def test_vars_in_constructor_pattern_runs_define(env):
    """The reference's canonical pattern — vars created in the
    constructor, equations in define() (Iso3dfdStencil's MAKE_VAR
    members) — must not be treated as already-defined (ADVICE r2:
    a silent zero-equation no-op)."""
    from yask_tpu.compiler.solution_base import yc_solution_base

    class VarsInCtor(yc_solution_base):
        def __init__(self):
            super().__init__("vars_in_ctor_test")
            self._t = self.new_step_index("t")
            self._x = self.new_domain_index("x")
            self.A = self.new_var("A", [self._t, self._x])

        def define(self):
            t, x = self._t, self._x
            self.A(t + 1, x).EQUALS(self.A(t, x) + 1.0)

    s = VarsInCtor()
    s.run_define()
    assert s.get_soln().get_num_equations() == 1
    s.run_define()   # idempotent
    assert s.get_soln().get_num_equations() == 1


def test_direct_define_call_not_rerun():
    """A user may call define() directly before handing the object to
    the runtime; run_define must then not re-run it (vars-only
    solutions would raise duplicate-var on the second pass)."""
    from yask_tpu.stencils.test_stencils import TestEmpty2d
    s = TestEmpty2d()
    s.define()          # creates var A, zero equations
    s.run_define()      # must be a no-op, not a duplicate-var error
    assert len(s.get_soln().get_vars()) == 1


def test_checkpoint_orbax_backend(env, tmp_path):
    """Orbax round trip: resume mid-run and finish identical to an
    uninterrupted run (async-capable storage backend for distributed
    states; the npz path stays the default)."""
    import pytest as _pt0
    _pt0.importorskip("orbax.checkpoint")
    ctx = make_heat(env, g=12)
    ctx.get_var("A").set_elements_in_seq(0.2)
    ctx.run_solution(0, 2)
    ck = str(tmp_path / "orbax_snap")
    ctx.save_checkpoint(ck, backend="orbax")
    ctx.run_solution(3, 5)

    other = make_heat(env, g=12)
    other.load_checkpoint(ck, backend="orbax")
    assert other._cur_step == 3
    other.run_solution(3, 5)
    assert other.compare_data(ctx) == 0

    import pytest as _pt
    from yask_tpu import YaskException
    with _pt.raises(YaskException, match="backend"):
        ctx.save_checkpoint(ck, backend="hdf5")
