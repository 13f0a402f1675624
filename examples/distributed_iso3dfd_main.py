"""Distributed iso3dfd mini-app: the multi-chip scaling recipe.

Counterpart of the reference's MPI-launched kernel runs (``yask.sh
-ranks N``, ``src/kernel/yask_main.cpp`` under ``mpirun``): decomposes an
acoustic wavefield over every available device with the ``shard_pallas``
path — ghost pads sized radius×K, one ppermute exchange per K fused
steps — seeds a point source, advances, and self-checks propagation,
stability, and cross-mode agreement with ``shard_map``.

Run on hardware:  ``python examples/distributed_iso3dfd_main.py -g 256``
Run anywhere:     ``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
                    python examples/distributed_iso3dfd_main.py``
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from yask_tpu import yk_factory


def build(fac, env, mode, g, radius, wf, nx, ny):
    ctx = fac.new_solution(env, stencil="iso3dfd", radius=radius)
    ctx.apply_command_line_options(f"-g {g} -wf_steps {wf}")
    ctx.get_settings().mode = mode
    ctx.set_num_ranks("x", nx)
    ctx.set_num_ranks("y", ny)
    ctx.prepare_solution()
    ctx.get_var("pressure").set_element(1.0, [0, g // 2, g // 2, g // 2])
    ctx.get_var("vel").set_all_elements_same(0.08)
    return ctx


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    g, steps, radius, wf = 64, 16, 2, 2
    i = 0
    while i < len(argv):
        if argv[i] == "-g":
            g = int(argv[i + 1]); i += 2
        elif argv[i] == "-steps":
            steps = int(argv[i + 1]); i += 2
        elif argv[i] == "-radius":
            radius = int(argv[i + 1]); i += 2
        elif argv[i] == "-wf_steps":
            wf = int(argv[i + 1]); i += 2
        else:
            print(f"usage: {sys.argv[0]} [-g N] [-steps N] [-radius R] "
                  f"[-wf_steps K]")
            return 2

    fac = yk_factory()
    env = fac.new_env()
    ndev = env.get_num_ranks()
    # the library's TPU-first compact factorization (minor dim whole)
    from yask_tpu.parallel.decomp import factorize_rank_grid
    grid = factorize_rank_grid(ndev, ["x", "y", "z"])
    nx, ny = grid["x"], grid["y"]
    print(f"iso3dfd on {env.get_platform()} x {ndev} device(s): "
          f"mesh {nx}x{ny}, g={g}^3, radius {radius}, K={wf}")

    ctx = build(fac, env, "shard_pallas", g, radius, wf, nx, ny)
    ctx.run_solution(0, steps - 1)
    st = ctx.get_stats()
    print(f"throughput: {st.get_pts_per_sec() / 1e9:.4g} GPts/s")

    field = ctx.get_var("pressure").get_elements_in_slice(
        [steps, 0, 0, 0], [steps, g - 1, g - 1, g - 1])
    assert np.isfinite(field).all(), "field diverged"
    spread = np.count_nonzero(np.abs(field) > 1e-12)
    assert spread > 100, f"wave did not propagate (spread {spread})"

    # cross-mode check: the explicit-exchange path must agree
    twin = build(fac, env, "shard_map", g, radius, 0, nx, ny)
    twin.run_solution(0, steps - 1)
    bad = ctx.compare_data(twin, epsilon=1e-3, abs_epsilon=1e-4)
    assert bad == 0, f"{bad} mismatches vs shard_map"
    print(f"self-check passed: finite, spread {spread} points, "
          "shard_pallas == shard_map")
    return 0


if __name__ == "__main__":
    sys.exit(main())
