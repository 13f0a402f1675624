"""Parameter-sweep driver against the serving front.

Spawns ``tools/serve.py`` as a stdio child, opens N iso3dfd sessions
on ONE profile (one compiled executable serves all of them), gives
each tenant its own velocity constant + random initial pressure, and
submits the whole sweep through ``run_many`` so compatible requests
co-batch into one vmapped execution.

Self-check: every response must be BIT-identical to a solo
``run_solution`` with the same fills (float32 survives the JSON wire
exactly), and the serve journal must show batch occupancy > 1 —
otherwise the batching window never did its job.

Run: ``python examples/serve_sweep_main.py [-g N] [-steps N] [-n N]``
(a CPU run names its platform, ``JAX_PLATFORMS=cpu``; the child
inherits the environment.)
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from tools.serve_client import ServeClient


def solo_oracle(g: int, steps: int, vel: float, pressure):
    """The answer a lone ``run_solution`` gives for the same fills."""
    from yask_tpu import yk_factory
    from yask_tpu.serve.scheduler import extract_outputs
    fac = yk_factory()
    ctx = fac.new_solution(fac.new_env(), stencil="iso3dfd", radius=2)
    ctx.apply_command_line_options(f"-g {g} -wf_steps 2")
    ctx.prepare_solution()
    ctx.get_var("vel").set_all_elements_same(vel)
    ctx.get_var("pressure").set_elements_in_slice(
        pressure, [0, 0, 0, 0], [0, g - 1, g - 1, g - 1])
    ctx.run_solution(0, steps - 1)
    return extract_outputs(ctx)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    g, steps, n = 16, 4, 6
    i = 0
    while i < len(argv):
        if argv[i] == "-g":
            g = int(argv[i + 1]); i += 2
        elif argv[i] == "-steps":
            steps = int(argv[i + 1]); i += 2
        elif argv[i] == "-n":
            n = int(argv[i + 1]); i += 2
        else:
            print(f"unknown arg {argv[i]}"); return 2

    vels = [0.3 + 0.1 * k for k in range(n)]        # the sweep axis
    seeds = [np.random.RandomState(100 + k)
             .rand(1, g, g, g).astype(np.float32) for k in range(n)]

    with ServeClient.spawn(stderr=sys.stderr) as c:
        sids = []
        for k in range(n):
            sid = c.open(stencil="iso3dfd", radius=2, g=g,
                         mode="jit", wf=2)
            c.fill(sid, "vel", vels[k])
            c.fill_slice(sid, "pressure", seeds[k],
                         [0, 0, 0, 0], [0, g - 1, g - 1, g - 1])
            sids.append(sid)
        resps = c.run_many([(sid, 0, steps - 1) for sid in sids],
                           timeout=600)
        m = c.metrics()

    occupancies = sorted(r["batch"] for r in resps)
    print(f"serve sweep: {n} tenants x {steps} steps on {g}^3; "
          f"occupancies={occupancies}; "
          f"p50 total {m['p50_total_ms']:.1f} ms")

    bad = 0
    for k, r in enumerate(resps):
        assert r["status"] == "ok", f"tenant {k}: {r}"
        want = solo_oracle(g, steps, vels[k], seeds[k])
        for var, arr in want.items():
            if not np.array_equal(arr, r["outputs"][var]):
                bad += 1
                print(f"tenant {k} var {var}: NOT bit-identical "
                      f"to the solo oracle")
    assert bad == 0, f"{bad} mismatched outputs"
    assert max(occupancies) > 1, \
        "no request ever co-batched — the window never grouped anything"
    print("serve sweep example: PASS "
          f"(all {n} tenants bit-identical to solo run_solution)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
