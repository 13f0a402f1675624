"""RTM-mini: a 3-stage producer→consumer pipeline fused into one program.

The classic reverse-time-migration shape, miniaturized: a forward
acoustic wave (``rtm_fwd``, order-2r Laplacian), an imaging
correlation that accumulates the squared wavefield (``rtm_img``), and
a 27-point box smoothing of the image (``rtm_smooth``).  Run as three
separate solutions, the wavefield and the raw image each round-trip
HBM — and host copies — between stages every step.  Declared as a
``SolutionPipeline`` with two bindings::

    img.fwd_in    <- fwd.pressure     (the fresh wavefield)
    smooth.img_in <- img.img          (the fresh image)

the three stages merge into ONE program per mode: 2× less modeled
HBM traffic (48 → 24 bytes/point fp32) and zero host pushes.

Self-check: the fused arm must be BIT-identical to the host-chained
oracle (per step, per stage, bindings pushed through host interior
copies) on the same temporal schedule, and the plan's structured
``reasons`` must record the engage decision.

Run: ``python examples/rtm_pipeline_main.py [-g N] [-steps N]
[-mode jit|pallas] [-radius N]`` (a CPU run names its platform:
``JAX_PLATFORMS=cpu``.)
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def make_pipe(env, g, mode, radius, fuse):
    from yask_tpu.ops.pipeline import SolutionPipeline, rtm_chain
    stages, bindings = rtm_chain(radius=radius)
    pipe = SolutionPipeline(env, stages, bindings)
    pipe.apply_command_line_options(f"-g {g} -mode {mode} -wf_steps 1")
    pipe.prepare(fuse=fuse)
    # a localized source burst in the wavefield, every ring slot
    v = pipe.get_var("fwd", "pressure")
    rng = np.random.RandomState(42)
    src = (rng.rand(g, g, g).astype(np.float32) - 0.5) * 0.1
    for t in range(v.get_first_valid_step_index(),
                   v.get_last_valid_step_index() + 1):
        v.set_elements_in_slice(src, [t, 0, 0, 0],
                                [t, g - 1, g - 1, g - 1])
    return pipe


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    g, steps, mode, radius = 24, 6, "jit", 2
    i = 0
    while i < len(argv):
        if argv[i] == "-g":
            g = int(argv[i + 1]); i += 2
        elif argv[i] == "-steps":
            steps = int(argv[i + 1]); i += 2
        elif argv[i] == "-mode":
            mode = argv[i + 1]; i += 2
        elif argv[i] == "-radius":
            radius = int(argv[i + 1]); i += 2
        else:
            print(f"unknown arg {argv[i]}"); return 2

    from yask_tpu import yk_factory
    env = yk_factory().new_env()

    fused = make_pipe(env, g, mode, radius, fuse=True)
    chained = make_pipe(env, g, mode, radius, fuse=False)
    engage = [r for r in fused.plan()["reasons"]
              if r["code"] == "pipeline-engaged"]
    print(f"plan: fused={fused.fused} "
          f"({engage[0]['msg'] if engage else 'no engage reason'})")

    # first window warms both arms (compile + cache); second is timed
    fused.run(0, steps - 1)
    chained.run(0, steps - 1)
    t0 = time.perf_counter()
    fused.run(steps, 2 * steps - 1)
    t_fused = time.perf_counter() - t0
    t0 = time.perf_counter()
    chained.run(steps, 2 * steps - 1)
    t_chain = time.perf_counter() - t0

    bad = fused.compare(chained)   # epsilon=0: exact bit-equality
    from yask_tpu.ops.pipeline import pipeline_hbm_model
    m = pipeline_hbm_model(fused)
    print(f"rtm3 r={radius} {g}^3 {mode}: fused {t_fused:.3f}s, "
          f"host-chained {t_chain:.3f}s "
          f"({t_chain / max(t_fused, 1e-12):.2f}x), "
          f"hbm model {m['chained_bytes_pp']}->{m['fused_bytes_pp']} "
          f"bytes/pt ({m['ratio']:.1f}x)")
    if bad:
        print(f"FAIL: fused arm differs from the host-chained oracle "
              f"({bad} mismatching elements)")
        return 1
    img = fused._interior("smooth", "smooth",
                          fused.get_var("smooth", "smooth")
                          .get_last_valid_step_index())
    print(f"self-check OK: bit-identical arms; final image "
          f"max={float(np.abs(img).max()):.3e}")
    fused.end()
    chained.end()
    return 0


if __name__ == "__main__":
    sys.exit(main())
