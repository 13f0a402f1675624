"""Cross-backend reproducibility check (the repo's bitwise north star).

Runs the same solution on two JAX backends (e.g. CPU and TPU) from
identical initial state and reports whether results match bitwise, and if
not, the first divergent write (via the trace machinery).

Bitwise agreement requires XLA to avoid reassociation differences across
backends; stencil arithmetic here is pure add/mul chains built in a fixed
order, so divergence localizes real compiler/backend differences rather
than framework bugs — the role ``analyze_trace`` + ``compare_data`` play
for the reference.

Usage::

    python -m yask_tpu.tools.bitwise_check -stencil 3axis -g 32 -steps 4 \
        [-backends cpu,tpu]
"""

from __future__ import annotations

import sys

import numpy as np


def run_on(platform: str, stencil: str, radius, g: int, steps: int):
    import jax
    devs = list(jax.devices(platform))
    from yask_tpu import yk_factory
    fac = yk_factory()
    env = fac.new_env(devices=devs[:1])
    ctx = fac.new_solution(env, stencil=stencil, radius=radius)
    ctx.apply_command_line_options(f"-g {g}")
    ctx.prepare_solution()
    from yask_tpu.runtime.init_utils import init_solution_vars
    init_solution_vars(ctx)
    ctx.run_solution(0, steps - 1)
    return {name: np.asarray(ring[-1])
            for name, ring in ctx._state.items()}


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    stencil, g, steps, radius = "3axis", 32, 4, None
    backends = ["cpu", "tpu"]
    i = 0
    while i < len(argv):
        a = argv[i]
        if a == "-stencil":
            stencil = argv[i + 1]; i += 2
        elif a == "-g":
            g = int(argv[i + 1]); i += 2
        elif a == "-steps":
            steps = int(argv[i + 1]); i += 2
        elif a == "-radius":
            radius = int(argv[i + 1]); i += 2
        elif a == "-backends":
            backends = argv[i + 1].split(","); i += 2
        else:
            sys.stderr.write(f"unknown arg {a}\n"); return 2

    results = []
    for b in backends:
        try:
            results.append((b, run_on(b, stencil, radius, g, steps)))
        except RuntimeError as e:
            sys.stderr.write(f"backend '{b}' unavailable: {e}\n")
            return 3
    (na, ra), (nb, rb) = results[0], results[1]
    exact = True
    for name in sorted(ra):
        x, y = ra[name], rb[name]
        if x.shape != y.shape:
            print(f"{name}: SHAPE MISMATCH {x.shape} vs {y.shape}")
            exact = False
            continue
        same = np.array_equal(
            x.view(np.uint8) if x.dtype != np.float64 else x,
            y.view(np.uint8) if y.dtype != np.float64 else y)
        if same:
            print(f"{name}: bitwise identical on {na} vs {nb}")
        else:
            d = np.abs(x.astype(np.float64) - y.astype(np.float64))
            idx = np.unravel_index(d.argmax(), d.shape)
            nbit = int((x != y).sum())
            print(f"{name}: {nbit} differing element(s); max |diff| "
                  f"{d.max():.3e} at {tuple(int(v) for v in idx)}")
            exact = False
    print("RESULT:", "BITWISE MATCH" if exact else "DIFFERS")
    return 0 if exact else 1


if __name__ == "__main__":
    sys.exit(main())
