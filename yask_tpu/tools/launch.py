"""Run launcher: host/device introspection + harness invocation.

Counterpart of the reference's ``yask.sh`` (``yask.sh:41-98,227``): where the
shell script detects arch/cores/NUMA/GPUs and synthesizes an
``mpirun … numactl … yask_kernel.exe`` command, this launcher detects the
JAX platform and device count, derives a default mesh (ranks = devices, the
way yask.sh defaults ranks to NUMA nodes), sets the environment XLA needs,
and runs the harness — printing the equivalent command line for the log.

Usage::

    python -m yask_tpu.tools.launch -stencil iso3dfd -g 512
"""

from __future__ import annotations

import os
import sys
from typing import List


def detect() -> dict:
    import jax
    devs = jax.devices()
    return {
        "platform": devs[0].platform if devs else "none",
        "num_devices": len(devs),
        "device_kind": devs[0].device_kind if devs else "",
    }


def build_args(argv: List[str], info: dict) -> List[str]:
    args = list(argv)
    # Default decomposition: one rank per device over the outer-most dim
    # (yask.sh defaults ranks to NUMA nodes / GPUs the same way).
    if info["num_devices"] > 1 and "-mode" not in args \
            and not any(a.startswith("-nr") for a in args):
        args += ["-mode", "sharded", "-nr_x", str(info["num_devices"])]
    return args


def main(argv: List[str] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    info = detect()
    sys.stdout.write(
        f"yask_tpu launcher: platform={info['platform']} "
        f"devices={info['num_devices']} kind='{info['device_kind']}'\n")
    args = build_args(argv, info)
    sys.stdout.write("equivalent command: python -m yask_tpu.main "
                     + " ".join(args) + "\n")
    from yask_tpu.main import run_harness
    return run_harness(args)


if __name__ == "__main__":
    sys.exit(main())
