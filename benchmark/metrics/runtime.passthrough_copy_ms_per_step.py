"""Runtime: device time of the ``copy`` operations XLA puts beside the
kernel in a chunk's module, over the steps traced.  A launch returns
every array of the state and donates none, so each array the kernel
does not write (an older ring slot, a read-only array) is copied at
every launch.  The chunks' modules are named like their kernels
(``kernel`` of the rows of ``StencilContext.compiled_plans()``), and
``program_spans`` lists what ran in such a module beside the call as
``<kernel> <opcode>``.  0 where a traced chunk copies nothing;
``None`` where the program offers no such accessor (an older commit)
or opened no span."""

import program_plans
import program_spans


def read(run):
    kernels = {row["kernel"] for row in program_plans.plans(run)}
    spans = program_spans.load(run)
    steps = spans.get("steps")
    if not kernels or not steps or "by_label_ms" not in spans:
        return None
    return sum(ms for label, ms in spans["by_label_ms"].items()
               if label.endswith(" copy")
               and label.partition(" ")[0] in kernels) / steps
