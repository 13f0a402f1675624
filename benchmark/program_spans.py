"""The program's own view of a traced slice: the ``yt.*`` spans it
opens on the profiler's host plane, and the device operations that
carry a name it chose.

``trace_reduce`` keeps host events named ``bench.*`` and finds kernels
by opcode; this module reads what the program says about itself:

* host events ``yt.<name>`` (``yask_tpu/obs/tracer.py``: every
  ``span()`` enters a ``TraceAnnotation``) with their stats (``rid``,
  ``k``, ``n``, ``mode``) and thread, beside ``bench.*``.  A child
  belongs to a request by its ``rid`` (the phases run on the worker's
  thread; a batch's chunk names every member in ``rids``), and to a
  ``yt.run.call`` by thread and time;
* device operations under the program's names, looked for in this
  order.  A Pallas kernel ``yt_<solution>_r<radius>_k<K>[_core|_shell|
  _fill]`` (``ops/pallas_stencil.py kernel_name``) is the HLO
  instruction's own name, so it is in the event's name on the line
  ``XLA Ops``.  A ``jax.named_scope`` (``yt_exchange_pack`` ...) is in
  no event: this libtpu keeps it in the instruction's ``op_name``
  metadata, so it is joined on from the optimised HLO text of the
  executables that ran (``StencilContext.compiled_texts``), by module
  and instruction name.  Every other operation takes the name of the
  compiled module it ran in (line ``XLA Modules``: ``jit_<function>``;
  the program names its jitted functions ``yt_xla_chunk``,
  ``yt_shard_pallas``, and a Pallas chunk like its kernel).

:func:`load` parses the ``.xplane.pb`` under ``run.cell.scratch/trace``
once and memoises on ``run``; :func:`reduce` works on plain lists
alone, so a recorded event list kept as JSON checks every number
without a chip.  Times are nanoseconds on the trace's one clock.  With
a program that opens no ``yt.*`` span (an older commit) every reader
returns ``None`` and raises nothing.
"""

import os
import re
import statistics

import trace_reduce as tr

SPAN_PREFIXES = ("yt.", "bench.")
MODULES_LINE = "XLA Modules"
#: the roots of the program's span trees: idle time inside a root and
#: inside none of its children is time nothing accounts for
ROOTS = ("yt.run.call", "yt.serve.request")
#: the modes whose launches are fused Pallas kernels
PALLAS_MODES = ("pallas", "shard_pallas")
LABEL = re.compile(r"yt_[A-Za-z0-9_]+")
KERNEL = re.compile(r"^yt_.+_r\d+_k\d+(_[a-z]+)?$")
INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*\bop_name="([^"]*)"')
#: gaps shorter than this are left out of the unspanned share: the
#: device's clock runs 1-2 ms off the host's
MIN_GAP_NS = 2_000_000


def kernel_of(op: str) -> str:
    """The program's kernel name where ``<instruction> <opcode>`` is one
    of its Pallas calls (the instruction is named like
    ``pl.pallas_call(name=)``, with XLA's ``.N`` behind), else ''."""
    base = op.partition(" ")[0].partition(".")[0]
    return base if tr.is_kernel(op) and KERNEL.match(base) else ""


def module_of(name: str) -> str:
    """``yt_xla_chunk`` of ``jit_yt_xla_chunk(123)``; '' for a module
    the program did not name."""
    found = LABEL.search(name)
    return found.group(0) if found else ""


def scope_map(texts) -> dict:
    """``{module: {instruction: scope}}`` from optimised HLO texts: the
    innermost ``yt_*`` component of each instruction's ``op_name`` that
    is no kernel's name (a ``pallas_call(name=)`` is a component too).
    An instruction that two executables of one module name put under
    different scopes is left out."""
    out = {}
    for text in texts:
        table = out.setdefault(module_of(text.split(None, 2)[1]), {})
        for line in text.splitlines():
            found = INSTRUCTION.match(line)
            scopes = found and [p for p in found.group(2).split("/")
                                if p.startswith("yt_")
                                and not KERNEL.match(p)]
            if scopes:
                name = found.group(1)
                table[name] = (scopes[-1] if table.get(name, scopes[-1])
                               == scopes[-1] else "")
    return out


def load_xplane(path: str, dry_run: bool = False) -> dict:
    """``{"spans": [[name, start, dur, thread, stats], ...],
    "devices": {plane: [[op, start, dur, kernel], ...]},
    "modules": {plane: [[module, start, dur], ...]}}``.  A thread is
    its line's name and place (two threads can share a name).  In a
    CPU dry run the host's XLA client threads stand in for the device,
    as in ``trace_reduce.load_xplane``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans, devices, modules = [], {}, {}
    for plane in data.planes:
        if plane.name.startswith(tr.DEVICE_PLANE):
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    ops = [[tr.op_name(e.name), e.start_ns, e.duration_ns]
                           for e in line.events]
                    devices[plane.name] = [o + [kernel_of(o[0])]
                                           for o in ops]
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [
                        [module_of(e.name), e.start_ns, e.duration_ns]
                        for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append([e.name, e.start_ns, e.duration_ns,
                                      f"{line.name}#{i}", dict(e.stats)])
                    elif (dry_run and line.name.startswith("tf_XLA")
                          and e.duration_ns > 0
                          and not e.name.startswith(
                              ("end:", "Threadpool"))):
                        devices.setdefault("/host:CPU dry run", []).append(
                            [e.name, e.start_ns, e.duration_ns, ""])
    return {"spans": spans, "devices": devices, "modules": modules}


def compiled_texts(run) -> list:
    """The optimised HLO of the executables the cell's context holds;
    nothing where the program offers none (an older commit, a served
    cell) or the backend keeps no text."""
    texts = getattr(getattr(run.cell.kind, "ctx", None),
                    "compiled_texts", None)
    try:
        return texts() if texts else []
    except (RuntimeError, NotImplementedError):
        return []


def load(run):
    """What :func:`reduce` makes of the run's trace, parsed once;
    ``{}`` where there is no trace or the program opened no span."""
    if getattr(run, "program_spans", None) is None:
        run.program_spans = {}
        base = os.path.join(run.cell.scratch, "trace")
        paths = [os.path.join(d, f) for d, _s, files in os.walk(base)
                 for f in files if f.endswith(".xplane.pb")]
        if paths:
            events = load_xplane(paths[0], dry_run=run.cell.tiny)
            events["scopes"] = scope_map(compiled_texts(run))
            run.program_spans = reduce(events)
    return run.program_spans


def within(spans, name, parent):
    """Events called ``name`` on ``parent``'s thread that start inside
    it."""
    lo, hi = parent[1], parent[1] + parent[2]
    return [s for s in spans if s[0] == name and s[3] == parent[3]
            and lo <= s[1] <= hi]


def of_request(spans, name, rid):
    """Events called ``name`` that carry the request's ``rid`` (alone,
    or among a batch's ``rids``)."""
    return [s for s in spans if s[0] == name
            and (s[4].get("rid") == rid
                 or rid in str(s[4].get("rids", "")).split(","))]


def median(values):
    return statistics.median(values) if values else None


def reduce(events: dict) -> dict:
    """Every number the program-span readers take from one traced
    slice; ``{}`` where the program opened no ``yt.*`` span."""
    spans = events["spans"]
    if not any(s[0].startswith("yt.") for s in spans):
        return {}
    out = {}
    # the traced slice: the benchmark's units where it marked them
    marks = [s for s in spans if s[0].startswith("bench.")] or spans
    lo = min(s[1] for s in marks)
    hi = max(s[1] + s[2] for s in marks)

    def whole(name):
        return [s for s in spans if s[0] == name
                and lo <= s[1] and s[1] + s[2] <= hi]

    # ---- serving: a request's phases, median over the requests
    requests = whole("yt.serve.request")
    for key, name in (("snapshot_s", "yt.serve.snapshot"),
                      ("run_s", "yt.serve.chunk"),
                      ("respond_s", "yt.serve.respond")):
        per = [sum(c[2] for c in of_request(spans, name, r[4].get("rid")))
               for r in requests]
        out["serve_" + key] = (median(per) / 1e9 if requests else None)

    # ---- runtime: launches and the remainder inside each call
    calls = whole("yt.run.call")
    enqueue, share, fused_steps = [], [], 0
    for c in calls:
        launches = within(spans, "yt.run.launch", c)
        rems = within(spans, "yt.run.remainder", c)
        enqueue.append(sum(s[2] for s in launches))
        share.append(100.0 * sum(s[2] for s in rems) / c[2]
                     if c[2] else 0.0)
        if c[4].get("mode") in PALLAS_MODES:
            fused_steps += sum(
                int(s[4].get("k", 0)) for s in launches
                if not any(r[1] <= s[1] <= r[1] + r[2] for r in rems))
    out["enqueue_ms_per_call"] = (median(enqueue) / 1e6 if calls
                                  else None)
    out["remainder_share"] = median(share)
    out["fused_steps"] = fused_steps
    out["steps"] = sum(int(c[4].get("n", 0)) for c in calls)

    # ---- the device: named kernels, and idle time no span explains
    per_dev = []
    for plane, ops in events["devices"].items():
        mods = [m for m in events.get("modules", {}).get(plane, [])
                if m[0]]
        ops = [(n, max(s, lo), min(s + d, hi) - max(s, lo), kernel,
                next((m[0] for m in mods if m[1] <= s <= m[1] + m[2]),
                     ""))
               for n, s, d, kernel in ops if s + d > lo and s < hi]
        busy = tr.clip(tr.union([o[1], o[1] + o[2]] for o in ops),
                       lo, hi)
        per_dev.append({"ops": ops, "busy": busy,
                        "busy_ns": tr.total(busy)})
    if not per_dev or not any(d["busy_ns"] > 0 for d in per_dev):
        return out
    top = max(per_dev, key=lambda d: d["busy_ns"])
    # a kernel goes by its own name, an operation under a named scope
    # by the scope, any other by its module; what XLA put beside the
    # call in a module named like its kernel (a copy of an operand, a
    # pad fusion) is kept apart as "<name> <opcode>"
    scopes = events.get("scopes", {})
    by_label = {}
    for n, _s, d, kernel, mod in top["ops"]:
        if n.endswith(tr.WRAPPER_OPCODES):
            continue        # a loop's event spans its body's, listed too
        lab = (kernel or scopes.get(mod, {}).get(n.partition(" ")[0])
               or (f"{mod} {n.rpartition(' ')[2]}" if KERNEL.match(mod)
                   else mod))
        if lab:
            by_label[lab] = by_label.get(lab, 0.0) + d
    out["by_label_ms"] = {k: v / 1e6 for k, v in by_label.items()}
    kernel_ns = sum(v for k, v in by_label.items() if KERNEL.match(k))
    shell_ns = sum(v for k, v in by_label.items()
                   if KERNEL.match(k) and k.endswith("_shell"))
    if kernel_ns and fused_steps:
        out["fused_ms_per_step"] = kernel_ns / 1e6 / fused_steps
        if shell_ns:
            out["shell_ms_per_step"] = shell_ns / 1e6 / fused_steps
    named = {s for table in scopes.values() for s in table.values()}
    scoped_ns = sum(v for k, v in by_label.items() if k in named)
    if scoped_ns and out["steps"]:
        out["scoped_ms_per_step"] = scoped_ns / 1e6 / out["steps"]

    gaps = [g for g in tr.subtract([[lo, hi]], top["busy"])
            if g[1] - g[0] >= MIN_GAP_NS]
    covered = tr.union([s[1], s[1] + s[2]] for s in spans
                       if s[0].startswith("yt.") and s[0] not in ROOTS)
    idle = tr.total(gaps)
    out["idle_unspanned_share"] = (
        100.0 * tr.total(tr.subtract(gaps, covered)) / idle
        if idle else 0.0)
    return out
