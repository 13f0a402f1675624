"""From a profiler trace to numbers: device busy and idle time, kernel
and collective time, idle gaps by benchmark span.

:func:`load_xplane` reads an ``.xplane.pb`` with ``jax.profiler.
ProfileData`` into plain lists; :func:`reduce` works on those lists
alone, so a recorded trace kept as JSON checks it without a chip.
Times are nanoseconds on the trace's one clock.

What the trace calls things today (libtpu 0.0.34, jax 0.9): a device
is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event
per executed HLO operation, named by the instruction's whole text
(``%chunk.1 = (f32[...], ...) custom-call(...)``).  :func:`op_name`
cuts that to ``chunk.1 custom-call``: the instruction's name and its
opcode.  A Pallas kernel is an operation whose opcode is
``custom-call`` (every ``tpu_custom_call`` counts, whatever wraps it),
a halo exchange one whose opcode starts with ``collective-permute``.
An asynchronous collective is in flight from its ``-start`` to its
``-done``; that interval is an event of the line ``Async XLA Ops``,
while ``XLA Ops`` holds only the two short operations (the ``-done``
is where the core waits).  Busy and idle are the core's: ``XLA Ops``
alone.  The benchmark's own spans are host events named ``bench.*``.
"""

import statistics

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
SPAN_PREFIX = "bench."
KERNEL_MARKS = ("custom-call", "tpu_custom_call")
COLLECTIVE_MARKS = ("collective-permute",)
#: control flow whose event spans the operations of its body, which
#: the trace lists beside it: left out of the ranking of operations
WRAPPER_OPCODES = (" while", " conditional", " call")


def op_name(text: str) -> str:
    """``<instruction name> <opcode>`` of an HLO instruction's text;
    a name that is no instruction text is returned as it is."""
    if " = " not in text:
        return text
    name, rest = text.split(" = ", 1)
    rest = rest.lstrip()
    if rest.startswith("("):            # a tuple shape: balanced
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    return f"{name.lstrip('%')} {rest.lstrip().partition('(')[0].strip()}"


def is_kernel(name: str) -> bool:
    return any(m in name for m in KERNEL_MARKS)


def is_collective(name: str) -> bool:
    return any(m in name for m in COLLECTIVE_MARKS)


def load_xplane(path: str, dry_run: bool = False) -> dict:
    """``{"devices": {plane: [[name, start, dur], ...]}, "async":
    {plane: [...]}, "spans": [[name, start, dur], ...]}``.  In a CPU
    dry run there is no device plane: the host's XLA client threads
    stand in, so that the same code runs (its numbers are the CPU's
    and labelled so)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, in_flight, spans = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                into = {OPS_LINE: devices, ASYNC_LINE: in_flight}.get(
                    line.name)
                if into is not None:
                    into[plane.name] = [
                        [op_name(e.name), e.start_ns, e.duration_ns]
                        for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, e.start_ns, e.duration_ns])
                    elif (dry_run and line.name.startswith("tf_XLA")
                          and e.duration_ns > 0
                          and not e.name.startswith(
                              ("end:", "Threadpool"))):
                        devices.setdefault("/host:CPU dry run", []).append(
                            [e.name, e.start_ns, e.duration_ns])
    return {"devices": devices, "async": in_flight, "spans": spans}


def union(intervals):
    """Sorted, merged ``[start, end]`` intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def total(intervals) -> float:
    return float(sum(b - a for a, b in intervals))


def clip(intervals, lo, hi):
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(intervals, holes):
    """The part of merged ``intervals`` that no merged ``holes``
    covers."""
    out = []
    for a, b in intervals:
        at = a
        for c, d in holes:
            if d <= at or c >= b:
                continue
            if c > at:
                out.append([at, c])
            at = max(at, d)
        if at < b:
            out.append([at, b])
    return out


def span_at(spans, t) -> str:
    """The innermost benchmark span that holds ``t``; between two
    spans the benchmark itself holds the host (``bench.between``)."""
    best = None
    for name, start, dur in spans:
        if start <= t <= start + dur and (best is None or dur < best[1]):
            best = (name, dur)
    return best[0] if best else "bench.between"


def reduce(events: dict, steps: int) -> dict:
    """Everything the per-layer readers take from a trace of ``steps``
    traced time steps.  Per-device figures are the busiest device's
    (the one that bounds a step), but for the exchange, which is read
    on the device that records it; ``busy_s`` is the mean over
    devices, as the result line's ``device`` asks."""
    spans = events["spans"]
    if not spans or not events["devices"]:
        return {}
    lo = min(s for _n, s, _d in spans)
    hi = max(s + d for _n, s, d in spans)
    window = hi - lo
    per_dev = {}
    for plane, ops in events["devices"].items():
        # the part of each operation that lies inside the window (the
        # device's clock runs a millisecond or two off the host's, so
        # an operation can straddle a span's edge)
        ops = [(n, max(s, lo), min(s + d, hi) - max(s, lo))
               for n, s, d in ops if s + d > lo and s < hi]
        busy = clip(union([s, s + d] for _n, s, d in ops), lo, hi)
        kern = clip(union([s, s + d] for n, s, d in ops
                          if is_kernel(n)), lo, hi)
        flying = events.get("async", {}).get(plane, [])
        coll = clip(union([s, s + d] for n, s, d in list(ops) + flying
                          if is_collective(n)), lo, hi)
        by_name = {}
        for n, _s, d in ops:
            if not n.endswith(WRAPPER_OPCODES):
                by_name[n] = by_name.get(n, 0.0) + d
        per_dev[plane] = {
            "ops": ops, "busy": busy, "busy_ns": total(busy),
            "kernel_ns": float(sum(d for n, _s, d in ops
                                   if is_kernel(n))),
            "collective_ns": total(coll),
            "exposed_ns": total(subtract(coll, kern)),
            "by_name": by_name}
    if not any(d["busy_ns"] > 0 for d in per_dev.values()):
        return {}
    top = max(per_dev.values(), key=lambda d: d["busy_ns"])
    # the profiler records ``Async XLA Ops`` for the first device only:
    # the exchange is read where it is recorded, kernels of that device
    par = max(per_dev.values(), key=lambda d: d["collective_ns"])
    gaps = subtract([[lo, hi]], top["busy"])
    by_span = {}
    for a, b in gaps:
        name = span_at(spans, (a + b) / 2)
        by_span[name] = by_span.get(name, 0.0) + (b - a)
    longest = max(gaps, key=lambda g: g[1] - g[0], default=None)

    # idle time between consecutive kernel launches inside one call
    call_gaps = []
    for name, s0, d0 in spans:
        if name != "bench.call":
            continue
        ks = sorted((s, s + d) for n, s, d in top["ops"]
                    if is_kernel(n) and s >= s0 and s + d <= s0 + d0)
        for (_a, end), (start, _b) in zip(ks, ks[1:]):
            if start > end:
                call_gaps.append(total(subtract([[end, start]],
                                                top["busy"])))

    # device time inside request intervals over request time
    req = union([s, s + d] for n, s, d in spans if n == "bench.request")
    busy_in_req = sum(total(clip(top["busy"], a, b)) for a, b in req)

    def ranked(table):
        return [[n, v / 1e9] for n, v in sorted(
            table.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "window_s": window / 1e9,
        "busy_s": statistics.fmean(
            d["busy_ns"] for d in per_dev.values()) / 1e9,
        "devices": len(per_dev),
        "steps": steps,
        "idle_share": 100.0 * (1.0 - top["busy_ns"] / window),
        "longest_gap_ms": ((longest[1] - longest[0]) / 1e6
                           if longest else 0.0),
        "longest_gap_span": (span_at(spans, sum(longest) / 2)
                             if longest else ""),
        "kernel_ms_per_step": top["kernel_ns"] / 1e6 / steps,
        "collective_ms_per_step": par["collective_ns"] / 1e6 / steps,
        "exposed_share": 100.0 * par["exposed_ns"] / window,
        "has_collectives": par["collective_ns"] > 0,
        "call_gap_ms": (statistics.median(call_gaps) / 1e6
                        if call_gaps else None),
        "request_busy_share": (100.0 * busy_in_req / total(req)
                               if req else None),
        "breakdown": {"device_ops": ranked(top["by_name"]),
                      "idle_gaps": ranked(by_span)},
    }
