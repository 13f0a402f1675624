"""Communication-pattern scheduling for the explicit shard modes.

The reference pumps its MPI halo exchange in a fixed neighbor/rank
order and overlaps the flight with interior compute
(``context.cpp:377-478``); large TPU meshes add a twist the reference
never had: axes differ in transport — on-slice ICI torus links vs the
host-crossing DCN, three orders of magnitude apart in latency.  The
``CommPlan`` is the TilePlan analog for that problem, derived once per
prepared solution (pure geometry, never raises) and consumed by BOTH
the shard_map/shard_pallas exchange executors and the static checker's
``COMM-*`` rules, so the executed schedule and the reported one cannot
drift.  Per mesh axis it decides:

* **ordering** — which axis exchanges first.  DCN axes go first (their
  longer flight time needs the most downstream work to hide under),
  then ICI axes by descending modeled flight time, off the link model
  below (``link_model``/``order_comm_axes``).  An explicit
  ``-comm_order`` list overrides.
* **coalescing** — every buffer's ghost slab for one (axis, direction)
  packed into a single concatenated ``ppermute`` payload instead of
  one collective per buffer per face (the channel-merging move of
  "Improving Communication Patterns in Polyhedral Process Networks",
  arxiv 1801.04821, applied to halo channels).  ``ppermute`` only
  moves bytes, so the packed schedule is bit-identical to the serial
  one.
* **corners** — nothing: diagonal ghosts are already composed axis
  exchanges (a later axis's slab spans the earlier axes' freshly
  filled ghosts, so X-then-Y forwards the received edges), and that
  composition survives coalescing because the packed path still goes
  axis-by-axis in plan order.  The plan just guarantees an order
  exists; no dedicated diagonal collectives on 2-D/3-D meshes.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


class CommPlan:
    """One solution's communication schedule (see module docstring).

    ``order``         — mesh axes in exchange order (only axes that
                        actually carry ghost traffic);
    ``coalesce``      — pack all slabs per (axis, direction) into one
                        ppermute payload;
    ``axes``          — per-dim model: nranks, ici/dcn kind, payload
                        slabs ("items"), bytes per steady-state round,
                        link gbps/latency and modeled flight secs;
    ``rounds`` / ``rounds_serial`` — modeled collective count per full
                        exchange round under this plan vs the serial
                        per-buffer schedule;
    ``reasons``       — structured decision records (explain-pass
                        style), ``errors`` — invalid-knob messages (the
                        run paths raise on them; the checker reports
                        them as ``COMM-ORDER`` instead).
    """

    __slots__ = ("order", "coalesce", "axes", "reasons", "errors",
                 "rounds", "rounds_serial", "mesh_shape", "K", "mode")

    def __init__(self, order, coalesce, axes, reasons, errors,
                 rounds, rounds_serial, mesh_shape, K, mode):
        self.order = list(order)
        self.coalesce = bool(coalesce)
        self.axes = axes
        self.reasons = reasons
        self.errors = errors
        self.rounds = rounds
        self.rounds_serial = rounds_serial
        self.mesh_shape = mesh_shape
        self.K = K
        self.mode = mode

    def key(self):
        """Compiled-schedule cache-key suffix: the parts of the plan a
        traced exchange body bakes in."""
        return (",".join(self.order), self.coalesce)

    def record(self) -> Dict:
        """Structured record for tiling dicts and checker details —
        every per-axis decision, JSON-clean."""
        return {
            "order": list(self.order),
            "coalesce": self.coalesce,
            "mesh": dict(self.mesh_shape),
            "K": self.K,
            "mode": self.mode,
            "axes": {d: dict(a) for d, a in self.axes.items()},
            "rounds": self.rounds,
            "rounds_serial": self.rounds_serial,
            "reasons": [dict(r) for r in self.reasons],
            "errors": list(self.errors),
        }


# ---------------------------------------------------------------------------
# ICI/DCN link model (the comm-side analog of the HBM peak table in
# runtime/env.py): per-axis link bandwidth + latency by device kind, by
# which build_comm_plan orders mesh axes.  Pure numbers, so the checker
# and the CPU proxy can cost a plan without a backend.
# ---------------------------------------------------------------------------

# (substring match on jax device_kind, lowercased) -> (GB/s per link
# direction, one-way latency in µs).  ICI figures follow the public
# per-chip interconnect specs (per-direction share of the torus links);
# DCN is the inter-host data-center network — orders of magnitude more
# latency, so axes that cross hosts must start their flight first.
_ICI_LINKS = (
    (("v5 lite", "v5e"), (45.0, 1.0)),
    (("v5p", "v5"), (90.0, 1.0)),
    (("v6", "trillium"), (90.0, 1.0)),
    (("v4",), (50.0, 1.0)),
    (("v3",), (35.0, 1.0)),
    (("v2",), (25.0, 1.0)),
)
_DCN_LINK = (12.5, 25.0)          # ~100 Gb/s NIC share, host-to-host RTT/2
_ICI_DEFAULT = (40.0, 1.0)        # unknown chip (CPU proxy mesh): any
#                                   positive numbers — only the ici/dcn
#                                   asymmetry matters for ordering there


def link_model(device_kind: str = "", kind: str = "ici") -> Dict:
    """Modeled link characteristics for one mesh axis.

    ``device_kind`` — jax's ``device_kind`` string ("" = unknown, e.g.
    the CPU proxy mesh); ``kind`` — ``"ici"`` for on-slice torus axes,
    ``"dcn"`` for axes that cross host processes.  Returns
    ``{"kind", "gbps", "latency_us"}``.
    """
    if kind == "dcn":
        gbps, lat = _DCN_LINK
    else:
        kd = (device_kind or "").lower()
        gbps, lat = _ICI_DEFAULT
        for keys, spec in _ICI_LINKS:
            if any(k in kd for k in keys):
                gbps, lat = spec
                break
    return {"kind": kind, "gbps": gbps, "latency_us": lat}


def link_secs(nbytes: float, link: Dict) -> float:
    """Modeled one-way flight time of an ``nbytes`` payload on ``link``
    (latency + bytes/bandwidth)."""
    return (link["latency_us"] * 1e-6
            + float(nbytes) / (link["gbps"] * 1e9))


def order_comm_axes(axis_costs: Dict[str, Dict]) -> list:
    """Exchange ordering off the link model: DCN axes first (their
    longer flight time needs the most compute to hide under — the
    rank-order pumping stance of the reference's halo loop,
    ``context.cpp:377-478``), then ICI axes by descending modeled
    flight time; ties keep the input (domain-dim) order.

    ``axis_costs`` maps dim -> {"kind": "ici"|"dcn", "secs": float}.
    """
    dims = list(axis_costs)
    return sorted(
        dims,
        key=lambda d: (0 if axis_costs[d]["kind"] == "dcn" else 1,
                       -axis_costs[d]["secs"], dims.index(d)))


def mesh_axis_kinds(mesh, dims) -> Dict[str, str]:
    """ici/dcn per mesh axis: an axis whose device row crosses jax
    process boundaries is DCN (multi-host), everything else ICI.  A
    ``None`` mesh (unprepared context) classifies everything ICI."""
    kinds = {d: "ici" for d in dims}
    if mesh is None:
        return kinds
    devs = np.asarray(mesh.devices)
    pidx = np.vectorize(lambda dev: getattr(dev, "process_index", 0))(devs)
    names = list(mesh.axis_names)
    for i, name in enumerate(names):
        if name in kinds and devs.shape[i] > 1:
            first = np.take(pidx, [0], axis=i)
            if bool((pidx != first).any()):
                kinds[name] = "dcn"
    return kinds


def build_comm_plan(ctx, K: Optional[int] = None, prog=None) -> CommPlan:
    """Derive the CommPlan for a configured solution context.

    Pure geometry — never raises, never allocates, never touches a
    device; invalid knobs land in ``plan.errors`` (run paths raise on
    them, the checker reports them).  ``K`` is the fused group size the
    exchange serves (shard_pallas moves radius×K slabs of the min(K,
    slots) newest ring slots; shard_map moves every slot at the raw
    halo widths).
    """
    opts = ctx._opts
    ana = ctx._ana
    dims = list(ana.domain_dims)
    mode = ctx._mode or opts.mode
    if K is None:
        K = max(opts.wf_steps, 1) if mode == "shard_pallas" else 1
    K = max(int(K), 1)
    if prog is None:
        prog = ctx._program if ctx._program is not None \
            else ctx._plan_geometry()
    nr = {d: int(opts.num_ranks[d]) for d in dims}
    lsizes = opts.rank_domain_sizes
    rad = ana.fused_step_radius()
    hK = {d: rad.get(d, 0) * K for d in dims}
    eb = int(np.dtype(prog.dtype).itemsize)
    reasons: List[dict] = []
    errors: List[str] = []

    kinds = mesh_axis_kinds(ctx._mesh, dims)
    dev_kind = ""
    try:
        devs = ctx._env.get_devices()
        if devs:
            dev_kind = getattr(devs[0], "device_kind", "") or ""
    except Exception:
        pass

    # ---- per-axis payload model (mirrors the executed schedule: the
    # steady-state exchange round the halo calibration times) ----------
    geoms = {k: g for k, g in prog.geoms.items() if not g.is_scratch}
    # shard_pallas: a var's widths by side where the analysis can say
    # them (one step of one stage), else the cone, as the program sends
    need = ana.group_ghost_widths(K) if mode == "shard_pallas" else None
    axes: Dict[str, dict] = {}
    for d in dims:
        if nr.get(d, 1) <= 1 or hK.get(d, 0) <= 0:
            continue
        items = 0
        nbytes = 0
        for name, g in geoms.items():
            if d not in g.domain_dims:
                continue
            if mode == "shard_pallas":
                # per-K-group refresh: written vars only, min(K, slots)
                # newest slots, radius×K widths both sides -- or, of
                # one step of one stage, what is read of the var a side
                if not g.is_written:
                    continue
                moved = min(K, g.num_slots)
                wl, wr = (hK[d], hK[d]) if need is None \
                    else need.get(name, {}).get(d, (0, 0))
                if (wl, wr) == (0, 0):
                    continue
            else:
                hl, hr = g.var.halo.get(d, (0, 0))
                if (hl, hr) == (0, 0):
                    continue
                moved = g.num_slots
                wl, wr = hl, hr
            cross = 1
            for i, (dn, kind) in enumerate(g.axes):
                if dn == d and kind == "domain":
                    continue
                cross *= (int(lsizes[dn]) if kind == "domain"
                          else int(g.shape[i]))
            items += moved
            nbytes += moved * (wl + wr) * cross * eb
        if items:
            link = link_model(dev_kind, kinds[d])
            secs = link_secs(nbytes, link)
            axes[d] = {"nranks": nr[d], "kind": kinds[d],
                       "items": items, "bytes": int(nbytes),
                       "gbps": link["gbps"],
                       "latency_us": link["latency_us"],
                       "secs": secs}
            reasons.append({"code": "comm_axis", "dim": d,
                            "kind": kinds[d], "items": items,
                            "bytes": int(nbytes),
                            "secs": round(secs, 9)})

    # ---- ordering -----------------------------------------------------
    auto_order = order_comm_axes(
        {d: {"kind": axes[d]["kind"], "secs": axes[d]["secs"]}
         for d in axes})
    setting_order = (getattr(opts, "comm_order", "") or "").strip()
    if setting_order:
        req = [s.strip() for s in setting_order.replace(";", ",")
               .split(",") if s.strip()]
        order: List[str] = []
        for dn in req:
            if dn not in axes:
                errors.append(
                    f"-comm_order names '{dn}' which is not an "
                    f"exchanged mesh axis (have {sorted(axes)})")
            elif dn in order:
                errors.append(f"-comm_order repeats '{dn}'")
            else:
                order.append(dn)
        missing = [d for d in auto_order if d not in order]
        if missing and not errors:
            reasons.append({
                "code": "comm_order_appended", "dims": list(missing),
                "cause": "-comm_order omitted exchanged axes; appended "
                         "in cost-model order"})
        order += missing
        cause = f"explicit -comm_order '{setting_order}'"
    else:
        order = auto_order
        cause = ("cost model: dcn axes first, then descending modeled "
                 "flight time")
    reasons.append({"code": "comm_order", "order": list(order),
                    "cause": cause})

    # ---- coalescing ---------------------------------------------------
    rounds_serial = sum(2 * axes[d]["items"] for d in order)
    rounds_coal = 2 * len(order)
    cset = str(getattr(opts, "coalesce", "auto")).lower()
    if cset in ("on", "true", "1"):
        coal, ccause = True, "coalesce=on (forced)"
    elif cset in ("off", "false", "0"):
        coal, ccause = False, "coalesce=off"
    elif cset == "auto":
        coal = rounds_serial > rounds_coal
        ccause = (f"auto: {rounds_serial} serial collectives per round "
                  f"vs {rounds_coal} coalesced" if coal else
                  "auto: no axis carries more than one slab — the "
                  "serial schedule already hits the collective floor")
    else:
        errors.append(f"-coalesce '{cset}' is not one of on|off|auto")
        coal, ccause = False, "invalid setting"
    rounds = rounds_coal if coal else rounds_serial
    reasons.append({
        "code": "comm_coalesce_engaged" if coal else "comm_coalesce_off",
        "cause": ccause, "rounds": rounds,
        "rounds_serial": rounds_serial})

    mesh_shape = {d: nr[d] for d in dims if nr.get(d, 1) > 1}
    return CommPlan(order=order, coalesce=coal, axes=axes,
                    reasons=reasons, errors=errors, rounds=rounds,
                    rounds_serial=rounds_serial, mesh_shape=mesh_shape,
                    K=K, mode=mode)
