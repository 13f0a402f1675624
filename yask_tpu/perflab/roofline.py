"""The single HBM-roofline model every perf producer consumes.

Before perflab, the same three lines of arithmetic (modeled bytes/point ×
achieved rate vs the chip's aggregate peak) were duplicated — with
drifting key names — in ``yask_tpu/main.py`` (harness print),
``bench.py`` (contract line: ``hbm_roofline``), ``tools/bench_suite.py``
(none at all), and ``tools/tpu_session.py`` (``roofline_frac``).  This
module is the hoist: one function, one set of keys, recorded under
``roofline`` on every ledger row that has a traffic model.
"""

from __future__ import annotations

from typing import Dict, Optional


def roofline(rate_gpts: float, bytes_pp: float,
             peak_bytes_per_sec: float = 0.0, ndev: int = 1) -> Dict:
    """Roofline context for one measured rate.

    ``rate_gpts``  — achieved global throughput in GPts/s;
    ``bytes_pp``   — modeled HBM bytes per point per step (read+write,
                     from ``ctx.hbm_model_bytes_pp()``);
    ``peak_bytes_per_sec`` — per-chip peak HBM bandwidth
                     (``env.get_hbm_peak_bytes_per_sec()``; 0 = unknown,
                     e.g. the CPU proxy mesh);
    ``ndev``       — chips the rate is aggregated over (the roofline
                     denominator scales with the mesh).

    Returns ``{"hbm_bytes_pp", "hbm_gbps", "roofline_frac"}``;
    ``roofline_frac`` is None when the peak is unknown (the ledger drops
    None entries, so CPU rows simply lack the key rather than carrying
    a fake 0).
    """
    bpp = float(bytes_pp)
    gbps = float(rate_gpts) * bpp        # 1 GPt/s × B/pt == 1 GB/s
    out = {
        "hbm_bytes_pp": round(bpp, 2),
        "hbm_gbps": round(gbps, 1),
        "roofline_frac": None,
    }
    peak = float(peak_bytes_per_sec) * max(int(ndev), 1)
    if peak > 0:
        out["roofline_frac"] = round(gbps * 1e9 / peak, 4)
    return out


def ctx_roofline(ctx, env, rate_gpts: float) -> Dict:
    """Roofline context straight from a prepared solution context: the
    configured execution path's traffic model + the environment's peak.
    Producers that hold a context call this instead of re-deriving the
    inputs."""
    rb, wb = ctx.hbm_model_bytes_pp()
    return roofline(rate_gpts, rb + wb,
                    env.get_hbm_peak_bytes_per_sec(),
                    ndev=env.get_num_ranks())


def modeled_compute_secs(measured_secs: float,
                         roofline_frac: Optional[float]
                         ) -> Optional[float]:
    """The attribution join's modeled compute time: ``roofline_frac``
    is achieved/roofline rate, so a run at exactly the model's HBM
    roofline would have finished the same work in ``measured × frac``
    seconds.  None when the peak (and hence the fraction) is unknown —
    the attribution row then carries measured time only.  Lives here so
    measured-vs-modeled comparisons share the ONE roofline definition
    with every other producer."""
    if roofline_frac is None:
        return None
    return float(measured_secs) * float(roofline_frac)


def format_roofline(roof: Dict) -> str:
    """The harness' human-readable lines for one roofline dict (the
    log keys ``tools/log_to_csv.py`` scrapes)."""
    lines = [f"  hbm-bytes-per-point (read+write): "
             f"{roof['hbm_bytes_pp']:.6g}\n",
             f"  achieved-HBM (GB/s): {roof['hbm_gbps']:.6g}\n"]
    frac = roof.get("roofline_frac")
    if frac is not None:
        lines.append(f"  hbm-roofline-fraction (%): {100.0 * frac:.4g}\n")
    return "".join(lines)


# ---------------------------------------------------------------------------
# ICI/DCN link model (the comm-side analog of the HBM peak table above in
# env.py): per-axis link bandwidth + latency by device kind, consumed by
# the CommPlan scheduler (yask_tpu/parallel/comm_plan.py) to order mesh
# axes and decide message coalescing.  Pure numbers — this module never
# imports jax (provenance invariant), so the checker and the CPU proxy
# can cost a plan without a backend.
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


def vmem_sweep_margin_model(stencil: str = "iso3dfd", radius: int = 8,
                            g: int = 512, fuse_steps: int = 2,
                            budgets_mib=(64, 96, 120),
                            dtype_bytes: Optional[int] = None,
                            max_skew_dims: int = 2) -> Dict:
    """Modeled (block, margin_overhead) per VMEM budget — the no-chip
    variant of the ``-vmem_mb`` hardware sweep and
    the model behind the auto-tuner's vmem ladder: runs the actual tile
    planner + margin model on the CPU, no backend needed.  Returns
    {budget_mib: {"block": {...}, "margin_overhead": f}}.

    The numbers come from the ACTUAL kernel build (``build_pallas_chunk``
    in interpret mode — planning + tracing setup only, nothing runs):
    ``chunk.tiling`` is the same exact per-(sub-step, stage) accounting
    a hardware run would report, so the modeled table and a later
    measured one are directly comparable.  ``max_skew_dims`` mirrors
    the ``-skew_dims`` knob (2 = multi-dim skew allowed; 1 = the 1-D
    A/B arm); each row records which dims actually engaged.
    """
    from yask_tpu.compiler.solution_base import create_solution
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk
    from yask_tpu.utils.idx_tuple import IdxTuple

    sb = create_solution(stencil, radius=radius)
    if dtype_bytes:
        sb.get_soln().set_element_bytes(dtype_bytes)
    csol = sb.get_soln().compile()
    sizes = IdxTuple(**{d: g for d in csol.ana.domain_dims})
    K = fuse_steps
    rK = {d: csol.ana.fused_step_radius().get(d, 0) * K
          for d in csol.ana.domain_dims[:-1]}
    prog = csol.plan(sizes, extra_pad={d: (m, m) for d, m in rK.items()})
    out = {}
    for mib in budgets_mib:
        chunk, tile_bytes = build_pallas_chunk(
            prog, fuse_steps=K, interpret=True,
            vmem_budget=int(mib) * 2 ** 20,
            max_skew_dims=max_skew_dims)
        t = chunk.tiling
        out[int(mib)] = {
            "block": dict(t["block"]), "skew": t["skew"],
            "skew_dims": list(t.get("skew_dims", [])),
            "margin_overhead": t["margin_overhead"],
            "tile_mib": round(tile_bytes / 2 ** 20, 1),
        }
    return out
