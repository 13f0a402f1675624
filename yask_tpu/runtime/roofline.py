"""The HBM-roofline model of a measured rate: modeled bytes per point ×
achieved rate against the chip's aggregate peak.  The harness prints it
in its stats block (``yask_tpu/main.py``).
The bytes are the *moved* bytes of the configured execution path
(``ctx.hbm_model_bytes_pp()``), not the benchmark's need-bytes.
"""

from __future__ import annotations

from typing import Dict


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
    ``roofline_frac`` is None when the peak is unknown (a CPU run has
    no fraction rather than a fake 0).
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
    configured execution path's traffic model + the environment's peak."""
    rb, wb = ctx.hbm_model_bytes_pp()
    return roofline(rate_gpts, rb + wb,
                    env.get_hbm_peak_bytes_per_sec(),
                    ndev=env.get_num_ranks())


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
