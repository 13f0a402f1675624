"""Run statistics: the ``yk_stats`` API.

Counterpart of the reference's ``Stats``/``yk_stats``
(``src/kernel/lib/context.hpp:145-198``, printed by ``get_stats``,
``soln_apis.cpp:349,536-551``): points/reads/writes/FLOP throughput over the
steps done since the last reset, plus the per-phase timer breakdown the
reference keeps for halo exchange (``context.hpp:318-328``).
"""

from __future__ import annotations


class yk_stats:
    def __init__(self, npts: int, nsteps: int, nreads_pp: int,
                 nwrites_pp: int, nfpops_pp: int, elapsed: float,
                 halo_secs: float = 0.0, compile_secs: float = 0.0,
                 halo_exchange_secs: float = 0.0,
                 halo_pack_secs: float = 0.0,
                 halo_cal_spread: float = 0.0,
                 halo_cal_unstable: bool = False,
                 halo_cal_reps: int = 0,
                 halo_overlap_eff: float = 0.0,
                 halo_collectives: int = 0,
                 read_bytes_pp: float = 0.0, write_bytes_pp: float = 0.0,
                 hbm_peak: float = 0.0, tiling: dict | None = None):
        self._npts = npts
        self._nsteps = nsteps
        self._nreads_pp = nreads_pp
        self._nwrites_pp = nwrites_pp
        self._nfpops_pp = nfpops_pp
        self._elapsed = elapsed
        self._halo = halo_secs
        self._compile = compile_secs
        self._halo_xround = halo_exchange_secs
        self._halo_xpack = halo_pack_secs
        self._halo_cal_spread = halo_cal_spread
        self._halo_cal_unstable = halo_cal_unstable
        self._halo_cal_reps = halo_cal_reps
        self._halo_overlap_eff = halo_overlap_eff
        self._halo_collectives = halo_collectives
        self._rb_pp = read_bytes_pp
        self._wb_pp = write_bytes_pp
        self._hbm_peak = hbm_peak
        self._tiling = tiling

    def get_tiling(self) -> dict | None:
        """The Pallas tiling the built kernel actually chose (blocks,
        skew, pipelining flags, modeled margin overhead), or None on
        non-pallas paths / before the first build.  Returns a copy —
        the underlying dict also drives the context's HBM traffic
        model."""
        if self._tiling is None:
            return None
        out = dict(self._tiling)
        if isinstance(out.get("block"), dict):
            out["block"] = dict(out["block"])
        return out

    def get_num_elements(self) -> int:
        """Points in the global domain (per step)."""
        return self._npts

    def get_num_steps_done(self) -> int:
        return self._nsteps

    def get_num_writes_done(self) -> int:
        return self._npts * self._nwrites_pp * self._nsteps

    def get_num_reads_done(self) -> int:
        return self._npts * self._nreads_pp * self._nsteps

    def get_est_fp_ops_done(self) -> int:
        return self._npts * self._nfpops_pp * self._nsteps

    def get_elapsed_secs(self) -> float:
        return self._elapsed

    def get_halo_secs(self) -> float:
        return self._halo

    def get_compile_secs(self) -> float:
        """TPU-specific: XLA compilation time excluded from throughput
        (the analog of the reference excluding auto-tuner warmup)."""
        return self._compile

    # -- derived throughput (the log lines YaskUtils.pm:40-58 scrapes) -----

    def get_pts_per_sec(self) -> float:
        tot = self._npts * self._nsteps
        return tot / self._elapsed if self._elapsed > 0 else 0.0

    def get_flops(self) -> float:
        return (self.get_est_fp_ops_done() / self._elapsed
                if self._elapsed > 0 else 0.0)

    def get_halo_exchange_secs(self) -> float:
        """Calibrated cost of ONE bare ghost-exchange round (pack +
        collectives + unpack) — next to get_halo_secs(), which includes
        overlap effects."""
        return self._halo_xround

    def get_halo_pack_secs(self) -> float:
        """Slab pack/unpack share of one exchange round (the round with
        collectives elided) — reference pack/unpack timers,
        ``context.hpp:318-328``."""
        return self._halo_xpack

    def get_halo_collective_secs(self) -> float:
        """Collective-wait share of one exchange round (round − pack) —
        reference MPI wait-timer analog."""
        return max(0.0, self._halo_xround - self._halo_xpack)

    def get_halo_cal_spread(self) -> float:
        """Relative spread ((max−min)/median) across the ≥3 calibration
        trials behind the halo fraction (real program vs no-exchange
        twin).  A fraction whose spread is of the same magnitude is
        noise, not signal — read it next to the fraction so short-run
        twin jitter can't masquerade as a halo-cost change."""
        return self._halo_cal_spread

    def get_halo_cal_unstable(self) -> bool:
        """True when the halo calibration stayed outlier-contaminated
        even after its one full re-time (an extreme trial beyond 3× the
        agreeing pair's spread, twice in a row).  The fraction is still
        reported — the median is the best available estimate — but
        it is noise, not evidence: the harness prints the halo time as
        null.  Unstable is only declared after one
        LAST scaled round (2·trials+1 samples) also failed —
        :func:`get_halo_cal_reps` says how many were burned."""
        return self._halo_cal_unstable

    def get_halo_cal_reps(self) -> int:
        """Total calibration trials run across the (real, twin) pair —
        6 when every round was clean, more when outliers forced
        re-times / the final scaled round.  0 when no calibration ran
        (non-shard modes, measure_halo off)."""
        return self._halo_cal_reps

    def get_halo_collectives(self) -> int:
        """Collectives (ppermutes) one full ghost-exchange round issues
        under the scheduled comm plan — counted while tracing the
        exchange-only calibration twin, so it is the executed schedule,
        not a model.  Message coalescing (CommPlan) drops this to
        2 × (exchanged mesh axes); the serial per-buffer schedule pays
        2 × slabs per axis.  0 before halo calibration runs."""
        return self._halo_collectives

    def get_halo_overlap_eff(self) -> float:
        """Fraction of the bare collective cost the shard_pallas
        schedule hid: 1 − measured-halo-cost / (rounds × bare exchange
        round), clamped to [0, 1].  Nonzero for the serial arm too
        (XLA hides some latency regardless); the overlapped core/shell
        split should push it toward 1.  0 when the calibration is
        missing or nothing was hidden — the MPI-overlap efficiency the
        reference derives from its exterior/interior timers."""
        return self._halo_overlap_eff

    def get_hbm_bytes_per_point(self) -> float:
        """Modeled HBM traffic (read+write) per point per step."""
        return self._rb_pp + self._wb_pp

    def get_hbm_bytes_per_sec(self) -> float:
        return self.get_pts_per_sec() * self.get_hbm_bytes_per_point()

    def get_hbm_roofline_fraction(self) -> float:
        """Achieved / peak HBM bandwidth (0 when the peak is unknown)."""
        if self._hbm_peak <= 0:
            return 0.0
        return self.get_hbm_bytes_per_sec() / self._hbm_peak

    def format(self) -> str:
        gpts = self.get_pts_per_sec() / 1e9
        return (f"num-points-per-step: {self._npts}\n"
                f"num-steps-done: {self._nsteps}\n"
                f"elapsed-time (sec): {self._elapsed:.6g}\n"
                f"throughput (num-points/sec): {self.get_pts_per_sec():.6g}\n"
                f"throughput (GPts/s): {gpts:.6g}\n"
                f"throughput (est-FLOPS): {self.get_flops():.6g}\n"
                f"halo-time (sec): {self._halo:.6g}\n"
                f"halo-fraction (%): "
                f"{100.0 * self._halo / self._elapsed if self._elapsed else 0.0:.4g}\n"
                f"halo-exchange-round (sec): {self._halo_xround:.6g}\n"
                f"halo-pack (sec): {self._halo_xpack:.6g}\n"
                f"halo-cal-spread (rel): {self._halo_cal_spread:.4g}\n"
                + ("halo-cal-unstable: true\n"
                   if self._halo_cal_unstable else "")
                + (f"halo-cal-reps: {self._halo_cal_reps}\n"
                   if self._halo_cal_reps else "")
                + f"halo-collective (sec): "
                f"{self.get_halo_collective_secs():.6g}\n"
                + (f"halo-collectives-per-round: "
                   f"{self._halo_collectives}\n"
                   if self._halo_collectives else "")
                + (f"halo-overlap-eff (%): "
                   f"{100.0 * self._halo_overlap_eff:.4g}\n"
                   if self._halo_overlap_eff > 0 else "")
                + f"hbm-bytes-per-point (read+write): "
                f"{self.get_hbm_bytes_per_point():.6g}\n"
                f"achieved-HBM (GB/s): "
                f"{self.get_hbm_bytes_per_sec() / 1e9:.6g}\n"
                f"hbm-roofline-fraction (%): "
                f"{100.0 * self.get_hbm_roofline_fraction():.4g}\n"
                + (f"pallas-tiling: {self._tiling}\n"
                   if self._tiling else "")
                + f"compile-time (sec): {self._compile:.6g}\n")
