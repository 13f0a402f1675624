"""Result-sanity guards: validate outputs before they are released.

An all-zero field from real hardware once passed as a clean result —
vacuously "matching" because the oracle was zero too.  These guards
run on the data behind a served response or a journal row and turn
that class of incident into a structured ``ANOMALY``:

* **all-zero** — a zero fraction above :data:`ZERO_FRAC_MAX` on data
  that was seeded nonzero means the device returned nothing;
* **non-finite** — NaN/Inf anywhere (divergence or corrupt DMA);
* **oracle mismatch** — relative L2 error against a cheap CPU
  reference beyond tolerance, where one is available.

A failed verdict never silently drops the result: the served path
withholds the outputs and answers ``anomaly``, and the journal row
carries the verdict (``quarantined: true`` + the ``anomaly`` field) so
the artifact records WHAT happened.
"""

from __future__ import annotations

from math import isfinite
from typing import Dict, List, Optional

#: zero fraction at/above which seeded data counts as "came back
#: all-zero".  High enough that legitimately sparse fields (an impulse
#: a few steps old is checked via an interior slice, not the full
#: domain) never trip it.
ZERO_FRAC_MAX = 0.999

#: default relative-L2 tolerance against a CPU oracle.
ORACLE_REL_TOL = 0.05


def _as_arrays(data) -> List:
    """Flatten an ndarray / list of ndarrays / var→ring state dict into
    a list of numpy arrays."""
    import numpy as np
    if isinstance(data, dict):
        out = []
        for ring in data.values():
            for a in (ring if isinstance(ring, (list, tuple))
                      else [ring]):
                out.append(np.asarray(a))
        return out
    if isinstance(data, (list, tuple)):
        return [np.asarray(a) for a in data]
    return [np.asarray(data)]


#: elements per block of :func:`array_stats`' streaming pass: 1 MiB of
#: fp32, so a block is still in cache for its second and third look.
_BLOCK_ELEMS = 1 << 18


def _exact_scan(a):
    """(nonfinite count, largest finite magnitude) of one block the
    slow, exact way: in float64 with a finite mask.  Only a block that
    holds a NaN or an Inf pays for it."""
    import numpy as np
    a = np.asarray(a, dtype=np.float64)
    finite = np.isfinite(a)
    nonfinite = int(a.size - int(finite.sum()))
    max_abs = float(np.abs(a[finite]).max()) if finite.any() else 0.0
    return nonfinite, max_abs


def array_stats(data) -> Dict:
    """Aggregate {n, zero_frac, nonfinite_frac, max_abs} over arrays /
    state dicts (device arrays are pulled to host via asarray).

    One streaming pass in the arrays' own dtype, no full-size
    temporary: each array is walked in blocks along its first axis
    (views, so a non-contiguous array is not copied), and a block
    gives its ``min``, ``max`` and zero count.  NaN and ±Inf all
    surface in ``min``/``max``: when both are finite the block has no
    non-finite value and its largest magnitude is ``max(|min|,
    |max|)``; otherwise that block alone takes :func:`_exact_scan`.
    The numbers are those of a float64 recomputation either way, and
    the exact scan ran iff ``nonfinite_frac > 0``
    (:func:`took_exact_scan`)."""
    import numpy as np
    n = zeros = nonfinite = 0
    max_abs = 0.0
    for a in _as_arrays(data):
        if a.size == 0:
            continue
        if a.ndim == 0:
            a = a.reshape(1)
        n += a.size
        rows = max(1, _BLOCK_ELEMS // (a.size // a.shape[0]))
        for i in range(0, a.shape[0], rows):
            blk = a[i:i + rows]
            zeros += int(np.count_nonzero(blk == 0))
            # ml_dtypes' reductions warn "invalid value" on a NaN
            with np.errstate(invalid="ignore"):
                lo, hi = float(blk.min()), float(blk.max())
            if isfinite(lo) and isfinite(hi):
                max_abs = max(max_abs, abs(lo), abs(hi))
            else:
                bad, finite_max = _exact_scan(blk)
                nonfinite += bad
                max_abs = max(max_abs, finite_max)
    return {"n": n,
            "zero_frac": (zeros / n) if n else 0.0,
            "nonfinite_frac": (nonfinite / n) if n else 0.0,
            "max_abs": max_abs}


def took_exact_scan(stats: Dict) -> bool:
    """Whether :func:`array_stats` fell to :func:`_exact_scan` for
    these stats (or this verdict): a block takes it iff its ``min`` or
    ``max`` is not finite, which is iff it holds a non-finite value."""
    return stats["nonfinite_frac"] > 0.0


def check_output(data, oracle=None, rel_tol: float = ORACLE_REL_TOL,
                 zero_frac_max: float = ZERO_FRAC_MAX) -> Dict:
    """The sanity verdict for one measurement's backing data.

    Returns ``{"ok": bool, "anomalies": [...], **array_stats}`` (plus
    ``oracle_rel_err`` when an oracle was supplied).  ``data`` and
    ``oracle`` accept an ndarray, a list of ndarrays, or a var→ring
    state dict."""
    import numpy as np
    stats = array_stats(data)
    anomalies: List[str] = []
    if stats["n"] and stats["nonfinite_frac"] > 0.0:
        anomalies.append("nonfinite")
    if stats["n"] and stats["zero_frac"] >= zero_frac_max:
        anomalies.append("all_zero")
    verdict = {"anomalies": anomalies, **stats}
    if oracle is not None:
        got = np.concatenate([np.asarray(a, dtype=np.float64).ravel()
                              for a in _as_arrays(data)])
        want = np.concatenate([np.asarray(a, dtype=np.float64).ravel()
                               for a in _as_arrays(oracle)])
        if got.shape == want.shape and want.size:
            denom = float(np.linalg.norm(want))
            err = float(np.linalg.norm(got - want)) / max(denom, 1e-30)
            verdict["oracle_rel_err"] = round(err, 6)
            if not np.isfinite(err) or err > rel_tol:
                anomalies.append("oracle_mismatch")
        else:
            anomalies.append("oracle_shape_mismatch")
    verdict["ok"] = not anomalies
    return verdict


def check_state(state, **kw) -> Dict:
    """:func:`check_output` over a runtime state dict (var → ring of
    padded device arrays)."""
    return check_output(state, **kw)


def anomaly_fields(verdict: Dict) -> Dict:
    """The fields a quarantined result carries — the served response's
    ``anomaly`` and its journal row."""
    return {"quarantined": True,
            "anomaly": {"classification": "ANOMALY",
                        "anomalies": list(verdict.get("anomalies", [])),
                        **{k: round(verdict[k], 6)
                           for k in ("zero_frac", "nonfinite_frac",
                                     "max_abs", "oracle_rel_err")
                           if k in verdict}}}
