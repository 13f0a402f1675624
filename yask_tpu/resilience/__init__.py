"""yask_tpu.resilience — fault-tolerant TPU sessions.

One shared policy for every device-facing producer: fault classes +
classification (:mod:`.faults`), deadlines/retry/killable subprocess
(:mod:`.guard`), journaled resume (:mod:`.journal`), portable run
checkpoints + the mode-degradation ladder (:mod:`.checkpoint`), and
result-sanity guards (:mod:`.sanity`).  Fault injection via ``YT_FAULT_PLAN`` drives all of
it from fast CPU tests — see ``docs/resilience.md``.
"""

from yask_tpu.resilience.checkpoint import (  # noqa: F401
    CKPT_SCHEMA, apply_snapshot, default_ckpt_dir, degradation_ladder,
    extract_snapshot, peek_checkpoint, restore_checkpoint,
    save_checkpoint, snapshot_mismatches)
from yask_tpu.resilience.faults import (  # noqa: F401
    FAULT_KINDS, Breaker, CompileFailed, CompilerOOM, DeviceHang, Fault,
    BackendUnavailable, ResultAnomaly, active_plan, classify, classify_message,
    default_breaker_path, fault_point, maybe_corrupt, reset_faults)
from yask_tpu.resilience.guard import (  # noqa: F401
    RETRYABLE, deadline, guarded_call, python_cmd, run_deadlined)
from yask_tpu.resilience.journal import (  # noqa: F401
    JOURNAL_BASENAME, SCHEMA as JOURNAL_SCHEMA, TERMINAL_OUTCOMES,
    SessionJournal, default_journal_path, max_journal_bytes)
from yask_tpu.resilience.sanity import (  # noqa: F401
    ORACLE_REL_TOL, ZERO_FRAC_MAX, anomaly_fields, array_stats,
    check_output, check_state)

__all__ = [
    "Fault", "BackendUnavailable", "DeviceHang", "CompilerOOM", "CompileFailed",
    "ResultAnomaly", "FAULT_KINDS", "classify", "classify_message",
    "Breaker", "default_breaker_path", "fault_point", "maybe_corrupt",
    "reset_faults", "active_plan",
    "deadline", "guarded_call", "run_deadlined", "python_cmd",
    "RETRYABLE",
    "SessionJournal", "JOURNAL_SCHEMA", "JOURNAL_BASENAME",
    "TERMINAL_OUTCOMES", "default_journal_path", "max_journal_bytes",
    "CKPT_SCHEMA", "extract_snapshot", "apply_snapshot",
    "save_checkpoint", "restore_checkpoint", "peek_checkpoint",
    "snapshot_mismatches", "default_ckpt_dir", "degradation_ladder",
    "check_output", "check_state", "array_stats", "anomaly_fields",
    "ZERO_FRAC_MAX", "ORACLE_REL_TOL",
]
