"""Trace-contract analyzer: jaxpr linter + RNG-lineage checker.

Static analysis over *traced programs* (jaxprs) rather than runs:

  * `repro.analysis.trace` — walk a jaxpr into a `TraceReport` (peak
    intermediate bytes, dtype census, FLOPs, pallas dispatch counts,
    host-sync detection, donation verification);
  * `repro.analysis.rng` — RNG-lineage checker (reused-key / loop-reuse
    detection, the PR 8 bug class) plus the source-level `fold_in` sweep;
  * `repro.analysis.streams` — the registry of named RNG streams every
    `fold_in` in `src/repro` must belong to;
  * `repro.analysis.contracts` — per-entry-point budget manifest
    (`contracts.toml`) and its evaluator;
  * `repro.analysis.hardware` — the per-`device_kind` peak table
    (`HardwareModel` rows) shared with the roofline extractor in
    `repro.launch.analysis`.

Gate: ``python -m repro.analysis check`` (``--update`` ratchets measured
peaks downward, like the coverage gate).
"""
from repro.analysis.hardware import (  # noqa: F401
    PEAKS,
    TPU_V5E,
    HardwareModel,
    get_default_hardware,
    hardware_for,
)
from repro.analysis.rng import (  # noqa: F401
    RngIssue,
    RngReport,
    check_fold_in_sites,
    report_from_jaxpr as rng_report_from_jaxpr,
    rng_report,
    sweep_fold_in_sites,
)
from repro.analysis.trace import (  # noqa: F401
    TraceReport,
    all_shapes,
    count_pallas_calls,
    max_intermediate_elems,
    peak_intermediate_bytes,
    report_from_jaxpr,
    trace_report,
    verify_donation,
)

__all__ = [
    "PEAKS",
    "TPU_V5E",
    "HardwareModel",
    "get_default_hardware",
    "hardware_for",
    "RngIssue",
    "RngReport",
    "check_fold_in_sites",
    "rng_report_from_jaxpr",
    "rng_report",
    "sweep_fold_in_sites",
    "TraceReport",
    "all_shapes",
    "count_pallas_calls",
    "max_intermediate_elems",
    "peak_intermediate_bytes",
    "report_from_jaxpr",
    "trace_report",
    "verify_donation",
]
