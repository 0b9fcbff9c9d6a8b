"""Measured kernel-crossover autotuning (ROADMAP item 1, "learned
crossover").

The round-3 lesson is the charter: a hand-fused Pallas kernel can LOSE
to XLA's own fusion because the ``pallas_call`` boundary costs more than
the saved traffic at some shapes (PERF.md round 3: the bn→act→conv plan
measured 20-25% slower). Static gates cannot know which side wins — only a
measurement on the target hardware can. This package makes that
measurement a persistent, consultable artifact:

- ``crossover.KernelCrossoverStore`` records paired kernel-vs-fallback
  timings keyed by a stable shape/dtype/impl fingerprint and persists
  them to a committed ``KERNEL_CROSSOVER.json`` (the TPULINT_BASELINE
  pattern: load → consult → ratchet), so ONE run on a TPU calibrates
  every future run. Entries carry platform + device kind — a
  CPU-calibrated entry never decides a TPU run.
- ``plan`` resolves user-facing execution plans
  (``net.fit(..., execution_plan="auto"|"fused"|"xla")``) against the
  store: the first slice of the step-compiler seam (ROADMAP item 5) —
  kernels become a composable plan layer on the step builders instead
  of a bench-only env flag.
- ``calibrate`` is the explicit measurement harness that fills the
  store from a run on the chip (per-shape paired timings of the fused
  training kernels).
"""

from deeplearning4j_tpu.tuning.crossover import (  # noqa: F401
    CROSSOVER_NAME, IMPL_REVS, KernelCrossoverStore, default_store,
    fingerprint, reset_default_store, stem_fingerprint,
    bottleneck_fingerprint, winner)
from deeplearning4j_tpu.tuning.plan import (  # noqa: F401
    EXECUTION_PLANS, apply_execution_plan, modeled_train_step_traffic)
from deeplearning4j_tpu.tuning.calibrate import (  # noqa: F401
    calibrate_training_kernels)

__all__ = [
    "CROSSOVER_NAME", "EXECUTION_PLANS", "IMPL_REVS",
    "KernelCrossoverStore", "apply_execution_plan",
    "bottleneck_fingerprint", "calibrate_training_kernels",
    "default_store", "fingerprint", "modeled_train_step_traffic",
    "reset_default_store", "stem_fingerprint", "winner",
]
