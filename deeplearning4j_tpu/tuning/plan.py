"""Execution-plan resolution: the first slice of the step-compiler seam.

``net.fit(..., execution_plan="auto"|"fused"|"xla")`` is the user-facing
switch for the fused training kernels — what ``BENCH_FUSE`` used to gate
for the bench only, lifted behind the fit loops all seven step builders
share (MultiLayerNetwork, ComputationGraph, ParallelWrapper). Resolution
happens ONCE per fit() entry, host-side, from explicit inputs (never
from env vars inside a step builder — the retrace-on-flip class of bug
tpulint's recompile-hazard rule now flags):

- ``"xla"``   — the unfused graph (the measured-best static default,
  PERF.md round 3);
- ``"fused"`` — every eligible bottleneck chain runs the Pallas kernel
  cascade (nn/layers/bottleneck.py); the space-to-depth stem
  (nn/layers/stem.py) additionally engages iff the crossover store
  says it wins (its expected ceiling is ~2% — only a measurement may
  turn it on);
- ``"auto"``  — per shape from the measured crossover store
  (tuning/crossover.py): each candidate block (and the stem) runs the
  kernel only where a calibrated, platform-matching entry says the
  kernel wins. Uncalibrated (or mismatched) entries resolve to the XLA
  plan — "auto" on a fresh machine is exactly "xla" until a live
  window calibrates it.

``set_fusion`` applies the resolved plan with change detection, so
re-resolving the same plan on every fit() call never rebuilds jitted
steps: zero retraces after warmup holds with the plan layer on.
"""

from __future__ import annotations

import logging
from typing import Optional

from deeplearning4j_tpu.tuning.crossover import (
    KernelCrossoverStore, bottleneck_fingerprint, default_store,
    stem_fingerprint)

log = logging.getLogger(__name__)

EXECUTION_PLANS = ("auto", "fused", "xla")


def _net_dtype(net) -> str:
    return getattr(net.conf, "dtype", None) or "float32"


def _block_key(group: dict, dtype: str) -> str:
    return bottleneck_fingerprint(
        group["h"], group["w"], group["cin"], group["cmid"],
        group["cout"], group.get("stride", 1), "conv_skip" in group,
        dtype)


def _stem_key(group: dict, dtype: str) -> str:
    return stem_fingerprint(group["h"], group["w"], group["cin"],
                            group["cout"], dtype)


def apply_execution_plan(net, plan: Optional[str], *,
                         store: Optional[KernelCrossoverStore] = None
                         ) -> Optional[dict]:
    """Resolve ``plan`` onto ``net``'s step builders. Returns the
    resolution record ({plan, level, blocks, stem, keys}) for
    bench/test introspection, or None when plan is None (leave the
    net's current plan untouched — fit() without the kwarg must not
    reset an explicitly set_fusion'd net)."""
    if plan is None:
        return None
    if plan not in EXECUTION_PLANS:
        raise ValueError(
            f"execution_plan must be one of {EXECUTION_PLANS}, got "
            f"{plan!r}")
    if not hasattr(net, "set_fusion"):
        # sequential nets (MultiLayerNetwork): the plan seam exists —
        # the kwarg validates and resolves — but the fused chains are
        # residual-graph features, so every plan runs the XLA step.
        # Bit-exactness of "fused" vs "xla" here is definitional.
        if plan == "fused":
            log.debug("execution_plan='fused' on %s: no fusable graph "
                      "chains — running the XLA plan",
                      type(net).__name__)
        return {"plan": plan, "level": False, "blocks": 0,
                "stem": False, "keys": {}}
    if plan == "xla":
        net.set_fusion(False)
        return {"plan": plan, "level": False, "blocks": 0,
                "stem": False, "keys": {}}
    store = default_store() if store is None else store
    dtype = _net_dtype(net)
    bcands, scands = net.fusion_candidates()
    keys = {}
    if plan == "fused":
        chosen = set(bcands)
        only = None
    else:
        chosen = set()
        for name, grp in bcands.items():
            key = _block_key(grp, dtype)
            choice = store.choose(key, default="fallback")
            keys[name] = {"key": key, "choice": choice}
            if choice == "kernel":
                chosen.add(name)
        only = frozenset(chosen)
    # the stem is store-gated under BOTH fused and auto: its expected
    # win is ~2% of step time and the round-3 lesson (a pallas boundary
    # can cost more than it saves) applies — only a measured verdict
    # may engage it (PERF.md round 5)
    stem_on = False
    for name, grp in scands.items():
        key = _stem_key(grp, dtype)
        choice = store.choose(key, default="fallback")
        keys[name] = {"key": key, "choice": choice}
        stem_on = stem_on or choice == "kernel"
    if not chosen and not stem_on:
        net.set_fusion(False)
        return {"plan": plan, "level": False, "blocks": 0,
                "stem": False, "keys": keys}
    net.set_fusion("bottleneck", stem=stem_on, only=only)
    return {"plan": plan, "level": "bottleneck", "blocks": len(chosen),
            "stem": stem_on, "keys": keys}


# ---------------------------------------------------------------------------
# per-step HBM-traffic model (tokens of truth for the bench record)
# ---------------------------------------------------------------------------

#: tensor traversals per STAGE OUTPUT per train step, from the
#: bottleneck.py accounting: XLA plan — conv write, BN stats read,
#: normalize read+write, next-conv read fwd; stats/elementwise re-reads
#: in backward (~14 per bottleneck ≈ 4.7 per stage tensor); fused plan —
#: 1W+1R fwd, 3R+1W bwd per stage (~8 per bottleneck ≈ 2.7 per stage).
_XLA_TRAVERSALS = 14 / 3.0
_FUSED_TRAVERSALS = 8 / 3.0
#: stem: XLA — conv W, stats R, normalize R+W, pool R fwd + ~3 bwd
#: re-reads of the 112²×64 activation; fused — conv W + one fused
#: output-stage R fwd, recompute R + dy W/R bwd (stem.py docstring)
_XLA_STEM_TRAVERSALS = 8.0
_FUSED_STEM_TRAVERSALS = 4.0


def modeled_train_step_traffic(net, batch_size: int) -> dict:
    """Crude per-step HBM-traffic model over the net's fusable chains:
    bytes moved across the BN/elementwise tensors under the XLA vs the
    fused plan. Not a simulator — a consistent accounting that lets a
    bench record say how much traffic the plan REMOVES, priced against
    the measured img/s (PERF.md profile: the model is HBM-bound on
    exactly these tensors)."""
    bpe = 2 if _net_dtype(net) in ("bfloat16", "bf16") else 4
    if not hasattr(net, "fusion_candidates"):
        return {"xla_bytes": 0, "fused_bytes": 0, "blocks": 0,
                "stems": 0}
    bcands, scands = net.fusion_candidates()
    xla = fused = 0.0
    for grp in bcands.values():
        s = grp.get("stride", 1)
        ho, wo = grp["h"] // s, grp["w"] // s
        stage = batch_size * ho * wo * bpe
        tensors = stage * (grp["cmid"] * 2 + grp["cout"]
                           * (2 if "conv_skip" in grp else 1))
        xla += tensors * _XLA_TRAVERSALS
        fused += tensors * _FUSED_TRAVERSALS
    for grp in scands.values():
        ho, wo = (grp["h"] - 1) // 2 + 1, (grp["w"] - 1) // 2 + 1
        y = batch_size * ho * wo * grp["cout"] * bpe
        xla += y * _XLA_STEM_TRAVERSALS
        fused += y * _FUSED_STEM_TRAVERSALS
    return {"xla_bytes": int(xla), "fused_bytes": int(fused),
            "blocks": len(bcands), "stems": len(scands)}
