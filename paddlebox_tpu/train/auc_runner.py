"""AUC-runner: slot-replacement feature-importance evaluation.

Role of the reference's AUC-runner mode (``box_wrapper.h:900-989`` with
``SlotsShuffle``, ``box_wrapper.h:1190`` / ``BoxPSDataset.slots_shuffle``):
rank each slot's contribution to a trained model by shuffling that slot's
values across records (decorrelating it from the label), re-evaluating
AUC, and reporting the degradation — a large drop means the slot carries
real signal; a near-zero drop flags a dead feature whose embedding table
can be evicted.

The eval path is read-only (``CTRTrainer.eval_pass`` aborts the pass
without write-back), so importance runs are safe against a production
store between training passes.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from paddlebox_tpu.core import log, monitor


def slot_replacement_eval(trainer, dataset, *,
                          slots: Optional[Sequence[str]] = None,
                          seed: int = 0) -> Dict[str, object]:
    """Evaluate per-slot AUC degradation on a trained CTRTrainer.

    Returns ``{"base_auc", "base_loss", "slots": {name: {"auc",
    "auc_drop", "loss"}}, "ranking": [names, most important first]}``.
    The dataset is restored to its original content afterwards.

    Results also land in the metric registry — ``quality/base_auc``
    plus per-slot ``quality/slot_auc/<slot>`` /
    ``quality/slot_auc_drop/<slot>`` gauges — so per-slot AUC
    degradation is recordable through the telemetry plane (JSONL
    export, ``metrics_snapshot`` scrape) instead of print-only.
    """
    base = trainer.eval_pass(dataset)
    names = list(slots) if slots is not None else [
        s.name for s in trainer.feed_config.sparse_slots]
    snap = dataset.snapshot_chunks()
    per_slot: Dict[str, Dict[str, float]] = {}
    try:
        for name in names:
            dataset.slots_shuffle([name], seed=seed)
            st = trainer.eval_pass(dataset)
            per_slot[name] = {
                "auc": float(st["auc"]),
                "auc_drop": float(base["auc"] - st["auc"]),
                "loss": float(st["loss"]),
            }
            dataset.restore_chunks(snap)
            log.vlog(1, "auc_runner slot %s: auc %.5f (drop %.5f)",
                     name, per_slot[name]["auc"],
                     per_slot[name]["auc_drop"])
    finally:
        dataset.restore_chunks(snap)
    ranking: List[str] = sorted(
        per_slot, key=lambda n: per_slot[n]["auc_drop"], reverse=True)
    monitor.set_gauge("quality/base_auc", float(base["auc"]))
    for name, st in per_slot.items():
        monitor.set_gauge(f"quality/slot_auc/{name}", st["auc"])
        monitor.set_gauge(f"quality/slot_auc_drop/{name}",
                          st["auc_drop"])
    return {"base_auc": float(base["auc"]),
            "base_loss": float(base["loss"]),
            "slots": per_slot,
            "ranking": ranking}
