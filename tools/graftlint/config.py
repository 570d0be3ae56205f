"""Graftlint configuration.

Everything repo-specific lives here — the analyzed roots, the declared
hot-path and replay root sets, and the doc files the drift passes
cross-check — so the passes themselves stay generic (the test fixtures
run them against tiny synthetic projects with their own config).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Sequence


@dataclasses.dataclass
class Config:
    root: str
    # analyzed file roots, relative to ``root``
    roots: Sequence[str] = ("paddlebox_tpu", "tools")
    exclude: Sequence[str] = ()
    # -- pass 1: hot-path sync detector -----------------------------------
    # Functions whose transitive callees must not sync the host: the
    # jitted step builders (a sync there is a tracer error waiting for a
    # shape change), the dispatch loop, the prefetch producer, the
    # lookup exchange, and every Pallas kernel caller.
    hot_roots: Sequence[str] = (
        "paddlebox_tpu.train.ctr_trainer:CTRTrainer._build_step",
        "paddlebox_tpu.train.ctr_trainer:CTRTrainer._build_eval_step",
        "paddlebox_tpu.train.ctr_trainer:CTRTrainer.train_pass",
        "paddlebox_tpu.train.ctr_trainer:CTRTrainer.eval_pass",
        "paddlebox_tpu.train.ctr_trainer:CTRTrainer._prefetch_batches",
        "paddlebox_tpu.embedding.lookup:compute_bucketing",
        "paddlebox_tpu.embedding.lookup:pull_local",
        "paddlebox_tpu.embedding.lookup:push_local",
        "paddlebox_tpu.ops.pallas_kernels.sorted_gather:*",
        "paddlebox_tpu.ops.pallas_kernels.sorted_scatter:*",
        "paddlebox_tpu.ops.pallas_kernels.flash_attention:*",
        "paddlebox_tpu.ops.pallas_kernels.seqpool_cvm:*",
    )
    # attribute-call suffixes treated as producing device values
    # (compiled-step handles: self._step_fn(...), self._mega_fn(...))
    device_fn_suffixes: Sequence[str] = ("_fn",)
    # function names whose NESTED defs are jit-traced bodies: every
    # parameter of those defs is a tracer (device value)
    traced_parents: Sequence[str] = ("_build_step", "_build_eval_step")
    # -- pass 2: flag hygiene ---------------------------------------------
    flags_module: str = "paddlebox_tpu/core/flags.py"
    # docs where every defined flag must appear as FLAGS_<name>
    flag_docs: Sequence[str] = ("README.md", "OBSERVABILITY.md",
                                "ROBUSTNESS.md")
    # -- pass 3: registry drift -------------------------------------------
    robustness_doc: str = "ROBUSTNESS.md"
    faultpoint_section: str = "Faultpoint site table"
    metric_docs: Sequence[str] = ("OBSERVABILITY.md", "ROBUSTNESS.md")
    # -- pass 5: replay purity --------------------------------------------
    replay_roots: Sequence[str] = (
        "paddlebox_tpu.train.day_runner:DayRunner.train_pass",
        "paddlebox_tpu.embedding.pass_engine:PassEngine.*",
        "paddlebox_tpu.embedding.device_store:*",
        # The streaming pass loop replays carved manifests bit-identical
        # after kill -9: its clock is INJECTED (clock=), so wall reads
        # on the closure would be a contract break, not telemetry.
        "paddlebox_tpu.stream.runner:StreamRunner.*",
        "paddlebox_tpu.stream.source:*",
        # The fleet trace generator replays seeded traces bit-identical
        # (the autopilot drill's determinism contract): its RNG and
        # clock are injected — wall time or a global draw would make
        # two replays of one config disagree.
        "paddlebox_tpu.serving.traceload:*",
    )
    # suppression
    baseline_path: Optional[str] = None   # default: <pkg>/baseline.json

    def abspath(self, rel: str) -> str:
        return os.path.join(self.root, rel)


def default_config(root: str) -> Config:
    return Config(root=os.path.abspath(root))


def fixture_config(root: str, **overrides) -> Config:
    """Config for a synthetic test project: analyze everything under
    ``root`` and let the test override the root sets / doc paths."""
    cfg = Config(root=os.path.abspath(root), roots=("",),
                 hot_roots=(), replay_roots=(),
                 flags_module="flags.py",
                 flag_docs=("DOCS.md",),
                 robustness_doc="DOCS.md",
                 metric_docs=("DOCS.md",))
    return dataclasses.replace(cfg, **overrides)
