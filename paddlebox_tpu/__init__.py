"""paddlebox_tpu — a TPU-native training framework with PaddleBox capabilities.

A brand-new JAX/XLA/Pallas framework reproducing the capabilities of
zhongweics/PaddleBox (Baidu's PaddlePaddle fork with the BoxPS/HeterPS
GPU-resident sparse parameter server for trillion-feature CTR models) —
re-designed TPU-first rather than ported:

- sparse embedding engine: pass-based tables sharded across TPU HBM,
  pull = all-to-all + gather, push = segment-sum + fused sparse optimizer
  (role of ``fleet/box_wrapper.h`` + ``fleet/heter_ps/`` in the reference)
- data pipeline: columnar slot-record batches with static padded shapes
  (role of ``framework/data_feed.{h,cc,cu}``, ``data_set.{h,cc}``)
- distributed: dp/mp/pp/sp/ep hybrid meshes over ICI/DCN via pjit/shard_map
  (role of ``python/paddle/distributed/fleet``), plus TPU-first long-context
  sequence parallelism (absent in the reference)
- metrics: exact distributed AUC via on-device bucketed histograms + psum
  (role of ``fleet/metrics.{h,cc}``)
- checkpointing: day/pass base+delta model dumps with done-file publication
  (role of ``BoxWrapper::SaveBase/SaveDelta``, ``fleet_util.py``)

See SURVEY.md at the repo root for the full structural map of the reference.
"""

from paddlebox_tpu.version import __version__

# Core runtime (role of paddle/fluid/platform: flags, monitor, timers).
from paddlebox_tpu.core import flags
from paddlebox_tpu.core.flags import get_flags, set_flags

__all__ = [
    "__version__",
    "flags",
    "get_flags",
    "set_flags",
]
