"""Runtime — epoch loop, pipelines, barriers (meta-lite, single node)."""

# DeviceWedged is re-exported here because it is part of the runtime's
# failure contract: barrier()/wait_barrier raise it when the blackbox
# sentinel classifies the device WEDGED (drivers catch it next to the
# other barrier faults)
from risingwave_tpu.blackbox import DeviceWedged
from risingwave_tpu.runtime.pipeline import Pipeline, TwoInputPipeline
from risingwave_tpu.runtime.runtime import StreamingRuntime
from risingwave_tpu.runtime.arrangements import ArrangementRegistry
from risingwave_tpu.runtime.fused_step import (
    FusedChainExecutor,
    fuse_chain,
    fuse_pipeline,
)
from risingwave_tpu.runtime.notification import NotificationHub
from risingwave_tpu.runtime.source_manager import SourceManager
from risingwave_tpu.runtime.dml import DmlManager

__all__ = [
    "ArrangementRegistry",
    "DeviceWedged",
    "DmlManager",
    "FusedChainExecutor",
    "Pipeline",
    "TwoInputPipeline",
    "StreamingRuntime",
    "SourceManager",
    "NotificationHub",
    "fuse_chain",
    "fuse_pipeline",
]
