"""Inference serving plane — KV-cache incremental decode + continuous
batching (the TPU-native analog of BigDL 2.0's Cluster Serving; see
engine.py for the design contract), plus the fleet plane above it:
EngineRouter (health-gated dispatch + failover, router.py) and the
SLO-driven Autoscaler (autoscaler.py). ISSUE 20 adds the scenario
plane: a declarative workload/chaos compiler (scenarios.py) and a
bench-calibrated fleet simulator (sim.py) that drive the SAME control
plane at 10^5+ requests on a virtual clock."""

from bigdl_tpu.serving.autoscaler import Autoscaler
from bigdl_tpu.serving.bucketing import (bucket_for, bucket_histogram,
                                         default_buckets, pad_rows,
                                         pad_tokens)
from bigdl_tpu.serving.distill import DraftDistiller
from bigdl_tpu.serving.engine import (STATUSES, EngineDegraded,
                                      EngineDraining, GenerationResult,
                                      HandoffPackage, InferenceEngine,
                                      OverloadError, Request,
                                      StepTimeout)
from bigdl_tpu.serving.kv_pool import BlockPool
from bigdl_tpu.serving.prefix_cache import RadixPrefixCache
from bigdl_tpu.serving.protocol import ServedModel
from bigdl_tpu.serving.router import (EngineRouter, NoHealthyEngine,
                                      ROUTER_LATENCY_BUCKETS)
from bigdl_tpu.serving.sampler import filter_logits, sample_logits
from bigdl_tpu.serving.scenarios import (BUILTIN_SCENARIOS,
                                         compile_scenario,
                                         list_scenarios, load_scenario)
from bigdl_tpu.serving.sim import CostModel, SimulatedEngine
from bigdl_tpu.serving.speculative import SpeculativeEngine
from bigdl_tpu.serving.tenancy import (TenancyController, TenantSpec,
                                       TokenBucket)
from bigdl_tpu.serving.vision import VisionEngine

# serving/tp.py imports models/ and parallel/, and a served model
# derives from serving/protocol.ServedModel: importing this package
# (which `import bigdl_tpu.serving.protocol` does) must import no
# model, so tp's names resolve on first use
_TP_NAMES = ("TPServingLM", "tp_serving_model", "tp_serving_specs",
             "gather_serving_params", "shard_serving_params")


def __getattr__(name):
    if name in _TP_NAMES:
        from bigdl_tpu.serving import tp

        return getattr(tp, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "InferenceEngine", "ServedModel", "Request", "GenerationResult",
    "STATUSES",
    "OverloadError", "StepTimeout", "EngineDegraded", "EngineDraining",
    "HandoffPackage", "EngineRouter", "NoHealthyEngine",
    "ROUTER_LATENCY_BUCKETS",
    "SpeculativeEngine", "DraftDistiller",
    "TenancyController", "TenantSpec", "TokenBucket", "VisionEngine",
    *_TP_NAMES,
    "CostModel", "SimulatedEngine", "BUILTIN_SCENARIOS",
    "compile_scenario", "load_scenario", "list_scenarios",
    "Autoscaler", "BlockPool", "RadixPrefixCache",
    "sample_logits", "filter_logits",
    "bucket_for", "bucket_histogram", "default_buckets", "pad_tokens",
    "pad_rows",
]
