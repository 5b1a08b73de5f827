"""Auto-parallel inference serving (ISSUE 10).

Two searched programs per decoder model (compute-priced prefill,
bandwidth-priced decode), a paged KV cache laid out by the winning decode
strategy, and a continuous-batching scheduler driving a device-resident
decode loop. Entry point: `compile_serving(model)`.
"""
from flexflow_tpu import telemetry as _tel

_T_START_US = _tel.now_us()

from flexflow_tpu.serving import tracefmt
from flexflow_tpu.serving.admission import AdmissionControl
from flexflow_tpu.serving.engine import ServingCompiled, compile_serving
from flexflow_tpu.serving.fleet import (FleetRouter, RollingSwapController,
                                        ServingFleet,
                                        merge_histograms, merge_slo_trackers)
from flexflow_tpu.serving.kv_cache import (ACTIVE_KEY, KVPoolExhausted,
                                           PAGE_TABLE_KEY, POS_KEY,
                                           PagedKVCache)
from flexflow_tpu.serving.program import clone_for_serving, serving_optimize
from flexflow_tpu.serving.reqtrace import (RequestTracer, StreamingHistogram,
                                           TERMINAL_FIELDS, terminal_record)
from flexflow_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                            Request, gpt2_prompt_inputs,
                                            gpt2_step_inputs,
                                            positions3_valid_prompt_inputs,
                                            positions3_valid_step_inputs,
                                            positions_valid_prompt_inputs,
                                            positions_valid_step_inputs,
                                            valid_prompt_inputs,
                                            valid_step_inputs)
from flexflow_tpu.serving.tracefmt import (Trace, TraceRecord, load_trace,
                                           save_trace)
from flexflow_tpu.serving.twin import (TwinCosts, TwinResult, TwinSpec,
                                       capacity_curve, simulate)

__all__ = [
    "compile_serving", "ServingCompiled", "PagedKVCache", "KVPoolExhausted",
    "ContinuousBatchingScheduler", "Request", "clone_for_serving",
    "serving_optimize", "gpt2_prompt_inputs", "gpt2_step_inputs",
    "valid_prompt_inputs", "valid_step_inputs",
    "positions_valid_prompt_inputs", "positions_valid_step_inputs",
    "positions3_valid_prompt_inputs", "positions3_valid_step_inputs",
    "PAGE_TABLE_KEY", "POS_KEY", "ACTIVE_KEY",
    "RequestTracer", "StreamingHistogram", "TERMINAL_FIELDS",
    "terminal_record",
    "ServingFleet", "AdmissionControl", "FleetRouter",
    "RollingSwapController", "merge_histograms", "merge_slo_trackers",
    "tracefmt", "Trace", "TraceRecord", "load_trace", "save_trace",
    "TwinSpec", "TwinCosts", "TwinResult", "simulate", "capacity_curve",
]

# this package is imported lazily and pulls in the whole compiler stack:
# its own stamp beside the package's `start/import`
_tel.record("start/import_serving", _T_START_US, cat="start")
