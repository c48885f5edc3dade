"""apex_tpu.utils — shared helpers (pytree numerics, misc)."""

from apex_tpu.utils.tree import (
    is_floating,
    tree_l2_norm,
    per_tensor_l2_norms,
    tree_scale,
    tree_axpby,
    tree_select,
    global_grad_clip_coef,
)
from apex_tpu.utils.flatten import flatten, unflatten
from apex_tpu.utils.checkpoint import (
    save_checkpoint, restore_checkpoint, checkpoint_manager,
)
from apex_tpu.utils import profiler
from apex_tpu.utils.debug import (
    enable_nan_checks, nan_check_mode, checkify_finite, tree_health,
)
from apex_tpu.utils.metrics import (
    MetricsWriter, log_metrics, namespaced_sink,
)
from apex_tpu.utils.tracecheck import (
    RetraceError, retrace_guard, trace_event_count,
    reset_trace_event_count,
)
from apex_tpu.utils.compile_cache import enable_compile_cache
from apex_tpu.utils import lockcheck
from apex_tpu.utils import numcheck
from apex_tpu.utils import shardcheck

__all__ = [
    "is_floating",
    "tree_l2_norm",
    "per_tensor_l2_norms",
    "tree_scale",
    "tree_axpby",
    "tree_select",
    "global_grad_clip_coef",
    "flatten",
    "unflatten",
    "save_checkpoint", "restore_checkpoint", "checkpoint_manager",
    "profiler",
    "enable_nan_checks", "nan_check_mode", "checkify_finite",
    "tree_health",
    "MetricsWriter", "log_metrics", "namespaced_sink",
    "RetraceError", "retrace_guard", "trace_event_count",
    "reset_trace_event_count",
    "enable_compile_cache",
    "lockcheck",
    "numcheck",
    "shardcheck",
]
