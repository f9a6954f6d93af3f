"""repro.runtime — the shared alignment runtime (engine registry,
compiled-plan cache, length-bucketed batching).

This is the fixed back-end of DP-HLS §5 recast as a software layer: every
caller (core.api, core.batch, core.tiling, serve, the chip benchmark)
resolves its engine through one registry, compiles through one plan
cache, and pads through one bucketing policy — instead of five
independent jit call sites and a global max_len pad.
"""
from .registry import (Engine, available_engines, engine_options,
                       engine_tunable, get_engine, register_engine)
from .plan import (CompiledPlan, align_impl, clear_plan_cache, get_plan,
                   lower_plan_hlo, plan_cache_info, resolve_engine_options,
                   traceback_bytes, validate_int_option,
                   validate_pow2_option)
from .bucketing import (Bucket, bucket_length, bucket_shape,
                        inverse_permutation, max_grid_bucket,
                        pack_by_bucket, pad_to_bucket)
from .dispatch import run_pairs, run_pipelined

__all__ = [
    "Engine", "available_engines", "engine_options", "engine_tunable",
    "get_engine", "register_engine",
    "CompiledPlan", "align_impl", "clear_plan_cache", "get_plan",
    "lower_plan_hlo", "plan_cache_info", "resolve_engine_options",
    "traceback_bytes", "validate_int_option", "validate_pow2_option",
    "Bucket", "bucket_length", "bucket_shape", "inverse_permutation",
    "max_grid_bucket", "pack_by_bucket", "pad_to_bucket",
    "run_pairs", "run_pipelined",
]
