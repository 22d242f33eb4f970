"""ASC-Hook core: the paper's mechanism, reproduced on a simulated AArch64.

Public surface::

    from repro.core import (
        Mechanism, prepare, run_prepared, run_with_c3, HookConfig,
        scan_image, census, programs,
    )
"""
from . import costmodel, fleet, isa, layout, programs
from .completeness import (C3Event, diagnose_c3, diagnose_c3_fleet,
                           run_with_c3)
from .fleet import (TraceState, admit_lanes, choose_bucket, compact_ladder,
                    fleet_counters, fleet_step, fleet_step_traced,
                    fleet_summary, make_halted_states, precompile_ladder,
                    restore_lanes, run_fleet, run_fleet_compact,
                    run_fleet_span, set_image_row, stack_images,
                    stack_states, unstack_state, unstack_trace,
                    update_policy_rows)
from .hookcfg import HookConfig, PinnedSite, PolicyRule
from .image import Image, build_minilibc, build_process
from .machine import (HALT_EXIT, HALT_FUEL, HALT_KILL, HALT_SEGV, HALT_TRAP,
                      DecodedImage, MachineState, decode_image, make_state,
                      mem_read, mem_read_block, mem_write, run_image)
from .rewriter import RewriteReport, rewrite_all_to_signal, rewrite_image
from .runtime import (FleetImageTable, ImageTableFull, Mechanism,
                      PreparedProcess, enable_compile_cache, fleet_trace,
                      hook_invocations, initial_state,
                      pack_fleet, precompile_compact, prepare,
                      run_fleet_prepared, run_prepared, update_fleet_policy)
from .scanner import SvcSite, census, scan_image

__all__ = [
    "C3Event", "DecodedImage", "FleetImageTable", "HALT_EXIT", "HALT_FUEL",
    "HALT_KILL", "HALT_SEGV", "HALT_TRAP", "HookConfig", "Image",
    "ImageTableFull", "MachineState", "Mechanism", "PinnedSite",
    "PolicyRule", "PreparedProcess", "RewriteReport", "SvcSite", "TraceState",
    "admit_lanes", "build_minilibc", "build_process", "census",
    "choose_bucket", "compact_ladder", "costmodel", "decode_image",
    "diagnose_c3", "diagnose_c3_fleet", "enable_compile_cache", "fleet",
    "fleet_counters",
    "fleet_step", "fleet_step_traced", "fleet_summary", "fleet_trace",
    "hook_invocations", "initial_state", "isa", "layout",
    "make_halted_states", "make_state", "mem_read", "mem_read_block",
    "mem_write", "pack_fleet", "precompile_compact", "precompile_ladder",
    "prepare", "programs", "restore_lanes",
    "rewrite_all_to_signal", "rewrite_image", "run_fleet",
    "run_fleet_compact", "run_fleet_prepared", "run_fleet_span", "run_image",
    "run_prepared", "run_with_c3", "scan_image", "set_image_row",
    "stack_images", "stack_states", "unstack_state", "unstack_trace",
    "update_fleet_policy", "update_policy_rows",
]
