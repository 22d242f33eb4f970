import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay first: jax locks the device count at first
initialisation, and the production meshes need 512 placeholder host devices.
Run as a script only (``python -m repro.launch.dryrun``); tests and benches
import nothing from here.

Per cell this:
  * builds the production mesh (16×16, or 2×16×16 with ``--multi-pod``),
  * lowers the real step function against ShapeDtypeStruct inputs
    (train_step for train shapes, serve prefill/decode for the others),
  * ``.compile()``s it — sharding mismatches, partitioner failures and
    compile-time OOMs all surface here,
  * records ``memory_analysis()`` (fits-in-HBM proof), ``cost_analysis()``,
    and the loop-aware HLO roofline stats (repro.launch.hloanalysis),
  * appends the cell to a JSON results file for EXPERIMENTS.md / benchmarks.
"""
import argparse
import dataclasses
import json
import pathlib
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.obs import now as obs_now

from repro.configs import ARCHS, applicable_shapes, get_config, get_smoke, shape_by_name
from repro.configs.base import ModelConfig, RunConfig, ShapeConfig
from repro.launch import specs as sp
from repro.launch.hloanalysis import HW, analyze, roofline_terms
from repro.launch.mesh import make_production_mesh, mesh_context
from repro.train.step import make_serve_steps, make_train_step

HBM_PER_CHIP = 16 * 1024 ** 3  # v5e


def dryrun_runconfig(**overrides) -> RunConfig:
    base = dict(remat_policy="nothing", attn_chunk=1024, mlstm_chunk=256,
                decode_budget=0, grad_compression="none", z_loss=1e-4,
                loss_chunk=512)
    base.update(overrides)
    return RunConfig(**base)


def model_flops_per_step(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """Analytic MODEL_FLOPS: 6·N·D train, 2·N·D forward-only."""
    n = cfg.n_active_params()
    mult = 6 if shape.kind == "train" else 2
    return float(mult * n * shape.tokens_per_step)


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               run: Optional[RunConfig] = None, smoke: bool = False):
    cfg = get_smoke(arch) if smoke else get_config(arch)
    shape = shape_by_name(shape_name)
    if smoke:
        shape = dataclasses.replace(shape, seq_len=min(shape.seq_len, 512),
                                    global_batch=min(shape.global_batch, 32))
    run = run or dryrun_runconfig()
    from repro.parallel.sharding import set_sharding_mode
    set_sharding_mode(run.sharding_mode)
    mesh = make_production_mesh(multi_pod=multi_pod)

    with mesh_context(mesh):
        if shape.kind == "train":
            state_sds, batch_sds, _ = sp.train_inputs(cfg, run, shape, mesh)
            step = make_train_step(cfg, run)
            lowered = jax.jit(step, donate_argnums=(0,)).lower(state_sds, batch_sds)
        elif shape.kind == "prefill":
            params_sds, batch_sds, _ = sp.prefill_inputs(cfg, run, shape, mesh)
            prefill_step, _ = make_serve_steps(cfg, run)
            lowered = jax.jit(prefill_step).lower(params_sds, batch_sds)
        else:  # decode
            params_sds, cache_sds, tokens, pos, _, _ = sp.decode_inputs(
                cfg, run, shape, mesh)
            _, decode_step = make_serve_steps(cfg, run)
            lowered = jax.jit(decode_step, donate_argnums=(1,)).lower(
                params_sds, cache_sds, tokens, pos)
        compiled = lowered.compile()
    return cfg, shape, mesh, compiled


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             run: Optional[RunConfig] = None, smoke: bool = False,
             label: str = "") -> Dict[str, Any]:
    # monotonic clock (obs.now): compile_s is a duration, and time.time()
    # can jump backwards under NTP slew mid-compile
    t0 = obs_now()
    chips = 512 if multi_pod else 256
    cell: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "label": label,
    }
    try:
        cfg, shape, mesh, compiled = lower_cell(
            arch, shape_name, multi_pod=multi_pod, run=run, smoke=smoke)
    except Exception as e:  # a failure here is a bug in the system
        cell.update(status="FAIL", error=f"{type(e).__name__}: {e}",
                    traceback=traceback.format_exc()[-2000:])
        return cell

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    stats = analyze(compiled.as_text())
    terms = roofline_terms(stats)
    model_fl = model_flops_per_step(cfg, shape) / chips  # per device

    live_bytes = int(mem.argument_size_in_bytes + mem.temp_size_in_bytes
                     + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    peak_bytes = int(mem.peak_memory_in_bytes or live_bytes)
    cell.update(
        status="OK",
        compile_s=round(obs_now() - t0, 1),
        bytes_per_device=live_bytes,
        peak_bytes_per_device=peak_bytes,
        fits_hbm=bool(max(live_bytes, peak_bytes) <= HBM_PER_CHIP),
        argument_bytes=int(mem.argument_size_in_bytes),
        temp_bytes=int(mem.temp_size_in_bytes),
        cost_analysis_flops=float(cost.get("flops", 0.0)),
        cost_analysis_bytes=float(cost.get("bytes accessed", 0.0)),
        hlo_dot_flops_per_device=int(stats.dot_flops),
        hlo_mem_bytes_per_device=int(stats.mem_bytes),
        collective_wire_bytes_per_device=int(stats.collective_wire_bytes),
        collectives={k: dataclasses.asdict(v)
                     for k, v in stats.collectives.items()},
        wire_bytes_by_group_size={str(k): v
                                  for k, v in stats.by_group_size.items()},
        mem_by_kind={k: v for k, v in sorted(stats.mem_by_kind.items(),
                                             key=lambda kv: -kv[1])[:12]},
        while_trips=stats.while_trips,
        roofline=terms.to_dict(),
        model_flops_per_device=model_fl,
        useful_flops_ratio=(model_fl / stats.dot_flops
                            if stats.dot_flops else 0.0),
        roofline_fraction=((model_fl / HW.peak_flops) / terms.bound_s
                           if terms.bound_s > 0 else 0.0),
    )
    return cell


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, choices=list(ARCHS))
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every applicable (arch x shape) cell")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced configs (CI-speed sanity pass)")
    ap.add_argument("--out", default="benchmarks/results/dryrun.json")
    ap.add_argument("--label", default="baseline")
    ap.add_argument("--set", action="append", default=[], metavar="K=V",
                    help="RunConfig override, e.g. --set attn_chunk_remat=1")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        if v.lower() in ("true", "false"):
            overrides[k] = v.lower() == "true"
        else:
            try:
                overrides[k] = int(v)
            except ValueError:
                try:
                    overrides[k] = float(v)
                except ValueError:
                    overrides[k] = v
    run = dryrun_runconfig(**overrides) if overrides else None

    cells = []
    if args.all:
        for arch in ARCHS:
            for shape in applicable_shapes(get_config(arch)):
                cells.append((arch, shape.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    out_path = pathlib.Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    results = []
    if out_path.exists():
        results = json.loads(out_path.read_text())

    for arch, shape in cells:
        for mp in meshes:
            print(f"=== {arch} × {shape} × {'2x16x16' if mp else '16x16'}",
                  flush=True)
            cell = run_cell(arch, shape, multi_pod=mp, smoke=args.smoke,
                            run=run, label=args.label)
            # replace any previous entry for the same cell+label
            results = [r for r in results
                       if (r["arch"], r["shape"], r["mesh"], r.get("label"))
                       != (cell["arch"], cell["shape"], cell["mesh"],
                           cell.get("label"))]
            results.append(cell)
            out_path.write_text(json.dumps(results, indent=1))
            status = cell["status"]
            if status == "OK":
                r = cell["roofline"]
                print(f"  OK compile={cell['compile_s']}s "
                      f"mem={cell['bytes_per_device']/2**30:.2f}GiB "
                      f"fits={cell['fits_hbm']} dominant={r['dominant']} "
                      f"terms(c/m/n)={r['compute_s']:.2e}/{r['memory_s']:.2e}/"
                      f"{r['collective_s']:.2e}s "
                      f"roofline_frac={cell['roofline_fraction']:.3f}",
                      flush=True)
            else:
                print(f"  FAIL: {cell['error']}", flush=True)


if __name__ == "__main__":
    main()
