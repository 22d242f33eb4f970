"""Train / serve step builders.

Two distribution styles, matching DESIGN.md §2.2:

* ``make_train_step``  — pure pjit/auto-SPMD: shardings come from param
  specs, the partitioner inserts all comm (the production path; this is what
  the multi-pod dry-run lowers).
* ``make_ddp_train_step`` — shard_map over the data axes with an *explicit*
  gradient psum.  Functionally identical; exists so the collective boundary
  is visible to the ASC-Hook layer (tracing, compression, schedule rewrite)
  — and it is what the hook benchmarks run.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, RunConfig
from repro.models import lm
from repro.optim import compress as compress_lib
from repro.optim.adamw import adamw_update, init_opt_state

Pytree = Any


def init_train_state(cfg: ModelConfig, run: RunConfig, key) -> Dict[str, Any]:
    params = lm.init_params(cfg, key)
    state = {"params": params, "opt": init_opt_state(params)}
    if run.grad_compression in ("int8_ef", "bf16_ef"):
        state["ef"] = compress_lib.init_ef_state(params)
    return state


def make_train_step(cfg: ModelConfig, run: RunConfig) -> Callable:
    """Auto-SPMD step: state/batch shardings drive the partitioner."""

    def train_step(state: Dict[str, Any], batch: Dict[str, Any]):
        def loss_of(p):
            if run.param_wire_bf16:
                # cast before use: the partitioner's FSDP all-gathers (and
                # their transposed grad reduce-scatters) then carry bf16
                p = jax.tree_util.tree_map(
                    lambda x: x.astype(jnp.bfloat16)
                    if x.dtype == jnp.float32 else x, p)
            return lm.loss_fn(cfg, run, p, batch)

        if run.microbatch > 1:
            # gradient accumulation: scan over microbatches, sum grads
            mb = run.microbatch

            def split(x):
                b = x.shape[0]
                assert b % mb == 0, (b, mb)
                return x.reshape(mb, b // mb, *x.shape[1:])

            microbatches = jax.tree_util.tree_map(split, batch)

            def acc_body(carry, mbatch):
                g_acc, m_acc = carry

                def loss_mb(p):
                    if run.param_wire_bf16:
                        p = jax.tree_util.tree_map(
                            lambda x: x.astype(jnp.bfloat16)
                            if x.dtype == jnp.float32 else x, p)
                    return lm.loss_fn(cfg, run, p, mbatch)

                (_, metrics), g = jax.value_and_grad(
                    loss_mb, has_aux=True)(state["params"])
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                m_acc = jax.tree_util.tree_map(jnp.add, m_acc, metrics)
                return (g_acc, m_acc), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state["params"])
            m0 = {k: jnp.zeros((), jnp.float32)
                  for k in ("ce", "z_loss", "aux", "loss")}
            (grads, metrics), _ = jax.lax.scan(acc_body, (g0, m0), microbatches)
            grads = jax.tree_util.tree_map(lambda g: g / mb, grads)
            metrics = jax.tree_util.tree_map(lambda m: m / mb, metrics)
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_of, has_aux=True)(state["params"])
        new_state = dict(state)
        if "ef" in state:
            codec = "int8" if run.grad_compression == "int8_ef" else "bf16"
            grads, new_state["ef"] = compress_lib.compress_grads(
                grads, state["ef"], codec)
        params, opt, opt_metrics = adamw_update(
            state["params"], grads, state["opt"], run)
        new_state.update(params=params, opt=opt)
        return new_state, {**metrics, **opt_metrics}

    return train_step


def make_ddp_train_step(cfg: ModelConfig, run: RunConfig, mesh,
                        data_axis: str = "data") -> Callable:
    """shard_map DP step with an explicit (hookable) gradient psum."""
    n_data = dict(zip(mesh.axis_names, mesh.devices.shape))[data_axis]

    def local_step(state, batch):
        def loss_of(p):
            return lm.loss_fn(cfg, run, p, batch)

        (loss, metrics), grads = jax.value_and_grad(
            loss_of, has_aux=True)(state["params"])
        # the explicit collective boundary — the svc of this program
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g, data_axis) / n_data, grads)
        metrics = jax.tree_util.tree_map(
            lambda m: jax.lax.psum(m, data_axis) / n_data, metrics)
        new_state = dict(state)
        if "ef" in state:
            codec = "int8" if run.grad_compression == "int8_ef" else "bf16"
            grads, new_state["ef"] = compress_lib.compress_grads(
                grads, state["ef"], codec)
        params, opt, opt_metrics = adamw_update(
            state["params"], grads, state["opt"], run)
        new_state.update(params=params, opt=opt)
        return new_state, {**metrics, **opt_metrics}

    state_specs = P()  # replicated params/opt (pure DP)
    batch_specs = P(data_axis)
    from repro.launch.mesh import shard_map_fn
    sm = shard_map_fn()
    return sm(local_step, mesh=mesh, in_specs=(state_specs, batch_specs),
              out_specs=(state_specs, P()), check_vma=False)


def make_serve_steps(cfg: ModelConfig, run: RunConfig):
    """(prefill_fn, decode_fn) for the serving engine and the dry-run."""

    def prefill_step(params, batch):
        return lm.prefill(cfg, run, params, batch)

    def decode_step(params, cache, tokens, pos):
        return lm.decode_step(cfg, run, params, cache, tokens, pos)

    return prefill_step, decode_step
