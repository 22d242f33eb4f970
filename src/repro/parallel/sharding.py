"""Logical-axis sharding rules: FSDP over ``data``(+``pod``), TP over ``model``.

Parameters are sharded 2-D (ZeRO-3 style over the data axes *and* tensor-
parallel over ``model``); activations get explicit constraints at the few
points where propagation is ambiguous (attention head layout, logits).

Head-layout fallback: shard the *heads* axis over ``model`` when divisible,
else the *head_dim* axis (legal for every assigned arch: head_dim is a
multiple of 16 whenever n_heads is not), else replicate.
"""
from __future__ import annotations

import re
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

DATA_AXES: Tuple[str, ...] = ("pod", "data")  # combined FSDP/batch axes
TP_AXIS = "model"

# Sharding mode: "2d" = FSDP over data × TP over model (default);
# "zero3" = fold the model axis into FSDP too — no tensor parallelism, no
# per-layer activation all-reduces; params/optimizer shard 256-way and are
# all-gathered layer-by-layer (the ZeRO-3 configuration, §Perf iteration 4).
_MODE = {"mode": "2d"}


def set_sharding_mode(mode: str) -> None:
    assert mode in ("2d", "zero3"), mode
    _MODE["mode"] = mode


def sharding_mode() -> str:
    return _MODE["mode"]


def data_axes() -> Tuple[str, ...]:
    if _MODE["mode"] == "zero3":
        return ("pod", "data", "model")
    return DATA_AXES


def tp_axis():
    return None if _MODE["mode"] == "zero3" else TP_AXIS


def abstract_mesh():
    """The mesh installed by ``launch.mesh.mesh_context``, else None."""
    m = jax.sharding.get_abstract_mesh()
    return m if m.axis_names else None


def mesh_axis_size(name: str) -> int:
    m = abstract_mesh()
    if m is None:
        return 1
    return dict(zip(m.axis_names, m.axis_sizes)).get(name, 1)


def data_axes_in_mesh() -> Tuple[str, ...]:
    m = abstract_mesh()
    if m is None:
        return ()
    return tuple(a for a in DATA_AXES if a in m.axis_names)


def _filter_spec(spec: P) -> Optional[P]:
    """Drop axes not usable in the current mesh; None when no mesh.

    Axes in Manual mode (inside a shard_map body) cannot take sharding
    constraints — they are filtered too, so model code works unchanged in
    both auto-SPMD and explicit-collective (DDP/shard_map) styles.
    """
    m = abstract_mesh()
    if m is None:
        return None
    auto = {n for n, t in zip(m.axis_names, m.axis_types)
            if t == jax.sharding.AxisType.Auto}
    if not auto:
        return None

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in auto)
            return kept if kept else None
        return entry if entry in auto else None

    return P(*(keep(e) for e in spec))


def constrain(x, *spec_entries):
    """with_sharding_constraint that no-ops outside a mesh context."""
    spec = _filter_spec(P(*spec_entries))
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def batch_spec(extra_dims: int = 1) -> P:
    return P(data_axes(), *([None] * extra_dims))


def head_axes(n_heads: int, head_dim: int) -> Tuple[Optional[str], Optional[str]]:
    """(heads_axis, hd_axis) for activation tensors (B, S, H, hd)."""
    if tp_axis() is None:
        return None, None
    tp = mesh_axis_size(TP_AXIS)
    if tp == 1:
        return None, None
    if n_heads % tp == 0:
        return TP_AXIS, None
    if head_dim % tp == 0:
        return None, TP_AXIS
    return None, None


# ---------------------------------------------------------------------------
# Parameter specs (by pytree path)
# ---------------------------------------------------------------------------

_FSDP = DATA_AXES  # shard the "d_model-like" dim over the combined data axes

# leaf-name -> spec for the *unstacked* rank (tiles add a leading None)
_RULES = {
    # (in_dim, out_dim): FSDP on in, TP on out
    r"(wq|wk|wv|w1|w3|w_x|w_gate|w_up|wq_x|router)$": P(_FSDP, TP_AXIS),
    r"(w_r|w_i)$": P(_FSDP, TP_AXIS),
    # (out_dim, d): TP on in, FSDP on out
    r"(wo|w2|w_down)$": P(TP_AXIS, _FSDP),
    # embeddings
    r"tok$": P(TP_AXIS, _FSDP),
    r"lm_head$": P(_FSDP, TP_AXIS),
    r"frontend_proj$": P(_FSDP, TP_AXIS),
    # biases on TP-sharded outputs
    r"(bq|bk|bv)$": P(TP_AXIS),
    # conv taps (W, dr)
    r"conv$": P(None, TP_AXIS),
    # small per-head / per-channel params: replicate
    r"(ln1|ln2|ln_x|norm|final_norm|enc_norm|q_norm|k_norm|lam|b_r|b_i|bf|bi)$": P(),
    r"(wi|wf)$": P(_FSDP, None),        # gate projections (d, n_heads)
    r"(rz|ri|rf|ro)$": P(),             # sLSTM block-diagonal recurrences
}

_MOE_RULES = {
    r"w1$": P(None, _FSDP, TP_AXIS),
    r"w3$": P(None, _FSDP, TP_AXIS),
    r"w2$": P(None, TP_AXIS, _FSDP),
    r"router$": P(_FSDP, None),
}


def _spec_for(path: str, ndim: int) -> P:
    # routed-expert weights are 3-D (E, in, out); the shared-expert MLP under
    # moe/shared/ is a plain dense block and takes the dense rules
    is_routed = "/moe/" in path and "/shared/" not in path
    rules = _MOE_RULES if is_routed else _RULES
    leaf = path
    stacked = path.startswith("tiles/") or path.startswith("enc_tiles/")
    for pat, spec in rules.items():
        if re.search(pat, leaf):
            entries = list(spec)
            if stacked:
                entries = [None] + entries
            # pad/truncate to rank
            while len(entries) < ndim:
                entries.append(None)
            return P(*entries[:ndim])
    # default: replicate
    return P(*([None] * ndim))


def _apply_mode(spec: P) -> P:
    """Rewrite a rule spec for the active sharding mode."""
    if _MODE["mode"] == "2d":
        return spec
    out = []
    for e in spec:
        if e == TP_AXIS:
            out.append(None)           # no tensor parallelism in zero3
        elif isinstance(e, (tuple, list)) and tuple(e) == tuple(DATA_AXES):
            out.append(data_axes())    # FSDP over every axis
        else:
            out.append(e)
    return P(*out)


def param_specs(params) -> "jax.tree_util.PyTreeDef":
    """Mirror the param pytree with PartitionSpecs."""

    def walk(tree, prefix):
        if isinstance(tree, dict):
            return {k: walk(v, f"{prefix}/{k}" if prefix else k)
                    for k, v in tree.items()}
        return _apply_mode(_spec_for(prefix, np.ndim(tree)))

    return walk(params, "")


# ---------------------------------------------------------------------------
# Fleet lane partitioning (ASC-Hook fleet engine)
# ---------------------------------------------------------------------------

LANE_AXIS = "lanes"


def fleet_mesh(devices=None):
    """1-D mesh over the local devices for lane-parallel fleet execution."""
    devices = list(devices if devices is not None else jax.devices())
    return jax.sharding.Mesh(np.array(devices), (LANE_AXIS,))


def lane_sharding(mesh, extra_dims: int = 0):
    """NamedSharding that splits the leading (lane) axis over the mesh."""
    return jax.sharding.NamedSharding(
        mesh, P(LANE_AXIS, *([None] * extra_dims)))


def _check_divides(n_lanes: int, ndev: int) -> None:
    if ndev > 1 and n_lanes % ndev:
        raise ValueError(
            f"a {n_lanes}-lane fleet cannot be split evenly over {ndev} "
            f"devices: use a lane count that is a multiple of {ndev}")


def fleet_divisor(n_lanes: int, mesh=None) -> int:
    """The lane-count divisor a partitioned fleet must respect: the device
    count (1 on a single device).  Feed it to
    ``fleet.compact_ladder(divisor=...)`` for per-shard bucket ladders —
    every rung then keeps an equal lane slice per device.  Raises
    ``ValueError`` when several devices do not divide ``n_lanes``."""
    mesh = mesh or fleet_mesh()
    ndev = int(np.prod(mesh.devices.shape))
    _check_divides(n_lanes, ndev)
    return ndev


def shard_fleet(imgs, img_ids, states, mesh=None, trace=None):
    """Partition a fleet across devices: states/ids split along lanes, the
    deduplicated decode tables replicated.  ``trace`` (a fleet
    ``TraceState``) is lane-leading like the states and splits the same way.

    No-op (returns inputs unchanged) on a single device.  Raises
    ``ValueError`` when several devices do not divide the lane count,
    rather than running the whole fleet on every device.  Returns a
    4-tuple iff ``trace`` was passed.
    """
    mesh = mesh or fleet_mesh()
    ndev = int(np.prod(mesh.devices.shape))
    n_lanes = int(states.pc.shape[0])
    _check_divides(n_lanes, ndev)
    if ndev == 1:
        return ((imgs, img_ids, states) if trace is None
                else (imgs, img_ids, states, trace))

    replicate = jax.sharding.NamedSharding(mesh, P())
    imgs = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, replicate), imgs)
    img_ids = jax.device_put(img_ids, lane_sharding(mesh))
    by_lane = lambda x: jax.device_put(x, lane_sharding(mesh, x.ndim - 1))
    states = jax.tree_util.tree_map(by_lane, states)
    if trace is None:
        return imgs, img_ids, states
    return imgs, img_ids, states, jax.tree_util.tree_map(by_lane, trace)


def cache_spec(cfg, cache) -> object:
    """Decode-cache specs: batch over data axes; heads or head_dim over TP."""
    h_ax, hd_ax = head_axes(cfg.n_kv_heads, cfg.hd)

    def walk(tree, prefix):
        if isinstance(tree, dict):
            return {k: walk(v, f"{prefix}/{k}" if prefix else k) for k, v in tree.items()}
        nd = np.ndim(tree)
        stacked = prefix.startswith("tiles/") or prefix.startswith("tail/")
        lead = [None] if prefix.startswith("tiles/") else []
        body = nd - len(lead)
        name = prefix.rsplit("/", 1)[-1]
        if name in ("k", "v", "xk", "xv"):        # (B, S, Hkv, hd)
            return P(*lead, data_axes(), None, h_ax, hd_ax)
        if name == "slot_pos":                     # (W,)
            return P(*lead, None)
        if name == "C":                            # (B, H, dh, dh)
            return P(*lead, data_axes(), None, None, None)
        if name in ("n", "conv"):                  # (B, H, dh) / (B, W-1, dr)
            return P(*lead, data_axes(), *([None] * (body - 1)))
        if name in ("h", "c", "m"):                # (B, d)
            return P(*lead, data_axes(), *([None] * (body - 1)))
        if name == "pos":
            return P()
        return P(*([None] * nd))

    return walk(cache, "")
