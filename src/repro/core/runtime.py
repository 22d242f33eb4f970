"""The ASC-Hook runtime: the LD_PRELOAD-entry equivalent (paper §3.4).

``prepare()`` plays the role of the constructor that runs before ``main``:
it walks the process image (procfs analogue), scans, classifies and rewrites
svc sites, installs the trampolines and the hook library, and registers the
signal handler when any R3 site exists.  It also implements the comparison
mechanisms of the paper's evaluation: pure signal interception, ptrace, and
LD_PRELOAD function interposition.
"""
from __future__ import annotations

import dataclasses
import enum
import hashlib
import os
import pathlib
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import fleet as F
from . import layout as L
from . import machine as M
from .hookcfg import HookConfig
from .image import HOOK_BASE, Image, build_process
from .isa import Asm
from .rewriter import RewriteReport, rewrite_all_to_signal, rewrite_image
from .trampoline import build_hook_library, build_signal_handler


class Mechanism(enum.Enum):
    NONE = "none"
    LD_PRELOAD = "ld_preload"
    SIGNAL = "signal"
    PTRACE = "ptrace"
    ASC = "asc"


@dataclasses.dataclass
class PreparedProcess:
    image: Image
    decoded: M.DecodedImage
    entry: int
    sig_handler: int
    mechanism: Mechanism
    report: Optional[RewriteReport]
    virtualize: bool
    cfg: Optional[HookConfig] = None


AppBuilder = Callable[[], Asm]


def prepare(app: Asm, mechanism: Mechanism, *,
            virtualize: bool = False,
            cfg: Optional[HookConfig] = None,
            extra: Optional[Dict[str, Asm]] = None) -> PreparedProcess:
    cfg = cfg or HookConfig()
    preload = virtualize if mechanism is Mechanism.LD_PRELOAD else None
    image = build_process(app, extra=extra, preload_virt=preload)

    report = None
    sig_handler = 0
    if mechanism in (Mechanism.ASC, Mechanism.SIGNAL):
        # hook library in its own namespace (dlmopen analogue, not rewritten)
        hook = build_hook_library(virtualize_getpid=virtualize)
        image.add_asm("hooklib.so", hook, rewrite=False)
        hook_entry = image.sym("hooklib.so:hook_entry")
        if mechanism is Mechanism.ASC:
            report = rewrite_image(image, hook_entry, cfg)
            needs_handler = report.needs_signal
        else:
            report = rewrite_all_to_signal(image, cfg)
            needs_handler = True
        if needs_handler:
            handler = build_signal_handler()
            image.add_asm("sighandler", handler, rewrite=False,
                          symbols={"hook_entry": hook_entry})
            sig_handler = image.sym("sighandler:sig_handler")

    decoded = M.decode_image(image.words)
    return PreparedProcess(
        image=image, decoded=decoded, entry=image.sym("app:main"),
        sig_handler=sig_handler, mechanism=mechanism, report=report,
        virtualize=virtualize, cfg=cfg)


def initial_state(pp: PreparedProcess, *, fuel: int = 2_000_000,
                  regs: Optional[Dict[int, int]] = None) -> M.MachineState:
    """The machine state ``run_prepared`` starts from (also the per-lane
    initial state of a fleet).

    ``regs`` seeds registers at entry ({index: value}) — how parameterised
    workloads (``programs.*_param``) receive their arguments, letting many
    fleet lanes share one image (argv for the simulated process).
    """
    st = M.make_state(pp.entry, fuel=fuel)
    if regs:
        r = st.regs
        for i, v in regs.items():
            assert 0 <= i <= 30, i
            r = r.at[i].set(jnp.int64(v))
        st = st._replace(regs=r)
    return st._replace(
        sig_handler=jnp.int64(pp.sig_handler),
        ptrace=jnp.int64(1 if pp.mechanism is Mechanism.PTRACE else 0),
        virt_getpid=jnp.int64(
            1 if (pp.mechanism is Mechanism.PTRACE and pp.virtualize) else 0),
        k_enabled=jnp.int64(
            1 if (pp.cfg is None or pp.cfg.emul_enabled) else 0),
    )


def run_prepared(pp: PreparedProcess, *, fuel: int = 2_000_000,
                 regs: Optional[Dict[int, int]] = None) -> M.MachineState:
    return M.run_image(pp.decoded, initial_state(pp, fuel=fuel, regs=regs))


def fleet_trace(pps: Sequence[PreparedProcess], *,
                cap: Optional[int] = None) -> F.TraceState:
    """The trace carry for a fleet of prepared processes: one ring per lane
    plus that lane's policy tables compiled from its ``HookConfig.policy``
    (empty policies compile to all-ALLOW — architecturally invisible).

    ``cap`` defaults to the largest ``trace_cap`` among the configs.
    """
    from repro.trace import recorder  # local: repro.trace depends on core
    if cap is None:
        caps = [pp.cfg.trace_cap for pp in pps if pp.cfg is not None]
        cap = max(caps) if caps else F.DEFAULT_TRACE_CAP
    pols = [pp.cfg.policy if pp.cfg is not None and pp.cfg.policy else None
            for pp in pps]
    return recorder.make_trace_state(len(pps), cap, policies=pols)


# <repo root>/.jax_cache: a fixed path, because the cache directory is part
# of what a later process must name to find the entries again
_REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that directory
    itself and nothing is overridden; otherwise the cache goes to the fixed
    ``<repo root>/.jax_cache``.  Returns the directory in use.  Call it from
    a program's ``main``, never at import: tests keep the cache off.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_REPO_CACHE_DIR))
    return str(_REPO_CACHE_DIR)


def _image_digest(pp: PreparedProcess) -> bytes:
    return hashlib.sha1(
        np.ascontiguousarray(pp.image.words).tobytes()).digest()


class ImageTableFull(RuntimeError):
    """Every row of a :class:`FleetImageTable` is live: admission has to
    wait until a running lane releases one."""


class FleetImageTable:
    """A fixed-capacity, content-deduplicated stack of packed decode tables
    with **incremental admission and eviction** — the serving-side extension
    of :func:`pack_fleet`'s dedup.

    The packed stack keeps a constant shape ``[capacity, CODE_WORDS]``, so a
    new request's image joins the table as one in-place row write
    (:func:`fleet.set_image_row`, donated buffers) and every jitted fleet
    entry point keeps its compilation cache — unchanged lanes are never
    recompiled.  Rows are refcounted; released rows keep their digest cached
    until the slot is actually reused (admission of a recently-seen binary
    is then free).
    """

    def __init__(self, capacity: int):
        assert capacity >= 1
        self.capacity = capacity
        self._images = F.FleetImages(
            packed=jnp.zeros((capacity, L.CODE_WORDS), jnp.int64),
            imm=jnp.zeros((capacity, L.CODE_WORDS), jnp.int64))
        self._row_of: Dict[bytes, int] = {}
        self._digest_of: List[Optional[bytes]] = [None] * capacity
        self._refs: List[int] = [0] * capacity
        self._free: List[int] = list(range(capacity))  # FIFO: oldest first
        self.admissions = 0      # row writes actually performed
        self.dedup_hits = 0      # admissions served from a live/cached row

    @property
    def images(self) -> F.FleetImages:
        return self._images

    def live_rows(self) -> int:
        return sum(1 for r in self._refs if r > 0)

    def admit(self, pp: PreparedProcess) -> int:
        """Return the row holding ``pp``'s decode table, admitting it (one
        in-place row write) if no live or cached row matches."""
        d = _image_digest(pp)
        row = self._row_of.get(d)
        if row is not None:
            if self._refs[row] == 0:     # cache hit on a released row
                self._free.remove(row)
            self._refs[row] += 1
            self.dedup_hits += 1
            return row
        if not self._free:
            raise ImageTableFull(
                f"FleetImageTable full ({self.capacity} rows all live); "
                f"size the table to pool width + expected binary diversity")
        row = self._free[0]
        # the row leaves the free list only once the device write landed:
        # a device error propagates and leaks nothing
        self._images = F.set_image_row(self._images, row, pp.decoded)
        self._free.pop(0)
        old = self._digest_of[row]
        if old is not None:              # evict the cached (dead) digest
            del self._row_of[old]
        self._row_of[d] = row
        self._digest_of[row] = d
        self._refs[row] = 1
        self.admissions += 1
        return row

    def refs(self, row: int) -> int:
        return self._refs[row]

    def release(self, row: int) -> None:
        assert self._refs[row] > 0, f"row {row} double-released"
        self._refs[row] -= 1
        if self._refs[row] == 0:
            self._free.append(row)       # digest stays cached until reuse


def pack_fleet(pps: Sequence[PreparedProcess], *,
               fuel: int = 2_000_000,
               regs: Optional[Sequence[Optional[Dict[int, int]]]] = None,
               table: Optional[FleetImageTable] = None,
               trace: Optional[bool] = None,
               ):
    """Stack prepared processes into (images, img_ids, states) for
    :func:`repro.core.fleet.run_fleet`.

    Decode tables are deduplicated by image content, so a census sweeping
    iteration counts or mechanisms over shared binaries ships each distinct
    image to the device once.  With ``table`` (a :class:`FleetImageTable`)
    the images are *admitted incrementally* into that fixed-capacity stack
    instead — the continuous-batching entry path, where later admissions
    must not reshape (and so recompile) the fleet.

    ``trace=True`` appends a fourth element: the
    :class:`repro.core.fleet.TraceState` carry from :func:`fleet_trace`,
    ready to pass to ``run_fleet(..., trace=...)``.  The return arity
    depends ONLY on this explicit argument (never on the configs), so
    existing 3-way unpack call sites can't break at a distance;
    ``HookConfig.trace_enabled`` is the *serving* default
    (:class:`repro.serve.fleet_server.FleetServer`), which returns traces
    via ``FleetResult`` instead of a tuple.
    """
    ids = np.zeros(len(pps), np.int32)
    if table is not None:
        for i, pp in enumerate(pps):
            ids[i] = table.admit(pp)
        imgs = table.images
    else:
        digests: Dict[bytes, int] = {}
        uniq: List[M.DecodedImage] = []
        for i, pp in enumerate(pps):
            d = _image_digest(pp)
            if d not in digests:
                digests[d] = len(uniq)
                uniq.append(pp.decoded)
            ids[i] = digests[d]
        imgs = F.pack_images(F.stack_images(uniq))
    if regs is None:
        regs = [None] * len(pps)
    states = F.stack_states([initial_state(pp, fuel=fuel, regs=rg)
                             for pp, rg in zip(pps, regs)])
    if not trace:
        return imgs, ids, states
    return imgs, ids, states, fleet_trace(pps)


def update_fleet_policy(trace: F.TraceState, lanes: Sequence[int],
                        rules: Sequence) -> F.TraceState:
    """Compile per-lane rule lists and swap them into the trace carry's
    policy rows in place (:func:`repro.core.fleet.update_policy_rows`) —
    the drain-mode counterpart of ``FleetServer.update_policy``.  ``rules``
    is one ``PolicyRule`` list per lane (``None`` = all-ALLOW); rules are
    validated up front (:func:`repro.trace.policy.validate_rules`)."""
    from repro.trace import policy as TP  # local: repro.trace depends on core
    rows = [TP.compile_policy(r) if r is not None else None for r in rules]
    return F.update_policy_rows(trace, lanes, rows)


def run_fleet_prepared(pps: Sequence[PreparedProcess], *,
                       fuel: int = 2_000_000,
                       chunk: Optional[int] = None,
                       regs: Optional[Sequence[Optional[Dict[int, int]]]] = None,
                       shard: bool = False,
                       trace: Optional[bool] = None,
                       compact: Optional[bool] = None,
                       compact_stats: Optional[dict] = None,
                       policy_overrides: Optional[Dict[int, Sequence]] = None,
                       engine: Optional[str] = None):
    """Run every prepared process to completion in ONE device dispatch.

    ``chunk`` defaults to the first process's ``HookConfig.fleet_chunk``.
    Lane i of the returned batched state is bit-identical to
    ``run_prepared(pps[i], fuel=fuel, regs=regs[i])``.

    With ``trace=True`` returns ``(states, trace_state)`` — the syscall
    rings and policy verdicts of the whole fleet, captured in the same
    single dispatch.  Arity depends only on the explicit argument (see
    :func:`pack_fleet`).

    ``compact`` switches to the occupancy-aware driver
    (:func:`repro.core.fleet.run_fleet_compact`): live lanes are compacted
    into narrowing bucket widths as the fleet drains, with the ladder
    parameters (``compact_min_bucket`` / ``compact_hysteresis``) taken from
    the first process's ``HookConfig``.  ``None`` defers to that config's
    ``compact_enabled``.  Results — and the return arity — are unchanged:
    compaction is bit-identical and lane-ordered.  ``compact_stats`` (a
    dict, filled in place) receives the occupancy ledger of a compacted
    run.

    ``policy_overrides`` (lane -> ``PolicyRule`` list; requires
    ``trace=True``) swaps those lanes' policy-table rows after packing and
    before the dispatch, through the same donated scatter the serving
    layer's mid-flight ``update_policy`` uses
    (:func:`repro.core.fleet.update_policy_rows`) — every other lane's
    carry is untouched, so overrides are bit-invisible to bystanders.

    ``engine`` selects the chunk dispatcher (``"xla"`` or ``"pallas"``,
    bit-identical results — see :func:`repro.core.fleet.run_fleet`);
    ``None`` defers to the first process's ``HookConfig.fleet_engine``.
    """
    packed = pack_fleet(pps, fuel=fuel, regs=regs, trace=trace)
    if policy_overrides:
        if len(packed) != 4:
            raise ValueError("policy_overrides require trace=True")
        lanes = sorted(policy_overrides)
        bad = [ln for ln in lanes if not 0 <= ln < len(pps)]
        if bad:
            # the scatter's mode="drop" is a padding convention for
            # internal callers — here a stray lane would silently leave
            # the fleet unenforced
            raise ValueError(
                f"policy_overrides lanes {bad} out of range for "
                f"{len(pps)} lanes")
        packed = packed[:3] + (update_fleet_policy(
            packed[3], lanes, [policy_overrides[ln] for ln in lanes]),)
    cfg = next((pp.cfg for pp in pps if pp.cfg is not None), None)
    if chunk is None:
        chunk = cfg.fleet_chunk if cfg is not None else F.DEFAULT_CHUNK
    if compact is None:
        compact = cfg.compact_enabled if cfg is not None else False
    if engine is None:
        engine = cfg.fleet_engine if cfg is not None else "xla"
    ts = packed[3] if len(packed) == 4 else None
    imgs, ids, states = packed[:3]
    if compact:
        ccfg = cfg or HookConfig()
        out = F.run_fleet_compact(
            imgs, states, ids, chunk=chunk, shard=shard, trace=ts,
            min_bucket=ccfg.compact_min_bucket,
            hysteresis=ccfg.compact_hysteresis, stats=compact_stats,
            engine=engine)
        return out
    if ts is None:
        return F.run_fleet(imgs, states, ids, chunk=chunk, shard=shard,
                           engine=engine)
    return F.run_fleet(imgs, states, ids, chunk=chunk, shard=shard, trace=ts,
                       engine=engine)


def precompile_compact(pps: Sequence[PreparedProcess], *,
                       chunk: Optional[int] = None,
                       min_bucket: Optional[int] = None,
                       interval: Optional[int] = None,
                       trace: Optional[bool] = None,
                       shard: bool = False) -> List[int]:
    """Warm every rung of the compaction ladder a
    ``run_fleet_prepared(compact=True)`` over ``pps`` will visit, so the
    timed (or serving) run never pays an XLA compile mid-flight.  Defaults
    mirror :func:`run_fleet_prepared`: chunk / min_bucket from the first
    process's config, ``interval = 8 * chunk``.  Returns the ladder."""
    cfg = next((pp.cfg for pp in pps if pp.cfg is not None), None) \
        or HookConfig()
    chunk = cfg.fleet_chunk if chunk is None else chunk
    min_bucket = cfg.compact_min_bucket if min_bucket is None else min_bucket
    divisor = 1
    if shard:
        from repro.parallel.sharding import fleet_divisor
        divisor = fleet_divisor(len(pps))
    ladder = F.compact_ladder(len(pps), min_bucket, divisor=divisor)
    imgs = pack_fleet(pps)[0]
    cap = None
    if trace:
        caps = [pp.cfg.trace_cap for pp in pps if pp.cfg is not None]
        cap = max(caps) if caps else F.DEFAULT_TRACE_CAP
    F.precompile_ladder(imgs, ladder, chunk=chunk, interval=interval,
                        trace_cap=cap, shard=shard)
    return ladder


def hook_invocations(state: M.MachineState) -> int:
    """Total hook executions across mechanisms (COUNTER word + ptrace count).

    One bulk readback instead of one device sync per field.
    """
    if state.mem.ndim == 2:  # batched fleet state: sum over lanes
        return int(F.fleet_counters(state).sum())
    counter = int(M.mem_read_block(state, L.COUNTER, 1)[0])
    return counter + int(state.hook_count)
