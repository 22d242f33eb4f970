"""CPU tests of the chip benchmark's parts: generators, the trace
reduction, the bytes function, and BENCHMARK.json against the files the
harness finds by name."""
import json
import os
import re
import statistics
import subprocess
import sys

import pytest

import _chipbench_path as P  # noqa: F401  (puts the benchmark on sys.path)

import harness  # noqa: E402
import roofline  # noqa: E402
import trace_reduce as T  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return harness.benchmark()


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(P.HERE, "data", "recorded_trace.json")) as f:
        return json.load(f)


# -- generators ----------------------------------------------------------------

def _mix(name):
    return harness.load_json(P.BENCH, "traffic", name + ".json")


def _config(name):
    return harness.load_json(P.BENCH, "configs", name + ".json")


def test_open_poisson_is_deterministic_per_seed():
    gen = harness.load_module("traffic", "open_poisson.py").generate
    mix, cfg = _mix("served"), _config("io_monitor")
    seed = 2**31 + 12345
    a = gen(mix, cfg, seed, 40.0)
    assert a == gen(mix, cfg, seed, 40.0)
    b = gen(mix, cfg, seed + 1, 40.0)
    assert a != b
    # every seed: the same requests and the same gaps, in another order
    assert sorted(r[1:] for r in a) == sorted(r[1:] for r in b)
    # the gaps, the last one (to the window's end) included
    gaps = lambda rs: sorted(y - x for x, y in zip(
        [r[0] for r in rs], [r[0] for r in rs[1:]] + [40.0]))
    assert len(a) == round(mix["rate_per_s"] * 40.0)
    assert all(0.0 <= r[0] < 40.0 for r in a)
    assert [r[0] for r in a] == sorted(r[0] for r in a)
    assert gaps(a) == pytest.approx(gaps(b), abs=1e-9)
    assert statistics.median(gaps(a)) == pytest.approx(
        statistics.median(gaps(b)), rel=1e-6)
    lo, hi = cfg["process_iterations"]
    assert {r[3] for r in a} == set(range(lo, hi + 1))
    assert {r[2] for r in a} == set(cfg["workloads"])


def test_open_poisson_blocks_hold_the_same_work():
    gen = harness.load_module("traffic", "open_poisson.py").generate
    mix, cfg = _mix("served"), _config("io_monitor")
    mk, mg, seconds = mix["kind_block"], mix["gap_block"], 51.0
    lo, hi = cfg["process_iterations"]
    kinds = sorted((mc, w, it) for mc in cfg["mechanisms"]
                   for w in cfg["workloads"] for it in range(lo, hi + 1))
    assert mk == len(kinds)

    def spans(a):
        starts = [a[b][0] for b in range(0, len(a), mg)] + [seconds]
        return [y - x for x, y in zip(starts, starts[1:])]

    for seed in (2**31 + 1, 2**40 + 2):
        a = gen(mix, cfg, seed, seconds)
        blocks = [a[b:b + mk] for b in range(0, len(a) - mk + 1, mk)]
        assert len(blocks) == len(a) // mk
        # one request of each kind in every block
        assert all(sorted(r[1:] for r in blk) == kinds for blk in blocks)
        # every gap block spans about the same time: one gap per stratum
        # (unstratified, the widest of 68 blocks spans 5 to 14 times the
        # narrowest)
        assert max(spans(a)) < 3 * min(spans(a))


class _SlowServer:
    """Publishes each request one ``step()`` after its submission; every
    step takes ``step_s``, so the window's last one overruns its end."""

    def __init__(self, step_s):
        from types import SimpleNamespace
        self.step_s, self._queue, self._next = step_s, [], 0
        self.ladder = [8]
        self.cfg = SimpleNamespace(compact_hysteresis=0.125)

    def precompile_ladder(self):
        return self.ladder

    def submit(self, pp, regs):
        self._next += 1
        self._queue.append(self._next)
        return self._next

    def step(self):
        import time
        from types import SimpleNamespace
        time.sleep(self.step_s)
        out, self._queue = self._queue, []
        return [SimpleNamespace(rid=r) for r in out]


class _Harness:
    def __init__(self, mix, config, seed, seconds, srv):
        import contextlib
        import itertools
        self.mix, self.config, self.seed, self.seconds = (
            mix, config, seed, seconds)
        self.srv = srv
        self.pps = {k: None for k in itertools.product(
            config["mechanisms"], config["workloads"])}
        self.span = lambda name: contextlib.nullcontext()
        self.maybe_stop_trace = lambda: None

    def window_begins(self):
        import time
        self.t_window = time.perf_counter()

    def window_ends(self):
        import time
        self.t_end = time.perf_counter()

    def drained(self):
        pass


def test_open_poisson_offers_every_request_due_in_the_window():
    mod = harness.load_module("traffic", "open_poisson.py")
    mix = dict(_mix("served"), rate_per_s=20.0)
    cfg, seed, seconds = _config("io_monitor"), 2**31 + 5, 1.0
    h = _Harness(mix, cfg, seed, seconds, _SlowServer(step_s=0.3))
    out = mod.drive(h)
    assert h.t_end - h.t_window > seconds          # the last step overran
    assert out["attempted"] == len(mod.generate(mix, cfg, seed, seconds))
    assert len(out["latencies_ms"]) == out["attempted"]
    assert min(out["latencies_ms"]) > 0


def test_closed_batch_is_deterministic_per_seed():
    gen = harness.load_module("traffic", "closed_batch.py").generate
    mix, cfg = _mix("batch"), _config("getpid_hook")
    seed = 2**33 + 7
    a = gen(mix, cfg, seed, cfg["pool"])
    assert a == gen(mix, cfg, seed, cfg["pool"])
    b = gen(mix, cfg, seed + 1, cfg["pool"])
    assert a != b
    assert len(a) == cfg["pool"]
    per = cfg["pool"] // len(cfg["mechanisms"])
    for lanes in (a, b):
        counts = {m: sum(1 for x in lanes if x[0] == m)
                  for m in cfg["mechanisms"]}
        assert set(counts.values()) == {per}
        assert all(mix["x19_min"] <= x[2] < 2 * mix["x19_min"]
                   for x in lanes)


# -- trace reduction -------------------------------------------------------------

def test_merge_and_clip():
    assert T.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert T.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


def test_reduction_on_recorded_trace(recorded):
    ev = recorded
    win = T.window(ev)
    assert win is not None and win[1] > win[0]
    dev = ev["devices"][sorted(ev["devices"])[0]]
    # busy union by hand: sweep the module events
    iv = sorted((s, s + d) for _, s, d in dev["modules"])
    union, cur = 0.0, None
    for s, e in iv:
        s, e = max(s, win[0]), min(e, win[1])
        if e <= s:
            continue
        if cur and s <= cur[1]:
            cur[1] = max(cur[1], e)
        else:
            if cur:
                union += cur[1] - cur[0]
            cur = [s, e]
    if cur:
        union += cur[1] - cur[0]
    busy = T.busy_seconds(ev, win)
    assert busy == pytest.approx(union / 1e9)
    assert 0.0 < busy <= (win[1] - win[0]) / 1e9
    span = T.span_seconds(ev, win)
    assert 0.0 < span <= busy + 1e-12
    ops = T.top_ops(ev)
    assert 0 < len(ops) <= T.TOP
    assert [o[1] for o in ops] == sorted((o[1] for o in ops), reverse=True)
    gaps = T.idle_gaps(ev, win)
    assert 0 < len(gaps) <= T.TOP
    idle = (win[1] - win[0]) / 1e9 - busy
    assert sum(g[1] for g in gaps) <= idle + 1e-9
    assert all(g[1] > 0 for g in gaps)


def test_reduction_on_a_made_up_trace():
    ev = {"host": [["bench.window", 0, 100], ["bench.step", 10, 30],
                   ["bench.sleep", 60, 30]],
          "devices": {"/device:TPU:0": {
              "modules": [["jit__unknown(1)", 20, 20],
                          ["jit_flip(2)", 35, 10],
                          ["jit__unknown(3)", 95, 20]],
              "ops": [["fusion.1", 30.0, 3], ["copy", 5.0, 1]]}}}
    win = T.window(ev)
    assert win == (0, 100)
    assert T.busy_seconds(ev, win) == pytest.approx(30e-9)   # 20-45, 95-100
    assert T.span_seconds(ev, win) == pytest.approx(25e-9)
    gaps = T.idle_gaps(ev, win)
    assert gaps[0] == ["sleep", pytest.approx(50e-9)]        # 45-95
    assert gaps[1] == ["step", pytest.approx(20e-9)]         # 0-20
    assert T.top_ops(ev)[0] == ["fusion.1", pytest.approx(30e-9)]


# -- bytes function --------------------------------------------------------------

def test_bytes_function():
    shapes = {"regs": (31,), "pc": (), "mem": (32768,), "k_fd_ofd": (16,),
              "k_ino_data": (4096,), "k_ino_size": (8,)}
    assert roofline.state_bytes(shapes) == 8 * (31 + 1 + 16 + 8)
    assert roofline.bytes_per_lane_step(shapes) == 2 * 8 * 56 + 4
    assert roofline.span_bytes(shapes, 10, data_bytes=16) == 10 * 900 + 16
    assert roofline.roofline_share(819e9, 2.0, 819e9) == pytest.approx(0.5)


def test_bytes_function_on_the_program_state():
    import jax
    from repro.core import fleet as F
    tree = jax.eval_shape(lambda: F.make_halted_states(2))
    shapes = {f: tuple(getattr(tree, f).shape[1:]) for f in tree._fields}
    # 31 registers, 20 scalars, the guest kernel's gate and rng, six fd
    # tables of 16 and three inode tables of 8
    words = 31 + 20 + 2 + 6 * 16 + 3 * 8
    assert roofline.state_bytes(shapes) == 8 * words
    assert roofline.bytes_per_lane_step(shapes) == 16 * words + 4


# -- BENCHMARK.json against the files ----------------------------------------------

def test_names_and_units(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names)), group
        for n in names:
            assert NAME.match(n), n
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    for c in bench["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert 0 < len(c["source"]) <= 200
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert 1 <= bench["run_seconds"] <= 51


def test_everything_named_is_found(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    for c in bench["configs"]:
        assert c["file"].startswith(bench["paths"][0] + "/")
        assert os.path.isfile(os.path.join(P.ROOT, c["file"]))
        assert _config(c["name"])["name"] == c["name"]
    kinds = set()
    for w in bench["workloads"]:
        cell, config, mix = harness.cell_parts(bench, w["name"])
        kinds.add(mix["kind"])
        for m in config["mechanisms"]:
            assert m in ("ASC", "SIGNAL", "PTRACE", "LD_PRELOAD")
        reported = {m["name"] for m in harness.cell_metrics(
            bench, w["name"], False)}
        assert "setup_s" in reported and len(reported) >= 2
        assert harness.cell_metrics(bench, w["name"], True)
    for k in kinds:
        mod = harness.load_module("traffic", k + ".py")
        assert callable(mod.generate) and callable(mod.drive)
        assert callable(mod.check)
    for m in bench["end_to_end"] + bench["per_layer"]:
        mod = harness.load_module("metrics", m["name"] + ".py")
        assert mod.UNIT == m["unit"] and mod.SOURCE == m["source"]
        assert callable(mod.reduce)
        if "layer" in m:
            assert mod.LAYER == m["layer"] and mod.MOVES == m["moves"]
            assert m["moves"] in e2e
            for cell in m.get("workloads", []):
                e = next(x for x in bench["end_to_end"]
                         if x["name"] == m["moves"])
                assert cell in e.get("workloads", [cell])


def test_peaks_are_keyed_by_device_kind():
    peaks = harness.load_json(P.BENCH, "peaks.json")
    v5e = peaks["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["source"]
    mod = harness.load_module("metrics", "span_roofline.py")
    ctx = {"traced": {"span_s": 1.0, "delta": {"executed_steps": 10}},
           "peaks": peaks, "device_kind": "TPU v9 imaginary",
           "lane_shapes": {"pc": ()}}
    with pytest.raises(KeyError):
        mod.reduce(ctx)


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(P.BENCH, "run.py"), "--workload",
         "io_monitor.served", "--seed", str(2**40 + 3), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=P.ROOT, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
