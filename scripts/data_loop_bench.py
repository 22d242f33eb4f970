"""Time the guest kernel's data loop alone, on one TPU.

Runs ``repro.emul.engine.run_data_loop`` on a fleet of B lanes of which
n move ``--words`` words each, as a file-churn firing does (a write, from
guest memory to the inode plane, then a read back), for every block size
``K_KIO`` and window ``W_KIO`` asked for.  Each case is one jitted loop
of ``--reps`` write+read pairs, timed ``--repeats`` times after a warm-up;
one JSON line per case gives the median ms per firing and a checksum of
both planes, which equals across cases that move the same words.

``--src`` imports ``repro`` from another source tree, so two commits can
be timed on one chip; a tree whose loop has no ``K_KIO`` is timed once
per window with ``"k": null``.  Without a TPU it exits 2 unless
``--rehearse`` is given (a CPU run for checking the script, whose times
are not device times).

    python scripts/data_loop_bench.py --fleet 4096:1024 --fleet 64:16 \\
        --k 128,256,512 --w 64,128 --out data_loop.jsonl
"""
import argparse
import json
import pathlib
import statistics
import sys
import time


def _ints(s):
    return [int(x) for x in s.split(",") if x]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--src", default=str(pathlib.Path(__file__).resolve()
                                         .parents[1] / "src"))
    ap.add_argument("--fleet", action="append", default=None,
                    help="B:n, lanes and moving lanes (repeatable)")
    ap.add_argument("--k", type=_ints, default=[128, 256, 512])
    ap.add_argument("--w", type=_ints, default=[64, 128])
    ap.add_argument("--words", type=int, default=64)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--seed", type=int, default=16)
    ap.add_argument("--label", default="")
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, args.src)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from repro.core import layout as L
    from repro.emul import engine as E

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"no TPU ({dev.platform}); --rehearse runs on it anyway",
              file=sys.stderr)
        return 2
    ipl = L.MAX_INODES * L.FILE_WORDS
    fleets = [tuple(map(int, f.split(":")))
              for f in (args.fleet or ["4096:1024", "64:16"])]
    blocked = hasattr(E, "K_KIO")

    def effects(B, movers, to_ino):
        lanes = np.arange(B)
        fio = np.zeros(B, bool)
        fio[movers] = True
        f = dict.fromkeys(E.EmulEffects._fields)
        f.update(fio_do=fio, nw=np.where(fio, args.words, 0),
                 mem_base=lanes * L.MEM_WORDS
                 + (L.HEAP_BASE - L.DATA_BASE) // 8,
                 ino_base=lanes * ipl, proc_base=lanes * L.PROC_WORDS,
                 dst_is_mem=np.full(B, not to_ino),
                 src_is_ino=np.full(B, not to_ino),
                 src_is_proc=np.zeros(B, bool), src_is_rand=np.zeros(B, bool),
                 rng0=lanes.astype(np.int64))
        return E.EmulEffects(**{k: v if v is None else jnp.asarray(v)
                                for k, v in f.items()})

    for B, n in fleets:
        movers = np.random.default_rng(args.seed).choice(B, n, replace=False)
        e_wr, e_rd = effects(B, movers, True), effects(B, movers, False)
        proc = jnp.zeros((B * L.PROC_WORDS,), jnp.int64)
        for w in args.w:
            for k in (args.k if blocked else [None]):
                E.W_KIO = w
                if k is not None:
                    E.K_KIO = k

                def firings(mem, ino):
                    def body(_, c):
                        c = E.run_data_loop(c[0], c[1], proc, e_wr)
                        return E.run_data_loop(c[0], c[1], proc, e_rd)
                    return lax.fori_loop(0, args.reps, body, (mem, ino))

                run = jax.jit(firings, donate_argnums=(0, 1))
                mem = jnp.arange(B * L.MEM_WORDS, dtype=jnp.int64)
                ino = -jnp.arange(B * ipl, dtype=jnp.int64)
                t0 = time.perf_counter()
                mem, ino = jax.block_until_ready(run(mem, ino))
                compile_s = time.perf_counter() - t0
                times = []
                for _ in range(args.repeats):
                    t0 = time.perf_counter()
                    mem, ino = jax.block_until_ready(run(mem, ino))
                    times.append(time.perf_counter() - t0)
                check = int(jnp.sum(mem) + 3 * jnp.sum(ino))
                row = dict(label=args.label, lanes=B, moving=n,
                           words=args.words, k=k, w=w,
                           ms_per_firing=statistics.median(times) * 1e3
                           / (2 * args.reps),
                           times_s=times, compile_s=compile_s,
                           checksum=check, platform=dev.platform,
                           device_kind=dev.device_kind)
                print(json.dumps(row), flush=True)
                if args.out:
                    with open(args.out, "a") as out:
                        out.write(json.dumps(row) + "\n")
                del mem, ino
    return 0


if __name__ == "__main__":
    sys.exit(main())
