"""Time one launch of the stacked sweep kernel's f32 main pass at the
benchmark cells' shapes, for several k, to split a grid step between the
top-k insertion loop and the rest of the step.

    PYTHONPATH=src python scripts/insert_loop_cost.py [--k 1 10 50]
        [--reps 5] [--tiles 2976 288] [--out PATH]

Shapes: ``music100`` (4 segments of 2,976 tiles, n0 = 128, d + 1 = 101
lane-padded to 128) and ``sun397`` (4 segments of 288 tiles, d + 1 = 513
lane-padded to 640), one block of 8 queries.  The operands are made on
the device from a fixed key: Gaussian points and queries, and bounds that
keep every tile and every point (zero ``<q, c>``, node bounds, radii and
cone tables), visited in stored order.  Two seeds of the running top-k:

* ``cold``: ``+inf``, so each row's top-k fills in the first tile and
  then changes about ``k ln(n / k)`` times over a segment of ``n``
  points, as in a scan of data in random order;
* ``warm``: a running k-th of 1e-9, below every candidate, so no
  candidate can enter the top-k: the step's work without insertions.

A loop that always runs k steps costs the same under either seed.
Prints one JSON line per (shape, k, seed) -- device, milliseconds per
launch (median of ``--reps`` after a warm-up call), microseconds per
grid step and, where the kernel reports them, the steps that scanned a
tile and the insertion steps run -- and appends them to ``--out``.  On
a CPU the kernel runs in interpret mode: a check of the script, not a
time.
"""
from __future__ import annotations

import argparse
import json
import statistics
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.kernels.stacked_sweep import stacked_sweep

#: (name, padded lane width, tiles per segment); 4 segments, n0 = 128
SHAPES = (("music100", 128, 2976), ("sun397", 640, 288))
N_SEG, N0, BQ = 4, 128, 8
WARM_KTH = 1e-9


def operands(dp: int, tiles: int, key):
    kp, kq = jax.random.split(key)
    N, L = N_SEG, tiles
    zeros = jnp.zeros((N, L, N0), jnp.float32)
    return dict(
        pts_tiles=jax.random.normal(kp, (N, L, N0, dp), jnp.float32),
        ids_tiles=jnp.arange(N * L * N0, dtype=jnp.int32).reshape(N, L, N0),
        rx_tiles=zeros, xc_tiles=zeros, xs_tiles=zeros,
        leaf_cnorm=jnp.ones((N, L, 1), jnp.float32),
        queries=jax.random.normal(kq, (BQ, dp), jnp.float32),
        qnorm=jnp.full((BQ, 1), float(dp) ** 0.5, jnp.float32),
        cap=jnp.full((BQ, 1), jnp.inf, jnp.float32),
        leaf_ip=jnp.zeros((N, BQ, L), jnp.float32),
        leaf_lb=jnp.zeros((N, BQ, L), jnp.float32),
        visit=jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32),
                               (N, 1, L)))


def time_launch(fn, args, reps: int) -> tuple[float, object]:
    out = jax.block_until_ready(fn(*args))  # compile + warm
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times), out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--k", type=int, nargs="+", default=[1, 10, 50])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--tiles", type=int, nargs=2, default=None,
                    help="tiles per segment of the two shapes (a smaller "
                         "size for a check on the CPU)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    device = jax.devices()[0]
    lines = []
    for s, (name, dp, tiles) in enumerate(SHAPES):
        if args.tiles:
            tiles = args.tiles[s]
        ops = operands(dp, tiles, jax.random.key(s))
        names = list(ops)
        for k in args.k:
            @jax.jit
            def launch(seed_d, *a, _k=k):
                return stacked_sweep(
                    **dict(zip(names, a)), seed_d=seed_d,
                    seed_i=jnp.full(seed_d.shape, -1, jnp.int32), k=_k,
                    bq=BQ)

            for seed in ("cold", "warm"):
                fill = jnp.inf if seed == "cold" else WARM_KTH
                sd = jnp.full((N_SEG, BQ, k), fill, jnp.float32)
                sec, out = time_launch(launch, (sd, *ops.values()),
                                       args.reps)
                steps = N_SEG * tiles
                line = {"device": device.device_kind,
                        "platform": device.platform, "shape": name,
                        "dp": dp, "steps": steps, "k": k, "seed": seed,
                        "ms": sec * 1e3, "us_per_step": sec * 1e6 / steps}
                if len(out) == 4:  # kernels that count their steps
                    st = jax.device_get(out[3]).sum(axis=(0, 1))
                    line["scan_steps"] = int(st[0])
                    line["insert_steps"] = int(st[1])
                print(json.dumps(line), flush=True)
                lines.append(line)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "a") as f:
            f.writelines(json.dumps(x) + "\n" for x in lines)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
