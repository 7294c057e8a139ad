"""Kill-and-recover chaos harness for the durability subsystem.

The WAL's contract (``repro.stream.wal``) is *recovery to the last
acknowledged write*: an op whose ack token came back from the group
commit must survive a SIGKILL; anything later may be lost.  This lane
measures and enforces exactly that, end to end, with a real process
kill -- not a mocked crash:

  * a **child process** (``--child`` mode of this module) opens a
    durable sharded index (``ShardedMutableP2HIndex.open``) and runs an
    endless mixed insert/delete storm.  Its ``on_ack`` callback appends
    one line per acknowledged op to ``acked.log`` (line-buffered: the
    bytes land in the OS page cache, which survives SIGKILL) plus the
    current epoch vector; delete *attempts* are logged before they are
    issued (an unacked-but-durable delete legally removes an acked
    insert -- per-shard log-prefix semantics -- so the checker must
    know about it).  Periodic checkpoints exercise the
    checkpoint-plus-tail recovery path and WAL prefix truncation.
  * the **parent** arms a :class:`repro.runtime.StepWatchdog` whose
    ``on_expire`` SIGKILLs the child, beats it until the storm has done
    enough acknowledged work, then lets it fire mid-storm.  Recovery
    (``ShardedMutableP2HIndex.open`` again) runs under
    :func:`repro.runtime.run_with_restarts` -- the supervisor loop a
    real deployment would use -- and is timed.
  * the parent then checks the recovered index against the ack log:
    every acked insert not covered by a delete attempt is live, no
    acked delete resurrects, no gid is owned by two shards, and the
    recovered epoch vector is componentwise >= the last acked vector.

Several kill rounds run back to back **against the same directory** --
each round's child resumes from the previous round's recovered state,
so recovery-of-a-recovery (double restore, truncated logs, grown id
space) is exercised for free.  ``run`` returns the JSON trajectory dict
(``BENCH_durability.json``): replay throughput, recovery p50/max, and
the three invariant counters CI fences at zero
(``tools/check_bench_json.py``).

The lane checks durability invariants, not device speed, so the storm
child runs on the CPU (``JAX_PLATFORMS=cpu``): it never contends for an
accelerator that the parent process may hold, and run standalone the
parent pins itself to the CPU as well.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time

import numpy as np

from benchmarks.common import pct

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ACK_LOG = "acked.log"


# ----------------------------------------------------------------------
# child: the write storm (runs in its own process; killed by the parent)
# ----------------------------------------------------------------------
def _child_main(args) -> None:
    from repro.stream.sharded import ShardedMutableP2HIndex
    from repro.stream.wal import WalConfig

    rng = np.random.default_rng(args.seed)
    state = {"idx": None}
    ack_fh = open(os.path.join(args.dir, _ACK_LOG), "a", buffering=1)

    def on_ack(tokens):
        # line-buffered: each line hits the OS page cache on the
        # newline, so it survives the parent's SIGKILL exactly like the
        # fsync'd WAL bytes it mirrors
        for kind, gid in tokens:
            ack_fh.write(f"{kind} {gid}\n")
        if state["idx"] is not None:
            ep = " ".join(str(e) for e in state["idx"].epoch)
            ack_fh.write(f"E {ep}\n")

    idx = ShardedMutableP2HIndex.open(
        args.dir, dim=args.dim, num_shards=args.shards,
        wal_config=WalConfig(fsync_every_n=args.fsync_every_n,
                             fsync_interval_ms=5.0),
        on_ack=on_ack)
    state["idx"] = idx

    issued: list[int] = []  # gids this incarnation inserted
    it = 0
    while True:  # until SIGKILL
        pts = rng.normal(size=(args.batch, args.dim)).astype(np.float32)
        issued += [int(g) for g in idx.insert_batch(pts)]
        if issued and rng.random() < 0.4:
            gid = issued.pop(int(rng.integers(len(issued))))
            # attempt line *before* the op: its WAL record may become
            # durable without the ack ever coming back
            ack_fh.write(f"d? {gid}\n")
            idx.delete(gid)
        it += 1
        if args.save_every and it % args.save_every == 0:
            idx.save(args.dir)  # checkpoint + WAL prefix truncation


# ----------------------------------------------------------------------
# parent: kill, recover, verify
# ----------------------------------------------------------------------
def _read_ack_log(path: str):
    """Parse the child's ack log: acked inserts/deletes, delete
    attempts, and the last *complete* epoch-vector line (a final line
    the kill tore mid-write is ignored -- its op was not acked from the
    checker's point of view either)."""
    acked_ins, acked_del, attempted = set(), set(), set()
    last_epochs = None
    if not os.path.exists(path):
        return acked_ins, acked_del, attempted, last_epochs
    with open(path, "rb") as fh:
        data = fh.read()
    lines = data.split(b"\n")
    if lines and lines[-1] != b"":
        lines = lines[:-1]  # torn final line (no newline): never acked
    for raw in lines:
        parts = raw.decode("utf-8", "replace").split()
        if not parts:
            continue
        if parts[0] == "ins":
            acked_ins.add(int(parts[1]))
        elif parts[0] == "del":
            acked_del.add(int(parts[1]))
        elif parts[0] == "d?":
            attempted.add(int(parts[1]))
        elif parts[0] == "E":
            last_epochs = tuple(int(e) for e in parts[1:])
    return acked_ins, acked_del, attempted, last_epochs


def _count_ack_lines(path: str) -> int:
    if not os.path.exists(path):
        return 0
    with open(path, "rb") as fh:
        return fh.read().count(b"\n")


def _wal_tail_ops(wal_dir: str) -> int:
    """Records currently in the WAL tails (what recovery will replay)."""
    from repro.stream.wal import ShardWal

    n = 0
    if not os.path.isdir(wal_dir):
        return 0
    for name in sorted(os.listdir(wal_dir)):
        if not name.endswith(".wal"):
            continue
        wal = ShardWal(os.path.join(wal_dir, name))
        n += sum(1 for _ in wal.records(0))
        wal.close()
    return n


def _kill_round(directory: str, *, dim: int, shards: int, seed: int,
                min_acks: int, kill_after_s: float, save_every: int,
                fsync_every_n: int, spawn_timeout_s: float = 180.0) -> dict:
    """One chaos round: storm, SIGKILL mid-storm, recover, verify."""
    from repro.runtime import RetryPolicy, StepWatchdog, run_with_restarts
    from repro.stream.sharded import ShardedMutableP2HIndex

    ack_path = os.path.join(directory, _ACK_LOG)
    baseline_lines = _count_ack_lines(ack_path)
    env = dict(os.environ)
    # the child runs this file as a script: it needs src/ (repro) and
    # the repo root (the benchmarks package itself) on its path
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_REPO_ROOT, "src"), _REPO_ROOT]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    env["JAX_PLATFORMS"] = "cpu"  # a chip belongs to one process
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child",
         "--dir", directory, "--dim", str(dim), "--shards", str(shards),
         "--seed", str(seed), "--save-every", str(save_every),
         "--fsync-every-n", str(fsync_every_n)],
        env=env, cwd=_REPO_ROOT,
        stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)
    # the watchdog IS the kill switch: beat it while the storm warms up
    # (imports, recovery of the previous round's state), stop beating
    # once enough acked work has accumulated, and its expiry SIGKILLs
    # the child mid-storm
    wd = StepWatchdog(kill_after_s, on_expire=proc.kill)
    wd.beat()
    t0 = time.monotonic()
    while (_count_ack_lines(ack_path) - baseline_lines < min_acks
           and proc.poll() is None
           and time.monotonic() - t0 < spawn_timeout_s):
        wd.beat()
        time.sleep(0.05)
    if proc.poll() is not None:  # died on its own: a bug, not a kill
        wd.stop()
        err = proc.stderr.read().decode("utf-8", "replace")
        raise RuntimeError(f"storm child exited rc={proc.returncode} "
                           f"before the kill: {err[-2000:]}")
    proc.wait()  # the watchdog's SIGKILL lands within kill_after_s
    wd.stop()
    proc.stderr.close()
    assert proc.returncode < 0, \
        f"child must die by signal, not rc={proc.returncode}"

    acked_ins, acked_del, attempted, last_epochs = _read_ack_log(ack_path)
    tail_ops = _wal_tail_ops(os.path.join(directory, "wal"))

    # recovery under the real supervisor loop: an IOError (torn
    # checkpoint leaf, unreadable log) would retry per the policy
    t0 = time.monotonic()
    idx, restarts = run_with_restarts(
        lambda: ShardedMutableP2HIndex.open(directory, dim=dim,
                                            num_shards=shards),
        lambda ix: ix, policy=RetryPolicy(max_restarts=2))
    recovery_s = time.monotonic() - t0

    per_shard = [set(int(g) for g in sh.live_gids()) for sh in idx.shards]
    live: set = set().union(*per_shard) if per_shard else set()
    dup_gids = sum(len(s) for s in per_shard) - len(live)
    # an acked insert may only be missing if a delete was *attempted*
    # on it (acked or not: the attempt's record can be durable without
    # its ack) -- anything else is lost acknowledged data
    lost = acked_ins - attempted - live
    resurrected = live & acked_del
    epochs = tuple(idx.epoch)
    epoch_regressions = 0
    if last_epochs is not None:
        epoch_regressions = sum(
            1 for a, b in zip(last_epochs, epochs) if b < a)
    # sanity: the recovered index serves queries over the survivors
    if live:
        q = np.zeros((1, dim + 1), np.float32)
        q[0, 0] = 1.0
        _, ids = idx.query(q, k=min(4, len(live)))
        ids = np.asarray(ids).ravel()
        assert np.all(np.isin(ids[ids >= 0], sorted(live)))
    misroutes = idx.stats()["misroutes"]
    idx.close()
    return {
        "acked_ops": len(acked_ins) + len(acked_del),
        "tail_ops": tail_ops,
        "recovery_s": recovery_s,
        "restarts": restarts,
        "acked_loss": len(lost),
        "dup_gids": dup_gids,
        "resurrected": len(resurrected),
        "epoch_regressions": epoch_regressions,
        "live_count": len(live),
        "misroutes": misroutes,
    }


def run(csv, smoke: bool = False) -> dict:
    """CSV rows per kill round + the BENCH_durability.json dict."""
    import tempfile

    rounds = 2 if smoke else 4
    min_acks = 40 if smoke else 300
    dim = 8 if smoke else 16
    with tempfile.TemporaryDirectory(prefix="p2h_chaos_") as directory:
        csv("durability,round,acked_ops,tail_ops,recovery_s,acked_loss,"
            "dup_gids,resurrected,epoch_regressions,live,misroutes")
        results = []
        for r in range(rounds):
            res = _kill_round(
                directory, dim=dim, shards=2, seed=1234 + r,
                min_acks=min_acks, kill_after_s=0.25,
                # checkpoint on even rounds so both recovery paths
                # (pure-WAL and checkpoint+tail) are exercised
                save_every=((5 if smoke else 20) if r % 2 == 0 else 0),
                fsync_every_n=4)
            results.append(res)
            csv(f"durability,{r},{res['acked_ops']},{res['tail_ops']},"
                f"{res['recovery_s']:.3f},{res['acked_loss']},"
                f"{res['dup_gids']},{res['resurrected']},"
                f"{res['epoch_regressions']},{res['live_count']},"
                f"{res['misroutes']}")
    rec = [r["recovery_s"] for r in results]
    replayed = sum(r["tail_ops"] for r in results)
    return {
        "rounds": rounds,
        "shards": 2,
        "acked_ops": sum(r["acked_ops"] for r in results),
        "replay_ops_per_s": replayed / max(sum(rec), 1e-9),
        "recovery_p50_s": pct(rec, 50),
        "recovery_max_s": max(rec),
        "restarts": sum(r["restarts"] for r in results),
        # the invariants; CI fences these at zero
        "acked_loss": sum(r["acked_loss"] for r in results),
        "dup_gids": sum(r["dup_gids"] for r in results),
        "epoch_regressions": sum(r["epoch_regressions"]
                                 for r in results),
    }


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true",
                    help="internal: run the write storm (killed by the "
                         "parent; never returns)")
    ap.add_argument("--dir", default=None)
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--save-every", type=int, default=0,
                    help="checkpoint every N storm iterations (0: never)")
    ap.add_argument("--fsync-every-n", type=int, default=4)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    if args.child:
        assert args.dir, "--child requires --dir"
        _child_main(args)
        return
    os.environ["JAX_PLATFORMS"] = "cpu"  # before this process imports jax
    res = run(print, smoke=args.smoke)
    import json

    print(json.dumps(res, indent=1, sort_keys=True))


if __name__ == "__main__":
    main()
