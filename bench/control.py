"""Readings of the control: the plain reference put in the program's
place at one precision below the configuration's (three bfloat16
passes), driven through the cell's own traffic and judged by the same
comparison as a run.  Run it on the chip; it must come out not correct.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

Prints one JSON line per seed: the comparison's numbers and verdict.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def control_readings(config, mix, seed, seconds) -> dict:
    """The comparison's numbers for the control over the queries and
    writes of a run of ``seconds``."""
    reference, check = harness.load_reference(config), \
        harness.load_check(config)
    data = harness.make_data(config, mix, seed, seconds)
    system = harness.ControlSystem(config, data, seed, reference)
    log, spans = harness.Log(data), harness.Spans()
    driver = harness.load_loop(mix).Driver(
        system, mix, data, log, spans, slot=config["slot_size"], seed=seed)
    driver.replay(seconds)
    readings = check.compare(log, reference, mix["k"])
    correct, _ = check.verdict(readings, config["limits"])
    return dict(readings, correct=correct)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    _, _, config, mix = harness.load_cell(args.workload)
    import jax

    print(f"device: {jax.devices()[0].device_kind}", flush=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = control_readings(config, mix, seed, args.seconds)
        out.update(seed=seed, seconds=time.perf_counter() - t0)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
