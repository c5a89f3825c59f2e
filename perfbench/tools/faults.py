"""Faults planted in the timed path, underneath the harness: each must
make ``correct`` come out false.

    python3 perfbench/tools/faults.py --workload npb256-sweep \
        --seconds 1 --seeds 101,102,103

Each fault replaces the stepper's device-to-host fetch
(``repro.backends.jax.engine._device_get``), so the window's own
buckets come back broken:

* ``state_unchanged``: every row's makespan and energy left at 0, as a
  stepper that returns its state unchanged would leave them;
* ``half_batch_left_out``: the first half of each bucket's rows replaced
  by copies of the second half;
* ``answer_altered``: every makespan 1 s late and every energy 1% high;
* ``rows_misplaced``: each bucket's rows rolled by one, as rows put on
  the wrong shard would land.

With ``--seeds`` it runs each fault on each seed at the cell's own size,
in one process, and prints ``correct`` with the numbers compared.  Runs
on the chip only; the tests plant the same faults on the CPU.
"""

import argparse
import json

import numpy as np

import _common  # noqa: F401  (paths)

FAULTS = ("state_unchanged", "half_batch_left_out", "answer_altered",
          "rows_misplaced")


def broken(kind):
    """A stand-in for ``engine._device_get`` that plants ``kind``."""
    import jax

    def get(out):
        out = {k: np.array(v) for k, v in jax.device_get(out).items()}
        rows = out["makespan"].shape[0]
        half = rows // 2
        for k, v in out.items():
            if kind == "state_unchanged" and k in ("makespan", "energy"):
                v[...] = 0.0
            elif kind == "half_batch_left_out" and rows >= 2:
                v[:half] = v[rows - half:]
            elif kind == "rows_misplaced":
                out[k] = np.roll(v, 1, axis=0)
        if kind == "answer_altered":
            out["makespan"] = out["makespan"] + 1.0
            out["energy"] = out["energy"] * 1.01
        return out

    return get


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    from pb import harness
    from pb.clock import Clock
    from repro.backends.jax import engine

    clock = Clock()
    plain = engine._device_get
    for seed in (int(s) for s in args.seeds.split(",")):
        for kind in FAULTS:
            engine._device_get = broken(kind)
            try:
                res = harness.run_cell(args.workload, seed, args.seconds,
                                       False, root=_common.ROOT, clock=clock)
            finally:
                engine._device_get = plain
            checks = {k: c["value"] for k, c in res["checks"].items()}
            print(f"[faults] seed {seed} {kind} correct {res['correct']} "
                  f"{json.dumps(checks)}", flush=True)


if __name__ == "__main__":
    main()
